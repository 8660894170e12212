"""A step's prompt rows, one chunk's a prefilling slot, go to the OLDEST
prefilling slot first, in one launch of up to four chunks and at most
`PREFILL_LAUNCH_ROWS` rows (`ServingEngine.step`): the spending rule as a
pure function, and the engine under it in the tiny configurations of the
rehearsals (chunk 16): a K/V family, a family with STATE layers (a
launch's padding rows stay out of the state: `valid`; Ling's, the other
kind of state, is served under the rule in tests/test_chunk_read_row.py
and tests/test_latent_chunk_attention.py) and a family whose window
scratch slides, which is refused by name and keeps one chunk a launch.  Served tokens are held to an engine that prefills ONE request at
a time, whose every launch is one chunk: the path of before the rule."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import trace as bench_trace  # noqa: E402
from hetu_tpu.serving import engine as engine_mod  # noqa: E402
from hetu_tpu.serving.engine import (PREFILL_LAUNCH_ROWS,  # noqa: E402
                                     launch_multiples,
                                     plan_prefill_launches)
from hetu_tpu.serving.request import Request  # noqa: E402
from test_chunk_read_row import CHUNK  # noqa: E402
from test_chunk_read_row import build as build_config  # noqa: E402
from test_chunk_read_row import engine as engine_with  # noqa: E402
from test_serving import launches  # noqa: E402

#: family module -> (its tiny configuration, the chunks a launch may carry)
FAMILIES = {"llama": ("tiny", (1, 2, 3, 4)),
            "jamba": ("tiny-jamba", (1, 2, 3, 4)),
            "mimo_v2": ("tiny-mimo", (1,))}


# ------------------------------------------------------- the pure rule
@pytest.mark.parametrize("chunk,slides,want", [
    (16, False, (1, 2, 3, 4)), (128, False, (1, 2, 3, 4)),
    (129, False, (1, 2, 3)), (256, False, (1, 2)), (257, False, (1,)),
    (512, False, (1,)), (1024, False, (1,)), (2048, False, (1,)),
    (16, True, (1,)), (128, True, (1,)), (512, True, (1,))])
def test_launch_multiples_by_the_chunk_and_the_scratch(chunk, slides, want):
    """k x chunk <= 512 rows, k <= 4; a chunk of more than 256 rows, or a
    scratch that slides by the chunk, keeps one chunk a launch, and the
    reason says which."""
    ks, why = launch_multiples(chunk, slides)
    assert ks == want and PREFILL_LAUNCH_ROWS == 512
    assert all(k * chunk <= PREFILL_LAUNCH_ROWS for k in ks[1:])
    assert ("slides" in why) == slides
    if not slides:
        assert ("pass the 512 rows" in why) == (want == (1,))


@pytest.mark.parametrize("left,multiples,want", [
    ([9], (1, 2, 3, 4), [1]),                    # alone: one chunk a step
    ([9, 9], (1, 2, 3, 4), [2, 0]),
    ([9, 9, 9], (1, 2, 3, 4), [3, 0, 0]),
    ([9, 9, 9, 9, 9], (1, 2, 3, 4), [4, 1, 0, 0, 0]),
    ([9] * 9, (1, 2, 3, 4), [4, 4, 1] + [0] * 6),
    ([1, 5, 2], (1, 2, 3, 4), [1, 2, 0]),        # never past what is left
    ([3, 1, 1, 7], (1, 2, 3, 4), [3, 1, 0, 0]),
    ([3, 1, 1, 7], (1, 2, 4), [2, 1, 1, 0]),     # 3 is no shape: 2, then on
    ([9, 9, 9], (1, 2), [2, 1, 0]),              # a chunk of 256 rows
    ([9, 2, 5], (1,), [1, 1, 1]),                # 512 rows, or a slide
    ([], (1, 2, 3, 4), [])])
def test_the_plan_of_a_step(left, multiples, want):
    assert plan_prefill_launches(left, multiples) == want


@pytest.mark.parametrize("seed", range(6))
def test_the_plan_spends_the_budget_exactly_and_oldest_first(seed):
    """Over random steps: the chunks planned are the prefilling slots'
    number (the budget: never exceeded, and never left, since every slot
    has a chunk left and 1 is a multiple); a slot's k is a multiple, within
    what it has left, and the LARGEST that what the older slots left of
    the budget allows; once the budget is spent everyone behind waits;
    with (1,) the plan is one chunk a slot, whatever is left."""
    rng = np.random.default_rng(seed)
    for _ in range(200):
        left = [int(n) for n in rng.integers(1, 12, size=rng.integers(1, 10))]
        multiples = [(1, 2, 3, 4), (1, 2, 4), (1, 2), (1,)][
            int(rng.integers(0, 4))]
        plan = plan_prefill_launches(left, multiples)
        assert len(plan) == len(left) and sum(plan) == len(left)
        budget = len(left)
        for k, n in zip(plan, left):
            assert k in (0, *multiples) and k <= n
            assert k == max([m for m in multiples if m <= min(n, budget)],
                            default=0)
            budget -= k
        if multiples == (1,):
            assert plan == [1] * len(left)
        else:       # nobody behind a slot that waits gets anything
            assert 0 not in plan or not any(plan[plan.index(0):])


# ---------------------------------------------------------- the engine
def build(family):
    return build_config(family, FAMILIES[family][0])


def engine(fam, cfg, model, params):
    # (Jamba's own configuration holds two prefill scratches at a time:
    # here every slot may prefill, so a step's budget reaches four chunks)
    return engine_with(fam, cfg, model, params, max_prefilling=0)


#: prompts of 4, 2, 3, 1 and 3 chunks, the first four admitted in ONE
#: step (four slots), none a whole number of chunks
PLENS = (61, 23, 40, 7, 35)


def _requests(cfg):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(
        0, cfg["vocab_size"], size=n).astype(np.int32), max_new_tokens=5)
        for i, n in enumerate(PLENS)]


@pytest.mark.parametrize("family", FAMILIES)
def test_prompts_prefilled_together_are_served_as_one_at_a_time(family):
    """Five requests at once against the same five, each served ALONE (a
    lone prefilling slot's budget is one chunk: every launch is one
    chunk, at the prompt's own pace): the same greedy tokens.  Together,
    the first step hands its four chunks of rows to the oldest prompt,
    in ONE launch that ends it (61 of 64 rows the prompt's: the state
    layers take no padding row, `valid`), and the counters say so; the
    family whose window scratch slides is refused by name and launches
    one chunk at a time, in admission order."""
    fam, cfg, model, params = build(family)
    _, multiples = FAMILIES[family]
    eng, reg = engine(fam, cfg, model, params)
    shapes = eng.kernel_routes["prefill_launch_rows"]
    assert shapes["rows"] == [k * CHUNK for k in multiples]
    assert ("slides" in shapes["why"]) == (multiples == (1,)) == eng._slide
    want = {}
    for req in _requests(cfg):          # (the one engine serves both ways)
        want.update({r.rid: r.tokens for r in eng.run([req])})
    chunks = sum(-(-n // CHUNK) for n in PLENS)
    assert launches(reg) == {CHUNK: chunks}

    start = bench_trace.counter_values(reg.snapshot())
    got = {r.rid: r.tokens for r in eng.run(_requests(cfg))}
    assert got == want
    count = bench_trace.counter_diff(
        start, bench_trace.counter_values(reg.snapshot()))
    by_rows = {int(k.split("rows=")[1][:-1]): int(n) for k, n in count.items()
               if k.startswith("serve.prefill_launches{") and n}
    assert set(by_rows) <= set(shapes["rows"])
    assert sum(by_rows.values()) == count["serve.prefill_chunks"]
    assert sum(r * n for r, n in by_rows.items()) == chunks * CHUNK
    assert count["serve.prefill_tokens"] == sum(PLENS)
    assert count["serve.prefill_tail_rows"] == len(PLENS)
    if multiples == (1,):
        assert by_rows == {CHUNK: chunks}
    else:
        # step 1: 4 chunks to request 0; step 2: 2 to request 1, 1 to
        # request 2; step 3: 2 to request 2 (request 3 waits again) ...
        assert by_rows[4 * CHUNK] == 1 and by_rows[2 * CHUNK] >= 2
        assert sum(by_rows.values()) < chunks
    if eng.stateful:
        # the rows of a launch past its prompt: never in a state
        assert count["serve.chunk_padded_rows"] == \
            chunks * CHUNK - sum(PLENS)
        assert count["serve.state_resets"] == len(PLENS)
    eng.close()


def test_the_oldest_slot_prefills_first_and_nobody_computes_more(rng):
    """Step by step (llama, three slots): the rows a step computes are
    one chunk's a prefilling slot, never more; they go to the slot
    admitted first, which therefore has its first token no later than
    under one chunk a slot a step, and the slots behind it wait,
    without a scratch."""
    fam, cfg, model, params = build("llama")
    eng, reg = engine(fam, cfg, model, params)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg["vocab_size"], size=n).astype(np.int32), max_new_tokens=30)
        for i, n in enumerate((96, 96, 96))]     # six chunks each
    for r in reqs:
        eng.submit(r, now=0.0)
    seen = []
    for step in range(8):
        before = reg.counter_value("serve.prefill_tokens")
        slots = [st for st in eng.scheduler.slots if st is not None]
        n = sum(st.prefilling for st in slots) if step else 3
        eng.step(float(step))
        rows = reg.counter_value("serve.prefill_tokens") - before
        assert rows == n * CHUNK, (step, rows, n)
        live = sorted((s for s in eng.scheduler.slots if s is not None),
                      key=lambda s: s.admit_seq)
        seen.append(tuple(st.chunks_done for st in live))
        # a slot holds a prefill scratch from its first launch to its
        # last, and none while it waits its turn
        assert [st.prefill_cache is not None for st in live] == [
            0 < st.chunks_done < 6 for st in live]
    # three chunks a step while three prefill, oldest first; then two
    assert seen[:4] == [(3, 0, 0), (6, 0, 0), (6, 2, 0), (6, 4, 0)]
    assert seen[4:] == [(6, 6, 0), (6, 6, 1), (6, 6, 2), (6, 6, 3)]
    assert launches(reg) == {3 * CHUNK: 2, 2 * CHUNK: 3, CHUNK: 3}
    eng.close()


def test_warmup_compiles_every_launch_shape(rng):
    """`warmup` runs the chunk program at every launch shape (and
    `lower_programs` lowers them all, the largest last: `run.py` sizes
    the peak from them), so a run of mixed prompt lengths, whose steps
    launch one to four chunks, compiles nothing inside a step."""
    fam, cfg, model, params = build("llama")
    eng, reg = engine(fam, cfg, model, params)
    low = eng.lower_programs()
    assert list(low)[-3:] == ["prefill_chunk_x2", "prefill_chunk_x3",
                              "prefill_chunk_x4"]
    assert [low[engine_mod._chunk_program(k)].args_info[0][1].shape
            for k in (1, 2, 3, 4)] == [(1, k * CHUNK) for k in (1, 2, 3, 4)]
    eng.warmup()

    def serve(rid0, plens):
        return eng.run([Request(
            rid=rid0 + i, max_new_tokens=4, prompt=rng.integers(
                0, cfg["vocab_size"], size=n).astype(np.int32))
            for i, n in enumerate(plens)])
    # (the host's eager one-operation programs compile in a first
    # request's steps: a lone short prompt, launches of one chunk)
    serve(0, (9,))
    assert launches(reg) == {CHUNK: 1}
    start = bench_trace.counter_values(reg.snapshot())
    serve(10, (64, 33, 48, 7, 20, 61, 5, 40))
    diff = bench_trace.counter_diff(
        start, bench_trace.counter_values(reg.snapshot()))
    assert diff.get("serve.step_compiles", 0) == 0
    assert {k * CHUNK for k in (1, 2, 3, 4)} == set(launches(reg))
    eng.close()


def test_the_metric_reads_rows_a_launch_from_the_two_counters():
    """`prefill_rows_launch` (benchmarks/metrics): `serve.prefill_tokens`
    over `serve.prefill_chunks`, the count of launches, by the `counter`
    rule over a window's differences; nothing to read where no chunk
    ran."""
    from benchmarks import traffic
    spec = traffic.load_json("metrics", "prefill_rows_launch")
    assert spec["device"] is False and spec["reduce"]["rule"] == "counter"
    ctx = {"registry": {"serve.prefill_tokens": 5700.0,
                        "serve.prefill_chunks": 20.0}}
    assert bench_trace.reduce_metric(spec, None, None, ctx) == 285.0
    assert bench_trace.reduce_metric(spec, None, None, {"registry": {}}) \
        is None
    # (by name, and the cell it was brought for first: later cells append
    # themselves to the list and entries behind it)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == "prefill_rows_launch")
    assert entry.pop("workloads")[0] == "internlm2-serve-longprompt"
    assert entry == {
        "name": "prefill_rows_launch", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "Serving engine loop",
        "moves": "serve_tokens_per_s"}


def test_the_harness_reads_the_metric_in_a_rehearsal():
    """`benchmarks/run.py --rehearse` on the tiny closed-loop cell, from
    the rehearsal file this metric brings (`benchmarks/tests/
    rehearsal-prefill-rows.json`): the traced run's line carries
    `prefill_rows_launch`, at least a chunk's rows less the prompts'
    padding and at most four chunks', and the run is `correct`."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "--benchmark-file", os.path.join(
             ROOT, "benchmarks", "tests", "rehearsal-prefill-rows.json"),
         "--workload", "tiny-batch", "--seed", "2147483659", "--seconds",
         "2", "--trace", "1"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] and not line["failed"]
    assert set(line["metrics"]) == {"cpu_rehearsal.prefill_rows_launch"}
    rows = line["metrics"]["cpu_rehearsal.prefill_rows_launch"]["value"]
    assert CHUNK / 2 < rows <= 4 * CHUNK
