"""The eighth family, `families/jamba.py` (AI21-Jamba2-3B: 26 Mamba-1
layers with normed dt / B / C whose state the pool holds by slot, 2
attention layers of ONE K/V head read by 20 query heads), through the
harness on the CPU: `rehearsal-jamba.json`'s `tiny-jamba-concurrent-turns`
cell under `--rehearse`, the cell's files and numbers as ISSUE 47 gives
them, the scope rules against the programs, the cost functions, and a
control: a reference without the inner norms, by the comparison that
decides `correct`.

It asserts that the cell's entries are PRESENT in `BENCHMARK.json`, not
that they are the last, nor how many they are: a later PR appends."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import trace, traffic  # noqa: E402

REHEARSAL = os.path.join(HERE, "rehearsal-jamba.json")
TINY = "tiny-jamba-concurrent-turns"
CELL = "jamba2-3b-serve-concurrent-turns"
CONFIG = "jamba2-3b"
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the engine-loop entries every serving cell reports since PR 57 (the five
# first cells had them since PR 40): the host's share of a step, where the
# device idles, the loop's own counts
ENGINE_LOOP = {
    "peak_hbm_gb", "host_work_ms_step", "starved_ms_step",
    "sync_idle_ms_step", "prefill_token_share_inside",
    "decode_unscoped_dev_ms", "engine_empty_pct", "stalled_steps_pct",
    "fetch_wait_ms_step", "decode_overlap_pct"}
# of those, what a run without a device plane reports too
ENGINE_LOOP_COUNTERS = ENGINE_LOOP - {
    "starved_ms_step", "sync_idle_ms_step", "decode_unscoped_dev_ms"}
# the 17 standing entries the cell joined in PR 47, the engine-loop
# entries and the decode program's `mlp` scope (PR 57), and the four it
# brought
JOINED = ENGINE_LOOP | {
    "decode_mlp_dev_ms",
    "decode_step_dev_ms", "prefill_chunk_dev_ms",
    "device_idle", "compiles_in_window",
    "decode_batch_inside", "decode_ctx_ktokens_step",
    "phi4f.decode_ssm_dev_ms", "phi4f.prefill_ssm_dev_ms",
    "phi4f.ssm_scan_roofline", "phi4f.ssm_state_roofline",
    "phi4f.prefill_tail_rows_pct", "decode_full_attn_dev_ms",
    "decode_kv_write_dev_ms", "paged_attn_roofline",
    "mimo.prefill_full_attn_dev_ms", "mimo.chunk_attn_roofline",
    "mimo.prefill_attended_kkeys_token"}
BROUGHT = {"jamba.ssm_state_gb_step", "jamba.decode_ssm_norm_dev_ms",
           "jamba.prefill_ssm_norm_dev_ms", "jamba.prefill_ssm_scan_dev_ms"}
# what the cell reports without a device plane (a rule file's `device`
# false)
COUNTER_METRICS = ENGINE_LOOP_COUNTERS | {
    "compiles_in_window", "decode_ctx_ktokens_step",
    "decode_batch_inside", "mimo.prefill_attended_kkeys_token",
    "phi4f.prefill_tail_rows_pct", "jamba.ssm_state_gb_step"}
ROOFLINES = {"phi4f.ssm_scan_roofline": "ssm_chunk_cost",
             "phi4f.ssm_state_roofline": "ssm_state_cost",
             "paged_attn_roofline": "paged_attn_cost",
             "mimo.chunk_attn_roofline": "chunk_attn_cost"}


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench():
    with open(BENCHMARK) as f:
        return json.load(f)


@pytest.mark.parametrize("trace_on", [0, 1])
def test_tiny_jamba_rehearses_correct(trace_on):
    line = last_line(run(
        "--rehearse", "--benchmark-file", REHEARSAL, "--workload", TINY,
        "--seed", "4700000019", "--seconds", "3", "--trace", str(trace_on)))
    # prompts of 9-104 (1-7 chunks of 16, two at a chunk's edge) and
    # answers of 10-24 over 4 slots that are reused all through the
    # window, 2 of them in prefill at most
    assert line["correct"] and not line["failed"]
    got = {k.removeprefix("cpu_rehearsal.") for k in line["metrics"]}
    assert got == (COUNTER_METRICS if trace_on
                   else {"serve_tokens_per_s", "setup_s"})
    if trace_on:
        m = {k.removeprefix("cpu_rehearsal.").split(".", 1)[-1]:
             v["value"] for k, v in line["metrics"].items()}
        assert m["compiles_in_window"] == 0
        # one tail row a prompt: 8 prompts of 460 tokens a block
        assert m["prefill_tail_rows_pct"] == pytest.approx(
            100 * 8 / 460, rel=0.25)
        # 13 layers x (8 + 3) x 192 float32 a row, read and written
        assert m["ssm_state_gb_step"] == pytest.approx(
            2 * m["decode_batch_inside"] * 13 * 11 * 192 * 4 * 1e-9,
            rel=1e-6)
        # a prompt token's query attends half its prompt in the mean
        assert 0.02 < m["prefill_attended_kkeys_token"] < 0.104


def test_the_cell_and_its_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    conf = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "concurrent-turns-closed", 1)
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    assert "unchecked" in cell["why"]       # its own regime, 256k contexts
    assert len(b["per_layer"]) <= 128 and len(b["workloads"]) <= 24
    cfg = traffic.load_json("configs", CONFIG)
    # NOTHING is reduced: every key of the catalog row as published
    assert cfg["reduced"] == {} and conf["reduced"] == []
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    assert conf["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert cfg["family"] == "jamba"
    a = cfg["assumed"]
    for point in ("layer_order", "feed_forward", "inner_norms", "biases",
                  "mamba_init", "positions", "attention", "state_layout",
                  "initializer_range", "weights"):
        assert point in a, point
    assert "whole model" in cfg["deployment"]
    from benchmarks.families import jamba
    assert jamba.counts(cfg)["total_params"] == cfg["parameters"] \
        == 3_029_337_472 == 26 * 104_161_472 + 2 * 76_682_240 + 167_774_720
    mixers = [jamba.mixer_of(l, cfg) for l in range(28)]
    assert [l for l, m in enumerate(mixers) if m == "full"] == [7, 21]
    assert mixers.count("ssm") == 26
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["kv_quant"],
            sv["param_dtype"], sv["sampling"]) == (
        128, 4608, "none", "bfloat16", "greedy")
    ps = sv["page_size"]
    # full reservation of both attention layers' pages (+ spare pages)
    assert sv["num_pages"] >= 128 * 4608 // ps
    assert sv["max_len"] % sv["prefill_chunk"] == 0 \
        and sv["prefill_chunk"] % ps == 0
    assert 0 <= sv["max_prefilling"] <= 128
    tf = traffic.load_traffic(cell["traffic"])
    assert tf["kind"] == "closed_loop" and tf["outstanding"] == 128 \
        == sv["num_slots"]
    assert (tf["strata"], tf["check_requests"], tf["trace_s"],
            tf["drain_limit_s"], tf["ramp_s"]) == (4, 4, 5.0, 0.0, 20.0)
    p, o = tf["prompt_lens"], tf["output_lens"]
    assert len(p) == 64 == len(o) and p == sorted(p) and o == sorted(o)
    assert p == [round(256 * 16 ** (i / 63)) for i in range(64)]
    assert (p[0], p[63]) == (256, 4096) and sum(x > 2048 for x in p) == 16
    assert o == [round(256 + 128 * i / 63) for i in range(64)]
    assert (min(o), max(o)) == (256, 384) and max(o) <= 384  # ROW_PAD
    assert sum(p) / 64 == pytest.approx(1398, abs=1)
    assert sum(o) / 64 == pytest.approx(320, abs=1)
    assert max(p) + max(o) <= sv["max_len"]
    assert sum(p) / (sum(p) + sum(o)) == pytest.approx(0.814, abs=0.001)
    # what is reported IN the cell, wherever the entries stand and
    # whichever other cells share them: the 28 joined and the 4 brought
    mine = [m for m in b["per_layer"] if CELL in m["workloads"]]
    assert {m["name"] for m in mine} == JOINED | BROUGHT
    assert all(m["workloads"] == [CELL] for m in mine
               if m["name"] in BROUGHT)
    with open(REHEARSAL) as f:      # exactly those are rehearsed
        rehearsed = json.load(f)["per_layer"]
    assert sorted(m["name"] for m in rehearsed) == \
        sorted(m["name"] for m in mine)
    assert all(m["workloads"] == [TINY] for m in rehearsed)
    for m in mine:
        assert m["moves"] == "serve_tokens_per_s"
        spec = traffic.load_json("metrics", m["name"])
        assert spec["reduce"]["rule"] in trace.RULES
        if m["name"] in ROOFLINES:
            assert (m["unit"], m["layer"]) == ("%", "Kernels")
            assert callable(getattr(jamba, spec["reduce"]["cost"]))
            assert spec["reduce"]["cost"] == ROOFLINES[m["name"]]
    e2e = next(m for m in b["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.055


def _tiny_engine():
    import jax
    from benchmarks.families import jamba as fam
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.engine import ServingEngine
    cfg = dict(traffic.load_json("configs", "tiny-jamba"))
    model = fam.build_model(cfg, cfg["serving"])
    params = model.init(jax.random.key(1))
    return cfg, fam, model, params, lambda p: ServingEngine(
        model, p, fam.serve_config(cfg), registry=MetricsRegistry())


def test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs():
    """The cell's device metrics that select by scope each find something
    to read in the programs the engine compiles for the tiny
    configuration: a trace with every instruction of every program once,
    a microsecond each.  What the scopes say of the program; no time of a
    device.  The standing `phi4f.*ssm*` entries read the mixer WITHOUT
    its inner norms, which stand under `ssm_norm`, the new entries'."""
    from benchmarks import peaks
    from benchmarks import run as runner
    cfg, fam, _, params, make = _tiny_engine()
    engine = make(params)
    texts = [low.compile().as_text()
             for low in engine.lower_programs().values()]
    engine.close()
    dev, ops, mods, t = "/device:TPU:0", [], [], 0.0
    for text in texts:
        module, index = trace.scope_index(text)
        start = t
        for name in index:
            ops.append(trace.Event(name, t, 1e-6))
            t += 1e-6
        mods.append(trace.Event(module + "(1)", start, t - start))
        t += 1e-3
    counters = {"serve.ssm_state_bytes": 1e6, "serve.decode_steps": 3,
                "serve.decode_slot_steps": 9, "serve.prefill_tokens": 64,
                "serve.prefill_chunks": 4}
    ctx = {"config": cfg, "family": fam, "hlo_texts": texts,
           "counters": {}, "registry": {},
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "window_counts": {"steps": 1, "counters": counters}}
    cell = runner.load_cell(BENCHMARK, CELL)
    by_scope = [m["name"] for m in cell["per_layer"]
                if runner.metric_spec(m["name"])["reduce"]["rule"]
                in ("scope_ms", "scope_roofline_pct")]
    assert set(by_scope) == {
        "phi4f.prefill_ssm_dev_ms", "phi4f.decode_ssm_dev_ms",
        "phi4f.ssm_scan_roofline", "phi4f.ssm_state_roofline",
        "decode_full_attn_dev_ms", "mimo.prefill_full_attn_dev_ms",
        "decode_kv_write_dev_ms", "jamba.decode_ssm_norm_dev_ms",
        "jamba.prefill_ssm_norm_dev_ms", "jamba.prefill_ssm_scan_dev_ms",
        "decode_mlp_dev_ms", "decode_unscoped_dev_ms"}
    tr = trace.Trace({dev: ops}, {dev: mods}, [])
    value = {}
    for name in by_scope:
        value[name] = trace.reduce_metric(runner.metric_spec(name), tr,
                                          (0.0, t), ctx)
        assert value[name] is not None and value[name] > 0, name
    # the scan's scope alone is a part of the mixer's
    assert value["jamba.prefill_ssm_scan_dev_ms"] \
        < value["phi4f.prefill_ssm_dev_ms"]


def test_cost_functions_count_what_the_model_needs():
    from benchmarks.families import jamba as fam
    cfg = traffic.load_json("configs", CONFIG)
    c = fam.counts(cfg)
    # 26 mixers' four products, 2 attention layers', 28 MLPs, the head
    assert c["matmul_params"] == 26 * 41_123_840 + 2 * 13_762_560 \
        + 28 * 62_914_560 + 2560 * 65536
    assert fam.ssm_state_bytes_per_slot(cfg) == 9_318_400
    w = {"counters": {
        "serve.decode_context_tokens": 1000.0, "serve.decode_slot_steps": 4,
        "serve.ssm_state_bytes": 2 * 4 * 9_318_400.0, "serve.decode_steps": 1,
        "serve.prefill_tokens": 2048.0, "serve.prefill_chunks": 4,
        "serve.prefill_attended_keys": 2048 * 400.0}}
    paged = fam.paged_attn_cost(cfg, w)
    # 2 layers read every position's ONE K head and ONE V head of 128
    assert paged["bytes"] == 2.0 * 2 * (2 * 1000 * 128 + 4 * 20 * 2 * 128)
    assert paged["ops"] == 2 * 4.0 * 1000 * 20 * 128
    state = fam.ssm_state_cost(cfg, w)
    assert state["bytes"] == 2 * 4 * 9_318_400.0     # the state alone
    assert state["ops"] == 26 * 4 * 7.0 * 16 * 5120
    scan = fam.ssm_chunk_cost(cfg, w)
    assert scan["ops"] == 26 * 7.0 * 16 * 5120 * 2048
    assert scan["bytes"] == 26 * (2048 * (2 * (5120 + 32) + 8 * 5120)
                                  + 4 * 2 * 4 * 16 * 5120)
    chunk = fam.chunk_attn_cost(cfg, w)
    assert chunk["ops"] == 2 * 4.0 * 20 * 128 * 2048 * 400
    assert chunk["bytes"] == 2.0 * 2 * (2048 * 2 * 20 * 128
                                        + 400 * 4 * 2 * 128)
    for fn in (fam.paged_attn_cost, fam.ssm_state_cost, fam.ssm_chunk_cost,
               fam.chunk_attn_cost):
        assert fn(cfg, {"counters": {}}) is None


def test_a_control_comes_out_not_correct():
    """The comparison that decides `correct` (reference.check_stream), on
    streams the tiny engine served: correct against the reference as it
    is, NOT correct against the reference without the three inner norms
    (what a mixer that dropped them would compute).  Every W_out is
    scaled by 16, in the program and the reference alike: at 192 channels
    and weights of std 0.02 a mixer's output is a small part of the
    residual stream, and the check (which asks whether the served token
    is the reference's largest logit, to 16 bf16 ulps) sees through it
    what it sees at 5,120 channels only once the mixers weigh in."""
    import jax
    from benchmarks import reference
    from hetu_tpu.serving.request import Request
    cfg, fam, _, params, make = _tiny_engine()
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: 16.0 * a if any(
            getattr(k, "key", None) == "w_out" for k in path) else a, params)
    engine = make(params)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
                    .astype(np.int32), max_new_tokens=24, arrival_t=0.0)
            for i, n in enumerate((81, 97, 104))]
    results = {r.rid: r for r in engine.run(reqs)}
    engine.close()

    def control(params, ids, rows, cfg):
        return fam.logits_at(params, ids, rows, cfg, "no_inner_norms")
    good, bad = [], []
    for req in reqs:
        toks = results[req.rid].tokens
        good.append(reference.check_stream(
            fam.logits_at, params, cfg, req.prompt, toks, 128))
        bad.append(reference.check_stream(
            control, params, cfg, req.prompt, toks, 128))
    assert all(s["ok"] for s in good), good
    assert not any(s["ok"] for s in bad), bad


def test_the_parent_fails_at_once_without_the_family_module(tmp_path):
    """What the driver's try of the new cell on the parent meets: a
    configuration whose family module is not there (or, as at the parent
    of PR 47, whose import of `hetu_tpu.models.jamba` fails) exits 2
    before any device is touched."""
    reg = json.load(open(REHEARSAL))
    cfg = dict(traffic.load_json("configs", "tiny-jamba"),
               family="jamba_not_there")
    path = tmp_path / "no-jamba.json"
    path.write_text(json.dumps(cfg))
    reg["configs"][0]["file"] = str(path)
    reg_path = tmp_path / "reg.json"
    reg_path.write_text(json.dumps(reg))
    p = run("--rehearse", "--benchmark-file", str(reg_path),
            "--workload", TINY, "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "jamba_not_there" in p.stderr
