"""The sixth family, `families/bailing_hybrid.py` (inclusionAI
Ling-3.0-flash: linear-attention layers whose state a sequence lies
beside the pages of the one latent-attention layer, group-limited sigmoid
routing with one group of experts held, a shared expert), through the
harness on the CPU: `rehearsal-ling.json`'s `tiny-ling-long-tail` cell
under `--rehearse`, the cell's files and numbers as ISSUE 41 gives them,
the near-tie passes at BOTH edges of the choice, and the two controls: a
program that takes a chunk's padding rows into the state (not correct by
the comparison that decides `correct`), and the state kept in bfloat16
(which that comparison does not see: not correct by the state's rows).

It asserts that the cell's entries are PRESENT in `BENCHMARK.json`, not
that they are the last: a later PR appends."""
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

REHEARSAL = os.path.join(HERE, "rehearsal-ling.json")
CELL = "ling-3.0-flash-serve-long-tail"
CONFIG = "ling-3.0-flash-ep8-depth7"
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size", "expert_swiglu_limit_list",
           "share_expert_swiglu_limit_list"]
# what the cell reports without a device plane (a rule file's `device`
# false): the entries the other serving cells have, the cell appended to
# their lists, and the one counter entry of its own
COUNTER_METRICS = {
    "stall_pct", "compiles_in_window", "host_work_ms_step",
    "prefill_token_share_inside", "engine_empty_pct", "stalled_steps_pct",
    "fetch_wait_ms_step", "decode_overlap_pct", "decode_ctx_ktokens_step",
    "decode_batch_inside", "experts_hit_per_layer_step",
    "local_assignment_pct", "experts_extra_blocks_pct",
    "ling.kda_state_gb_step", "peak_hbm_gb"}
# the entries that exist for this cell alone: what no other family has
OWN = {"ling.decode_kda_dev_ms", "ling.prefill_kda_dev_ms",
       "ling.kda_state_roofline", "ling.kda_chunk_roofline",
       "ling.kda_state_gb_step"}
ROOFLINES = {"ling.kda_state_roofline": "kda_state_cost",
             "ling.kda_chunk_roofline": "kda_chunk_cost",
             "paged_latent_attn_roofline": "paged_latent_attn_cost",
             "latent_chunk_attn_roofline": "latent_chunk_attn_cost",
             "grouped_matmul_roofline": "grouped_matmul_cost"}


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
def test_tiny_ling_rehearses_correct(trace_on):
    line = last_line(run(
        "--rehearse", "--benchmark-file", REHEARSAL, "--workload",
        "tiny-ling-long-tail", "--seed", "4100000019", "--seconds", "3",
        "--trace", str(trace_on)))
    # prompts of 9-104 (1-7 chunks of 16, most no multiple of 4) and
    # answers of 10-24 over 4 slots that are reused all through the
    # window; checked against the family's own forward under the near-tie
    # passes the full configuration runs
    assert line["correct"] and not line["failed"]
    got = {k.removeprefix("cpu_rehearsal.") for k in line["metrics"]}
    assert got == (COUNTER_METRICS if trace_on
                   else {"serve_tokens_per_s", "setup_s"})
    if trace_on:
        m = {k.removeprefix("cpu_rehearsal.").removeprefix("ling."):
             v["value"] for k, v in line["metrics"].items()}
        assert 0 < m["experts_hit_per_layer_step"] <= 8
        # 8 of 32 experts held: a quarter of the pairs, near enough
        assert 15 < m["local_assignment_pct"] < 35
        # 2 x rows x 6 layers x (4 x 16 x 16 + 3 x 192) x 4 B
        per_row = 2 * 6 * (4 * 16 * 16 + 3 * 192) * 4e-9
        assert 0 < m["kda_state_gb_step"] <= 4 * per_row * 1.001
        assert m["kda_state_gb_step"] == pytest.approx(
            m["decode_batch_inside"] * per_row, rel=1e-6)
        assert m["compiles_in_window"] == 0


def test_the_cell_and_its_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    conf = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "long-tail-closed", 1)
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    assert "8x" in cell["why"]          # attention's share against experts
    assert len(b["per_layer"]) <= 128 and len(b["workloads"]) <= 24
    cfg = traffic.load_json("configs", CONFIG)
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"]) \
        == sorted(REDUCED)
    # every key of the catalog row that is not reduced, as published:
    # every width among them
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    assert conf["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["kv_lora_rank"], cfg["qk_rope_head_dim"],
            cfg["qk_nope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["router_experts"], cfg["num_experts_per_tok"],
            cfg["n_group"], cfg["topk_group"], cfg["layer_group_size"],
            cfg["short_conv_kernel_size"], cfg["kda_lower_bound"]) == (
        2560, 32, 128, 512, 64, 128, 128, None, 6144, 768, 768, 512, 8, 8,
        4, 6, 4, -5)
    n = cfg["num_hidden_layers"]
    assert (n, cfg["first_k_dense_replace"]) == (7, 1)
    # the published rules then give [KDA+dense, KDA+experts x 4,
    # MLA+experts, KDA+experts]
    kda = [(l + 1) % cfg["layer_group_size"] != 0 for l in range(n)]
    assert kda == [True] * 5 + [False, True]
    assert cfg["expert_swiglu_limit_list"] == [0] * n \
        == row["config"]["expert_swiglu_limit_list"][:n]
    assert cfg["share_expert_swiglu_limit_list"] == [0] * n \
        == row["config"]["share_expert_swiglu_limit_list"][:n]
    assert (cfg["num_experts"], cfg["first_expert"], cfg["vocab_size"]) \
        == (64, 0, 19648)
    # one routing GROUP a chip, an eighth of the vocabulary
    assert cfg["num_experts"] == cfg["router_experts"] // cfg["n_group"]
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    for point in ("layer_pattern", "kda_heads", "kda_equations",
                  "kda_decay_init", "mla_equations", "rotation", "experts",
                  "swiglu_limits", "mtp", "e_score_correction_bias",
                  "initializer_range", "latent_lanes", "router_tie_logit"):
        assert point in cfg["assumed"], point
    assert "EP8" in conf["why"] and "8 chips" in cfg["deployment"]
    assert "nothing stands in" in cfg["deployment"]
    from benchmarks.families import bailing_hybrid
    assert bailing_hybrid.counts(cfg)["total_params"] == cfg["parameters"] \
        == 2_866_268_096
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["kv_quant"],
            sv["param_dtype"], sv["sampling"]) == (
        32, 32768, "none", "bfloat16", "greedy")
    # the latent pool at full reservation (+ 16 spare pages)
    ps = sv["page_size"]
    assert sv["num_pages"] == 32 * 32768 // ps + 16
    assert sv["max_len"] % sv["prefill_chunk"] == 0 \
        and sv["prefill_chunk"] % ps == 0
    assert "13.0 MB a slot" in sv["note"]
    tf = traffic.load_traffic(cell["traffic"])
    assert tf["kind"] == "closed_loop" and tf["outstanding"] == 32
    assert (tf["ramp_s"], tf["strata"], tf["check_requests"], tf["trace_s"],
            tf["drain_limit_s"]) == (16.0, 4, 4, 5.0, 0.0)
    p, o = tf["prompt_lens"], tf["output_lens"]
    assert len(p) == 64 == len(o) and p == sorted(p) and o == sorted(o)
    assert p == [round(1024 * 31.25 ** (i / 63)) for i in range(64)]
    assert (p[0], p[63]) == (1024, 32000)
    assert (min(o), max(o)) == (256, 384) and max(o) <= 384  # ROW_PAD
    assert sum(p) / 64 == pytest.approx(9119, abs=1)
    assert 5500 < float(np.median(p)) < 5800
    assert sum(x > 20000 for x in p) == 9
    assert sum(o) / 64 == pytest.approx(320, abs=1)
    assert max(p) + max(o) <= sv["max_len"]
    assert sum(p) / (sum(p) + sum(o)) == pytest.approx(0.966, abs=0.001)
    # what is reported IN the cell
    mine = [m for m in b["per_layer"] if CELL in m["workloads"]]
    assert COUNTER_METRICS < {m["name"] for m in mine}
    # one entry a quantity (PR 40): a rule another cell's entry already
    # states is that entry, this cell appended to its list
    assert {m["name"] for m in mine if m["workloads"] == [CELL]} == OWN \
        == {m["name"] for m in b["per_layer"]
            if m["name"].startswith("ling.")}
    assert len(mine) == 33
    with open(REHEARSAL) as f:      # exactly those are rehearsed
        rehearsed = json.load(f)["per_layer"]
    assert sorted(m["name"] for m in rehearsed) == \
        sorted(m["name"] for m in mine)
    assert all(m["workloads"] == ["tiny-ling-long-tail"] for m in rehearsed)
    for m in mine:
        assert m["moves"] == "serve_tokens_per_s"
        spec = traffic.load_json("metrics", m["name"])
        assert spec["reduce"]["rule"] in trace.RULES
        if m["name"] in ROOFLINES:
            assert (m["unit"], m["layer"]) == ("%", "Kernels")
            assert callable(getattr(bailing_hybrid, spec["reduce"]["cost"]))
            assert spec["reduce"]["cost"] == ROOFLINES[m["name"]]
    assert set(ROOFLINES) <= {m["name"] for m in mine}
    e2e = next(m for m in b["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.055
    assert 0 < cfg["router_tie_logit"] <= 0.2


def _tiny_engine():
    import jax
    from benchmarks.families import bailing_hybrid as fam
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.engine import ServingEngine
    cfg = dict(traffic.load_json("configs", "tiny-ling"))
    model = fam.build_model(cfg, cfg["serving"])
    params = model.init(jax.random.key(1))
    return cfg, fam, model, params, lambda p: ServingEngine(
        model, p, fam.serve_config(cfg), registry=MetricsRegistry())


def test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs():
    """The cell's device metrics that select by scope (`scope_ms`, and the
    harness's `scope_roofline_pct`) each find something to read in the
    programs the engine compiles for the tiny configuration: a trace with
    every instruction of every program once, a microsecond each.  What
    the scopes say of the program; no time of a device.  A program
    WITHOUT the scopes (what the parent's other families compile) gives
    the family's rule nothing to read, and it returns None."""
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
    counters = {"serve.kda_state_bytes": 1e6, "serve.decode_steps": 3,
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
    assert {"ling.decode_kda_dev_ms", "decode_mla_dev_ms",
            "ling.prefill_kda_dev_ms", "prefill_mla_dev_ms",
            "prefill_experts_dev_ms", "decode_experts_dev_ms",
            "decode_shared_expert_dev_ms", "decode_unscoped_dev_ms",
            "ling.kda_state_roofline", "ling.kda_chunk_roofline"} \
        <= set(by_scope)
    tr = trace.Trace({dev: ops}, {dev: mods}, [])
    for name in by_scope:
        value = trace.reduce_metric(runner.metric_spec(name), tr,
                                    (0.0, t), ctx)
        assert value is not None and value > 0, name
    # nothing counted, or a program without the scopes: nothing to read
    spec = runner.metric_spec("ling.kda_state_roofline")
    empty = dict(ctx, window_counts={"steps": 1, "counters": {}})
    empty.pop("scope_table", None)
    assert trace.reduce_metric(spec, tr, (0.0, t), empty) is None
    other = dict(spec, reduce=dict(spec["reduce"], phase=["no_such_scope"]))
    assert trace.reduce_metric(other, tr, (0.0, t), dict(ctx)) is None


def test_cost_functions_count_what_the_model_needs():
    from benchmarks.families import bailing_hybrid as fam
    cfg = traffic.load_json("configs", CONFIG)
    kda = 63_045_632 + 32 + 4096 + 128      # a KDA mixer with its vectors
    assert fam._kda_params(cfg) == 63_045_632
    assert fam._mla_params(cfg) == 31_965_184
    assert fam._kinds(cfg) == (6, 1) and kda > 0
    c = fam.counts(cfg)
    assert c["attn_width"] == 32 * 192              # the one MLA layer
    # decode: 31 rows a step for 10 steps
    per_row = 2 * 6 * (2_097_152 + 73_728)
    w = {"counters": {"serve.kda_state_bytes": 310 * per_row,
                      "serve.decode_steps": 10,
                      "serve.decode_slot_steps": 310,
                      "serve.decode_context_tokens": 310 * 9000,
                      "serve.prefill_tokens": 10240,
                      "serve.prefill_chunks": 10,
                      "serve.moe_expert_hits": 100,
                      "serve.moe_local_assignments": 400}}
    cost = fam.kda_state_cost(cfg, w)
    assert cost["bytes"] == 310 * per_row + 10 * 6 * 2 * 63_045_632
    # the state is two thirds of it, the weights a third... near enough
    assert 0.45 < 310 * per_row / cost["bytes"] < 0.6
    lat = fam.paged_latent_attn_cost(cfg, w)
    assert lat["bytes"] == 2.0 * (576 * 310 * 9000 + 310 * 32 * (576 + 512))
    chunk = fam.kda_chunk_cost(cfg, w)
    # a block of the program's 16 positions a head: 8 L^2 d + 6 L d^2
    per_block = 8 * 16 * 16 * 128 + 6 * 16 * 128 * 128
    assert chunk["ops"] == 6 * 32 * per_block * 10240 / 16
    assert chunk["bytes"] == 6 * 32 * 4.0 * (10240 * 641 + 10 * 2 * 128 * 128)
    assert fam.grouped_matmul_cost(cfg, w)["ops"] == 2.0 * 400 * 3 * 2560 * 768
    for fn in (fam.kda_state_cost, fam.kda_chunk_cost,
               fam.paged_latent_attn_cost, fam.grouped_matmul_cost):
        assert fn(cfg, {"counters": {}}) is None


#: the KDA state a finished request leaves in its slot's rows against the
#: reference's scan over the same stream, relative (rms over the six
#: layers), in float32 on the CPU: the program as it stands reads 1.0e-6
#: to 1.1e-6 on three streams, the state rounded to bfloat16 after every
#: chunk and decode step 1.5e-2 to 1.7e-2 (this file's control, PR 41); the
#: limit lies between, a factor of 100 from either.  On the chip the
#: program's bfloat16 projections move k, v and the gates by as much as
#: the control moves the state (every KDA mixer stands 0.5-0.6% from the
#: reference: PERF.md s6), so this comparison belongs where the program
#: computes in float32: the same code, held in a tier-1 test.
STATE_RTOL = 1e-4


def _state_errors(fam, params, cfg, engine, plan, results):
    """Each finished request's KDA state (its slot's rows of the pool,
    which stand until the slot is used again) against the reference's
    scan over prompt + tokens[:-1], every position fed: relative rms over
    the KDA layers; the slot is the one whose rows stand nearest."""
    import jax
    import jax.numpy as jnp
    rows = np.asarray(engine.pool.state[0], np.float64)  # [L, slots+1, ..]

    @jax.jit
    def scan(p, seq):
        kept = []
        fam.hidden_states(p, seq, cfg, states=kept,
                          keep_at=seq.shape[0] - 1)
        return [k for k in kept if not isinstance(k, tuple)]   # KDA's
    out = []
    for i, (ids, _) in enumerate(plan):
        seq = jnp.asarray(np.concatenate([ids, results[i].tokens[:-1]]))
        want = np.stack([np.asarray(k, np.float64)
                         for k in scan(params, seq)])
        err = np.sqrt(((rows - want[:, None]) ** 2).mean((0, 2, 3, 4))
                      / (want ** 2).mean())
        out.append(float(err.min()))
    return out


@pytest.mark.parametrize("control", ["unmasked", "bf16_state"])
def test_a_control_comes_out_not_correct(control):
    """The two controls of ISSUE 41 on this family, the tiny model served
    in float32, which as it stands comes out correct UNDER THE NEAR-TIE
    PASSES with every token the reference's argmax, no gap, and the state
    it leaves within `STATE_RTOL` of the reference's scan.
    `unmasked`: a program whose chunks take their PADDING rows into the
    state (every row of a chunk valid) is not correct by
    `reference.check_stream` in any stream whose prompt is no multiple of
    the chunk (on the chip: 4 of 4 streams, gaps of 3.5-4.7 against 0.25).
    `bf16_state`: the state rounded to bfloat16 after every chunk and
    decode step.  `check_stream` does NOT see it, here or on the chip
    (4 of 4 streams ok; the same four streams' largest gaps 0.044, 0.060,
    0.085, 0.067 as the program stands and 0.039, 0.146, 0.142, 0.077
    under the control, of 0.25: the delta rule corrects what it reads
    back, and a served token is held to logits; PERF.md s6, PR 41), so
    the state's precision is held HERE, on the rows of the pool: the
    control is not correct by `STATE_RTOL`.  (The cell's lower-precision
    controls that `check_stream` does fail are weights in e4m3, all of
    them or the KDA mixers' alone: 4 of 4 streams each, PERF.md s6.)"""
    import jax
    import jax.numpy as jnp
    from benchmarks import reference
    from hetu_tpu.models.bailing_hybrid import KDAttention
    from hetu_tpu.serving.request import Request
    cfg, fam, model, params, make = _tiny_engine()
    assert cfg["router_tie_logit"] == 0.02
    rng = np.random.default_rng(2)
    plan = [(rng.integers(0, cfg["vocab_size"], n).astype(np.int32), 48)
            for n in (33, 21, 40)]

    def served():
        engine = make(params)
        res = {r.rid: r for r in engine.run(
            [Request(rid=i, prompt=ids, max_new_tokens=n)
             for i, (ids, n) in enumerate(plan)])}
        streams = [reference.check_stream(fam.logits_at, params, cfg, ids,
                                          res[i].tokens,
                                          cfg["serving"]["max_len"])
                   for i, (ids, _) in enumerate(plan)]
        return streams, _state_errors(fam, params, cfg, engine, plan, res)
    plain, plain_state = served()
    assert all(s["ok"] and s["argmax_equal"] == s["tokens"]
               and s["max_gap"] == 0.0 for s in plain)
    assert max(plain_state) < STATE_RTOL / 10
    chunk, step = KDAttention.state_chunk, KDAttention.state_step
    patch = pytest.MonkeyPatch()
    try:
        if control == "unmasked":
            patch.setattr(
                KDAttention, "state_chunk", lambda self, p, hn, st, s, valid:
                chunk(self, p, hn, st, s, jnp.full_like(valid, hn.shape[1])))
        else:
            # (not an astype round trip: XLA on the TPU drops one)
            coarse16 = lambda st: (jax.lax.reduce_precision(  # noqa: E731
                st[0], exponent_bits=8, mantissa_bits=7), st[1])
            patch.setattr(KDAttention, "state_chunk", lambda self, *a: (
                lambda out, st: (out, coarse16(st)))(*chunk(self, *a)))
            patch.setattr(KDAttention, "state_step", lambda self, *a: (
                lambda out, st: (out, coarse16(st)))(*step(self, *a)))
        streams, state = served()
    finally:
        patch.undo()
    if control == "unmasked":
        assert not any(s["ok"] for s in streams)
    else:
        assert all(s["ok"] for s in streams)     # the blind spot, recorded
        assert min(state) > 10 * STATE_RTOL
    print(control, [s["max_gap"] for s in streams], plain_state, state)


def test_near_tie_passes_know_both_edges_and_change_only_their_rows():
    """`logits_at` under `router_tie_logit`: a row none of whose layers
    has a held expert (or the held group) within the margin is the plain
    forward's own; a row with one keeps the plain forward's argmax and is
    nowhere under the plain forward's standing; both edges are met: some
    token's nearest edge is the GROUP's, some token's the expert's; and
    the passes never see a served token."""
    import inspect
    import jax
    import jax.numpy as jnp
    from benchmarks.families import bailing_hybrid as fam
    assert list(inspect.signature(fam.logits_at).parameters) == [
        "params", "ids", "rows", "cfg"]
    cfg = dict(traffic.load_json("configs", "tiny-ling"))
    del cfg["router_tie_logit"]
    model = fam.build_model(cfg, cfg["serving"])
    params = model.init(jax.random.key(3))
    ids = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg["vocab_size"], 48).astype(np.int32))
    # consecutive rows, the last repeated: `reference.check_stream`'s
    rows = jnp.concatenate([jnp.arange(20, 44), jnp.full((4,), 43)])
    plain = np.asarray(fam.logits_at(params, ids, rows, cfg))
    whole = np.asarray(fam.logits_at(params, ids, jnp.arange(48), cfg))
    np.testing.assert_allclose(plain, whole[np.asarray(rows)], atol=1e-5)
    tied = dict(cfg, router_tie_logit=0.02)
    lg, moved, margins = fam.logits_by_pass(params, ids, rows, tied)
    # the plain pass + every set of one or two of the six expert layers
    masks = fam.pass_masks(tied)
    assert lg.shape[0] == moved.shape[0] == len(masks) == 1 + 6 + 15
    assert masks[0] == 0 and all(
        1 <= bin(m).count("1") <= fam.NEAR_TIE_LAYERS == 2 for m in masks[1:])
    np.testing.assert_allclose(np.asarray(lg[0]), plain, atol=1e-5)
    # a pass that tilts nothing (margin 0) is the plain pass, through the
    # rows-only walk of every layer: the KDA layers from the kept state
    lg0, moved0, _ = fam.logits_by_pass(params, ids, rows,
                                        dict(cfg, router_tie_logit=1e-9))
    assert not np.asarray(moved0).any()
    np.testing.assert_allclose(np.asarray(lg0[-1]), plain, atol=2e-5)
    got = np.asarray(fam.logits_at(params, ids, rows, tied))
    touched = np.asarray(moved.any(0))
    assert touched.any() and not touched.all()
    assert touched[np.asarray(margins).min(0) < 0.02].all()
    np.testing.assert_array_equal(got[~touched], plain[~touched])
    assert (got.argmax(-1) == plain.argmax(-1)).all()
    top = plain.max(-1, keepdims=True)
    assert (got >= plain - 1e-6).all() and (got <= top).all()
    # both edges, on the gate alone
    x = jnp.asarray(np.random.default_rng(5).standard_normal((512, 64)),
                    jnp.float32)
    mp = params["model"]["layers"]["layer_1"]["mlp"]
    scores = jax.nn.sigmoid(x @ mp["w_gate"])
    v = scores + mp["e_score_correction_bias"]
    wide = dict(cfg, router_tie_logit=0.3)
    push, lift, move, _ = fam._tilt_nearest_edge(v, scores, wide, 8,
                                                 jnp.bool_(True))
    by_group = np.asarray(jnp.any(lift != 0, -1))
    by_expert = np.asarray(jnp.any(push != 0, -1))
    assert by_group.any() and by_expert.any()
    assert not (by_group & by_expert).any()
    assert (np.asarray(move) == (by_group | by_expert)).all()
    # the held group (experts 8-15: group 1) changes sides where the
    # group's edge was the nearer, and there alone; a pushed expert
    # swaps exactly one chosen expert for the next candidate
    idx0, _, kept0, _ = fam._choose(v, wide)
    idx1, _, kept1, _ = fam._choose(v, wide, lift, push)
    turned = np.asarray(kept0[:, 1] != kept1[:, 1])
    assert (turned == by_group).all()
    assert not np.asarray(kept0 != kept1)[:, [0, 2, 3]][by_expert].any()
    for a, b_, one in zip(np.asarray(idx0), np.asarray(idx1), by_expert):
        if one:         # one expert out, the next one in; one of them held
            swapped = set(a) ^ set(b_)
            assert len(swapped) == 2 and any(8 <= e < 16 for e in swapped)


def test_the_parent_fails_at_once_without_the_family_module(tmp_path):
    """What the driver's try of the new cell on the parent meets: a
    configuration whose family module is not there (or, as at the parent
    of PR 41, whose import of `hetu_tpu.models.bailing_hybrid` fails)
    exits 2 before any device is touched."""
    reg = json.load(open(REHEARSAL))
    cfg = dict(traffic.load_json("configs", "tiny-ling"),
               family="bailing_hybrid_not_there")
    path = tmp_path / "no-ling.json"
    path.write_text(json.dumps(cfg))
    reg["configs"][0]["file"] = str(path)
    reg_path = tmp_path / "reg.json"
    reg_path.write_text(json.dumps(reg))
    p = run("--rehearse", "--benchmark-file", str(reg_path),
            "--workload", "tiny-ling-long-tail", "--seed", "1",
            "--seconds", "1", "--trace", "0")
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "bailing_hybrid_not_there" in p.stderr
