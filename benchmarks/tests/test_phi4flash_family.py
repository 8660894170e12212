"""The seventh family, `families/phi4flash.py` (microsoft
Phi-4-mini-flash-reasoning: Mamba-1 state by slot beside window and full
pages, differential attention, fourteen layers that keep no cache of
their own, a prefill that stops at the full layer for every row but the
last), through the harness on the CPU: `rehearsal-phi4flash.json`'s
`tiny-phi4flash-reasoning-turns` cell under `--rehearse`, the cell's files
and numbers as ISSUE 43 gives them, the scope rules against the programs,
the cost functions, and a control: a reference whose cross layers are
masked to the window, by the comparison that decides `correct`.

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

REHEARSAL = os.path.join(HERE, "rehearsal-phi4flash.json")
TINY = "tiny-phi4flash-reasoning-turns"
CELL = "phi-4-mini-flash-serve-reasoning-turns"
CONFIG = "phi-4-mini-flash-reasoning"
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
# what the cell reports without a device plane (a rule file's `device`
# false)
COUNTER_METRICS = ENGINE_LOOP_COUNTERS | {
    "compiles_in_window", "decode_ctx_ktokens_step",
    "decode_batch_inside", "decode_window_ctx_ktokens_step",
    "window_pages_released_step", "mimo.prefill_attended_kkeys_token",
    "phi4f.prefill_tail_rows_pct", "phi4f.shared_kv_gb_step"}
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
def test_tiny_phi4flash_rehearses_correct(trace_on):
    line = last_line(run(
        "--rehearse", "--benchmark-file", REHEARSAL, "--workload", TINY,
        "--seed", "4300000019", "--seconds", "3", "--trace", str(trace_on)))
    # prompts of 9-104 (1-7 chunks of 16, two at a chunk's edge, most past
    # the window of 24) and answers of 10-24 over 4 slots that are reused
    # all through the window, 2 of them in prefill at most
    assert line["correct"] and not line["failed"]
    got = {k.removeprefix("cpu_rehearsal.") for k in line["metrics"]}
    assert got == (COUNTER_METRICS if trace_on
                   else {"serve_tokens_per_s", "setup_s"})
    if trace_on:
        m = {k.removeprefix("cpu_rehearsal.").removeprefix("phi4f."):
             v["value"] for k, v in line["metrics"].items()}
        assert m["compiles_in_window"] == 0
        # one tail row a prompt: 8 prompts of 460 tokens a block
        assert m["prefill_tail_rows_pct"] == pytest.approx(
            100 * 8 / 460, rel=0.25)
        # two cross layers read what the full layer holds for each row
        assert m["shared_kv_gb_step"] == pytest.approx(
            2 * 1e3 * m["decode_ctx_ktokens_step"] * 5.12e-6, rel=1e-6)
        assert m["window_pages_released_step"] > 0
        assert 0 < m["decode_window_ctx_ktokens_step"] \
            <= 1e-3 * 24 * m["decode_batch_inside"] * 1.001


def test_the_cell_and_its_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    conf = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reasoning-turns-closed", 1)
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    assert "unchecked" in cell["why"]       # its own regime, 8k-32k out
    assert len(b["per_layer"]) <= 128 and len(b["workloads"]) <= 24
    cfg = traffic.load_json("configs", CONFIG)
    # NOTHING is reduced: every key of the catalog row as published
    assert cfg["reduced"] == {} and conf["reduced"] == []
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert conf["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    a = cfg["assumed"]
    assert (a["mamba_d_state"], a["mamba_d_conv"], a["mamba_expand"],
            a["mamba_dt_rank"]) == (16, 4, 2, 160)
    with open(CATALOG) as f:        # the row that bears the sizes out
        jamba = next(r for r in map(json.loads, f)
                     if r["name"] == "AI21-Jamba2-3B")["config"]
    assert jamba["hidden_size"] == cfg["hidden_size"]
    assert all(a[k] == jamba[k] for k in (
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank"))
    for point in ("mamba_sizes", "layer_pattern", "biases", "positions",
                  "head_order", "differential", "mamba_init", "memory",
                  "state_layout", "initializer_range"):
        assert point in a, point
    assert "whole model" in cfg["deployment"]
    from benchmarks.families import phi4flash
    assert phi4flash.counts(cfg)["total_params"] == cfg["parameters"] \
        == 3_852_562_960
    mixers = [phi4flash.mixer_of(l, cfg) for l in range(32)]
    assert mixers == ["ssm", "window"] * 8 + ["ssm", "full"] \
        + ["gmu", "cross"] * 7
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["kv_quant"],
            sv["param_dtype"], sv["sampling"]) == (
        32, 24576, "none", "bfloat16", "greedy")
    ps = sv["page_size"]
    # full reservation: layer 17's pages, and a window of 512 + a page in
    # the eight window layers (+ spare pages)
    hold = -(-511 // ps) + 1
    assert sv["num_pages"][0] >= 32 * 24576 // ps
    assert sv["num_pages"][1] >= 32 * hold
    assert sv["max_len"] % sv["prefill_chunk"] == 0 \
        and sv["prefill_chunk"] % ps == 0
    assert 1 <= sv["max_prefilling"] <= 8
    tf = traffic.load_traffic(cell["traffic"])
    assert tf["kind"] == "closed_loop" and tf["outstanding"] == 32
    assert (tf["strata"], tf["check_requests"], tf["trace_s"],
            tf["drain_limit_s"]) == (4, 4, 5.0, 0.0)
    p, o = tf["prompt_lens"], tf["output_lens"]
    assert len(p) == 64 == len(o) and p == sorted(p) and o == sorted(o)
    assert p == [round(2048 * 11.71875 ** (i / 63)) for i in range(64)]
    assert (p[0], p[63]) == (2048, 24000)
    assert (min(o), max(o)) == (256, 384) and max(o) <= 384  # ROW_PAD
    assert sum(p) / 64 == pytest.approx(8984, abs=1)
    assert sum(o) / 64 == pytest.approx(320, abs=1)
    assert max(p) + max(o) <= sv["max_len"]
    assert sum(p) / (sum(p) + sum(o)) == pytest.approx(0.966, abs=0.001)
    # what is reported IN the cell, wherever the entries stand and
    # whichever other cells share them
    mine = [m for m in b["per_layer"] if CELL in m["workloads"]]
    assert COUNTER_METRICS | ENGINE_LOOP | {"decode_mlp_dev_ms"} \
        < {m["name"] for m in mine}
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
            assert callable(getattr(phi4flash, spec["reduce"]["cost"]))
            assert spec["reduce"]["cost"] == ROOFLINES[m["name"]]
    assert set(ROOFLINES) <= {m["name"] for m in mine}
    e2e = next(m for m in b["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.055


def _tiny_engine():
    import jax
    from benchmarks.families import phi4flash as fam
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.engine import ServingEngine
    cfg = dict(traffic.load_json("configs", "tiny-phi4flash"))
    model = fam.build_model(cfg, cfg["serving"])
    params = model.init(jax.random.key(1))
    return cfg, fam, model, params, lambda p: ServingEngine(
        model, p, fam.serve_config(cfg), registry=MetricsRegistry())


def test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs():
    """The cell's device metrics that select by scope each find something
    to read in the programs the engine compiles for the tiny
    configuration: a trace with every instruction of every program once,
    a microsecond each.  What the scopes say of the program; no time of a
    device.  A program WITHOUT the scopes gives the rules nothing to
    read."""
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
    assert {"phi4f.prefill_ssm_dev_ms", "phi4f.decode_ssm_dev_ms",
            "phi4f.decode_cross_attn_dev_ms", "phi4f.decode_gmu_dev_ms",
            "phi4f.ssm_scan_roofline", "phi4f.ssm_state_roofline",
            "decode_window_attn_dev_ms", "decode_full_attn_dev_ms",
            "mimo.prefill_window_attn_dev_ms",
            "mimo.prefill_full_attn_dev_ms", "trinity.prefill_attn_dev_ms",
            "decode_kv_write_dev_ms", "decode_mlp_dev_ms",
            "decode_unscoped_dev_ms"} <= set(by_scope)
    tr = trace.Trace({dev: ops}, {dev: mods}, [])
    for name in by_scope:
        value = trace.reduce_metric(runner.metric_spec(name), tr,
                                    (0.0, t), ctx)
        assert value is not None and value > 0, name
    spec = runner.metric_spec("phi4f.ssm_state_roofline")
    empty = dict(ctx, window_counts={"steps": 1, "counters": {}})
    empty.pop("scope_table", None)
    assert trace.reduce_metric(spec, tr, (0.0, t), empty) is None
    other = dict(spec, reduce=dict(spec["reduce"], phase=["no_such_scope"]))
    assert trace.reduce_metric(other, tr, (0.0, t), dict(ctx)) is None


def test_cost_functions_count_what_the_model_needs():
    from benchmarks.families import phi4flash as fam
    cfg = traffic.load_json("configs", CONFIG)
    assert fam._mamba_params(cfg) == 41_241_600
    c = fam.counts(cfg)
    assert c["matmul_params"] == 3_851_059_200
    # a prompt token stops after layer 17's K/V projection: 56% of the
    # non-embedding products
    assert c["prefill_matmul_params"] == 1_870_888_960
    non_embed = c["matmul_params"] - 2560 * 200064
    assert c["prefill_matmul_params"] / non_embed == pytest.approx(
        0.56, abs=0.005)
    assert fam.ssm_state_bytes_per_slot(cfg) == 3_225_600
    w = {"counters": {
        "serve.decode_context_tokens": 1000.0, "serve.decode_slot_steps": 4,
        "serve.shared_kv_positions": 7000.0,
        "serve.decode_window_context_tokens": 900.0,
        "serve.ssm_state_bytes": 2 * 4 * 3_225_600.0, "serve.decode_steps": 1,
        "serve.prefill_tokens": 2048.0, "serve.prefill_chunks": 2,
        "serve.prefill_attended_keys{kind=window_512}": 2048 * 400.0}}
    paged = fam.paged_attn_cost(cfg, w)
    # layer 17's positions once for itself and once a reader (8 x 1000),
    # the 8 window layers' min(context, 512): K and V of 1,280 values
    tokens = 8 * 1000 + 8 * 900
    assert paged["bytes"] == 2.0 * (2 * tokens * 1280 + 16 * 4 * 40 * 192)
    assert paged["ops"] == 2.0 * tokens * 40 * 192
    state = fam.ssm_state_cost(cfg, w)
    assert state["bytes"] == 2 * 4 * 3_225_600.0     # the state alone
    scan = fam.ssm_chunk_cost(cfg, w)
    assert scan["ops"] == 9 * 7.0 * 16 * 5120 * 2048
    assert scan["bytes"] == 9 * (2048 * (2 * (5120 + 32) + 8 * 5120)
                                 + 2 * 2 * 4 * 16 * 5120)
    chunk = fam.chunk_attn_cost(cfg, w)
    assert chunk["ops"] == 2.0 * 40 * 192 * 8 * 2048 * 400
    for fn in (fam.paged_attn_cost, fam.ssm_state_cost, fam.ssm_chunk_cost,
               fam.chunk_attn_cost):
        assert fn(cfg, {"counters": {}}) is None


def test_a_control_comes_out_not_correct():
    """The comparison that decides `correct` (reference.check_stream), on
    streams the tiny engine served: correct against the reference as it
    is, NOT correct against the reference whose cross layers are masked
    to the window (what a cross layer handed its own kind's table, not
    the full layer's, would compute)."""
    from benchmarks import reference
    from hetu_tpu.serving.request import Request
    cfg, fam, _, params, make = _tiny_engine()
    engine = make(params)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
                    .astype(np.int32), max_new_tokens=24, arrival_t=0.0)
            for i, n in enumerate((81, 97, 104))]
    results = {r.rid: r for r in engine.run(reqs)}
    engine.close()

    def control(params, ids, rows, cfg):
        return fam.logits_at(params, ids, rows, cfg, "cross_window")
    good, bad = [], []
    for req in reqs:
        toks = results[req.rid].tokens
        good.append(reference.check_stream(
            fam.logits_at, params, cfg, req.prompt, toks, 128))
        bad.append(reference.check_stream(
            control, params, cfg, req.prompt, toks, 128))
    assert all(s["ok"] for s in good), good
    assert not all(s["ok"] for s in bad), bad


def test_the_parent_fails_at_once_without_the_family_module(tmp_path):
    """What the driver's try of the new cell on the parent meets: a
    configuration whose family module is not there (or, as at the parent
    of PR 43, whose import of `hetu_tpu.models.phi4_flash` fails) exits 2
    before any device is touched."""
    reg = json.load(open(REHEARSAL))
    cfg = dict(traffic.load_json("configs", "tiny-phi4flash"),
               family="phi4flash_not_there")
    path = tmp_path / "no-phi4.json"
    path.write_text(json.dumps(cfg))
    reg["configs"][0]["file"] = str(path)
    reg_path = tmp_path / "reg.json"
    reg_path.write_text(json.dumps(reg))
    p = run("--rehearse", "--benchmark-file", str(reg_path),
            "--workload", TINY, "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "phi4flash_not_there" in p.stderr
