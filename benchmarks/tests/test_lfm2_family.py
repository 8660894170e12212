"""The eleventh serving family, `families/lfm2_moe.py` (LFM2-8B-A1B: gated
short convolutions whose two-position tail the pool holds by slot,
attention over K/V heads of 64 stored two a lane row, sigmoid-routed
experts all held), through the harness on the CPU:
`rehearsal-lfm2.json`'s `tiny-lfm2-many-turns` cell under `--rehearse`,
the cell's files and numbers as ISSUE 62 gives them, the scope rules
against the programs, the cost functions, and the controls by the
comparison that decides `correct`.

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

REHEARSAL = os.path.join(HERE, "rehearsal-lfm2.json")
TINY = "tiny-lfm2-many-turns"
CELL = "lfm2-8b-a1b-serve-many-turns"
CONFIG = "lfm2-8b-a1b-depth12"
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ENGINE_LOOP = {
    "peak_hbm_gb", "host_work_ms_step", "starved_ms_step",
    "sync_idle_ms_step", "prefill_token_share_inside",
    "decode_unscoped_dev_ms", "engine_empty_pct", "stalled_steps_pct",
    "fetch_wait_ms_step", "decode_overlap_pct"}
ENGINE_LOOP_COUNTERS = ENGINE_LOOP - {
    "starved_ms_step", "sync_idle_ms_step", "decode_unscoped_dev_ms"}
# Jamba's lists without its Mamba entries, Xing's expert lists without
# its latent and stream entries, and the three the cell brought
JOINED = ENGINE_LOOP | {
    "decode_mlp_dev_ms", "decode_step_dev_ms", "prefill_chunk_dev_ms",
    "device_idle", "compiles_in_window", "decode_batch_inside",
    "decode_ctx_ktokens_step", "phi4f.prefill_tail_rows_pct",
    "decode_full_attn_dev_ms", "decode_kv_write_dev_ms",
    "paged_attn_roofline", "mimo.prefill_full_attn_dev_ms",
    "mimo.chunk_attn_roofline", "mimo.prefill_attended_kkeys_token",
    "decode_experts_dev_ms", "prefill_experts_dev_ms",
    "grouped_matmul_roofline", "experts_hit_per_layer_step",
    "experts_extra_blocks_pct", "local_assignment_pct",
    "prefill_rows_launch"}
BROUGHT = {"lfm2.prefill_conv_dev_ms", "lfm2.decode_conv_dev_ms",
           "lfm2.short_conv_roofline"}
COUNTER_METRICS = ENGINE_LOOP_COUNTERS | {
    "compiles_in_window", "decode_ctx_ktokens_step", "decode_batch_inside",
    "mimo.prefill_attended_kkeys_token", "phi4f.prefill_tail_rows_pct",
    "experts_hit_per_layer_step", "experts_extra_blocks_pct",
    "local_assignment_pct", "prefill_rows_launch"}
ROOFLINES = {"lfm2.short_conv_roofline": "short_conv_cost",
             "paged_attn_roofline": "paged_attn_cost",
             "mimo.chunk_attn_roofline": "chunk_attn_cost",
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
def test_tiny_lfm2_rehearses_correct(trace_on):
    line = last_line(run(
        "--rehearse", "--benchmark-file", REHEARSAL, "--workload", TINY,
        "--seed", "6200000019", "--seconds", "3", "--trace", str(trace_on)))
    assert line["correct"] and not line["failed"]
    got = {k.removeprefix("cpu_rehearsal.") for k in line["metrics"]}
    assert got == (COUNTER_METRICS if trace_on
                   else {"serve_tokens_per_s", "setup_s"})
    if trace_on:
        m = {k.removeprefix("cpu_rehearsal.").split(".", 1)[-1]:
             v["value"] for k, v in line["metrics"].items()}
        assert m["compiles_in_window"] == 0
        # every expert is held: every pair is local
        assert m["local_assignment_pct"] == 100.0
        assert 0 < m["experts_hit_per_layer_step"] <= 8


def test_the_cell_and_its_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    conf = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "many-turns-closed", 1)
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    assert len(b["per_layer"]) <= 128 and len(b["workloads"]) <= 24
    assert os.path.getsize(BENCHMARK) < 64 << 10
    cfg = traffic.load_json("configs", CONFIG)
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"]) == [
        "layer_types", "num_hidden_layers"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert conf["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "layer_types":
            assert cfg[key] == value[:12]
        elif key == "num_hidden_layers":
            assert (cfg[key], value) == (12, 24)
        else:
            assert cfg[key] == value, key
    assert cfg["family"] == "lfm2_moe"
    for point in ("tie_word_embeddings", "head_dim", "router_precision",
                  "route_norm_eps", "correction_bias_std",
                  "correction_bias_mean", "expert_initializer_range",
                  "conv_tap_std", "operator", "attention", "logits",
                  "router_tie_logit"):
        assert point in cfg["assumed"], point
    # the seeding that the check's sharpness rests on (the file says why)
    assert (cfg["correction_bias_std"], cfg["correction_bias_mean"],
            cfg["expert_initializer_range"], cfg["initializer_range"],
            cfg["router_tie_logit"]) == (0.002, -0.8, 0.016, 0.02, 0.07)
    assert "two pipeline stages of 12 layers" in cfg["deployment"]
    from benchmarks.families import lfm2_moe as fam
    # 2 dense/conv layers, 7 conv/expert layers, 3 attention/expert
    # layers, the tied embedding, the last norm
    conv, attn = 16_783_360 + 4096, 10_485_888 + 4096
    moe = 32 * 11_010_048 + 65_568
    assert fam.counts(cfg)["total_params"] == cfg["parameters"] \
        == 3_928_728_256 == 2 * (conv + 44_040_192) + 7 * (conv + moe) \
        + 3 * (attn + moe) + 134_217_728 + 2048
    sv = cfg["serving"]
    assert (sv["num_slots"], sv["max_len"], sv["kv_quant"],
            sv["param_dtype"], sv["sampling"]) == (
        128, 4608, "none", "bfloat16", "greedy")
    ps = sv["page_size"]
    assert sv["num_pages"] >= 128 * 4608 // ps
    assert sv["max_len"] % sv["prefill_chunk"] == 0 \
        and sv["prefill_chunk"] % ps == 0
    assert 0 <= sv["max_prefilling"] <= 128
    tf = traffic.load_traffic(cell["traffic"])
    assert tf["kind"] == "closed_loop" and tf["outstanding"] == 128 \
        == sv["num_slots"]
    assert (tf["check_requests"], tf["drain_limit_s"]) == (4, 0.0)
    p, o = tf["prompt_lens"], tf["output_lens"]
    assert p == [round(256 * 16 ** (i / 63)) for i in range(64)]
    assert o == [round(256 + 128 * i / 63) for i in range(64)]
    assert max(o) <= 384 and max(p) + max(o) <= sv["max_len"]  # ROW_PAD
    assert sum(p) / (sum(p) + sum(o)) == pytest.approx(0.814, abs=0.001)
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
            assert callable(getattr(fam, spec["reduce"]["cost"]))
            assert spec["reduce"]["cost"] == ROOFLINES[m["name"]]
    e2e = next(m for m in b["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.055


def _tiny_engine():
    import jax
    from benchmarks.families import lfm2_moe as fam
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.engine import ServingEngine
    cfg = dict(traffic.load_json("configs", "tiny-lfm2"))
    model = fam.build_model(cfg, cfg["serving"])
    params = model.init(jax.random.key(1))
    return cfg, fam, model, params, lambda p: ServingEngine(
        model, p, fam.serve_config(cfg), registry=MetricsRegistry())


def test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs():
    """The cell's device metrics that select by scope each find something
    to read in the programs the engine compiles for the tiny
    configuration: a trace with every instruction of every program once,
    a microsecond each.  What the scopes say of the program; no time of a
    device."""
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
    counters = {"serve.conv_state_bytes": 1e6, "serve.decode_steps": 3,
                "serve.decode_slot_steps": 9}
    ctx = {"config": cfg, "family": fam, "hlo_texts": texts,
           "counters": {}, "registry": {},
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "window_counts": {"steps": 1, "counters": counters}}
    cell = runner.load_cell(BENCHMARK, CELL)
    by_scope = [m["name"] for m in cell["per_layer"]
                if runner.metric_spec(m["name"])["reduce"]["rule"]
                in ("scope_ms", "scope_roofline_pct")]
    assert set(by_scope) == {
        "lfm2.prefill_conv_dev_ms", "lfm2.decode_conv_dev_ms",
        "lfm2.short_conv_roofline", "decode_full_attn_dev_ms",
        "mimo.prefill_full_attn_dev_ms", "decode_kv_write_dev_ms",
        "decode_experts_dev_ms", "prefill_experts_dev_ms",
        "decode_mlp_dev_ms", "decode_unscoped_dev_ms"}
    tr = trace.Trace({dev: ops}, {dev: mods}, [])
    for name in by_scope:
        value = trace.reduce_metric(runner.metric_spec(name), tr, (0.0, t),
                                    ctx)
        assert value is not None and value > 0, name


def test_cost_functions_count_what_the_model_needs():
    from benchmarks.families import lfm2_moe as fam
    cfg = traffic.load_json("configs", CONFIG)
    c = fam.counts(cfg)
    # 9 convolution operators, 3 attention operators, 2 dense FFNs, 10
    # routers and 4 experts of 32 a token, the tied head
    assert c["matmul_params"] == 9 * 16_777_216 + 3 * 10_485_760 \
        + 2 * 44_040_192 + 10 * (65_536 + 4 * 11_010_048) + 2048 * 65536
    assert fam.conv_state_bytes_per_slot(cfg) == 73_728
    w = {"counters": {
        "serve.decode_context_tokens": 1000.0, "serve.decode_slot_steps": 4,
        "serve.conv_state_bytes": 2 * 4 * 73_728.0, "serve.decode_steps": 1,
        "serve.prefill_tokens": 2048.0, "serve.prefill_chunks": 4,
        "serve.prefill_attended_keys": 2048 * 400.0,
        "serve.moe_expert_hits": 30.0, "serve.moe_local_assignments": 64.0}}
    paged = fam.paged_attn_cost(cfg, w)
    # 3 layers read every position's 8 K heads and 8 V heads of 64: the
    # model's 2,048 B a token a layer, no zero beside a query counted
    assert paged["bytes"] == 3 * (1000 * 2048 + 2.0 * 4 * 32 * 2 * 64)
    assert paged["ops"] == 3 * 4.0 * 1000 * 32 * 64
    chunk = fam.chunk_attn_cost(cfg, w)
    assert chunk["ops"] == 3 * 4.0 * 32 * 64 * 2048 * 400
    assert chunk["bytes"] == 2.0 * 3 * (2048 * 2 * 32 * 64
                                        + 400 * 4 * 2 * 8 * 64)
    conv = fam.short_conv_cost(cfg, w)
    assert conv["ops"] == 9 * 2.0 * 4 * 16_777_216
    assert conv["bytes"] == 2.0 * 9 * (16_777_216 + 4 * 2 * 2048) \
        + 2 * 4 * 73_728
    grouped = fam.grouped_matmul_cost(cfg, w)
    assert grouped["ops"] == 2.0 * 64 * 11_010_048
    assert grouped["bytes"] == 2.0 * (30 * 11_010_048
                                      + 64 * (2 * 2048 + 3 * 1792))
    for fn in (fam.paged_attn_cost, fam.chunk_attn_cost,
               fam.short_conv_cost, fam.grouped_matmul_cost):
        assert fn(cfg, {"counters": {}}) is None


def test_the_controls_come_out_not_correct():
    """The comparison that decides `correct` (reference.check_stream), on
    streams the tiny engine served: correct against the reference as it
    is (its near-tie passes on), NOT correct against the reference with a
    tap dropped or with the tail taken from a padding row, through the
    same passes.  (The tail's precision and the bias in the weights move
    a toy model's logits by less than its tokens win by: held on the
    logits in tests/test_lfm2_moe.py and on the chip, PERF.md s6.)"""
    import functools
    from benchmarks import reference
    from hetu_tpu.serving.request import Request
    cfg, fam, _, params, make = _tiny_engine()
    assert cfg["router_tie_logit"] > 0
    engine = make(params)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
                    .astype(np.int32), max_new_tokens=24, arrival_t=0.0)
            for i, n in enumerate((81, 97, 104))]
    results = {r.rid: r for r in engine.run(reqs)}
    engine.close()

    def correct(control):
        forward = functools.partial(fam.logits_at, control=control)
        return all(reference.check_stream(
            forward, params, cfg, req.prompt, results[req.rid].tokens,
            128)["ok"] for req in reqs)
    assert correct(None)
    assert not correct("dropped_tap") and not correct("pad_tail")


def test_the_parent_fails_at_once_without_the_family_module(tmp_path):
    """What the driver's try of the new cell on the parent meets: a
    configuration whose family module is not there (or, as at the parent
    of PR 62, whose import of `hetu_tpu.models.lfm2_moe` fails) exits 2
    before any device is touched."""
    reg = json.load(open(REHEARSAL))
    cfg = dict(traffic.load_json("configs", "tiny-lfm2"),
               family="lfm2_not_there")
    path = tmp_path / "no-lfm2.json"
    path.write_text(json.dumps(cfg))
    reg["configs"][0]["file"] = str(path)
    reg_path = tmp_path / "reg.json"
    reg_path.write_text(json.dumps(reg))
    p = run("--rehearse", "--benchmark-file", str(reg_path),
            "--workload", TINY, "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "lfm2_not_there" in p.stderr
