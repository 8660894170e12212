"""The tenth family, `families/xing4.py` (Xing4.0-29B-A4B: Kimi's latent
attention and experts inside a residual stream of four hidden vectors
mixed by manifold-constrained hyper-connections), through the harness on
the CPU: `rehearsal-xing4.json`'s `tiny-xing4-long-documents` cell under
`--rehearse`, the cell's files and numbers as ISSUE 55 gives them, the
scope rules against the programs, the cost functions, and what the parent
does on the cell.  (The stream against the reference, the n = 1 case and
the controls are tier-1's: tests/test_xing4.py.)

It asserts that the cell's entries are PRESENT in `BENCHMARK.json`, not
that they are the last of a list, nor how many the file holds: a later PR
appends."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import trace, traffic  # noqa: E402

REHEARSAL = os.path.join(HERE, "rehearsal-xing4.json")
TINY = "tiny-xing4-long-documents"
CELL = "xing4.0-serve-long-documents"
CONFIG = "xing4.0-29b-a4b-depth5"
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
# the 28 entries the cell shares with other cells (8 joined in PR 55; in
# PR 57 the four it had to bring as repeats were folded into the lists
# whose rule they state, and it joined the engine-loop entries and the
# six of Kimi's latent attention and experts that its programs have),
# and the one it brought
JOINED = ENGINE_LOOP | {
    "compiles_in_window", "decode_step_dev_ms",
    "prefill_chunk_dev_ms", "device_idle",
    "decode_ctx_ktokens_step", "decode_batch_inside",
    "phi4f.prefill_tail_rows_pct", "prefill_rows_launch",
    "prefill_experts_dev_ms", "grouped_matmul_roofline",
    "latent_chunk_attn_roofline", "paged_latent_attn_roofline",
    "decode_experts_dev_ms", "experts_hit_per_layer_step",
    "local_assignment_pct", "experts_extra_blocks_pct",
    "decode_mla_dev_ms", "prefill_mla_dev_ms"}
BROUGHT = {"xing.prefill_mhc_dev_ms"}
# what the cell reports without a device plane (a rule file's `device`
# false)
COUNTER_METRICS = ENGINE_LOOP_COUNTERS | {
    "compiles_in_window", "decode_ctx_ktokens_step",
    "decode_batch_inside", "phi4f.prefill_tail_rows_pct",
    "prefill_rows_launch", "experts_hit_per_layer_step",
    "local_assignment_pct", "experts_extra_blocks_pct"}
ROOFLINES = {
    "grouped_matmul_roofline": "grouped_matmul_cost",
    "paged_latent_attn_roofline": "paged_latent_attn_cost",
    "latent_chunk_attn_roofline": "latent_chunk_attn_cost"}
MHC = ["mhc_pre", "mhc_sinkhorn", "mhc_post"]


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
def test_tiny_xing4_rehearses_correct(trace_on):
    line = last_line(run(
        "--rehearse", "--benchmark-file", REHEARSAL, "--workload", TINY,
        "--seed", "5500000023", "--seconds", "3", "--trace", str(trace_on)))
    # prompts of 9-104 (1-7 chunks of 16) and answers of 10-24 over 4
    # slots that are reused all through the window; the check runs the
    # near-tie passes (the tiny file's `router_tie_logit`)
    assert line["correct"] and not line["failed"]
    got = {k.removeprefix("cpu_rehearsal.") for k in line["metrics"]}
    assert got == (COUNTER_METRICS if trace_on
                   else {"serve_tokens_per_s", "setup_s"})
    if trace_on:
        m = {k.removeprefix("cpu_rehearsal.").split(".", 1)[-1]:
             v["value"] for k, v in line["metrics"].items()}
        assert m["compiles_in_window"] == 0
        assert 2 < m["decode_batch_inside"] <= 4
        assert 8 < m["prefill_rows_launch"] <= 64


def test_the_cell_and_its_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    conf = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "long-documents-closed", 1)
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    # what the cut distorts, and what the cell leaves unmeasured
    for said in ("All 64 experts held", "1 token/expert/decode step",
                 "depth 5", "no shared prefix"):
        assert said in cell["why"], said
    assert len(b["per_layer"]) <= 128 and len(b["workloads"]) <= 24
    cfg = traffic.load_json("configs", CONFIG)
    assert conf["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert conf["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    # no width, no expert and no row of the vocabulary is cut
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 0)
    assert (cfg["n_routed_experts"], cfg["vocab_size"], cfg["hidden_size"],
            cfg["hc_mult"], cfg["hc_sinkhorn_iters"]) == (
        64, 131072, 3584, 4, 20)
    assert "router_experts" not in cfg and "first_expert" not in cfg
    assert cfg["family"] == "xing4"
    for point in ("sinkhorn_order", "h_res_clamp", "stream_norm",
                  "coefficients", "entry_exit", "mhc_init",
                  "e_score_correction_bias", "initializer_range", "rotation",
                  "router_dtype", "latent_lanes", "router_tie_logit"):
        assert point in cfg["assumed"], point
    assert "WHOLE" in cfg["deployment"] and "pipeline" in cfg["deployment"]
    from benchmarks.families import xing4
    assert xing4.counts(cfg)["total_params"] == cfg["parameters"] \
        == 4_047_680_782
    sv = cfg["serving"]
    assert (sv["max_len"], sv["kv_quant"], sv["param_dtype"],
            sv["sampling"], sv["page_size"], sv["prefill_chunk"]) == (
        33792, "none", "bfloat16", "greedy", 256, 1024)
    slots = sv["num_slots"]
    assert slots in (16, 12)      # 12 only if the chip refused the pool
    # the latent pool at full reservation (+ 16 spare pages)
    assert sv["num_pages"] == slots * 33792 // 256 + 16
    assert sv["max_len"] % sv["prefill_chunk"] == 0
    assert "1,280 B" in sv["note"] and "GB" in sv["note"]
    tf = traffic.load_traffic(cell["traffic"])
    assert tf["kind"] == "closed_loop" and tf["outstanding"] == slots
    ling = traffic.load_traffic("long-tail-closed")
    assert (tf["strata"], tf["slice_steps"]) == (ling["strata"],
                                                 ling["slice_steps"])
    assert (tf["check_requests"], tf["trace_s"], tf["drain_limit_s"],
            tf["ramp_s"], tf["plan_requests"]) == (4, 5.0, 0.0, 16.0, 512)
    p, o = tf["prompt_lens"], tf["output_lens"]
    assert len(p) == 64 == len(o) and p == sorted(p) and o == sorted(o)
    assert p == [round(4096 * 8 ** (i / 63)) for i in range(64)]
    assert (p[0], p[63]) == (4096, 32768)
    assert o == [round(128 + 256 * i / 63) for i in range(64)]
    assert (min(o), max(o)) == (128, 384) and max(o) <= 384  # ROW_PAD
    assert sum(p) / 64 == pytest.approx(13862, abs=1)
    assert (p[31] + p[32]) / 2 == 11587                     # the median
    assert sum(o) / 64 == pytest.approx(256, abs=1)
    assert max(p) + max(o) <= sv["max_len"]
    assert sum(p) / (sum(p) + sum(o)) == pytest.approx(0.982, abs=0.001)
    # what is reported IN the cell, wherever the entries stand and
    # whichever other cells share them: the 28 shared and the 1 brought
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
        assert spec["device"] == (m["name"] not in COUNTER_METRICS)
        if m["name"] in ROOFLINES:
            assert (m["unit"], m["layer"]) == ("%", "Kernels")
            assert callable(getattr(xing4, spec["reduce"]["cost"]))
            assert spec["reduce"]["cost"] == ROOFLINES[m["name"]]
        if "mhc" in m["name"]:
            assert spec["reduce"]["phase"] == MHC
    e2e = next(m for m in b["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.055
    # the near-tie margin and its passes: the file says on what readings
    assert cfg["router_tie_logit"] == 0.07
    assert "66 passes" in cfg["assumed"]["router_tie_logit"]
    assert len(xing4.pass_codes(cfg)) == 66


def test_the_cost_functions_count_what_the_counters_say():
    from benchmarks.families import kimi_k2, xing4
    cfg = traffic.load_json("configs", CONFIG)
    a_row = 2 * 5 * 14 * 3584 * 2           # sublayers x passes x C x 2 B
    counters = {"serve.prefill_chunks": 2.0,
                "serve.prefill_tokens": 1000.0,
                "serve.prefill_attended_keys": 5.0e6,
                "serve.decode_context_tokens": 4.0e5,
                "serve.decode_slot_steps": 32.0,
                "serve.moe_expert_hits": 300.0,
                "serve.moe_local_assignments": 4000.0}
    window = {"counters": counters}
    # the stream's mixes of the rows the chunk launches computed: no
    # entry reads it yet, the fused kernel's PR will
    cost = xing4.mhc_chunk_cost(cfg, window)
    phi = 4.0 * (14336 * 24 + 24 + 3)
    assert cost["bytes"] == 1000 * a_row + 10 * 2 * phi
    # the product with phi is most of the operations: 2 x 14,336 x 24
    assert 10 * 1000 * 2 * 14336 * 24 < cost["ops"] \
        < 1.5 * 10 * 1000 * 2 * 14336 * 24
    assert xing4.mhc_chunk_cost(cfg, {"counters": {}}) is None
    # Kimi's count of the paged kernel's bytes at this family's 5 layers
    # and 32 heads, LongCat's of the chunk kernel's pairs at 5 layers
    assert xing4.paged_latent_attn_cost(cfg, window) == \
        kimi_k2.paged_latent_attn_cost(cfg, window)
    chunk = xing4.latent_chunk_attn_cost(cfg, window)
    assert chunk["ops"] == 5 * 2.0 * 32 * (192 + 128) * 5.0e6
    assert xing4.latent_chunk_attn_cost(cfg, {"counters": {}}) is None
    # Kimi's count of the grouped products at this family's widths: an
    # expert hit streams 3 x 3,584 x 1,024 weights
    grouped = xing4.grouped_matmul_cost(cfg, window)
    assert grouped["ops"] == 2.0 * 4000 * 3 * 3584 * 1024
    assert grouped["bytes"] == 2.0 * (300 * 3 * 3584 * 1024
                                      + 4000 * (2 * 3584 + 3 * 1024))


def _tiny_engine():
    import jax
    from benchmarks.families import xing4 as fam
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.engine import ServingEngine
    cfg = dict(traffic.load_json("configs", "tiny-xing4"))
    model = fam.build_model(cfg, cfg["serving"])
    params = model.init(jax.random.key(1))
    return cfg, fam, ServingEngine(model, params, fam.serve_config(cfg),
                                   registry=MetricsRegistry())


def test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs():
    """The cell's device metrics that select by scope each find something
    to read in the programs the engine compiles for the tiny
    configuration: a trace with every instruction of every program once,
    a microsecond each.  What the scopes say of the program; no time of a
    device.  The stream's three scopes hold no attention and no expert
    operation: no product but the one with phi, no kernel."""
    from benchmarks import run as runner
    cfg, fam, engine = _tiny_engine()
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
    ctx = {"config": cfg, "family": fam, "hlo_texts": texts,
           "counters": {}, "registry": {}, "peaks": {
               "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "window_counts": {"steps": 1, "counters": {}}}
    cell = runner.load_cell(BENCHMARK, CELL)
    by_scope = [m["name"] for m in cell["per_layer"]
                if runner.metric_spec(m["name"])["reduce"]["rule"]
                in ("scope_ms", "scope_roofline_pct")]
    assert set(by_scope) == {
        "xing.prefill_mhc_dev_ms", "prefill_experts_dev_ms",
        "decode_experts_dev_ms", "decode_mla_dev_ms", "prefill_mla_dev_ms",
        "decode_unscoped_dev_ms"}
    tr = trace.Trace({dev: ops}, {dev: mods}, [])
    for name in by_scope:
        value = trace.reduce_metric(runner.metric_spec(name), tr, (0.0, t),
                                    ctx)
        assert value is not None and value > 0, name
    from hetu_tpu.obs import hlo_profile as hp
    for text in texts:
        if "write_fn" in text[:200]:
            continue
        placed = hp.scope_map(text)
        mine = {name for name, (g, _) in placed.items()
                if g.split("/")[-1] in MHC}
        assert mine
        for line in text.splitlines():
            m = hp.INSTR_PAT.match(line)
            if m and m.group(1) in mine:
                assert "pallas" not in line and "ragged" not in line
                assert not any(s in line for s in (
                    "mla_", "router", "experts", "kv_write")), line[:300]


def test_the_parent_fails_at_once_without_the_family_module(tmp_path):
    """What the driver's try of the new cell on the parent meets: a
    configuration whose family module is not there (or, as at the parent
    of PR 55, whose import of `hetu_tpu.models.xing4` fails) exits 2
    before any device is touched."""
    reg = json.load(open(REHEARSAL))
    cfg = dict(traffic.load_json("configs", "tiny-xing4"),
               family="xing4_not_there")
    path = tmp_path / "no-xing4.json"
    path.write_text(json.dumps(cfg))
    reg["configs"][0]["file"] = str(path)
    reg_path = tmp_path / "reg.json"
    reg_path.write_text(json.dumps(reg))
    p = run("--rehearse", "--benchmark-file", str(reg_path),
            "--workload", TINY, "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "xing4_not_there" in p.stderr
