"""The eleventh family, `families/deepseek_v32.py` (DeepSeek-V3.2: Kimi's
block whose latent attention attends the 2,048 positions a lightning
indexer selects, a token's two cache arrays under one page table),
through the harness on the CPU: `rehearsal-deepseek-v32.json`'s
`tiny-deepseek-v32-sparse-long-context` cell under `--rehearse`, the
cell's files and numbers as ISSUE 58 gives them, the scope rules against
the programs, the cost functions, and what the parent does on the cell.
(The selection against the reference, the Kimi limit, the tie rule and
the controls are tier-1's: tests/test_deepseek_v32.py.)

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

REHEARSAL = os.path.join(HERE, "rehearsal-deepseek-v32.json")
TINY = "tiny-deepseek-v32-sparse-long-context"
CELL = "deepseek-v3.2-serve-sparse-long-context"
CONFIG = "deepseek-v3.2-ep32-depth5"
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]
ENGINE_LOOP = {
    "peak_hbm_gb", "host_work_ms_step", "starved_ms_step",
    "sync_idle_ms_step", "prefill_token_share_inside",
    "decode_unscoped_dev_ms", "engine_empty_pct", "stalled_steps_pct",
    "fetch_wait_ms_step", "decode_overlap_pct"}
ENGINE_LOOP_COUNTERS = ENGINE_LOOP - {
    "starved_ms_step", "sync_idle_ms_step", "decode_unscoped_dev_ms"}
# the 27 entries the cell shares with other cells (Xing's, less the paged
# latent kernel's share, which this family's decode step does not run, and
# the tail rows; with Kimi's shared expert), and the nine it brought
JOINED = ENGINE_LOOP | {
    "compiles_in_window", "decode_step_dev_ms", "prefill_chunk_dev_ms",
    "device_idle", "decode_ctx_ktokens_step", "decode_batch_inside",
    "prefill_rows_launch", "prefill_experts_dev_ms",
    "grouped_matmul_roofline", "latent_chunk_attn_roofline",
    "decode_experts_dev_ms", "decode_shared_expert_dev_ms",
    "experts_hit_per_layer_step", "local_assignment_pct",
    "experts_extra_blocks_pct", "decode_mla_dev_ms", "prefill_mla_dev_ms"}
BROUGHT = {
    "dsv32.prefill_indexer_dev_ms", "dsv32.decode_indexer_dev_ms",
    "dsv32.prefill_select_dev_ms", "dsv32.decode_select_dev_ms",
    "dsv32.prefill_attend_dev_ms", "dsv32.indexer_score_roofline",
    "dsv32.sparse_latent_attn_roofline", "dsv32.attended_share_pct",
    "dsv32.indexer_cache_gb_step"}
COUNTER_METRICS = ENGINE_LOOP_COUNTERS | {
    "compiles_in_window", "decode_ctx_ktokens_step", "decode_batch_inside",
    "prefill_rows_launch", "experts_hit_per_layer_step",
    "local_assignment_pct", "experts_extra_blocks_pct",
    "dsv32.attended_share_pct", "dsv32.indexer_cache_gb_step"}
ROOFLINES = {
    "grouped_matmul_roofline": "grouped_matmul_cost",
    "latent_chunk_attn_roofline": "latent_chunk_attn_cost",
    "dsv32.indexer_score_roofline": "indexer_score_cost",
    "dsv32.sparse_latent_attn_roofline": "sparse_latent_attn_cost"}
DSA = ["dsa_index_q", "dsa_index_k", "dsa_score", "dsa_select", "dsa_attend"]


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
def test_tiny_deepseek_v32_rehearses_correct(trace_on):
    line = last_line(run(
        "--rehearse", "--benchmark-file", REHEARSAL, "--workload", TINY,
        "--seed", "5800000023", "--seconds", "3", "--trace", str(trace_on)))
    # prompts of 26-104 (2-7 chunks of 16), every one past the tiny
    # index_topk of 24, and answers of 10-24 over 4 slots that are reused
    # all through the window; the check runs the near-tie passes (the
    # tiny file's `router_tie_logit`)
    assert line["correct"] and not line["failed"]
    got = {k.removeprefix("cpu_rehearsal.") for k in line["metrics"]}
    assert got == (COUNTER_METRICS if trace_on
                   else {"serve_tokens_per_s", "setup_s"})
    if trace_on:
        m = {k.removeprefix("cpu_rehearsal."): v["value"]
             for k, v in line["metrics"].items()}
        assert m["compiles_in_window"] == 0
        assert 2 < m["decode_batch_inside"] <= 4
        assert 8 < m["prefill_rows_launch"] <= 64
        # every decode step selects: 24 of contexts of 27 and more
        assert 10 < m["dsv32.attended_share_pct"] < 90
        assert m["dsv32.indexer_cache_gb_step"] > 0


def test_the_cell_and_its_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    conf = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sparse-long-context-closed", 1)
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    # what the cut distorts, and what the cell is for
    for said in ("16 in flight = slots", "8k-32k", "2,048",
                 "0.5 tokens/held expert", "EP32", "depth 5"):
        assert said in cell["why"], said
    assert len(b["per_layer"]) <= 128 and len(b["workloads"]) <= 24
    cfg = traffic.load_json("configs", CONFIG)
    assert conf["reduced"] == list(cfg["reduced"]) == REDUCED
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V3.2")
    assert conf["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    # the published values stand beside the cut ones
    for key, was in (("num_hidden_layers", "61 -> 5"),
                     ("first_k_dense_replace", "3 -> 1"),
                     ("n_routed_experts", "256 -> 8"),
                     ("vocab_size", "129,280 -> 16,160"),
                     ("num_nextn_predict_layers", "1 -> 0")):
        assert cfg["reduced"][key].startswith(was), key
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 8, 16160, 0)
    # no width is cut: the indexer, the heads, the router and its groups
    assert (cfg["index_topk"], cfg["index_n_heads"], cfg["index_head_dim"],
            cfg["num_attention_heads"], cfg["router_experts"],
            cfg["first_expert"], cfg["num_experts_per_tok"], cfg["n_group"],
            cfg["topk_group"], cfg["hidden_size"]) == (
        2048, 64, 128, 128, 256, 0, 8, 8, 4, 7168)
    assert cfg["family"] == "deepseek_v32"
    for point in ("indexer_precision", "no_fp8_no_hadamard",
                  "indexer_rotation", "index_layer_norm", "tie_rule",
                  "indexer_init", "embed_initializer_range",
                  "selection_boundary",
                  "e_score_correction_bias", "initializer_range", "rotation",
                  "router_dtype", "latent_lanes", "router_tie_logit"):
        assert point in cfg["assumed"], point
    assert "32 chips" in cfg["deployment"] \
        and "arXiv:2412.19437" in cfg["deployment"]
    from benchmarks.families import deepseek_v32 as fam
    assert fam.counts(cfg)["total_params"] == cfg["parameters"] \
        == 3_226_232_064
    sv = cfg["serving"]
    assert (sv["max_len"], sv["kv_quant"], sv["param_dtype"],
            sv["sampling"], sv["page_size"]) == (
        33792, "none", "bfloat16", "greedy", 256)
    # ISSUE 58's: 16 slots, 2,128 pages (16 x 132 + 16 spare)
    slots = sv["num_slots"]
    assert slots == 16 and cfg["embed_initializer_range"] == 1.0
    assert sv["prefill_chunk"] in (512, 1024)
    assert sv["num_pages"] == slots * 33792 // 256 + slots == 2128
    assert sv["max_len"] % sv["prefill_chunk"] == 0
    assert "1,536 B" in sv["note"] and "sweep" in sv["note"]
    tf = traffic.load_traffic(cell["traffic"])
    assert tf["kind"] == "closed_loop" and tf["outstanding"] == slots
    docs = traffic.load_traffic("long-documents-closed")
    assert all(tf[k] == docs[k] for k in (
        "trace_s", "check_requests", "plan_requests", "drain_limit_s"))
    # the ramp outlasts the first wave of the 16 requests sent at once
    # (`ramp_from`: at that file's 16 s the window opens inside it)
    assert tf["ramp_s"] == 44.0 > docs["ramp_s"] and "16" in tf["ramp_from"]
    # sub-blocks of four prompts, one of each quarter of the range: at
    # that file's 4 the SEED moved the window by 3.6% and the driver's
    # check refused the cell (`strata_from`, `test_replay_*` below)
    assert tf["strata"] == 16 > docs["strata"] and "3.64%" in tf["strata_from"]
    p, o = tf["prompt_lens"], tf["output_lens"]
    assert len(p) == 64 == len(o) and p == sorted(p) and o == sorted(o)
    assert p == [round(8192 * 4 ** (i / 63)) for i in range(64)]
    assert (p[0], p[63]) == (8192, 32768)
    assert o == [round(256 + 128 * i / 63) for i in range(64)]
    assert (min(o), max(o)) == (256, 384) and max(o) <= 384  # ROW_PAD
    assert sum(p) / 64 == pytest.approx(17772, abs=1)
    assert sum(o) / 64 == pytest.approx(320, abs=1)
    assert max(p) + max(o) <= sv["max_len"]
    assert min(p) > cfg["index_topk"]        # every context selects
    assert sum(p) / (sum(p) + sum(o)) == pytest.approx(0.982, abs=0.001)
    # what is reported IN the cell, wherever the entries stand and
    # whichever other cells share them
    mine = [m for m in b["per_layer"] if CELL in m["workloads"]]
    assert {m["name"] for m in mine} == JOINED | BROUGHT
    assert all(m["workloads"] == [CELL] for m in mine
               if m["name"] in BROUGHT)
    assert len(BROUGHT) <= 9
    # the paged latent kernel is not on this family's decode path
    assert CELL not in next(m for m in b["per_layer"] if m["name"]
                            == "paged_latent_attn_roofline")["workloads"]
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
            assert callable(getattr(fam, spec["reduce"]["cost"]))
            assert spec["reduce"]["cost"] == ROOFLINES[m["name"]]
        if m["name"] in BROUGHT and "phase" in spec["reduce"]:
            assert set(spec["reduce"]["phase"]) <= set(DSA)
    e2e = next(m for m in b["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.055
    assert cfg["router_tie_logit"] > 0
    tiny = traffic.load_json("configs", "tiny-deepseek-v32")
    ttf = traffic.load_traffic("tiny-sparse-long-context-closed")
    assert min(ttf["prompt_lens"]) > tiny["index_topk"]


def test_the_cost_functions_count_what_the_counters_say():
    from benchmarks.families import deepseek_v32 as fam
    from benchmarks.families import kimi_k2
    cfg = traffic.load_json("configs", CONFIG)
    counters = {"serve.prefill_chunks": 2.0,
                "serve.prefill_tokens": 2000.0,
                "serve.prefill_attended_keys": 5.0e6,
                "serve.decode_context_tokens": 4.0e5,
                "serve.decode_selectable_tokens": 5 * 4.0e5,
                "serve.decode_selected_tokens": 5 * 32 * 2048.0,
                "serve.decode_slot_steps": 32.0,
                "serve.moe_expert_hits": 300.0,
                "serve.moe_local_assignments": 4000.0}
    window = {"counters": counters}
    # the scoring of the chunk launches: 2 x 64 x 128 a visible pair
    cost = fam.indexer_score_cost(cfg, window)
    assert cost["ops"] == 5 * 2.0 * 64 * 128 * 5.0e6
    assert cost["bytes"] == 5 * (2.0 * (128 * 5.0e6 / 2000 * 2
                                        + 2000 * 64 * 128)
                                 + 4.0 * (2000 * 64 + 5.0e6))
    assert fam.indexer_score_cost(cfg, {"counters": {}}) is None
    # a decode pass: the context's index keys scored by 64 heads, the
    # selected latents (NOT the context's) attended by 128 in the absorbed
    # form: Kimi's count over the selection
    cost = fam.sparse_latent_attn_cost(cfg, window)
    sel = counters["serve.decode_selected_tokens"]
    assert cost["ops"] == 2.0 * (5 * 64 * 128 * 4.0e5
                                 + 128 * (576 + 512) * sel)
    assert cost["bytes"] == 2.0 * (5 * 128 * 4.0e5 + 576 * sel
                                   + 5 * 32 * (64 * 128 + 128 * 1088))
    dense = kimi_k2.paged_latent_attn_cost(cfg, window)
    assert 128 * (576 + 512) * sel * 2.0 < dense["ops"]
    assert fam.sparse_latent_attn_cost(
        cfg, {"counters": {"serve.decode_selectable_tokens": 1.0}}) is None
    # the blockwise kernel is handed what the rows SEE: Kimi's dense count
    chunk = fam.latent_chunk_attn_cost(cfg, window)
    assert chunk == kimi_k2.latent_chunk_attn_cost(cfg, window)
    assert chunk["ops"] == 5 * 2.0 * 128 * (192 + 128) * 5.0e6
    grouped = fam.grouped_matmul_cost(cfg, window)
    assert grouped["ops"] == 2.0 * 4000 * 3 * 7168 * 2048
    # the weights a token multiplies here, and everything held
    n = fam.counts(cfg)
    assert n["matmul_params"] == kimi_k2.counts(cfg)["matmul_params"] \
        + 5 * (1536 * 8192 + 7168 * 128 + 7168 * 64)


def _tiny_engine():
    import jax
    from benchmarks.families import deepseek_v32 as fam
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.engine import ServingEngine
    cfg = dict(traffic.load_json("configs", "tiny-deepseek-v32"))
    model = fam.build_model(cfg, cfg["serving"])
    params = model.init(jax.random.key(1))
    return cfg, fam, ServingEngine(model, params, fam.serve_config(cfg),
                                   registry=MetricsRegistry())


def test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs():
    """The cell's device metrics that select by scope each find something
    to read in the programs the engine compiles for the tiny
    configuration: a trace with every instruction of every program once,
    a microsecond each.  What the scopes say of the program; no time of a
    device.  The five scopes hold no projection of MLA's and no expert."""
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
    counters = {"serve.prefill_chunks": 2.0, "serve.prefill_tokens": 32.0,
                "serve.prefill_attended_keys": 500.0,
                "serve.decode_context_tokens": 400.0,
                "serve.decode_selectable_tokens": 1200.0,
                "serve.decode_selected_tokens": 288.0,
                "serve.decode_slot_steps": 4.0}
    ctx = {"config": cfg, "family": fam, "hlo_texts": texts,
           "counters": {}, "registry": {}, "peaks": {
               "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "window_counts": {"steps": 1, "counters": counters}}
    cell = runner.load_cell(BENCHMARK, CELL)
    by_scope = [m["name"] for m in cell["per_layer"]
                if runner.metric_spec(m["name"])["reduce"]["rule"]
                in ("scope_ms", "scope_roofline_pct")]
    assert set(by_scope) == {
        "prefill_experts_dev_ms", "decode_experts_dev_ms",
        "decode_shared_expert_dev_ms", "decode_mla_dev_ms",
        "prefill_mla_dev_ms", "decode_unscoped_dev_ms"} | {
        m for m in BROUGHT if m not in COUNTER_METRICS}
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
                if g.split("/")[-1] in DSA}
        assert mine
        for line in text.splitlines():
            m = hp.INSTR_PAT.match(line)
            if m and m.group(1) in mine and 'op_name="' in line:
                path = line.split('op_name="')[1].split('"')[0]
                assert not any(s in path for s in (
                    "mla_", "router", "experts", "kv_write")), line[:300]


def test_the_parent_fails_at_once_without_the_family_module(tmp_path):
    """What the driver's try of the new cell on the parent meets: a
    configuration whose family module is not there (or, as at the parent
    of PR 58, whose import of `hetu_tpu.models.deepseek_v32` fails) exits
    2 before any device is touched."""
    reg = json.load(open(REHEARSAL))
    cfg = dict(traffic.load_json("configs", "tiny-deepseek-v32"),
               family="deepseek_v32_not_there")
    path = tmp_path / "no-deepseek-v32.json"
    path.write_text(json.dumps(cfg))
    reg["configs"][0]["file"] = str(path)
    reg_path = tmp_path / "reg.json"
    reg_path.write_text(json.dumps(reg))
    p = run("--rehearse", "--benchmark-file", str(reg_path),
            "--workload", TINY, "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode == 2 and p.stdout.strip() == ""


def test_the_controls_entry_runs_on_the_tiny_configuration(capsys):
    """`controls_deepseek_v32.py`, the committed entry that makes the
    cell's control and boundary readings again, on the CPU: the tiny
    cell's streams through `reference.check_stream` are `correct` under
    the sound reference and not under any control (exit 0), and the
    program fed the reference's selected sets stands no further from the
    reference than under its own."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "controls_deepseek_v32",
        os.path.join(HERE, "controls_deepseek_v32.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    tiny = ["--config", "tiny-deepseek-v32", "--seed", "7"]
    assert tool.main(["controls", "--control-streams", "1", "--traffic",
                      "tiny-sparse-long-context-closed"] + tiny) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] and set(last["controls_correct"]) == {
        "no_relu", "topk_1024", "recent_2048", "flat_heads", "e4m3"}
    assert tool.main(["boundary", "--length", "256", "--stride", "4",
                      "--worst", "4"] + tiny) == 0
    own, fed, swaps = map(json.loads,
                          capsys.readouterr().out.strip().splitlines())
    assert fed["max_gap"] <= own["max_gap"] and fed["over"] == 0
    assert len(swaps["layer0_swaps_of_selected"]["worst_rows"]) == 4


def test_the_replay_follows_a_chip_run_and_the_seeds_spread_little():
    """`replay_deepseek_v32.py` against `replay-deepseek-v32-steps.json`,
    the (prompt rows, tokens out) of every engine step of one window of
    the cell on the chip (a run of the engine as PR 58 left it: a change
    to how a step spends its prompt rows needs a new run here): the
    replay holds every step and reads the window's tokens per second
    within 1%; and over 24 drawn seeds the traffic file's `strata` and
    `ramp_s` hold the windows' quartile spread under 2% (half the bound
    is 2.75%), where ISSUE 58's 4 and the second hand-in's 48 s read
    over it."""
    replay = _load_replay()
    with open(os.path.join(HERE, "replay-deepseek-v32-steps.json")) as f:
        run = json.load(f)
    tf = traffic.load_traffic("sparse-long-context-closed")
    assert (run["strata"], run["ramp_s"]) == (tf["strata"], tf["ramp_s"])
    assert len(run["steps"]) > 400
    assert replay.follows(run["seed"], run["steps"])
    got, = replay.windows([run["seed"]], tf["ramp_s"])
    assert got["rate"] == pytest.approx(run["serve_tokens_per_s"], rel=0.01)
    seeds = replay.drawn_seeds(24)
    now = replay.windows(seeds, tf["ramp_s"])
    spread = replay.quartile_spread([g["rate"] for g in now])
    assert spread < 0.02
    assert min(g["due_and_done"] for g in now) >= 4
    then = replay.windows(seeds, 48.0, strata=4)
    assert replay.quartile_spread([g["rate"] for g in then]) > 1.5 * spread


def _load_replay():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "replay_deepseek_v32", os.path.join(HERE, "replay_deepseek_v32.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
