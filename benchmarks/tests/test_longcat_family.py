"""The ninth family, `families/longcat_flash.py` (LongCat-Flash-Chat: a
published layer of two latent-attention sublayers and two dense FFNs
with one expert branch carried across them, softmax routing over routed
and identity experts), through the harness on the CPU:
`rehearsal-longcat.json`'s `tiny-longcat-chat-turns` cell under
`--rehearse`, the cell's files and numbers as ISSUE 51 gives them, the
scope rules against the programs, and what the parent does on the cell.
(The controls that must come out not correct, the shares and the cost
functions are tier-1's: tests/test_longcat.py.)

It asserts that the cell's entries are PRESENT in `BENCHMARK.json`, not
that they are the last, nor how many the file holds: a later PR appends."""
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

REHEARSAL = os.path.join(HERE, "rehearsal-longcat.json")
TINY = "tiny-longcat-chat-turns"
CELL = "longcat-flash-serve-chat-turns"
CONFIG = "longcat-flash-ep32-depth4"
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
# the 29 entries the cell shares with other cells (8 joined in PR 51; of
# the 13 it had to bring, PR 57 folded ten repeats into the lists whose
# rule they state and opened two new rules' lists to other cells; it
# joined the engine-loop entries and the experts' row blocks), and the
# one that is its own
JOINED = ENGINE_LOOP | {
    "compiles_in_window", "decode_step_dev_ms",
    "prefill_chunk_dev_ms", "device_idle",
    "decode_ctx_ktokens_step", "decode_batch_inside",
    "phi4f.prefill_tail_rows_pct", "prefill_rows_launch",
    "decode_mla_dev_ms", "prefill_mla_dev_ms",
    "paged_latent_attn_roofline",
    "latent_chunk_attn_roofline", "grouped_matmul_roofline",
    "decode_experts_dev_ms", "prefill_experts_dev_ms",
    "decode_mlp_dev_ms", "local_assignment_pct",
    "experts_hit_per_layer_step", "experts_extra_blocks_pct"}
BROUGHT = {"longcat.zero_assignment_pct"}
# what the cell reports without a device plane (a rule file's `device`
# false)
COUNTER_METRICS = ENGINE_LOOP_COUNTERS | {
    "compiles_in_window", "decode_ctx_ktokens_step",
    "decode_batch_inside", "phi4f.prefill_tail_rows_pct",
    "prefill_rows_launch", "longcat.zero_assignment_pct",
    "local_assignment_pct", "experts_hit_per_layer_step",
    "experts_extra_blocks_pct"}
ROOFLINES = {
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
def test_tiny_longcat_rehearses_correct(trace_on):
    line = last_line(run(
        "--rehearse", "--benchmark-file", REHEARSAL, "--workload", TINY,
        "--seed", "5100000023", "--seconds", "3", "--trace", str(trace_on)))
    # prompts of 9-104 (1-7 chunks of 16, two at a chunk's edge) and
    # answers of 10-24 over 4 slots that are reused all through the window
    assert line["correct"] and not line["failed"]
    got = {k.removeprefix("cpu_rehearsal.") for k in line["metrics"]}
    assert got == (COUNTER_METRICS if trace_on
                   else {"serve_tokens_per_s", "setup_s"})
    if trace_on:
        m = {k.removeprefix("cpu_rehearsal.").split(".", 1)[-1]:
             v["value"] for k, v in line["metrics"].items()}
        assert m["compiles_in_window"] == 0
        # 8 of the router's 24 outputs are identity experts, 4 are held
        assert 20 < m["zero_assignment_pct"] < 45
        assert 5 < m["local_assignment_pct"] < 35
        assert 0 < m["experts_hit_per_layer_step"] <= 4
        # one row a prompt runs the head: 8 prompts of 460 tokens a block
        assert m["prefill_tail_rows_pct"] == pytest.approx(
            100 * 8 / 460, rel=0.25)
        assert 8 < m["prefill_rows_launch"] <= 64


def test_the_cell_and_its_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    conf = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "chat-turns-closed", 1)
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    # what the cut distorts, and what the uniform ids leave unmeasured
    assert "EP32 48" in cell["why"] and "Uniform ids" in cell["why"]
    assert len(b["per_layer"]) <= 128 and len(b["workloads"]) <= 24
    cfg = traffic.load_json("configs", CONFIG)
    assert conf["reduced"] == list(cfg["reduced"]) == [
        "num_layers", "n_routed_experts", "vocab_size"]
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Chat")
    assert conf["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    # the floors of a model_config cut: four layers, eight routed
    # experts, an eighth of the vocabulary
    assert (cfg["num_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 16, 131072 // 8)
    assert (cfg["router_experts"], cfg["zero_expert_num"],
            cfg["moe_topk"], cfg["first_expert"]) == (512, 256, 12, 0)
    assert cfg["family"] == "longcat_flash"
    for point in ("hidden_act", "router_bias", "norm_topk_prob",
                  "tie_word_embeddings", "rotation", "initializer_range",
                  "e_score_correction_bias", "router_dtype", "latent_lanes",
                  "mla_scales", "router_tie_logit"):
        assert point in cfg["assumed"], point
    assert "EP32" in conf["why"] and "32 chips" in cfg["deployment"]
    assert "nothing stands in" in cfg["deployment"]
    from benchmarks.families import longcat_flash
    assert longcat_flash.counts(cfg)["total_params"] == cfg["parameters"] \
        == 5_172_749_312
    sv = cfg["serving"]
    assert (sv["max_len"], sv["kv_quant"], sv["param_dtype"],
            sv["sampling"]) == (2560, "none", "bfloat16", "greedy")
    ps, slots = sv["page_size"], sv["num_slots"]
    assert slots in (96, 64)      # 64 only if the chip refused the pool
    # the latent pool at full reservation (+ 16 spare pages)
    assert sv["num_pages"] == slots * 2560 // ps + 16
    assert sv["max_len"] % sv["prefill_chunk"] == 0 \
        and sv["prefill_chunk"] % ps == 0
    assert "1,280 B" in sv["note"] and "sweep" in sv["note"]
    tf = traffic.load_traffic(cell["traffic"])
    assert tf["kind"] == "closed_loop" and tf["outstanding"] == slots
    assert (tf["strata"], tf["check_requests"], tf["trace_s"],
            tf["drain_limit_s"], tf["ramp_s"], tf["plan_requests"]) == (
        4, 4, 5.0, 0.0, 20.0, 2048)
    p, o = tf["prompt_lens"], tf["output_lens"]
    assert len(p) == 64 == len(o) and p == sorted(p) and o == sorted(o)
    assert p == [round(256 * 8 ** (i / 63)) for i in range(64)]
    assert (p[0], p[32], p[63]) == (256, 736, 2048)
    assert o == [round(256 + 128 * i / 63) for i in range(64)]
    assert (min(o), max(o)) == (256, 384) and max(o) <= 384  # ROW_PAD
    assert sum(p) / 64 == pytest.approx(866, abs=1)
    assert sum(o) / 64 == pytest.approx(320, abs=1)
    assert max(p) + max(o) <= sv["max_len"]
    assert sum(p) / (sum(p) + sum(o)) == pytest.approx(0.730, abs=0.001)
    # what is reported IN the cell, wherever the entries stand and
    # whichever other cells share them: the 29 shared and the 1 its own
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
            assert callable(getattr(longcat_flash, spec["reduce"]["cost"]))
            assert spec["reduce"]["cost"] == ROOFLINES[m["name"]]
    e2e = next(m for m in b["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.055
    # no near-tie pass: the file says on what reading
    assert cfg["router_tie_logit"] == 0
    assert "plain pass" in cfg["assumed"]["router_tie_logit"]


def _tiny_engine():
    import jax
    from benchmarks.families import longcat_flash as fam
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.engine import ServingEngine
    cfg = dict(traffic.load_json("configs", "tiny-longcat"))
    model = fam.build_model(cfg, cfg["serving"])
    params = model.init(jax.random.key(1))
    return cfg, fam, ServingEngine(model, params, fam.serve_config(cfg),
                                   registry=MetricsRegistry())


def test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs():
    """The cell's device metrics that select by scope each find something
    to read in the programs the engine compiles for the tiny
    configuration: a trace with every instruction of every program once,
    a microsecond each.  What the scopes say of the program; no time of a
    device.  The dense FFNs' entry reads what of `mlp` no inner scope
    names: `router`, `experts` and `zero_experts` are groups of their
    own."""
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
           "counters": {}, "registry": {},
           "window_counts": {"steps": 1, "counters": {}}}
    cell = runner.load_cell(BENCHMARK, CELL)
    by_scope = [m["name"] for m in cell["per_layer"]
                if runner.metric_spec(m["name"])["reduce"]["rule"]
                == "scope_ms"]
    assert set(by_scope) == {
        "decode_mla_dev_ms", "prefill_mla_dev_ms",
        "decode_experts_dev_ms", "prefill_experts_dev_ms",
        "decode_mlp_dev_ms", "decode_unscoped_dev_ms"}
    tr = trace.Trace({dev: ops}, {dev: mods}, [])
    value = {}
    for name in by_scope:
        value[name] = trace.reduce_metric(runner.metric_spec(name), tr,
                                          (0.0, t), ctx)
        assert value[name] is not None and value[name] > 0, name
    # the three scopes inside `mlp` are not the dense FFNs' time
    from hetu_tpu.obs import hlo_profile as hp
    decode = next(text for text in texts if "decode_fn" in text[:200])
    by_group = {}
    for g, _ in hp.scope_map(decode).values():
        by_group[g] = by_group.get(g, 0) + 1
    assert all(by_group.get(g) for g in (
        "layer/mlp", "layer/router", "layer/experts", "layer/zero_experts"))


def test_the_parent_fails_at_once_without_the_family_module(tmp_path):
    """What the driver's try of the new cell on the parent meets: a
    configuration whose family module is not there (or, as at the parent
    of PR 51, whose import of `hetu_tpu.models.longcat_flash` fails)
    exits 2 before any device is touched."""
    reg = json.load(open(REHEARSAL))
    cfg = dict(traffic.load_json("configs", "tiny-longcat"),
               family="longcat_flash_not_there")
    path = tmp_path / "no-longcat.json"
    path.write_text(json.dumps(cfg))
    reg["configs"][0]["file"] = str(path)
    reg_path = tmp_path / "reg.json"
    reg_path.write_text(json.dumps(reg))
    p = run("--rehearse", "--benchmark-file", str(reg_path),
            "--workload", TINY, "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "longcat_flash_not_there" in p.stderr
