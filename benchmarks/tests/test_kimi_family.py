"""The third family, `families/kimi_k2.py` (latent attention, dropless
routed experts held in part, a shared expert, YaRN), through the harness
on the CPU: `rehearsal-kimi.json`'s `tiny-kimi-chat` cell under
`--rehearse`, and its metric files reduced from a hand-made device trace
as `main()` reduces them (a CPU run has no device plane)."""
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

REHEARSAL = os.path.join(HERE, "rehearsal-kimi.json")
CELL = "kimi-k2.6-serve-agent-turns"
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
# what the cell reports without a device plane (a rule file's `device`
# false): the names carry no cell's prefix where the rule is shared
COUNTER_METRICS = {
    "experts_hit_per_layer_step", "local_assignment_pct",
    "experts_extra_blocks_pct", "decode_ctx_ktokens_step",
    "decode_batch_inside", "prefill_token_share_inside",
    "host_work_ms_step", "peak_hbm_gb", "stall_pct", "compiles_in_window",
    "engine_empty_pct", "stalled_steps_pct",
    "fetch_wait_ms_step", "decode_overlap_pct"}


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
def test_tiny_kimi_chat_rehearses_correct(trace_on):
    line = last_line(run(
        "--rehearse", "--benchmark-file", REHEARSAL, "--workload",
        "tiny-kimi-chat", "--seed", "2700000019", "--seconds", "3",
        "--trace", str(trace_on)))
    # held 4 of 16 experts, checked against the family's own forward
    assert line["correct"] and not line["failed"]
    got = {k.removeprefix("cpu_rehearsal.") for k in line["metrics"]}
    assert got == (COUNTER_METRICS if trace_on
                   else {"serve_tokens_per_s", "setup_s"})
    if trace_on:
        m = {k.removeprefix("cpu_rehearsal."): v["value"]
             for k, v in line["metrics"].items()}
        assert 0 < m["experts_hit_per_layer_step"] <= 4
        assert 0 < m["local_assignment_pct"] < 100
        assert 0 <= m["experts_extra_blocks_pct"] <= 100
        assert m["compiles_in_window"] == 0


def test_the_cell_and_its_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-k2.6-ep32-depth6", "agent-turns-closed", 1)
    assert len(cell["why"]) <= 200
    conf = next(c for c in b["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    cfg = traffic.load_json("configs", "kimi-k2.6-ep32-depth6")
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
    # every number of the catalog row that is not reduced, as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["num_experts_per_tok"], cfg["router_experts"]) == (
        7168, 64, 128, 64, 128, 1536, 512, 2048, 18432, 8, 384)
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 12, 20480)
    sv = cfg["serving"]
    assert sv["num_slots"] == 64 and sv["max_len"] == 4096
    assert sv["num_pages"] * sv["page_size"] >= 64 * 4096
    tf = traffic.load_traffic(cell["traffic"])
    assert tf["kind"] == "closed_loop" and tf["outstanding"] == 64
    assert len(tf["prompt_lens"]) == 64 == len(tf["output_lens"])
    assert (min(tf["prompt_lens"]), max(tf["prompt_lens"])) == (1024, 3072)
    assert (min(tf["output_lens"]), max(tf["output_lens"])) == (128, 384)
    assert sum(tf["prompt_lens"]) / 64 == pytest.approx(2048, abs=1)
    assert sum(tf["output_lens"]) / 64 == pytest.approx(256, abs=1)
    # what is reported IN the cell, wherever the entries stand and
    # whichever other cells share them
    mine = [m for m in b["per_layer"] if CELL in m["workloads"]]
    assert COUNTER_METRICS < {m["name"] for m in mine}
    with open(REHEARSAL) as f:      # exactly those are rehearsed
        rehearsed = json.load(f)["per_layer"]
    assert sorted(m["name"] for m in rehearsed) == \
        sorted(m["name"] for m in mine)
    assert all(m["workloads"] == ["tiny-kimi-chat"] for m in rehearsed)
    for m in mine:
        assert m["moves"] == "serve_tokens_per_s"
        assert traffic.load_json("metrics", m["name"])["reduce"][
            "rule"] in trace.RULES
    e2e = next(m for m in b["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.055
    # the check's near-tie margin is the configuration's, with its reason
    assert cfg["router_tie_logit"] > 0 and "router_tie_logit" in cfg["assumed"]


def test_device_metrics_from_a_hand_made_trace():
    """Scope, module and both roofline metrics of the cell, reduced from
    a trace of two executions of a decode program whose instructions
    carry the family's scopes."""
    from benchmarks import run as runner
    cell = runner.load_cell(BENCHMARK, CELL)
    assert cell["family"].__name__ == "benchmarks.families.kimi_k2"

    def ins(name, shape, scope):
        return (f'  %{name} = bf16[{shape}]{{0}} fusion(%p), metadata='
                f'{{op_name="jit(decode_fn)/{scope}"}}\n')
    hlo = ("HloModule jit_decode_fn, is_scheduled=true\n\nENTRY %main {\n"
           + ins("fusion.1", "64,1536", "layer/attn/mla_q/dot_general")
           + ins("pallas_paged_latent_attention.2", "64,64,512",
                 "layer/attn/pallas_paged_latent_attention/pallas_call")
           + ins("fusion.3", "64,384", "layer/mlp/router/dot_general")
           + ins("fusion.4", "64,7168", "layer/mlp/experts/scatter-add")
           + ins("fusion.5", "64,4096",
                 "layer/mlp/shared_expert/dot_general")
           + '  %ragged-dot-none.6 = bf16[64,4096]{0} custom-call(%fusion.4), '
             'metadata={op_name="ragged-dot-none"}\n'
           + '  %copy.7 = bf16[64,4096]{0} copy(%p), '
             'metadata={op_name="copy-none"}\n}\n')
    names = ["fusion.1_bf16_64_1536_",
             "pallas_paged_latent_attention.2_bf16_64_64_512_",
             "fusion.3_bf16_64_384_", "fusion.4_bf16_64_7168_",
             "fusion.5_bf16_64_4096_", "ragged-dot-none.6_bf16_64_4096_",
             "copy.7_bf16_64_4096_"]
    durs = [0.001, 0.004, 0.0005, 0.0002, 0.0008, 0.003, 0.0005]
    dev, ops, mods = "/device:TPU:0", [], []
    for start in (0.0, 0.02):
        t = start
        for n, d in zip(names, durs):
            ops.append(trace.Event(n, t, d))
            t += d
        mods.append(trace.Event("jit_decode_fn(7)", start, t - start))
    tr = trace.Trace({dev: ops}, {dev: mods}, [])
    counters = {"serve.decode_context_tokens": 2000.0,
                "serve.decode_slot_steps": 128.0,
                "serve.moe_expert_hits": 90.0,
                "serve.moe_local_assignments": 160.0}
    ctx = {"config": cell["config"], "family": cell["family"],
           "hlo_texts": [hlo], "counters": {}, "registry": {},
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "window_counts": {"steps": 2, "counters": counters}}
    got = {m["name"]: trace.reduce_metric(
        runner.metric_spec(m["name"]), tr, (0.0, 0.04), ctx)
        for m in cell["per_layer"]
        if runner.metric_spec(m["name"])["device"]}
    assert got["decode_step_dev_ms"] == pytest.approx(10.0)
    assert got["decode_mla_dev_ms"] == pytest.approx(5.0)
    # the grouped product's custom call has lost its path in the
    # compiler and takes the scope of the rows it multiplies
    assert got["decode_experts_dev_ms"] == pytest.approx(3.2)
    assert got["decode_shared_expert_dev_ms"] == pytest.approx(0.8)
    assert got["decode_unscoped_dev_ms"] == pytest.approx(0.5)
    fam = cell["family"]
    lat = fam.paged_latent_attn_cost(cell["config"], ctx["window_counts"])
    assert got["paged_latent_attn_roofline"] == pytest.approx(
        100 * max(lat["bytes"] / 819e9, lat["ops"] / 197e12) / 0.008)
    gm = fam.grouped_matmul_cost(cell["config"], ctx["window_counts"])
    assert got["grouped_matmul_roofline"] == pytest.approx(
        100 * max(gm["bytes"] / 819e9, gm["ops"] / 197e12) / 0.006)
    assert got["prefill_chunk_dev_ms"] is None    # no chunk program
    assert 0 < got["device_idle"] < 100


def test_the_parent_fails_at_once_without_the_family_module(tmp_path):
    """What the driver's try of the new cell on the parent meets: a
    configuration whose family module is not there exits 2 before any
    device is touched."""
    reg = json.load(open(REHEARSAL))
    cfg = dict(traffic.load_json("configs", "tiny-kimi-k2"),
               family="kimi_k2_not_there")
    path = os.path.join(BENCH, "configs", "zz-no-family.json")
    try:
        with open(path, "w") as f:
            json.dump(cfg, f)
        reg["configs"][0]["file"] = "benchmarks/configs/zz-no-family.json"
        reg_path = tmp_path / "reg.json"
        reg_path.write_text(json.dumps(reg))
        p = run("--rehearse", "--benchmark-file", str(reg_path),
                "--workload", "tiny-kimi-chat", "--seed", "1", "--seconds",
                "1", "--trace", "0")
        assert p.returncode == 2 and p.stdout.strip() == ""
        assert "kimi_k2_not_there" in p.stderr
    finally:
        os.remove(path)
