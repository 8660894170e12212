"""The runner end to end at a tiny size on the CPU (`--rehearse`), the
refusals, and that cells, configurations, traffic mixes, per-layer
metrics and whole architectures are data: added by new files and one new
entry each."""
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

REHEARSAL = os.path.join(HERE, "rehearsal.json")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(*args, devices=1, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("BENCH_RUN", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- static

def test_benchmark_json_is_backed_by_files():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"]: w for w in b["workloads"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    # what PR 31 found there stays; a later PR adds to it, by files alone
    assert {"internlm2-serve-chat", "internlm2-serve-longprompt",
            "internlm2-train-dp2tp2-4chip", "kimi-k2.6-serve-agent-turns",
            "mistral7b-train-1chip"} <= set(cells)
    assert len(b["end_to_end"]) >= 4 and len(b["per_layer"]) >= 77
    assert sum(w["chips"] == 4 for w in cells.values()) <= 1
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        for key in ("source", "reduced", "assumed", "deployment"):
            assert key in cfg, (c["name"], key)
        # the entry's `reduced` keys are the ones the configuration states
        # (and says why), and none of them is a width
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) and
                       k != "vocab_size" for k in c["reduced"]), c["name"]
    for w in cells.values():
        assert traffic.load_traffic(w["traffic"])["kind"] in traffic.KINDS
        reported = [m for m in e2e.values()
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2                 # setup_s and one more
    for m in b["per_layer"]:
        spec = traffic.load_json("metrics", m["name"])
        assert spec["reduce"]["rule"] in trace.RULES
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells), \
                (m["name"], cell)
    layers = {m["layer"] for m in b["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in layers)


def test_traffic_files_hold_their_lists_and_the_rate():
    chat = traffic.load_traffic("chat-open")
    assert len(chat["prompt_lens"]) == 64 == len(chat["output_lens"])
    assert isinstance(chat["rate_per_s"], (int, float))
    batch = traffic.load_traffic("longprompt-batch")
    assert min(batch["prompt_lens"]) == 1024 and max(
        batch["prompt_lens"]) == 1792
    assert min(batch["output_lens"]) == 16 and max(
        batch["output_lens"]) == 64


# ------------------------------------------------------------- refusals

def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    cell = bench()["workloads"][0]["name"]
    p = run("--workload", cell, "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_too_few_chips_exits_nonzero():
    p = run("--rehearse", "--benchmark-file", REHEARSAL, "--workload",
            "tiny-train-4dev", "--seed", "1", "--seconds", "1",
            "--trace", "0", devices=2)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs 4 chip" in p.stderr


def test_unknown_device_kind_is_an_error():
    from benchmarks import peaks
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12


# ------------------------------------------------------------ rehearsals

@pytest.mark.parametrize("cell,devices,trace_on,expect", [
    ("tiny-train", 1, 0, {"train_tokens_per_s_chip", "setup_s"}),
    ("tiny-train-4dev", 4, 1, {"train_stall_pct", "train_step_median_ms",
                               "train.compiles_in_window"}),
    ("tiny-chat", 1, 0, {"norm_latency_mean_ms", "setup_s"}),
    ("tiny-batch", 1, 1, {"prefill_token_share_inside", "stall_pct",
                          "compiles_in_window"}),
    ("tiny-gpt2-chat", 1, 0, {"norm_latency_mean_ms", "setup_s"}),
    ("tiny-gpt2-train", 1, 1, {"train_stall_pct", "train_step_median_ms",
                               "train.compiles_in_window"}),
])
def test_rehearsal_prints_the_contracts_line(cell, devices, trace_on, expect):
    p = run("--rehearse", "--benchmark-file", REHEARSAL, "--workload", cell,
            "--seed", "3000000019", "--seconds", "2", "--trace",
            str(trace_on), devices=devices,
            env_extra={"BENCH_RUN": "ignored"})
    line = last_line(p)
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    names = set(line["metrics"])
    # no number of a CPU run under a device metric's name
    assert all(n.startswith("cpu_rehearsal.") for n in names)
    assert {"cpu_rehearsal." + e for e in expect} <= names
    device_only = {m for m in os.listdir(os.path.join(BENCH, "metrics"))
                   if traffic.load_json("metrics", m[:-5]).get("device")}
    assert not {n[len("cpu_rehearsal."):] + ".json" for n in names} \
        & device_only
    assert "busy_s" not in line["device"] and "breakdown" not in line
    if trace_on:
        assert line["metrics"]["cpu_rehearsal." + sorted(
            e for e in expect if e.endswith("compiles_in_window"))[0]
        ]["value"] == 0
    readings = [json.loads(ln) for ln in p.stdout.splitlines()
                if '"phase": "readings"' in ln][0]["path"]
    with open(os.path.join(ROOT, readings)) as f:
        r = json.load(f)
    assert r["seed"] == 3000000019
    assert ("intervals_s" in r) or ("slice_rates" in r and "requests" in r)


# ---------------------------------------------- added by files, not edits

@pytest.fixture
def dummies(tmp_path):
    """A dummy configuration, traffic mix, per-layer metric and cell: three
    new files and one new entry each in a copy of the rehearsal registry.
    Nothing that exists is edited."""
    made = []

    def write(rel, obj):
        path = os.path.join(BENCH, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)
        made.append(path)
    try:
        cfg = traffic.load_json("configs", "tiny")
        cfg["num_hidden_layers"] = 1
        write("configs/zz-dummy.json", cfg)
        tf = traffic.load_traffic("tiny-closed")
        tf["outstanding"] = 3
        write("traffic/zz-dummy-closed.json", tf)
        write("metrics/zz.dummy_steps.json", {
            "name": "zz.dummy_steps", "what": "a counter the loop keeps",
            "device": False,
            "reduce": {"rule": "counter", "counter": "compiles_in_window"}})
        with open(REHEARSAL) as f:
            reg = json.load(f)
        reg["configs"].append({"name": "zz-dummy", "source": "none",
                               "file": "benchmarks/configs/zz-dummy.json",
                               "reduced": [], "why": "dummy"})
        reg["workloads"].append({"name": "zz-dummy-cell",
                                 "config": "zz-dummy",
                                 "traffic": "zz-dummy-closed", "chips": 1,
                                 "why": "dummy"})
        for m in reg["end_to_end"]:
            if m["name"] == "serve_tokens_per_s":
                m["workloads"].append("zz-dummy-cell")
        reg["per_layer"].append({
            "name": "zz.dummy_steps", "unit": "count", "better": "lower",
            "source": "program_counter", "layer": "Serving engine loop",
            "moves": "serve_tokens_per_s", "workloads": ["zz-dummy-cell"]})
        reg_path = tmp_path / "registry.json"
        reg_path.write_text(json.dumps(reg))
        yield str(reg_path)
    finally:
        for path in made:
            os.remove(path)


def test_a_cell_config_traffic_and_metric_are_added_by_files_alone(dummies):
    common = ["--rehearse", "--benchmark-file", dummies, "--workload",
              "zz-dummy-cell", "--seed", "5", "--seconds", "2"]
    e2e = last_line(run(*common, "--trace", "0"))
    assert set(e2e["metrics"]) == {"cpu_rehearsal.serve_tokens_per_s",
                                   "cpu_rehearsal.setup_s"}
    per_layer = last_line(run(*common, "--trace", "1"))
    assert set(per_layer["metrics"]) == {"cpu_rehearsal.zz.dummy_steps"}
    assert per_layer["correct"] and e2e["correct"]


# ------------------------------------- an architecture, by files alone

ZZ_FAMILY = '''"""A family added by a test: models/gpt with an UNTIED head, and a cost
function of its own."""
from benchmarks.families.gpt2 import (  # noqa: F401
    build_model, counts, logits_at, serve_config)


def zz_decode_cost(cfg, window):
    """One operation and two bytes per decoded token and layer."""
    queries = window["counters"].get("serve.decode_slot_steps")
    if not queries:
        return None
    return {"ops": 1.0 * cfg["n_layer"] * queries,
            "bytes": 2.0 * cfg["n_layer"] * queries}
'''

ZZ_METRICS = {
    "zz.decode_mlp_dev_ms": {"rule": "scope_ms", "program": "decode_fn",
                             "phase": ["mlp"]},
    "zz.tokens_per_decode_step": {"rule": "counter",
                                  "counter": "serve.tokens_out",
                                  "over": "serve.decode_steps"},
    "zz.decode_roofline": {"rule": "roofline_pct", "match": "^zz_kernel",
                           "cost": "zz_decode_cost"},
}


@pytest.fixture
def architecture(tmp_path):
    """A family module, a configuration that names it, a cell and three
    per-layer metrics (a scope, a counter of the program's registry, a
    roofline share with the family's own cost function): six new files
    and the entries in a copy of the rehearsal registry.  Nothing that
    exists is edited."""
    made = []

    def write(rel, text):
        path = os.path.join(BENCH, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)
        made.append(path)
    try:
        write("families/zz_arch.py", ZZ_FAMILY)
        cfg = dict(traffic.load_json("configs", "tiny-gpt2"),
                   family="zz_arch", n_layer=1, tie_word_embeddings=False)
        write("configs/zz-arch.json", json.dumps(cfg))
        for name, reduce in ZZ_METRICS.items():
            write(f"metrics/{name}.json", json.dumps({
                "name": name, "what": "added by a test", "device": False,
                "reduce": reduce}))
        with open(REHEARSAL) as f:
            reg = json.load(f)
        reg["configs"].append({"name": "zz-arch", "source": "none",
                               "file": "benchmarks/configs/zz-arch.json",
                               "reduced": [], "why": "dummy"})
        reg["workloads"].append({"name": "zz-arch-cell", "config": "zz-arch",
                                 "traffic": "tiny-open", "chips": 1,
                                 "why": "dummy"})
        for m in reg["end_to_end"]:
            if m["name"] == "norm_latency_mean_ms":
                m["workloads"].append("zz-arch-cell")
        reg["per_layer"] += [
            {"name": name, "unit": "x", "better": "lower",
             "source": "device_trace", "layer": "Model programs",
             "moves": "norm_latency_mean_ms", "workloads": ["zz-arch-cell"]}
            for name in ZZ_METRICS]
        reg_path = tmp_path / "registry.json"
        reg_path.write_text(json.dumps(reg))
        yield str(reg_path)
    finally:
        for path in made:
            os.remove(path)


def test_an_architecture_is_added_by_files_alone(architecture):
    common = ["--rehearse", "--benchmark-file", architecture, "--workload",
              "zz-arch-cell", "--seed", "7", "--seconds", "2"]
    e2e = last_line(run(*common, "--trace", "0"))
    assert set(e2e["metrics"]) == {"cpu_rehearsal.norm_latency_mean_ms",
                                   "cpu_rehearsal.setup_s"}
    # `correct` is decided against the new family's forward (untied head)
    assert e2e["correct"] and not e2e["failed"]
    per_layer = last_line(run(*common, "--trace", "1"))
    # a CPU run's trace has no device plane: the scope and the roofline
    # metric find nothing to read there and are left out, not refused
    assert set(per_layer["metrics"]) == {
        "cpu_rehearsal.zz.tokens_per_decode_step"}
    assert per_layer["metrics"]["cpu_rehearsal.zz.tokens_per_decode_step"][
        "value"] >= 1.0
    assert per_layer["correct"]

    # the same three files reduced as `main()` reduces them, from a
    # hand-made device trace of the new family's decode program
    from benchmarks import run as runner
    cell = runner.load_cell(architecture, "zz-arch-cell")
    assert cell["family"].__name__ == "benchmarks.families.zz_arch"
    assert [m["name"] for m in cell["per_layer"]] == list(ZZ_METRICS)
    hlo = ("HloModule jit_decode_fn, is_scheduled=true\n\nENTRY %main {\n"
           '  %fusion.4 = f32[4,256]{0} fusion(%p), metadata={op_name="jit('
           'decode_fn)/layer/while/body/closed_call/mlp/dot_general"}\n'
           '  ROOT %zz_kernel.5 = f32[4,64]{0} fusion(%p), metadata={op_name'
           '="jit(decode_fn)/layer/attn/dot_general"}\n}\n')
    dev = "/device:TPU:0"
    ops = [trace.Event("fusion.4_f32_4_256_", 0.0, 0.002),
           trace.Event("zz_kernel.5_f32_4_64_", 0.002, 0.004),
           trace.Event("fusion.4_f32_4_256_", 0.01, 0.002),
           trace.Event("zz_kernel.5_f32_4_64_", 0.012, 0.004)]
    mods = [trace.Event("jit_decode_fn(1)", 0.0, 0.006),
            trace.Event("jit_decode_fn(1)", 0.01, 0.006)]
    tr = trace.Trace({dev: ops}, {dev: mods}, [])
    ctx = {"config": cell["config"], "family": cell["family"],
           "hlo_texts": [hlo], "counters": {},
           "peaks": {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e3},
           "registry": {"serve.tokens_out": 6.0, "serve.decode_steps": 2.0},
           "window_counts": {"steps": 2, "counters": {
               "serve.decode_slot_steps": 4.0}}}
    got = {name: trace.reduce_metric(runner.metric_spec(name), tr,
                                     (0.0, 0.02), ctx)
           for name in ZZ_METRICS}
    # 2 bytes x 1 layer x 4 queries at 1e3 B/s over 8 ms of the kernel
    assert got == pytest.approx({"zz.decode_mlp_dev_ms": 2.0,
                                 "zz.tokens_per_decode_step": 3.0,
                                 "zz.decode_roofline": 100.0})
