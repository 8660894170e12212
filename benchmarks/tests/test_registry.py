"""The registry of per-layer metrics: `BENCHMARK.json`'s `per_layer` list
and the rule files under `benchmarks/metrics/`, held to each other and to
the limits of the file (PR 40: one entry a quantity, each with the list
of the cells that report it; PR 57: one entry a RULE and end-to-end
metric, held by a guard).  Plain tests, not one a metric: a later fold
or prune does not change how many there are."""
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import trace, traffic  # noqa: E402


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_entries_and_rule_files_stand_one_to_one():
    names = [m["name"] for m in bench()["per_layer"]]
    assert len(names) == len(set(names))
    assert set(os.listdir(os.path.join(BENCH, "metrics"))) == {
        n + ".json" for n in names}
    for name in names:
        spec = traffic.load_json("metrics", name)
        assert spec["name"] == name
        assert spec["reduce"]["rule"] in trace.RULES, name
        assert isinstance(spec["device"], bool) and spec["what"], name


def test_the_file_keeps_within_its_limits():
    b = bench()
    assert len(b["per_layer"]) <= 128 and len(b["workloads"]) <= 24
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_entry_lists_its_cells_and_every_listed_name_is_a_cell():
    """An entry WITHOUT a list would be reported by every later cell; a
    name in a list that is no cell would be reported by none."""
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m.get("workloads"), m["name"]
        assert len(set(m["workloads"])) == len(m["workloads"]), m["name"]
    for m in b["per_layer"] + b["end_to_end"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
    # the cells of a list stand in the order of `workloads`
    order = {w["name"]: i for i, w in enumerate(b["workloads"])}
    for m in b["per_layer"]:
        at = [order[c] for c in m["workloads"]]
        assert at == sorted(at), m["name"]


def test_no_two_entries_of_one_end_to_end_metric_state_the_same_rule():
    """PR 57 folded twenty entries that repeated, under a cell's prefix,
    a rule (`reduce` and `device` of the rule file) that an entry moving
    the same end-to-end metric already stated: a cell that reports a
    quantity joins that entry's list.  A repeat would come back as a
    second name for one reading."""
    seen = {}
    for m in bench()["per_layer"]:
        spec = traffic.load_json("metrics", m["name"])
        key = (m["moves"], json.dumps(spec["reduce"], sort_keys=True),
               spec["device"])
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]


def test_a_shared_roofline_finds_its_cost_in_every_listed_cells_family():
    """`rule_roofline_pct` takes the cost function from the family module
    of the cell it runs in: one `grouped_matmul_roofline` entry reads
    Kimi's, Trinity's and MiMo's `grouped_matmul_cost`, each in its own
    cell, and a cell whose family lacks it would raise in the run."""
    b = bench()
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    seen = 0
    for m in b["per_layer"]:
        red = traffic.load_json("metrics", m["name"])["reduce"]
        if red["rule"] != "roofline_pct":
            continue
        seen += 1
        assert m["unit"] == "%" and m["name"].endswith("_roofline")
        for cell in m["workloads"]:
            with open(os.path.join(
                    ROOT, configs[cells[cell]["config"]]["file"])) as f:
                family = importlib.import_module(
                    "benchmarks.families." + json.load(f)["family"])
            assert callable(getattr(family, red["cost"], None)), (
                m["name"], cell, red["cost"])
    assert seen >= 6
