"""The tier-1 twin of `benchmarks/tests/test_registry.py` (PERF.md s7:
owed since PR 40): the registry of per-layer metrics, `BENCHMARK.json`'s
`per_layer` list and the rule files under `benchmarks/metrics/`, held to
each other, to the limits of the file and to the cost functions of each
listed cell's family, where the driver counts; and three families' files
with their CPU rehearsals, from `benchmarks/tests/test_ling_family.py`,
`benchmarks/tests/test_phi4flash_family.py` and
`benchmarks/tests/test_jamba_family.py` (the newest family's twin is
`tests/test_benchmark_longcat.py`: a file is what one worker of the
tier-1 run takes whole).
The tests are the benchmark's own, imported and called: nothing is written
twice."""
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# a family may bring a reduction rule of its own, registered at import, as
# `run.load_cell` imports a cell's family before any metric is reduced
# (benchmarks/tests/conftest.py does the same for that directory)
import benchmarks.families.bailing_hybrid  # noqa: E402,F401


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "benchmarks_tests_" + name,
        os.path.join(ROOT, "benchmarks", "tests", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


registry = _load("test_registry")
ling = _load("test_ling_family")
phi4 = _load("test_phi4flash_family")
jamba = _load("test_jamba_family")


@pytest.mark.parametrize("name", [
    n for n in dir(registry) if n.startswith("test_")])
def test_registry(name):
    getattr(registry, name)()


@pytest.mark.parametrize("name", [
    "test_the_cell_and_its_files",
    "test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs",
    "test_cost_functions_count_what_the_model_needs",
    "test_near_tie_passes_know_both_edges_and_change_only_their_rows"])
def test_ling_family(name):
    getattr(ling, name)()


@pytest.mark.parametrize("control", ["unmasked", "bf16_state"])
def test_a_control_comes_out_not_correct(control):
    ling.test_a_control_comes_out_not_correct(control)


@pytest.mark.parametrize("trace_on", [0, 1])
def test_tiny_ling_rehearses_correct(trace_on):
    """`benchmarks/run.py --rehearse` on `tiny-ling-long-tail`: the cell's
    whole path on the CPU, the check under the near-tie passes."""
    ling.test_tiny_ling_rehearses_correct(trace_on)


def test_the_parent_fails_at_once_on_the_new_cell(tmp_path):
    ling.test_the_parent_fails_at_once_without_the_family_module(tmp_path)


@pytest.mark.parametrize("name", [
    "test_the_cell_and_its_files",
    "test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs",
    "test_cost_functions_count_what_the_model_needs",
    "test_a_control_comes_out_not_correct"])
def test_phi4flash_family(name):
    getattr(phi4, name)()


@pytest.mark.parametrize("trace_on", [0, 1])
def test_tiny_phi4flash_rehearses_correct(trace_on):
    """`benchmarks/run.py --rehearse` on `tiny-phi4flash-reasoning-turns`:
    the cell's whole path on the CPU."""
    phi4.test_tiny_phi4flash_rehearses_correct(trace_on)


def test_the_parent_fails_at_once_on_the_phi4flash_cell(tmp_path):
    phi4.test_the_parent_fails_at_once_without_the_family_module(tmp_path)


@pytest.mark.parametrize("name", [
    "test_the_cell_and_its_files",
    "test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs",
    "test_cost_functions_count_what_the_model_needs",
    "test_a_control_comes_out_not_correct"])
def test_jamba_family(name):
    getattr(jamba, name)()


@pytest.mark.parametrize("trace_on", [0, 1])
def test_tiny_jamba_rehearses_correct(trace_on):
    """`benchmarks/run.py --rehearse` on `tiny-jamba-concurrent-turns`:
    the cell's whole path on the CPU."""
    jamba.test_tiny_jamba_rehearses_correct(trace_on)


def test_the_parent_fails_at_once_on_the_jamba_cell(tmp_path):
    jamba.test_the_parent_fails_at_once_without_the_family_module(tmp_path)


def test_the_recorded_spans_trace_reads_as_the_scope_map_places_it(tmp_path):
    """`benchmarks/tests/test_trace_spans_scopes.py` (PR 53): the trace
    recorded on a v5e reduced with `obs.scope_map` as it resolves now,
    every guard of the standing `head` test kept standing where the
    driver counts (what reads no scope as `head` has it, the rest as
    `tiny-chat-spans.scopes-pr53.json`, the rows' sums unmoved)."""
    _load("test_trace_spans_scopes") \
        .test_the_spans_trace_reads_as_head_but_for_what_the_map_now_places(
            tmp_path)
