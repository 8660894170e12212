"""The tier-1 twin of `benchmarks/tests/test_xing4_family.py`: the
Xing4.0 cell's files, its scope rules against the programs, its cost
functions, its CPU rehearsal and what the parent does on the cell.  The
tests are the benchmark's own, imported and called; in a file of their own
because a file is what one worker of the tier-1 run takes whole
(`tests/test_benchmark_registry.py` is the longest there is)."""
import pytest

from test_benchmark_registry import _load

xing4 = _load("test_xing4_family")


@pytest.mark.parametrize("name", [
    "test_the_cell_and_its_files",
    "test_the_cost_functions_count_what_the_counters_say",
    "test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs"])
def test_xing4_family(name):
    getattr(xing4, name)()


def test_tiny_xing4_rehearses_correct():
    """`benchmarks/run.py --rehearse` on `tiny-xing4-long-documents`: the
    cell's whole path on the CPU, traced (the benchmark's own file runs
    it untraced too: the same path less the readings)."""
    xing4.test_tiny_xing4_rehearses_correct(1)


def test_the_parent_fails_at_once_on_the_xing4_cell(tmp_path):
    xing4.test_the_parent_fails_at_once_without_the_family_module(tmp_path)
