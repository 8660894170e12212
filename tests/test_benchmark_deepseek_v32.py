"""The tier-1 twin of `benchmarks/tests/test_deepseek_v32_family.py`: the
DeepSeek-V3.2 cell's files, its scope rules against the programs, its cost
functions, its CPU rehearsal, the entry that makes its control readings again, and
what the parent does on the cell.  The
tests are the benchmark's own, imported and called; in a file of their own
because a file is what one worker of the tier-1 run takes whole
(`tests/test_benchmark_registry.py` is the longest there is)."""
import pytest

from test_benchmark_registry import _load

dsv32 = _load("test_deepseek_v32_family")


@pytest.mark.parametrize("name", [
    "test_the_cell_and_its_files",
    "test_the_cost_functions_count_what_the_counters_say",
    "test_every_scope_rule_of_the_cell_finds_its_scope_in_the_programs",
    "test_the_replay_follows_a_chip_run_and_the_seeds_spread_little"])
def test_deepseek_v32_family(name):
    getattr(dsv32, name)()


def test_the_controls_entry_runs_on_the_tiny_configuration(capsys):
    dsv32.test_the_controls_entry_runs_on_the_tiny_configuration(capsys)


def test_tiny_deepseek_v32_rehearses_correct():
    """`benchmarks/run.py --rehearse` on
    `tiny-deepseek-v32-sparse-long-context`: the cell's whole path on the
    CPU, traced (the benchmark's own file runs it untraced too: the same
    path less the readings)."""
    dsv32.test_tiny_deepseek_v32_rehearses_correct(1)


def test_the_parent_fails_at_once_on_the_deepseek_v32_cell(tmp_path):
    dsv32.test_the_parent_fails_at_once_without_the_family_module(tmp_path)
