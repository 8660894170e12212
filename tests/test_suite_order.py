"""The order the tier-1 files are handed out in (tests/conftest.py):
read from the record of the last junit, `tests/durations.json`."""
import json

from conftest import DURATIONS, longest_first, write_durations


def test_a_file_the_record_has_not_timed_goes_first():
    seconds = {"test_a": 10.0, "test_b": 300.0, "test_c": 40.0}
    order = longest_first(
        {"test_a", "test_b", "test_c", "test_new", "test_also_new"}, seconds)
    assert order == ["test_also_new", "test_new",
                     "test_b", "test_c", "test_a"]


def test_the_record_is_a_junits_seconds_by_file(tmp_path):
    junit = tmp_path / "t1.xml"
    junit.write_text(
        '<testsuites><testsuite>'
        '<testcase classname="tests.test_a" name="x" time="1.26" />'
        '<testcase classname="tests.test_b" name="y[0]" time="30.0" />'
        '<testcase classname="tests.test_a" name="z" time="2.5" />'
        '</testsuite></testsuites>')
    out = tmp_path / "durations.json"
    assert write_durations(str(junit), str(out)) == {
        "test_b": 30.0, "test_a": 3.8}
    assert list(json.loads(out.read_text())) == ["test_b", "test_a"]
    # and the committed record is one: stems of this directory, seconds
    with open(DURATIONS) as fh:
        committed = json.load(fh)
    assert committed and all(
        stem.startswith("test_") and s >= 0 for stem, s in committed.items())
