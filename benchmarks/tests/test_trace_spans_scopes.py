"""`tiny-chat-spans.xplane.pb.gz` read with the scope map as it places
instructions since PR 53 (`obs.hlo_profile.scope_map`: own `op_name`, a
fusion's body, the first scoped user, the first scoped operand).

`test_trace.py::test_a_trace_with_the_programs_spans_reads_as_head_read_it`
holds the file to `head`, the readings of the map before PR 53, and stops
at the first of the three `scope_ms` metrics that the new map moves (the
recorded decode text is not fully scoped: 16.6 of its 24.1 us were pool
copies, `copy-start` / `-done` pairs and fusions without metadata).  Until
a `benchmark` PR records `head` again, this file keeps every guard of that
test standing: what does not read a scope reads as `head` has it, and what
does reads as `tiny-chat-spans.scopes-pr53.json` has it, every program's
rows summing to what `head`'s rows sum to."""
import gzip
import importlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import peaks, trace, traffic  # noqa: E402

# the standing test's own helpers, by path (this directory is no package)
_spec = importlib.util.spec_from_file_location(
    "benchmarks_tests_test_trace", os.path.join(HERE, "test_trace.py"))
tt = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tt)

MOVED = {"chat.decode_attn_dev_ms", "chat.decode_kv_write_dev_ms",
         "chat.decode_unscoped_dev_ms"}


def row_sum(program):
    return sum(r["ms"] for r in program["rows"])


def test_the_spans_trace_reads_as_head_but_for_what_the_map_now_places(
        tmp_path):
    with gzip.open(tt.SPANS_CTX, "rt") as f:
        saved = json.load(f)
    with open(os.path.join(HERE, "tiny-chat-spans.scopes-pr53.json")) as f:
        since = json.load(f)
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(tt.SPANS_TRACE) as f:
        path.write_bytes(f.read())
    head, emitted = saved["head"], []
    ctx = dict(saved["ctx"], peaks=peaks.peaks_for("TPU v5 lite"),
               family=importlib.import_module(saved["ctx"]["family"]),
               emit=lambda **rec: emitted.append(rec))
    tr = trace.read_xplane(str(path))
    w = trace.window_of(tr)
    assert list(w) == head["window"]
    assert tt.close(trace.busy_seconds(tr, w), head["busy_s"])
    assert set(since["metrics"]) == MOVED
    want = {**head["metrics"], **since["metrics"]}
    assert want.pop("chat.admit_stall_pct") == 0.0      # retired, PR 40
    for name, was in want.items():
        spec = traffic.load_json("metrics", name)
        assert tt.close(trace.reduce_metric(spec, tr, w, ctx), was), name
    assert sum(v is not None for v in want.values()) == 21
    assert tt.close([list(x) for x in trace.top_device_ops(tr, w)],
                    head["device_ops"])
    assert tt.close([list(x) for x in trace.attribute_gaps(tr, w)],
                    head["idle_gaps"])
    assert tt.close({n: trace.idle_position_ms(tr, w, n)
                     for n in trace.SYNC_SPANS},
                    head["sync_idle_position_ms"])
    assert trace.eager_dispatches(tr.launches, w) == head["eager_dispatches"]
    scopes = json.loads(json.dumps(emitted[0]["programs"]))
    assert tt.close(scopes, since["scopes"])
    # time moved between a program's rows, none made or lost; and less of
    # every program is `unscoped` than `head` read, never more
    assert set(scopes) == set(head["scopes"])
    for name, program in scopes.items():
        was = head["scopes"][name]
        assert program["executions"] == was["executions"]
        assert tt.same(program["device_ms_per_execution"],
                       was["device_ms_per_execution"])
        assert tt.same(row_sum(program), row_sum(was), tol=1e-12)
        unscoped = [sum(r["ms"] for r in p["rows"] if r["group"] == "unscoped")
                    for p in (program, was)]
        assert unscoped[0] <= unscoped[1] + 1e-12, name
    tt.assert_reduces_as_before(tr, w)
