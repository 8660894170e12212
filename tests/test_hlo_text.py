"""The shared HLO tokenizer (hetu_tpu/obs/hlo_text.py): line anatomy,
payload resolution, replica_groups, computation structure, trip counts,
dot FLOPs, and the module contracts the linter reads.  These pin the
layer obs/comm.py, obs/hlo_profile.py and hetu_tpu/analysis all stand
on — a behavior change here moves three byte models at once."""
import os

import pytest

from hetu_tpu.obs import hlo_text as H

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "hlo")


def _fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name)) as fh:
        return fh.read()


# ---------------------------------------------------------------------------
# shapes / payloads
# ---------------------------------------------------------------------------

def test_component_bytes_tuple_and_layouts():
    # tiled layouts and tuple components both resolve; T(8,128) must not
    # read as a shape
    comps = H.component_bytes("(f32[8,128]{1,0:T(8,128)}, s32[4]{0})")
    assert comps == [8 * 128 * 4, 4 * 4]
    assert H.shape_bytes("bf16[2,3,4]") == 24 * 2
    assert H.shape_bytes("pred[]") == 1


def test_payload_bytes_sync_sums_async_takes_max():
    section = "(f32[1024]{0}, f32[256]{0}, u32[]{:S(2)})"
    # sync: tuple components sum (a tuple all-to-all's local buffer)
    assert H.payload_bytes(section, is_start=False) == 4096 + 1024 + 4
    # async -start carries operand AND result: max is the full buffer
    assert H.payload_bytes(section, is_start=True) == 4096


def test_first_group_explicit_and_iota():
    line = "%ar = f32[8]{0} all-reduce(%x), replica_groups={{0,1},{2,3}}"
    assert H.first_group(line, 1) == (2, (0, 1))
    iota = "%ag = f32[8]{0} all-gather(%x), replica_groups=[2,4]<=[8]"
    assert H.first_group(iota, 1) == (4, (0, 1, 2, 3))
    # transposed iota: group 0 strides by num_groups
    iota_t = "%ag = f32[8]{0} all-gather(%x), replica_groups=[2,4]<=[8]T(1,0)"
    assert H.first_group(iota_t, 1) == (4, (0, 2, 4, 6))
    # no groups attribute: the default world
    assert H.first_group("%ar = f32[8]{0} all-reduce(%x)", 8)[0] == 8


def test_ring_wire_formulas_match_wire_py():
    """The tokenizer's ring formulas and comm/wire.py price the same
    algorithms — formula drift between the two is the failure mode the
    cross-validation test in test_comm exists for; pin the tokenizer
    side here at exact values."""
    n, payload = 4, 1024.0
    assert H.ring_wire_bytes("all-reduce", payload, n, False) == \
        2.0 * 3 / 4 * payload
    assert H.ring_wire_bytes("all-gather", payload, n, False) == \
        3 / 4 * payload
    # sync reduce-scatter payload is the SHARD -> (n-1) * shard
    assert H.ring_wire_bytes("reduce-scatter", payload, n, False) == \
        3 * payload
    # async start payload is the FULL buffer -> (n-1)/n * input
    assert H.ring_wire_bytes("reduce-scatter", payload, n, True) == \
        3 / 4 * payload
    assert H.ring_wire_bytes("collective-permute", payload, n, False) == \
        payload
    assert H.ring_wire_bytes("all-reduce", payload, 1, False) == 0.0


def test_maybe_collective_start_done_forms():
    # (base, is_start, LINE_PAT match) — the match rides along so
    # callers never pay a second LINE_PAT scan of the same line
    base, is_start, m = H.maybe_collective("%x = f32[8]{0} all-reduce(%y)")
    assert (base, is_start) == ("all-reduce", False)
    assert m.group("out") == "f32[8]{0}"
    base, is_start, m = H.maybe_collective(
        "%x = (f32[8]{0}, f32[8]{0}) all-reduce-start(%y)")
    assert (base, is_start) == ("all-reduce", True)
    assert H.maybe_collective("%x = f32[8]{0} all-reduce-done(%y)") is None
    assert H.maybe_collective("%x = f32[8]{0} add(%y, %z)") is None


# ---------------------------------------------------------------------------
# computation structure
# ---------------------------------------------------------------------------

_NESTED_WHILE = """\
%inner_cond (s.1: (s32[], f32[8])) -> pred[] {
  %s.1 = (s32[], f32[8]) parameter(0)
  %i.1 = s32[] get-tuple-element((s32[], f32[8]) %s.1), index=0
  %c.1 = s32[] constant(3)
  ROOT %lt.1 = pred[] compare(s32[] %i.1, s32[] %c.1), direction=LT
}

%inner_body (s.2: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s.2 = (s32[], f32[8]) parameter(0)
  ROOT %t.2 = (s32[], f32[8]) tuple(%s.2)
}

%outer_cond (s.3: (s32[], f32[8])) -> pred[] {
  %s.3 = (s32[], f32[8]) parameter(0)
  %i.3 = s32[] get-tuple-element((s32[], f32[8]) %s.3), index=0
  %c.3 = s32[] constant(5)
  ROOT %lt.3 = pred[] compare(s32[] %i.3, s32[] %c.3), direction=LT
}

%outer_body (s.4: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s.4 = (s32[], f32[8]) parameter(0)
  ROOT %w.4 = (s32[], f32[8]) while((s32[], f32[8]) %s.4), condition=%inner_cond, body=%inner_body
}

ENTRY %main (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  ROOT %w.5 = (s32[], f32[8]) while((s32[], f32[8]) %p), condition=%outer_cond, body=%outer_body
}
"""


def test_split_computations_and_entry():
    comps = H.split_computations(_NESTED_WHILE)
    # blank separator lines collect into the anonymous "" computation
    # (same for real as_text() output) — harmless, but pinned here
    assert set(comps) - {""} == {"inner_cond", "inner_body",
                                 "outer_cond", "outer_body", "main"}
    assert H.entry_computation(_NESTED_WHILE) == "main"
    # headerless snippets map to one anonymous computation
    loose = H.split_computations("%x = f32[8]{0} add(%a, %b)")
    assert list(loose) == [""]


def test_cond_trip_count_lt_and_unresolvable():
    comps = H.split_computations(_NESTED_WHILE)
    assert H.cond_trip_count(comps["inner_cond"]) == 3
    assert H.cond_trip_count(comps["outer_cond"]) == 5
    # a bound that is not a literal constant is not recoverable
    assert H.cond_trip_count(
        ["%lt = pred[] compare(s32[] %i, s32[] %n), direction=LT"]) is None


def test_cond_trip_count_reads_the_tpu_compilers_condition():
    """The compiler for a TPU gives the bound a layout and the compare
    untyped operands; XLA:CPU's text (above) does neither."""
    assert H.cond_trip_count([
        "%constant.1830 = s32[]{:T(128)} constant(16)",
        "%i = s32[]{:T(128)} get-tuple-element(%p), index=0",
        "ROOT %lt.80 = pred[]{:T(512)} compare(%i, %constant.1830), "
        "direction=LT"]) == 16
    assert H.cond_trip_count([
        "%c = s32[]{:T(128)} constant(4)",
        "ROOT %gt = pred[]{:T(512)} compare(%c, %i), direction=GT"]) == 4


def test_while_multipliers_nested_compose():
    comps = H.split_computations(_NESTED_WHILE)
    mults = H.while_multipliers(comps)
    assert mults["outer_body"] == (5, False)
    assert mults["inner_body"] == (15, False)   # 5 x 3
    assert mults["main"] == (1, False)
    # conditions execute at caller cadence under while_multipliers
    assert mults["outer_cond"] == (1, False)


def test_call_multipliers_follow_fusion_edges():
    txt = """\
%fused (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  ROOT %m.9 = f32[8]{0} multiply(f32[8]{0} %a, f32[8]{0} %a)
}

%cond9 (s.9: (s32[], f32[8])) -> pred[] {
  %s.9 = (s32[], f32[8]) parameter(0)
  %i.9 = s32[] get-tuple-element((s32[], f32[8]) %s.9), index=0
  %c.9 = s32[] constant(7)
  ROOT %lt.9 = pred[] compare(s32[] %i.9, s32[] %c.9), direction=LT
}

%body9 (s.8: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s.8 = (s32[], f32[8]) parameter(0)
  %x.8 = f32[8]{0} get-tuple-element((s32[], f32[8]) %s.8), index=1
  %f.8 = f32[8]{0} fusion(f32[8]{0} %x.8), kind=kLoop, calls=%fused
  ROOT %t.8 = (s32[], f32[8]) tuple(%s.8)
}

ENTRY %main (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  ROOT %w.7 = (s32[], f32[8]) while((s32[], f32[8]) %p), condition=%cond9, body=%body9
}
"""
    comps = H.split_computations(txt)
    mults = H.call_multipliers(comps)
    # a fusion inside a scanned body inherits the trip count — the
    # profiler's accounting (while_multipliers stops at body edges)
    assert mults["body9"] == (7.0, False)
    assert mults["fused"] == (7.0, False)
    assert H.while_multipliers(comps)["fused"] == (1, False)


def test_line_wire_bytes_composes():
    line = ("%ag.1 = f32[256,256]{1,0} all-gather(f32[64,256]{1,0} %x), "
            "replica_groups={{0,1,2,3}}, dimensions={0}")
    assert H.line_wire_bytes(line, 1) == 3 / 4 * 256 * 256 * 4
    assert H.line_wire_bytes("%a = f32[8]{0} add(%x, %y)", 4) == 0.0


# ---------------------------------------------------------------------------
# FLOPs + module contracts
# ---------------------------------------------------------------------------

_DOT_ATTRS = "lhs_contracting_dims={1}, rhs_contracting_dims={0}"


@pytest.mark.parametrize("dot", [
    # an earlier jax printed an operand's shape before its name
    "dot(f32[8,32]{1,0} %a, f32[32,16]{1,0} %b)",
    # jax 0.9 (XLA:CPU and the TPU compiler) prints the name alone
    "dot(%a, %b)",
], ids=["operand_shapes", "operand_names"])
def test_dot_flops(dot):
    lines = ["%a = f32[8,32]{1,0} parameter(0)",
             "%b = f32[32,16]{1,0} parameter(1)",
             f"ROOT %dot.1 = f32[8,16]{{1,0}} {dot}, {_DOT_ATTRS}"]
    defs = H.definitions({"main": lines})
    assert H.dot_flops(lines[2], defs) == 2.0 * (8 * 16) * 32
    assert H.dot_flops("%s = f32[8]{0} add(%x, %y)", defs) == 0.0


def test_cond_trip_count_follows_a_fused_compare():
    """XLA:CPU wraps a condition's lone compare in a fusion: the bound is
    the fusion's operand that the compare's right parameter stands for."""
    comps = H.split_computations("""
%wrapped_compare_computation (param_0.2: s32[], param_1.2: s32[]) -> pred[] {
  %param_0.2 = s32[] parameter(0)
  %param_1.2 = s32[] parameter(1)
  ROOT %lt.3 = pred[] compare(%param_0.2, %param_1.2), direction=LT
}

%cond (param.1: (s32[], f32[256])) -> pred[] {
  %param.1 = (s32[], f32[256]{0}) parameter(0)
  %constant.7 = s32[] constant(8)
  %get-tuple-element.12 = s32[] get-tuple-element(%param.1), index=0
  ROOT %wrapped_compare = pred[] fusion(%get-tuple-element.12, %constant.7), kind=kLoop, calls=%wrapped_compare_computation
}
""")
    assert H.cond_trip_count(comps["cond"], comps) == 8
    assert H.cond_trip_count(comps["cond"]) is None   # the callee unseen
    assert H.call_operands(comps["cond"][-1]) == [
        "get-tuple-element.12", "constant.7"]


_POSITIONS = """HloModule jit_f, is_scheduled=true

FileNames
1 "/root/repo/tests/test_comm.py"

FunctionNames
1 "test_a"

FileLocations
1 {file_name_id=1 function_name_id=1 line=%d end_line=%d column=4 end_column=49}

StackFrames
1 {file_location_id=1 parent_frame_id=1}


ENTRY %%main.1 (a.1: f32[8]) -> f32[8] {
  %%a.1 = f32[8]{0} parameter(0), metadata={op_name="a"}
  ROOT %%r.1 = f32[8]{0} %s(%%a.1), metadata={op_name="jit(f)/r" stack_frame_id=1}
}
"""


def test_without_source_positions_keeps_the_program_alone():
    """Two lowers of one program from two lines of a file compare equal;
    two programs from one line do not; a text with no positions (a traced
    module's) comes back as it went in."""
    at_191 = _POSITIONS % (191, 191, "negate")
    at_192 = _POSITIONS % (192, 192, "negate")
    other = _POSITIONS % (191, 191, "exponential")
    assert at_191 != at_192
    bare = H.without_source_positions(at_191)
    assert bare == H.without_source_positions(at_192)
    assert bare != H.without_source_positions(other)
    assert "line=" not in bare and "stack_frame_id" not in bare
    assert 'negate(%a.1), metadata={op_name="jit(f)/r"}' in bare
    assert H.without_source_positions(bare) == bare


def test_donated_parameters_and_entry_parameters():
    txt = _fixture("donation_ok.hlo")
    has_alias, donated = H.donated_parameters(txt)
    assert has_alias and donated == frozenset({0})
    miss = _fixture("donation_miss.hlo")
    has_alias2, donated2 = H.donated_parameters(miss)
    assert not has_alias2 and donated2 == frozenset()
    comps = H.split_computations(txt)
    params = H.entry_parameters(comps[H.entry_computation(txt, comps)])
    assert [p["number"] for p in params] == [0, 1]
    assert all(p["bytes"] == 1024 * 1024 * 4 for p in params)


def test_consumers_share_the_tokenizer():
    """obs.comm and obs.hlo_profile walk THROUGH hlo_text (no private
    regex forks left): the analyzer's rows on a synthetic module match
    hand computation via the tokenizer primitives."""
    from hetu_tpu.obs.comm import collective_table
    txt = _fixture("gather_param_sized.hlo")
    rows = collective_table(txt)
    assert len(rows) == 1 and rows[0]["op"] == "all-gather"
    assert rows[0]["group_size"] == 4
    assert rows[0]["wire_bytes"] == 3 / 4 * 256 * 256 * 4
    # and the profiler's module-level import is the shared one
    import hetu_tpu.obs.hlo_profile as hp
    assert hp.split_computations is H.split_computations
    assert hp.call_multipliers is H.call_multipliers
