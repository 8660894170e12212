"""The flag-identity sweep (hetu_tpu/analysis/flag_identity.py,
docs/static_analysis.md): every registered contract is held against
every canonical program, by a second lower where the program's build
reads the flag and by the record of reads where it does not; a broken
contract is DETECTED.  A file of its own: under `--dist loadfile` the
sweep is one worker's, the rest of the linter's tests another's."""
import pytest

from hetu_tpu.analysis import flag_identity, programs
from hetu_tpu.analysis.flag_identity import identity_sweep
from hetu_tpu.utils import flags


def test_identity_sweep_rejects_unknown_flag():
    with pytest.raises(ValueError, match="no identity contract"):
        identity_sweep(only_flags=["HETU_TPU_RUNLOG"])


def test_identity_sweep_detects_a_broken_contract(monkeypatch):
    """A contract that genuinely changes the program must be CAUGHT:
    temporarily register identity=\"2\" on HETU_TPU_SERVE_SLOTS (slots
    reshape the decode program) and watch the sweep fail it."""
    import dataclasses
    fake = dataclasses.replace(flags.REGISTRY["HETU_TPU_SERVE_SLOTS"],
                               identity="2")
    monkeypatch.setitem(flags.REGISTRY, "HETU_TPU_SERVE_SLOTS", fake)
    sweep = identity_sweep(only_flags=["HETU_TPU_SERVE_SLOTS"],
                           programs=["decode"])
    errors = [f for f in sweep["findings"] if f.severity == "error"]
    assert len(errors) == 1
    assert errors[0].lint == "flag-identity"
    assert "HETU_TPU_SERVE_SLOTS" in errors[0].message
    assert not sweep["rows"][0]["ok"]


@pytest.fixture()
def counted_programs(monkeypatch):
    """Two stand-in programs whose builds are counted: `reader` asks for
    HETU_TPU_SERVE_SAMPLE and shows what it got, `deaf` asks for no
    flag."""
    calls = {"reader": 0, "deaf": 0}

    def reader():
        calls["reader"] += 1
        return f"module sample={flags.bool_flag('HETU_TPU_SERVE_SAMPLE')}"

    def deaf():
        calls["deaf"] += 1
        return "module"
    monkeypatch.setattr(flag_identity, "PROGRAMS",
                        {"reader": reader, "deaf": deaf})
    return calls


def test_a_flag_the_program_never_reads_is_held_by_the_record(
        counted_programs):
    sweep = identity_sweep(only_flags=["HETU_TPU_SERVE_SAMPLE"],
                           programs=["deaf"])
    assert counted_programs["deaf"] == 1          # the baseline alone
    row, = sweep["rows"]
    assert row["ok"] and row["read"] is False
    assert row["fingerprint"] == sweep["baseline"]["deaf"]


def test_a_flag_the_program_reads_is_lowered_again(counted_programs):
    sweep = identity_sweep(only_flags=["HETU_TPU_SERVE_SAMPLE",
                                       "HETU_TPU_SPEC_K"],
                           programs=["reader"])
    # the baseline, and one more for the flag it read: not for the other
    assert counted_programs["reader"] == 2
    by_flag = {r["flag"]: r for r in sweep["rows"]}
    assert by_flag["HETU_TPU_SERVE_SAMPLE"]["read"] is True
    assert by_flag["HETU_TPU_SERVE_SAMPLE"]["ok"]   # "0" is the default
    assert by_flag["HETU_TPU_SPEC_K"]["read"] is False


def test_identity_sweep_covers_every_contract_and_holds():
    """Acceptance: 100% of registered byte-identity flags, each against
    ALL FOUR canonical programs (train, serving decode, the MoE
    forward+backward added with the numerics observatory, and the ep=2
    expert-parallel MoE step added with the explicit dispatch) — zero
    violations: the systematic replacement for the per-flag hand-written
    byte-identity tests.  The flags the serving engine alone reads are
    held against the three train steps by the record of reads, and
    against the decode program by a second lower."""
    table = flags.identity_flags()
    # the surface under contract — shrinkage is a failure
    assert set(table) >= {
        "HETU_TPU_GRAD_COMPRESS", "HETU_TPU_SP_COMPRESS",
        "HETU_TPU_ZERO_COMPRESS", "HETU_TPU_COMM_TOPOLOGY",
        "HETU_TPU_PALLAS", "HETU_TPU_PALLAS_KERNELS",
        "HETU_TPU_KV_QUANT", "HETU_TPU_PROFILE",
        "HETU_TPU_COMM_ANALYZE", "HETU_TPU_LINT",
        "HETU_TPU_NUMERICS", "HETU_TPU_MOE_DISPATCH",
        # the PR 15 decoding subsystem (decode-program contracts)
        "HETU_TPU_SERVE_SAMPLE", "HETU_TPU_SPEC_DECODE",
        "HETU_TPU_SPEC_K", "HETU_TPU_SERVE_PREFIX_CACHE",
        "HETU_TPU_SERVE_PREFIX_PAGES", "HETU_TPU_SERVE_PREEMPT",
        # the distributed-tracing flight recorder (PR 20: clock basis,
        # tier/replica trace context, hedge_withdrawn terminals — all
        # host-side, decode-program contract)
        "HETU_TPU_SERVE_TRACE"}
    assert set(programs.PROGRAMS) == {"train", "decode", "moe", "moe_ep"}
    want = {(f, p) for f in table for p in programs.PROGRAMS}
    sweep = identity_sweep()
    covered = {(r["flag"], r["program"]) for r in sweep["rows"]}
    assert covered == want
    violations = [r for r in sweep["rows"] if not r["ok"]]
    assert violations == [], violations
    assert not any(f.severity == "error" for f in sweep["findings"])
    # the routing flags the deleted per-flag tests held are READ by the
    # train step, so a second lower holds each of them
    lowered = {(r["flag"], r["program"]) for r in sweep["rows"]
               if r["read"]}
    assert {("HETU_TPU_GRAD_COMPRESS", "train"),
            ("HETU_TPU_ZERO_COMPRESS", "train"),
            ("HETU_TPU_COMM_TOPOLOGY", "train"),
            ("HETU_TPU_KV_QUANT", "decode"),
            ("HETU_TPU_MOE_DISPATCH", "moe_ep")} <= lowered
