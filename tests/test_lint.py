"""Graph-contract linter (hetu_tpu/analysis, tools_lint.py,
docs/static_analysis.md): every HLO lint against its positive/negative
fixture pair, every AST lint against synthetic offenders, the allowlist
policy (the flag-identity sweep has tests/test_flag_identity.py, a
worker of its own), the HETU_TPU_LINT per-compile trainer hook, and the CLI
acceptance runs — incl. `--self` as the tier-1 gate: this suite failing
means a convention violation landed."""
import json
import os
import sys
import textwrap

import pytest

from hetu_tpu.analysis import (Allowlist, Finding, counts_by_severity,
                               lint_record)
from hetu_tpu.analysis.ast_lints import lint_file, lint_repo
from hetu_tpu.analysis.hlo_lints import (lint_donation, lint_dtype_drift,
                                         lint_hlo, lint_replica_groups,
                                         lint_replication,
                                         lint_scope_coverage)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "hlo")


def _fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name)) as fh:
        return fh.read()


# ---------------------------------------------------------------------------
# HLO lints: one positive + one negative fixture per lint
# ---------------------------------------------------------------------------

def test_donation_lint_pair():
    bad = lint_donation(_fixture("donation_miss.hlo"))
    assert {f.lint for f in bad} == {"donation"}
    assert {f.severity for f in bad} == {"error"}
    # both 4 MiB dying params are named with their byte cost
    assert {f.data["parameter"] for f in bad} == {0, 1}
    assert all(f.data["bytes"] == 4 * 1024 * 1024 for f in bad)
    assert lint_donation(_fixture("donation_ok.hlo")) == []


def test_donation_lint_respects_min_bytes():
    # the same miss below the size floor is noise, not a finding
    assert lint_donation(_fixture("donation_miss.hlo"),
                         min_bytes=8 * 1024 * 1024) == []


def test_donation_lint_one_finding_per_free_output():
    """One free output can absorb exactly ONE dying input: two dying
    params racing for a single undonated output must yield one finding,
    not two (the second would be unfixable once the first aliases)."""
    txt = """\
HloModule one_out

ENTRY %main (p0: f32[1024,1024], p1: f32[1024,1024]) -> (f32[1024,1024]) {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %p1 = f32[1024,1024]{1,0} parameter(1)
  %add.1 = f32[1024,1024]{1,0} add(f32[1024,1024]{1,0} %p0, f32[1024,1024]{1,0} %p1)
  ROOT %tuple.1 = (f32[1024,1024]{1,0}) tuple(f32[1024,1024]{1,0} %add.1)
}
"""
    assert len(lint_donation(txt)) == 1


def test_donation_lint_tpu_tiled_layout_alias_header():
    """TPU module headers append entry_computation_layout (with tiled
    layouts like {1,0:T(8,128)}) after input_output_alias on the SAME
    line — brace-balanced extraction must not harvest `T(8,` as a bogus
    donated parameter 8 and must keep parameter 0's real donation."""
    from hetu_tpu.obs.hlo_text import donated_parameters
    txt = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias) }, "
           "entry_computation_layout={(f32[256,256]{1,0:T(8,128)}, "
           "f32[256,256]{1,0:T(8,128)})->f32[256,256]{1,0:T(8,128)}}\n")
    has_alias, donated = donated_parameters(txt)
    assert has_alias and donated == frozenset({0})


def test_replica_groups_lint_pair():
    bad = lint_replica_groups(_fixture("branches_mismatch.hlo"))
    assert len(bad) == 1 and bad[0].severity == "error"
    assert "deadlock" in bad[0].message
    # the finding carries both branches' signatures for the report
    assert set(bad[0].data["branches"]) == {"branch_a", "branch_b"}
    assert lint_replica_groups(_fixture("branches_ok.hlo")) == []


def test_replication_lint_pair():
    bad = lint_replication(_fixture("gather_param_sized.hlo"))
    assert len(bad) == 1 and bad[0].severity == "warning"
    assert bad[0].data["bytes"] == 256 * 256 * 4
    assert lint_replication(_fixture("gather_ok.hlo")) == []


def test_dtype_drift_lint_pair():
    bad = lint_dtype_drift(_fixture("dtype_drift.hlo"), "bf16")
    assert len(bad) == 1 and bad[0].severity == "warning"
    assert "layer_0/attn" in bad[0].location
    assert lint_dtype_drift(_fixture("dtype_ok.hlo"), "bf16") == []
    # no declared dtype -> the lint cannot judge and stays silent
    assert lint_dtype_drift(_fixture("dtype_drift.hlo"), None) == []


def test_scope_coverage_lint_pair():
    bad = lint_scope_coverage(_fixture("scope_gap.hlo"))
    warns = [f for f in bad if f.severity == "warning"]
    assert len(warns) == 1 and warns[0].data["coverage"] == 0.5
    ok = lint_scope_coverage(_fixture("scope_ok.hlo"))
    assert [f.severity for f in ok] == ["info"]
    assert ok[0].data["coverage"] == 1.0


# ---------------------------------------------------------------------------
# AST lints: synthetic offenders (tmp files) + clean twins
# ---------------------------------------------------------------------------

def _lint_src(tmp_path, src: str):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(src))
    return lint_file(str(p), root=str(tmp_path))


def test_env_bypass_lint(tmp_path):
    bad = _lint_src(tmp_path, """\
        import os
        a = os.environ["HETU_TPU_PROFILE"]
        b = os.environ.get("HETU_TPU_RUNLOG", "")
        c = os.getenv("HETU_TPU_HEALTH")
        d = os.environ.get("JAX_PLATFORMS")          # not ours
        os.environ["HETU_TPU_WORKER_ID"] = "3"       # writes are fine
        """)
    assert [f.lint for f in bad] == ["env-bypass"] * 3
    assert {f.data["flag"] for f in bad} == {
        "HETU_TPU_PROFILE", "HETU_TPU_RUNLOG", "HETU_TPU_HEALTH"}
    good = _lint_src(tmp_path, """\
        from hetu_tpu.utils import flags
        a = flags.bool_flag("HETU_TPU_PROFILE")
        """)
    assert good == []


def test_env_bypass_allowed_in_flags_module(tmp_path):
    d = tmp_path / "utils"
    d.mkdir()
    p = d / "flags.py"
    p.write_text('import os\nx = os.environ.get("HETU_TPU_PROFILE")\n')
    assert lint_file(str(p), root=str(tmp_path)) == []


def test_vjp_signature_lint(tmp_path):
    bad = _lint_src(tmp_path, """\
        import functools
        import jax

        @jax.custom_vjp
        def f(x, y):
            return x * y

        def f_fwd(x):                 # primal takes 2
            return x, None

        def f_bwd(res, ct, extra):    # needs (res, ct) only
            return ct, ct

        f.defvjp(f_fwd, f_bwd)

        @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
        def g(x, y, flag, mode):
            return x + y

        def g_fwd(x, y, flag, mode):
            return x + y, None

        def g_bwd(flag, mode, res, ct):
            return ct, ct

        g.defvjp(g_fwd, g_bwd)
        """)
    assert [f.lint for f in bad] == ["vjp-signature"] * 2
    assert "f_fwd takes 1" in bad[0].message
    assert "f_bwd takes 3" in bad[1].message
    # g's pair is correct (2 nondiff + res + ct = 4) — not flagged
    assert not any("g_" in f.message for f in bad)


def test_shardmap_constraints_lint(tmp_path):
    bad = _lint_src(tmp_path, """\
        from jax import lax
        from jax import shard_map

        def run(mesh, spec, x):
            def region(v):
                return lax.with_sharding_constraint(v, spec)
            return shard_map(region, mesh=mesh, in_specs=spec,
                             out_specs=spec)(x)
        """)
    assert [f.lint for f in bad] == ["shardmap-constraints"]
    # constraint OUTSIDE the region composes via GSPMD — legal
    good = _lint_src(tmp_path, """\
        from jax import lax
        from jax import shard_map

        def run(mesh, spec, x):
            x = lax.with_sharding_constraint(x, spec)
            def region(v):
                return v * 2
            return shard_map(region, mesh=mesh, in_specs=spec,
                             out_specs=spec)(x)
        """)
    assert good == []
    # a module that references suppress_constraints knows the hatch
    hatched = _lint_src(tmp_path, """\
        from jax import lax
        from jax import shard_map
        from hetu_tpu.dstates import suppress_constraints

        def run(mesh, spec, x):
            def region(v):
                return lax.with_sharding_constraint(v, spec)
            with suppress_constraints():
                return shard_map(region, mesh=mesh, in_specs=spec,
                                 out_specs=spec)(x)
        """)
    assert hatched == []


def test_unseeded_rng_lint(tmp_path):
    bad = _lint_src(tmp_path, """\
        import random
        import numpy as np

        r = random.Random()
        x = random.random()
        y = np.random.normal(size=3)
        """)
    assert [f.lint for f in bad] == ["unseeded-rng"] * 3
    good = _lint_src(tmp_path, """\
        import random
        import numpy as np

        r = random.Random(42)
        rng = np.random.default_rng(0)
        y = rng.normal(size=3)
        """)
    assert good == []


def test_repo_ast_lints_clean_modulo_allowlist():
    """The tier-1 convention gate, as a library call: the only
    error-severity finding over the repo's own Python is the
    allowlisted rpc backoff jitter."""
    findings = lint_repo(REPO)
    errors = [f for f in findings if f.severity == "error"]
    assert [f.lint for f in errors] == ["unseeded-rng"]
    assert "rpc/client.py" in errors[0].location
    allow = Allowlist.load(os.path.join(REPO, "lint_allowlist.json"))
    kept, suppressed = allow.apply(findings)
    assert len(suppressed) == 1
    assert counts_by_severity(kept)["error"] == 0


# ---------------------------------------------------------------------------
# allowlist policy
# ---------------------------------------------------------------------------

def _f(lint="donation", loc="train_step:main", sev="error"):
    return Finding(lint, sev, loc, "msg")


def test_allowlist_reason_suppresses(tmp_path):
    p = tmp_path / "allow.json"
    p.write_text(json.dumps({"entries": [
        {"lint": "donation", "match": "train_step", "reason": "known"}]}))
    kept, suppressed = Allowlist.load(str(p)).apply([_f()])
    assert suppressed and not kept


def test_allowlist_without_reason_is_itself_an_error(tmp_path):
    p = tmp_path / "allow.json"
    p.write_text(json.dumps({"entries": [
        {"lint": "donation", "match": "train_step", "reason": ""}]}))
    kept, suppressed = Allowlist.load(str(p)).apply([_f()])
    # the original finding stays AND the entry is flagged
    assert not suppressed
    assert sorted(f.lint for f in kept) == ["allowlist-reason", "donation"]
    assert all(f.severity == "error" for f in kept)


def test_allowlist_unused_entry_warns(tmp_path):
    p = tmp_path / "allow.json"
    p.write_text(json.dumps({"entries": [
        {"lint": "donation", "match": "nowhere", "reason": "stale"}]}))
    kept, suppressed = Allowlist.load(str(p)).apply([])
    assert [f.lint for f in kept] == ["allowlist-unused"]
    assert kept[0].severity == "warning"


def test_allowlist_torn_file_raises(tmp_path):
    p = tmp_path / "allow.json"
    p.write_text("{not json")
    with pytest.raises(json.JSONDecodeError):
        Allowlist.load(str(p))


def test_lint_record_shape():
    rec = lint_record([_f(), _f("replication", sev="warning"),
                       _f("scope-coverage", sev="info")])
    assert rec["findings"] == 3 and rec["errors"] == 1 \
        and rec["warnings"] == 1
    assert rec["lints"] == {"donation": 1, "replication": 1,
                            "scope-coverage": 1}
    assert rec["messages"][0].startswith("[donation]")


# ---------------------------------------------------------------------------
# tools_lint.py CLI
# ---------------------------------------------------------------------------

def _tools_lint(capsys, *argv):
    sys.path.insert(0, REPO)
    try:
        import tools_lint
        rc = tools_lint.main(list(argv))
    finally:
        sys.path.pop(0)
    return rc, capsys.readouterr().out


def test_cli_self_is_clean(capsys):
    """tools_lint.py --self exits zero on the repo — THE tier-1 gate:
    a future PR reintroducing a convention violation fails here."""
    rc, out = _tools_lint(capsys, "--self")
    assert rc == 0, out
    assert "0 error(s)" in out


def test_cli_acceptance_injected_violations_fail_named(capsys):
    """Acceptance: a donation miss AND a replica_groups mismatch
    injected via fixtures exit nonzero with both lints named."""
    rc, out = _tools_lint(
        capsys,
        "--hlo-file", os.path.join(FIXTURES, "donation_miss.hlo"),
        "--hlo-file", os.path.join(FIXTURES, "branches_mismatch.hlo"))
    assert rc == 1
    assert "[donation]" in out and "donation_miss.hlo" in out
    assert "[replica-groups]" in out and "branches_mismatch.hlo" in out


def test_cli_json_and_allowlist(tmp_path, capsys):
    allow = tmp_path / "allow.json"
    allow.write_text(json.dumps({"entries": [
        {"lint": "donation", "match": "donation_miss.hlo",
         "reason": "fixture: the miss is the point"}]}))
    rc, out = _tools_lint(
        capsys, "--hlo-file",
        os.path.join(FIXTURES, "donation_miss.hlo"),
        "--allowlist", str(allow), "--json")
    payload = json.loads(out)
    assert rc == 0 and payload["errors"] == 0
    assert len(payload["suppressed"]) == 2
    assert all(f["lint"] == "donation" for f in payload["suppressed"])


def test_cli_hlo_file_does_not_stale_standing_waivers(tmp_path, capsys):
    """A fixture-only run must not call the repo's standing HLO waivers
    stale: an entry pinned to the real program ('train_step') suppresses
    nothing here, yet no allowlist-unused warning may fire (the lint ids
    executed by --hlo-file don't count toward staleness)."""
    allow = tmp_path / "allow.json"
    allow.write_text(json.dumps({"entries": [
        {"lint": "donation", "match": "train_step",
         "reason": "standing waiver for the real program"}]}))
    rc, out = _tools_lint(
        capsys, "--hlo-file",
        os.path.join(FIXTURES, "donation_ok.hlo"),
        "--allowlist", str(allow), "--json")
    payload = json.loads(out)
    assert rc == 0
    assert not [f for f in payload["findings"]
                if f["lint"] == "allowlist-unused"]


def test_cli_dtype_flag(capsys):
    rc, out = _tools_lint(
        capsys, "--hlo-file", os.path.join(FIXTURES, "dtype_drift.hlo"),
        "--expected-dtype", "bf16")
    assert rc == 0  # warnings never fail
    assert "[dtype-drift]" in out


# ---------------------------------------------------------------------------
# the HETU_TPU_LINT per-compile hook
# ---------------------------------------------------------------------------

def test_trainer_lint_hook(tmp_path, monkeypatch):
    """HETU_TPU_LINT=1: every fresh compile leaves a `lint` RunLog
    record + lint.* counters; the canonical (donated) train step lints
    with ZERO errors — our own program honors the contracts; and
    tools_obs_report surfaces the section.  Flag unset: no lint
    records (the identity half lives in the sweep)."""
    from hetu_tpu.analysis.programs import canonical_batch, canonical_trainer
    from hetu_tpu.obs.metrics import get_registry
    from hetu_tpu.obs.runlog import RunLog

    monkeypatch.setenv("HETU_TPU_LINT", "1")
    monkeypatch.setenv("HETU_TPU_RUNLOG", str(tmp_path / "runlog.jsonl"))
    tr = canonical_trainer()
    tr.train_step(canonical_batch())
    tr.close()
    records = RunLog.read(str(tmp_path / "runlog.jsonl"))
    lints = [r for r in records if r.get("kind") == "lint"]
    assert len(lints) == 1
    rec = lints[0]
    assert rec["name"] == "train_step"
    assert rec["errors"] == 0  # the donated step passes its own lints
    assert rec["findings"] >= 1  # scope-coverage info at minimum
    assert "scope-coverage" in rec["lints"]
    snap = json.dumps(get_registry().snapshot())
    assert "lint.findings" in snap

    # section in the report CLI
    sys.path.insert(0, REPO)
    try:
        import tools_obs_report
        section = tools_obs_report.summarize(records).get("lint")
    finally:
        sys.path.pop(0)
    assert section and section["records"] == 1 \
        and section["errors"] == 0

    # flag off: not a single lint record
    monkeypatch.delenv("HETU_TPU_LINT")
    monkeypatch.setenv("HETU_TPU_RUNLOG", str(tmp_path / "runlog2.jsonl"))
    tr2 = canonical_trainer()
    tr2.train_step(canonical_batch())
    tr2.close()
    rec2 = RunLog.read(str(tmp_path / "runlog2.jsonl"))
    assert not [r for r in rec2 if r.get("kind") == "lint"]
