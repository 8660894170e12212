"""Env-flag surface tests (reference: GetExecEnvs,
executable_graph.cc:1163-1313 — the runtime-behavior env contract)."""
import numpy as np
import pytest

from hetu_tpu.utils import flags


def test_defaults():
    assert flags.bool_flag("HETU_TPU_SWITCH_PROFILE") is False
    assert flags.bool_flag("HETU_TPU_EVENT_TIMING") is False
    assert flags.str_flag("HETU_TPU_CP_SPLIT") == "sym"
    assert flags.str_flag("HETU_TPU_PALLAS") == "auto"
    assert flags.int_flag("HETU_TPU_NUM_PROCESSES") == 0


def test_env_overrides(monkeypatch):
    monkeypatch.setenv("HETU_TPU_EVENT_TIMING", "1")
    assert flags.bool_flag("HETU_TPU_EVENT_TIMING") is True
    monkeypatch.setenv("HETU_TPU_SWITCH_PROFILE", "1")
    assert flags.bool_flag("HETU_TPU_SWITCH_PROFILE") is True
    monkeypatch.setenv("HETU_TPU_CP_SPLIT", "stripe")
    assert flags.str_flag("HETU_TPU_CP_SPLIT") == "stripe"
    monkeypatch.setenv("HETU_TPU_CP_SPLIT", "bogus")
    with pytest.raises(ValueError):
        flags.str_flag("HETU_TPU_CP_SPLIT")
    monkeypatch.setenv("HETU_TPU_NUM_PROCESSES", "4")
    assert flags.int_flag("HETU_TPU_NUM_PROCESSES") == 4


def test_unknown_flag_rejected():
    with pytest.raises(KeyError):
        flags.bool_flag("HETU_TPU_NOT_A_FLAG")


def test_every_env_read_is_registered():
    """Flag-registry audit: every HETU_TPU_* name the runtime source
    mentions must be registered in utils/flags.py — an env var someone
    reads via os.environ but never registers is invisible to
    `flags.describe()` and silently undocumented."""
    import pathlib
    import re

    root = pathlib.Path(flags.__file__).resolve().parents[2]
    sources = (list((root / "hetu_tpu").rglob("*.py"))
               + list(root.glob("tools_*.py"))
               + [root / "bench.py"])
    assert len(sources) > 50, "audit walked the wrong root"
    pat = re.compile(r"HETU_TPU_[A-Z0-9_]+")
    found: dict = {}
    for py in sources:
        for name in pat.findall(py.read_text()):
            found.setdefault(name, py.name)
    # the test file itself fabricates one unknown name on purpose
    unregistered = {n: f for n, f in found.items() if n not in flags.REGISTRY}
    assert not unregistered, (
        f"HETU_TPU_* env reads not registered in utils/flags.py: "
        f"{unregistered}")
    # and the new telemetry/health/rotation flags are part of the surface
    for name in ("HETU_TPU_TELEMETRY_PUSH", "HETU_TPU_HEALTH",
                 "HETU_TPU_RUNLOG_MAX_MB"):
        assert name in flags.REGISTRY
    # the serving surface (hetu_tpu/serving, docs/serving.md), incl.
    # the PR 15 production-decoding flags (sampling, speculative
    # decoding, radix prefix cache, preemptive admission)
    for name in ("HETU_TPU_KV_QUANT", "HETU_TPU_SERVE_SLOTS",
                 "HETU_TPU_SERVE_PAGE", "HETU_TPU_SERVE_MAX_LEN",
                 "HETU_TPU_SERVE_PREFILL_CHUNK", "HETU_TPU_SERVE_PAGES",
                 "HETU_TPU_SERVE_TRACE", "HETU_TPU_SERVE_SAMPLE",
                 "HETU_TPU_SPEC_DECODE", "HETU_TPU_SPEC_K",
                 "HETU_TPU_SERVE_PREFIX_CACHE",
                 "HETU_TPU_SERVE_PREFIX_PAGES", "HETU_TPU_SERVE_PREEMPT",
                 "HETU_TPU_SERVE_QUOTAS",
                 "HETU_TPU_RUNLOG_SERVE_SAMPLE"):
        assert name in flags.REGISTRY
    # the analytic step profiler + perf-budget surface
    # (obs.hlo_profile / obs.budget, docs/observability.md)
    for name in ("HETU_TPU_PROFILE", "HETU_TPU_PROFILE_TOPK",
                 "HETU_TPU_BUDGETS"):
        assert name in flags.REGISTRY
    # the fused-kernel layer's routing knobs (ops/pallas,
    # docs/kernels.md): the whole-layer switch + the per-kernel bisect
    for name in ("HETU_TPU_PALLAS", "HETU_TPU_PALLAS_KERNELS"):
        assert name in flags.REGISTRY
    # the graph-contract linter's per-compile hook
    # (hetu_tpu/analysis, docs/static_analysis.md)
    assert "HETU_TPU_LINT" in flags.REGISTRY
    # the numerics observatory (obs/numerics.py, docs/observability.md):
    # the main gate + its sampling-interval sub-flag
    for name in ("HETU_TPU_NUMERICS", "HETU_TPU_NUMERICS_EVERY"):
        assert name in flags.REGISTRY
    # the explicit expert-parallel MoE dispatch (nn/moe_dispatch.py,
    # docs/moe.md)
    assert "HETU_TPU_MOE_DISPATCH" in flags.REGISTRY
    # the serving fault-tolerance surface (docs/fault_tolerance.md):
    # engine failover retries, deadlines, brownout shedding, KV
    # re-paging across reshards
    for name in ("HETU_TPU_SERVE_RETRY", "HETU_TPU_SERVE_DEADLINE",
                 "HETU_TPU_SERVE_BROWNOUT", "HETU_TPU_SERVE_KV_REPAGE"):
        assert name in flags.REGISTRY
    # the disaggregated prefill/decode fleet + fault-tolerant frontend
    # (serving/disagg.py, serving/frontend.py, docs/serving.md)
    for name in ("HETU_TPU_SERVE_DISAGG", "HETU_TPU_SERVE_SHIP_QUANT",
                 "HETU_TPU_SERVE_HEDGE"):
        assert name in flags.REGISTRY


def test_identity_contract_table():
    """The declarative byte-identity table (docs/static_analysis.md):
    each entry's value must be a LEGAL value of its flag (a contract on
    an unsettable value would sweep vacuously), routing flags carry
    their neutral value, analysis flags carry "1", and the known
    contracted surface never silently shrinks — the flag-identity sweep
    (tests/test_flag_identity.py) enforces the semantics; this pins the table."""
    table = flags.identity_flags()
    for name, value in table.items():
        f = flags.REGISTRY[name]
        if f.choices:
            assert value in f.choices, (name, value)
        if f.kind == "bool":
            assert value in ("0", "1"), (name, value)
    assert table["HETU_TPU_GRAD_COMPRESS"] == "none"
    assert table["HETU_TPU_COMM_TOPOLOGY"] == "flat"
    assert table["HETU_TPU_PALLAS"] == "0"
    assert table["HETU_TPU_PROFILE"] == "1"
    assert table["HETU_TPU_LINT"] == "1"
    # the serving flight recorder is host-side only: ON must be a no-op
    # for the compiled programs.  Since the distributed-tracing layer
    # (PR 20) it also stamps clock/tier/replica trace context and the
    # hedge_withdrawn terminal — still pure bookkeeping
    assert table["HETU_TPU_SERVE_TRACE"] == "1"
    # the numerics observatory changes the traced program when ON (the
    # stats ride the step outputs), so its contract is the OFF value
    assert table["HETU_TPU_NUMERICS"] == "0"
    # the explicit MoE dispatch reshapes the traced program when routed,
    # so its contract is the GSPMD default
    assert table["HETU_TPU_MOE_DISPATCH"] == "gspmd"
    # the decoding subsystem: every new serve/spec flag is contracted
    # at its off/neutral value
    assert table["HETU_TPU_SERVE_SAMPLE"] == "0"
    assert table["HETU_TPU_SPEC_DECODE"] == "none"
    assert table["HETU_TPU_SPEC_K"] == "4"
    assert table["HETU_TPU_SERVE_PREFIX_CACHE"] == "0"
    assert table["HETU_TPU_SERVE_PREEMPT"] == "0"
    # the fleet-observatory surface: quota-free / log-everything are the
    # identity values (host-side policy only; decode program unchanged)
    assert table["HETU_TPU_SERVE_QUOTAS"] == ""
    assert table["HETU_TPU_RUNLOG_SERVE_SAMPLE"] == "1"
    # the serving fault-tolerance flags: all host-side policy, each
    # contracted at a SETTABLE value (retry sweeps a nonzero budget —
    # the budget only gates requeue bookkeeping, never the program)
    assert table["HETU_TPU_SERVE_RETRY"] == "3"
    assert table["HETU_TPU_SERVE_DEADLINE"] == "1"
    assert table["HETU_TPU_SERVE_BROWNOUT"] == "1"
    assert table["HETU_TPU_SERVE_KV_REPAGE"] == "1"
    # the disaggregated fleet + frontend: all host-side orchestration
    # (the tiers run the engine's own chunk/write/decode programs), so
    # each is contracted at an ON value — disagg enabled, int8 wire,
    # hedging armed.  The TOKEN-identity half (exact wire only) lives in
    # tests/test_disagg.py
    assert table["HETU_TPU_SERVE_DISAGG"] == "1"
    assert table["HETU_TPU_SERVE_SHIP_QUANT"] == "int8"
    assert table["HETU_TPU_SERVE_HEDGE"] == "2"
    assert len(table) >= 29
    # flags with NO contract must stay contract-free: these genuinely
    # change program shapes, so an identity entry would be a lie the
    # sweep turns into a tier-1 failure
    for name in ("HETU_TPU_SERVE_SLOTS", "HETU_TPU_SERVE_MAX_LEN",
                 "HETU_TPU_MAX_PLANS", "HETU_TPU_RUNLOG"):
        assert name not in table


def test_doc_flag_drift():
    """Doc-drift gate: every HETU_TPU_* name in docs/*.md + README
    exists in the registry (docs naming dead flags fail loudly) and
    every registered flag is documented somewhere a reader can find it
    (README flag reference / the subsystem docs)."""
    import pathlib
    import re

    root = pathlib.Path(flags.__file__).resolve().parents[2]
    docs = sorted((root / "docs").glob("*.md")) + [root / "README.md"]
    assert len(docs) >= 6, "doc-drift walked the wrong root"
    pat = re.compile(r"HETU_TPU_[A-Z0-9_]+")
    mentioned: dict = {}
    for d in docs:
        for name in pat.findall(d.read_text()):
            mentioned.setdefault(name, d.name)
    dead = {n: f for n, f in mentioned.items() if n not in flags.REGISTRY}
    assert not dead, f"docs mention unregistered flags: {dead}"
    undocumented = sorted(set(flags.REGISTRY) - set(mentioned))
    assert not undocumented, (
        f"registered flags documented nowhere in docs/*.md or README: "
        f"{undocumented}")
    # the distributed-tracing doc surface (PR 20): the observability doc
    # owns the "Distributed tracing" section, the serving doc and README
    # point at it, and the CLI drill-down is documented where a reader
    # debugging one slow request would look
    obs_doc = (root / "docs" / "observability.md").read_text()
    assert "## Distributed tracing" in obs_doc
    for needle in ("FleetTrace.stitch", "hedge_withdrawn", "clock",
                   "critical_path", "stitched_trace"):
        assert needle in obs_doc, f"observability.md lost {needle!r}"
    serving_doc = (root / "docs" / "serving.md").read_text()
    assert "Distributed tracing" in serving_doc
    assert "--request" in serving_doc
    readme = (root / "README.md").read_text()
    assert "FleetTrace.stitch" in readme and "--request" in readme


def test_profile_flag_defaults_are_off_path():
    """Profiler defaults: off, top-8, no budget file —
    and all of them are post-compile analysis only (the HLO
    byte-identity half lives in tests/test_hlo_profile.py)."""
    assert flags.bool_flag("HETU_TPU_PROFILE") is False
    assert flags.int_flag("HETU_TPU_PROFILE_TOPK") == 8
    assert flags.str_flag("HETU_TPU_BUDGETS") == ""


def test_serving_flag_defaults_are_off_path(monkeypatch):
    """Serving defaults: kv cache exact, shapes sane; the flags feed
    ServeConfig.from_flags and nothing on the training path reads them."""
    assert flags.str_flag("HETU_TPU_KV_QUANT") == "none"
    assert flags.int_flag("HETU_TPU_SERVE_PAGES") == 0
    monkeypatch.setenv("HETU_TPU_KV_QUANT", "int3")
    import pytest as _pytest
    with _pytest.raises(ValueError):
        flags.str_flag("HETU_TPU_KV_QUANT")
    monkeypatch.setenv("HETU_TPU_KV_QUANT", "int8")
    monkeypatch.setenv("HETU_TPU_SERVE_SLOTS", "2")
    from hetu_tpu.serving.engine import ServeConfig
    cfg = ServeConfig.from_flags(page_size=8, max_len=32, prefill_chunk=8)
    assert cfg.kv_quant == "int8" and cfg.num_slots == 2
    assert cfg.num_pages == 2 * (32 // 8)


def test_describe_and_active(monkeypatch):
    monkeypatch.setenv("HETU_TPU_TRACE_DIR", "/tmp/t")
    text = flags.describe()
    for name in flags.REGISTRY:
        assert name in text
    assert flags.active().get("HETU_TPU_TRACE_DIR") == "/tmp/t"


def test_cp_split_flag_drives_default(monkeypatch):
    """cp_split_batch with split=None follows HETU_TPU_CP_SPLIT
    (reference: HETU_PARALLEL_ATTN_SPLIT_PATTERN)."""
    from hetu_tpu.data.bucket import cp_split_batch
    batch = {"input_ids": np.arange(16)[None, :].repeat(2, 0)}
    monkeypatch.setenv("HETU_TPU_CP_SPLIT", "normal")
    parts = cp_split_batch(batch, cp=2)
    np.testing.assert_array_equal(parts[0]["input_ids"][0], np.arange(8))
    monkeypatch.setenv("HETU_TPU_CP_SPLIT", "sym")
    parts = cp_split_batch(batch, cp=2)
    np.testing.assert_array_equal(
        parts[0]["input_ids"][0],
        np.concatenate([np.arange(4), np.arange(12, 16)]))


def test_pallas_flag_forces_route(monkeypatch):
    """HETU_TPU_PALLAS force-routes between the Pallas kernel (interpret
    mode on the CPU backend) and the XLA composition; both must agree."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops.attention import flash_attention
    k = jax.random.key(0)
    q = jax.random.normal(k, (1, 256, 2, 128), jnp.float32)
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    xla = flash_attention(q, q, q)
    assert xla.shape == q.shape
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    pallas = flash_attention(q, q, q)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(pallas),
                               atol=2e-5)
