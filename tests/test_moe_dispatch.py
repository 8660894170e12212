"""Explicit expert-parallel MoE dispatch (HETU_TPU_MOE_DISPATCH,
nn/moe_dispatch.py): goldens vs the GSPMD path, analyzer-verified
bytes-on-wire for fp32 vs int8 vs two-level, quantized loss parity,
envelope errors, expert-load gauges + capacity rebalancing, the
dense<->MoE-sharded hot switch, cost-model/searcher EP terms, serving
MoE decode with resident quantized experts, and the moe-dispatch HLO
lint."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.analysis.programs import scoped_env
from hetu_tpu.core.mesh import MeshConfig
from hetu_tpu.nn.moe import MoEConfig, MoELayer
from hetu_tpu.parallel import ParallelStrategy

H, INTER, E = 32, 64, 8


def _layer(st, **moe_kw):
    kw = dict(num_experts=E, top_k=2, capacity_factor=2.0)
    kw.update(moe_kw)
    return MoELayer(H, INTER, MoEConfig(**kw), st)


def _x(b=2, s=16, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(b, s, H)),
                       jnp.float32)


@pytest.fixture(scope="module")
def ep8():
    st = ParallelStrategy(mesh=MeshConfig(ep=8))
    return st, st.build_mesh()


@pytest.fixture(scope="module")
def lowered(ep8):
    """One lowered MoE-layer program per dispatch mode (compiled once
    for the whole module): {mode: (optimized_text, collective_report,
    outputs)}."""
    from hetu_tpu.obs.comm import collective_report
    st, mesh = ep8
    layer = _layer(st)
    x = _x()
    out = {}
    for name, env in [
            ("gspmd", {}),
            ("fp32", {"HETU_TPU_MOE_DISPATCH": "fp32"}),
            ("int8", {"HETU_TPU_MOE_DISPATCH": "int8"}),
            ("two_level", {"HETU_TPU_MOE_DISPATCH": "int8",
                           "HETU_TPU_COMM_TOPOLOGY": "two_level"}),
            ("fp32_2lvl", {"HETU_TPU_MOE_DISPATCH": "fp32",
                           "HETU_TPU_COMM_TOPOLOGY": "two_level"}),
    ]:
        with scoped_env(**env):
            with ht.use_mesh(mesh):
                p = layer.init(jax.random.key(2), mesh=mesh)
                compiled = jax.jit(lambda p_, x_: layer(p_, x_)) \
                    .lower(p, x).compile()
                y, aux = compiled(p, x)
        txt = compiled.as_text()
        out[name] = (txt, collective_report(txt, default_world=1),
                     (np.asarray(y), float(aux)))
    return out


# ------------------------------------------------------------- goldens
def test_fp32_dispatch_bit_matches_gspmd(lowered):
    """The explicit fp32 a2a path routes and combines EXACTLY like the
    GSPMD path: same plan, disjoint scatter destinations, exact
    collectives — outputs bit-compare."""
    _, _, (y_ref, aux_ref) = lowered["gspmd"]
    _, _, (y_fp, aux_fp) = lowered["fp32"]
    np.testing.assert_array_equal(y_ref, y_fp)
    assert aux_ref == aux_fp


def test_fp32_two_level_still_exact(lowered):
    """The hierarchical schedule re-stages the sums but every partial
    hits a disjoint destination, so fp32 two-level is exact too."""
    _, _, (y_ref, _) = lowered["gspmd"]
    _, _, (y_2l, _) = lowered["fp32_2lvl"]
    np.testing.assert_array_equal(y_ref, y_2l)


def test_int8_dispatch_within_tolerance(lowered):
    """Quantized dispatch stays within blockwise-int8 tolerance of the
    exact path (loss-level parity is pinned by the training test)."""
    _, _, (y_ref, aux_ref) = lowered["gspmd"]
    _, _, (y_q, aux_q) = lowered["int8"]
    rel = np.linalg.norm(y_ref - y_q) / max(np.linalg.norm(y_ref), 1e-9)
    assert rel < 0.03, rel
    assert aux_q == aux_ref          # routing is never quantized
    _, _, (y_2l, _) = lowered["two_level"]
    rel2 = np.linalg.norm(y_ref - y_2l) / max(np.linalg.norm(y_ref), 1e-9)
    assert rel2 < 0.05, rel2         # one extra re-quantize per stage


def test_int8_dispatch_grads_flow(ep8):
    st, mesh = ep8
    layer = _layer(st)
    x = _x(seed=3)
    with scoped_env(HETU_TPU_MOE_DISPATCH="int8"):
        with ht.use_mesh(mesh):
            p = layer.init(jax.random.key(1), mesh=mesh)
            g = jax.jit(jax.grad(
                lambda p_: jnp.sum(layer(p_, x)[0] ** 2)
                + layer(p_, x)[1]))(p)
    leaves = jax.tree.leaves(g)
    assert leaves and all(np.isfinite(np.asarray(v)).all() for v in leaves)
    # expert weights receive gradient through the quantized transports
    assert float(jnp.abs(g["w_gate_up"]).sum()) > 0
    assert float(jnp.abs(g["w_down"]).sum()) > 0


# --------------------------------------------- analyzer acceptance gates
def test_dispatch_bytes_acceptance(lowered):
    """The ISSUE's analyzer gates, measured from lowered HLO: the int8
    dispatch moves >= 3.5x fewer bytes than the fp32 a2a path, and the
    two-level schedule moves >= 2x fewer INTER-slice bytes than the
    flat slice-spanning a2a (profile topology: 2 slices of 4)."""
    rep32 = lowered["fp32"][1]
    rep8 = lowered["int8"][1]
    rep2l = lowered["two_level"][1]
    assert "all-to-all" in rep32["collectives"]
    assert "all-gather" in rep32["collectives"]
    ratio = rep32["total_wire_bytes"] / rep8["total_wire_bytes"]
    assert ratio >= 3.5, ratio
    # ep=8 spans the profile's 4-chip slices: the flat schedule lands
    # every byte on inter links, two-level only the 1/k exchange
    assert rep8["wire_bytes_inter"] > 0
    inter_ratio = rep8["wire_bytes_inter"] / max(
        rep2l["wire_bytes_inter"], 1.0)
    assert inter_ratio >= 2.0, inter_ratio
    # and the analytic wire model tells the same story
    from hetu_tpu.comm.wire import moe_dispatch_report
    rep = moe_dispatch_report(4096, 8, slice_devices=4)
    assert rep["ratio_int8"] >= 3.5
    assert rep["inter_ratio_two_level"] >= 2.0
    # the GSPMD path moves full-width bytes too (the compiler's combine
    # transport) — the explicit int8 path beats it
    gsp = lowered["gspmd"][1]["total_wire_bytes"]
    assert gsp == 0 or gsp > rep8["total_wire_bytes"]


def test_quantized_dispatch_loss_parity(ep8):
    """<1% final-loss parity: the same tiny regression trained through
    the exact GSPMD dispatch vs the int8 explicit dispatch."""
    st, mesh = ep8
    layer = _layer(st, capacity_factor=4.0)
    x = _x(b=4, s=16, seed=5)
    tgt = jnp.asarray(np.random.default_rng(6).normal(size=(4, 16, H)),
                      jnp.float32)

    def run(env):
        with scoped_env(**env):
            with ht.use_mesh(mesh):
                p = layer.init(jax.random.key(7), mesh=mesh)

                def loss(p_):
                    y, aux = layer(p_, x)
                    return jnp.mean((y - tgt) ** 2) + 0.01 * aux

                step = jax.jit(lambda p_: (
                    loss(p_),
                    jax.tree.map(lambda w, g: w - 0.05 * g, p_,
                                 jax.grad(loss)(p_))))
                l = None
                for _ in range(30):
                    l, p = step(p)
                return float(l)

    l_exact = run({})
    l_q = run({"HETU_TPU_MOE_DISPATCH": "int8"})
    assert np.isfinite(l_exact) and np.isfinite(l_q)
    assert abs(l_q - l_exact) / max(abs(l_exact), 1e-9) < 0.01, \
        (l_exact, l_q)


# ------------------------------------------------------------ envelope
def test_explicit_dispatch_envelope_errors(ep8):
    from hetu_tpu.nn import moe_dispatch as md
    st_tp = ParallelStrategy(mesh=MeshConfig(ep=2, tp=2))
    layer = _layer(st_tp)
    with scoped_env(HETU_TPU_MOE_DISPATCH="int8"):
        with pytest.raises(ValueError, match="tp=1"):
            md.validate_envelope(st_tp, layer.moe, 64)
        # pair count must split over ep
        st, _mesh = ep8
        with pytest.raises(ValueError, match="divide"):
            md.validate_envelope(st, layer.moe, 63)
        # dense parity dispatcher stays on GSPMD
        with pytest.raises(ValueError, match="sort"):
            md.validate_envelope(st, MoEConfig(num_experts=E,
                                               dispatch="dense"), 64)
        # plan-time rejection through the one validate chokepoint
        from hetu_tpu.parallel.strategy import StrategyValidationError
        with pytest.raises(StrategyValidationError, match="tp=1"):
            st_tp.validate()


def test_flag_is_noop_at_ep1():
    """resolved_mode demotes to gspmd without an ep axis — the layer
    computes identically with the flag set or unset."""
    layer = _layer(ParallelStrategy())
    p = layer.init(jax.random.key(0))
    x = _x(seed=8)
    y0, _ = layer(p, x)
    with scoped_env(HETU_TPU_MOE_DISPATCH="int8"):
        from hetu_tpu.nn.moe_dispatch import resolved_mode
        assert resolved_mode(ParallelStrategy()) == "gspmd"
        y1, _ = layer(p, x)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


# ------------------------------------- expert-load gauges + rebalancing
def test_router_gauges_flow_on_explicit_path(ep8):
    """The PR 12 moe.* telemetry must survive the shard_map: the
    explicit path threads per-group router stats out of the manual
    region and lands the same loads the GSPMD path reports."""
    from hetu_tpu.obs import numerics
    st, mesh = ep8
    layer = _layer(st)
    x = _x(seed=9)

    def collect(env):
        with scoped_env(**env):
            with ht.use_mesh(mesh):
                p = layer.init(jax.random.key(4), mesh=mesh)

                def f(p_, x_):
                    with numerics.collecting() as col:
                        y, _aux = layer(p_, x_)
                        stats = col.finalize()
                    return y, stats

                _, stats = jax.jit(f)(p, x)
        return jax.device_get(stats)

    ref = collect({})
    exp = collect({"HETU_TPU_MOE_DISPATCH": "int8"})
    assert "moe" in exp and "load" in exp["moe"]
    np.testing.assert_allclose(np.asarray(exp["moe"]["load"]),
                               np.asarray(ref["moe"]["load"]),
                               rtol=1e-6)
    # load is per-token fractions summing to ~top_k
    assert abs(float(np.sum(exp["moe"]["load"])) - 2.0) < 1e-3


def test_capacity_rebalancer_grows_and_shrinks():
    from hetu_tpu.nn.moe_rebalance import CapacityRebalancer, apply
    from hetu_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    rb = CapacityRebalancer(num_experts=4, top_k=2, capacity_factor=1.25,
                            registry=reg, strikes=2, headroom=1.1)
    assert rb.observe() is None          # gauges not published yet

    def publish(loads):
        for i, v in enumerate(loads):
            reg.set_gauge("moe.expert_load", v, expert=str(i))

    # collapsed router: expert 0 carries everything -> needed cf = 2*k/k
    publish([1.6, 0.2, 0.1, 0.1])        # load_max*E/k = 3.2 > 1.25
    assert rb.observe() is None          # strike 1: hysteresis holds
    dec = rb.observe()                   # strike 2: grow
    assert dec is not None and dec.reason == "grow"
    assert dec.capacity_factor == pytest.approx(3.2 * 1.1)
    assert reg.gauge_value("moe.capacity_factor") == \
        pytest.approx(dec.capacity_factor)
    # balanced router under the inflated factor -> shrink back
    publish([0.5, 0.5, 0.5, 0.5])        # needed = 1.0
    assert rb.observe() is None
    dec2 = rb.observe()
    assert dec2 is not None and dec2.reason == "shrink"
    assert dec2.capacity_factor == pytest.approx(1.1)
    # a single noisy spike between strikes resets the streak
    publish([1.6, 0.2, 0.1, 0.1])
    assert rb.observe() is None
    publish([0.55, 0.5, 0.5, 0.45])
    assert rb.observe() is None
    publish([1.6, 0.2, 0.1, 0.1])
    assert rb.observe() is None          # streak restarted
    cfg = apply(MoEConfig(num_experts=4, top_k=2), dec2.capacity_factor)
    assert cfg.capacity_factor == pytest.approx(1.1)


# ------------------------------------------------- dense<->MoE hot switch
def test_dense_to_moe_sharded_hot_switch():
    """The existing parallel/switch machinery moves MoE params between a
    replicated-experts (dp) layout and the ep-sharded layout: outputs
    identical, and the profiler sees real bytes move."""
    from hetu_tpu.parallel.switch import profile_switch, switch_tree
    st_dp = ParallelStrategy(mesh=MeshConfig(dp=8))
    st_ep = ParallelStrategy(mesh=MeshConfig(ep=8))
    l_dp = _layer(st_dp, capacity_factor=4.0)
    l_ep = _layer(st_ep, capacity_factor=4.0)
    mesh_dp = st_dp.build_mesh()
    mesh_ep = st_ep.build_mesh()
    x = _x(seed=11)
    with ht.use_mesh(mesh_dp):
        p = l_dp.init(jax.random.key(3), mesh=mesh_dp)
        y_dense, _ = jax.jit(lambda p_, x_: l_dp(p_, x_))(p, x)
    src = jax.tree.map(lambda v: v.sharding, p)
    dst = l_ep.shardings(mesh_ep)
    # dense(replicated) -> ep-sharded is FREE: every device already
    # holds its expert slice (the profiler proves the claim)
    down = profile_switch(p, src, dst)
    assert down.moved_bytes == 0
    assert down.total_bytes == down.moved_bytes + down.local_bytes
    p2 = switch_tree(p, dst, donate=False)
    with ht.use_mesh(mesh_ep):
        y_moe, _ = jax.jit(lambda p_, x_: l_ep(p_, x_))(p2, x)
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_moe),
                               rtol=1e-5, atol=1e-6)
    # ep-sharded -> dense re-replicates the experts: 7/8 of each
    # stacked expert tensor crosses devices
    up = profile_switch(p2, dst, src)
    assert up.moved_bytes > 0
    exp_bytes = sum(int(np.prod(p[k].shape)) * 4
                    for k in ("w_gate_up", "w_down"))
    assert up.moved_bytes == pytest.approx(8 * exp_bytes * 7 / 8)
    p3 = switch_tree(p2, src, donate=False)
    with ht.use_mesh(mesh_dp):
        y_back, _ = jax.jit(lambda p_, x_: l_dp(p_, x_))(p3, x)
    np.testing.assert_array_equal(np.asarray(y_dense), np.asarray(y_back))


# --------------------------------------------------- cost model / search
def test_cost_model_ep_memory_and_dispatch():
    from hetu_tpu.search.cost_model import CostModel, StrategyCandidate
    from hetu_tpu.search.profiler import HardwareProfile
    hw = HardwareProfile(topology={"slice_devices": 4,
                                   "intra_gbps": 45.0,
                                   "inter_gbps": 6.25})
    kw = dict(hw=hw, num_layers=8, hidden=1024, intermediate=2816,
              vocab=32000, global_batch=64, seq_len=2048,
              num_experts=8, moe_top_k=2)
    n_dense = 8 * (4 * 1024 * 1024 + 3 * 1024 * 2816) + 32000 * 1024 * 2
    cm = CostModel(num_params=n_dense + int(CostModel(
        num_params=1, **kw).expert_params), **kw)
    c1 = StrategyCandidate()
    c8 = StrategyCandidate(ep=8, moe_dispatch="int8")
    assert c8.num_devices == 8
    # the satellite fix: an ep candidate's stacked expert memory divides
    # by ep instead of reading as replicated
    m1, m8 = cm.per_device_memory(c1), cm.per_device_memory(c8)
    exp = cm.expert_params
    assert m1 - m8 == pytest.approx(16.0 * exp * 7 / 8, rel=1e-6)
    assert "ep8" in c8.describe() and "moe-int8" in c8.describe()
    # dispatch pricing: int8 < fp32 < (flat, slice-spanning) and the
    # two-level schedule undercuts the flat int8 on a multi-slice ep
    t_fp = cm._moe_dispatch_s(StrategyCandidate(ep=8,
                                                moe_dispatch="fp32"))
    t_q = cm._moe_dispatch_s(c8)
    t_2l = cm._moe_dispatch_s(StrategyCandidate(
        ep=8, moe_dispatch="int8", comm_topology="two_level"))
    assert t_q < t_fp
    if getattr(hw, "topology", None):
        assert t_2l < t_q
    # step_time includes the term (ep grows comm but shrinks nothing
    # else here, so the ep=8 int8 candidate is strictly costlier than
    # the same mesh without the dispatch charge)
    assert cm.step_time(c8) > 0


def test_searcher_enumerates_ep_for_moe():
    from types import SimpleNamespace
    from hetu_tpu.search.cost_model import CostModel
    from hetu_tpu.search.profiler import HardwareProfile
    from hetu_tpu.search.searcher import search_strategy
    cm = CostModel(hw=HardwareProfile(), num_layers=8, hidden=512,
                   intermediate=1408, vocab=32000,
                   num_params=200_000_000, global_batch=64, seq_len=512,
                   num_experts=8, moe_top_k=2)
    cfg = SimpleNamespace(num_attention_heads=8, num_key_value_heads=8,
                          num_hidden_layers=8, num_experts=8,
                          use_scan=True, attention_dropout=0.0)
    res = search_strategy(cm, 8, model_cfg=cfg, moe_dispatch="int8",
                          topk=50)
    assert res, "no feasible candidates"
    eps = {c.ep for c, _t, _m in res}
    assert 8 in eps or 4 in eps or 2 in eps, eps
    for c, _t, _m in res:
        if c.ep > 1:
            assert c.moe_dispatch == "int8"
            assert cm.num_experts % c.ep == 0
        else:
            assert c.moe_dispatch == "gspmd"
    # explicit-mode candidates stay inside the dispatch envelope
    assert not any(c.ep > 1 and (c.tp > 1 or c.pp > 1)
                   for c, _t, _m in res)
    # a flag exported in the PLANNING process must not veto gspmd
    # candidates: the searcher judges each candidate under ITS OWN mode
    # (validate's moe_dispatch param), while the trainer path — no
    # param — still reads the live flag
    from hetu_tpu.parallel.strategy import StrategyValidationError
    from hetu_tpu.search.cost_model import StrategyCandidate
    from hetu_tpu.search.searcher import candidate_strategy
    with scoped_env(HETU_TPU_MOE_DISPATCH="int8"):
        c = StrategyCandidate(ep=2, tp=2)            # moe_dispatch=gspmd
        candidate_strategy(c).validate(cfg, moe_dispatch=c.moe_dispatch)
        with pytest.raises(StrategyValidationError, match="tp=1"):
            candidate_strategy(c).validate(cfg)


# --------------------------------------------------------------- lint
def test_moe_dispatch_lint_pair(lowered):
    """Positive: the flat slice-spanning int8 program warns (two-level
    was available); negative: the two-level program does not."""
    from hetu_tpu.analysis.hlo_lints import lint_moe_dispatch
    flat = lint_moe_dispatch(lowered["int8"][0], program="flat")
    assert flat and all(f.lint == "moe-dispatch"
                        and f.severity == "warning" for f in flat)
    assert "two-level" in flat[0].message
    two = lint_moe_dispatch(lowered["two_level"][0], program="2lvl")
    assert two == []
    # vacuous without a topology
    from hetu_tpu.comm.topology import Topology
    none_topo = lint_moe_dispatch(
        lowered["int8"][0],
        topology=Topology(slice_devices=1, intra_gbps=45.0,
                          inter_gbps=6.25))
    assert none_topo == []
    # the two-level schedule's own strided inter TRANSVERSAL (one rank
    # per slice) is exactly the recommended shape — never a finding,
    # while a flat group holding whole slices still warns
    k2 = Topology(slice_devices=2, intra_gbps=45.0, inter_gbps=6.25)

    def _mod(groups):
        return ("HloModule m\n\nENTRY %main {\n"
                "  %x = f32[64]{0} parameter(0)\n"
                "  ROOT %a2a = f32[64]{0} all-to-all(f32[64]{0} %x), "
                f"replica_groups={groups}\n}}\n")

    strided = lint_moe_dispatch(_mod("{{0,2,4,6},{1,3,5,7}}"),
                                topology=k2)
    assert strided == [], [f.message for f in strided]
    flat2 = lint_moe_dispatch(_mod("{{0,1,2,3,4,5,6,7}}"), topology=k2)
    assert len(flat2) == 1 and flat2[0].severity == "warning"


# ------------------------------------------------------------- serving
@pytest.fixture(scope="module")
def tiny_moe_llama():
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                           use_flash_attention=False, num_experts=4,
                           moe_top_k=2)
    model = LlamaLMHeadModel(cfg)
    return model, model.init(jax.random.key(0))


def test_serving_moe_decode_matches_generate(tiny_moe_llama):
    """MoE decode through the engine: token-for-token vs sequential
    generate() (the continuous-batching goldens extend to MoE)."""
    from hetu_tpu import serving
    from conftest import generate
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.request import Request
    model, params = tiny_moe_llama
    prompt = np.random.default_rng(5).integers(0, 250, 10).astype(np.int32)
    eng = serving.ServingEngine(
        model, params,
        serving.ServeConfig(num_slots=2, page_size=8, max_len=64,
                            prefill_chunk=8),
        registry=MetricsRegistry())
    res = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=5)])
    gold = generate(model, params, jnp.asarray(prompt[None]),
                    max_new_tokens=5)
    assert res[0].tokens == list(np.asarray(gold)[0, 10:])


def test_serving_resident_int8_experts(tiny_moe_llama):
    """moe_dispatch=int8 stores the stacked expert weights resident-
    quantized: engine output is token-exact vs generate() on the
    DEQUANTIZED weights (quantize-once determinism), the resident-bytes
    gauges land (~3.9x), and the reshard hook is refused."""
    from hetu_tpu import serving
    from conftest import generate
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.experts import (dequantize_expert_tree,
                                          quantize_expert_tree)
    model, params = tiny_moe_llama
    prompt = np.random.default_rng(7).integers(0, 250, 9).astype(np.int32)
    reg = MetricsRegistry()
    eng = serving.ServingEngine(
        model, params,
        serving.ServeConfig(num_slots=2, page_size=8, max_len=64,
                            prefill_chunk=8, moe_dispatch="int8"),
        registry=reg)
    res = eng.run([serving.Request(rid=0, prompt=prompt,
                                   max_new_tokens=5)])
    pq, spec = quantize_expert_tree(params, 4, bits=8)
    pdq = dequantize_expert_tree(pq, spec)
    gold = generate(model, pdq, jnp.asarray(prompt[None]),
                    max_new_tokens=5)
    assert res[0].tokens == list(np.asarray(gold)[0, 9:])
    qb = reg.gauge_value("serve.moe_expert_bytes")
    fb = reg.gauge_value("serve.moe_expert_bytes_fp")
    assert qb and fb and fb / qb >= 3.5
    with pytest.raises(ValueError, match="reshard"):
        serving.ServingEngine(
            model, params,
            serving.ServeConfig(num_slots=2, page_size=8, max_len=64,
                                prefill_chunk=8, moe_dispatch="int8"),
            registry=MetricsRegistry(), reshard=object())
