"""Quantized + hierarchical collectives (hetu_tpu/comm/collectives.py,
comm/topology.py): int8/int4 all-gather / reduce-scatter / all-to-all /
all-reduce inside shard_map, the custom-vjp quantized transposes, the
SP routing through dstates.convert (HETU_TPU_SP_COMPRESS), the quantized
ZeRO refresh (HETU_TPU_ZERO_COMPRESS), and the two-level topology
scheme (HETU_TPU_COMM_TOPOLOGY).  See docs/comm_compression.md."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from hetu_tpu.comm import collectives as qc
from hetu_tpu.comm.compress import pack_int4, unpack_int4
from hetu_tpu.comm.topology import Topology
from hetu_tpu.core.mesh import MeshConfig, create_mesh


def _mesh(dp=4):
    return create_mesh(MeshConfig(dp=dp))


def _run(mesh, body, *xs, in_specs=None, out_specs=P("dp")):
    in_specs = in_specs or tuple(P("dp") for _ in xs)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))(*xs)


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


# ---------------------------------------------------------------------------
# int4 packing
# ---------------------------------------------------------------------------

def test_int4_pack_roundtrip_exact():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-7, 8, size=(16, 256)), jnp.int8)
    p = pack_int4(q)
    assert p.dtype == jnp.uint8 and p.shape == (16, 128)
    np.testing.assert_array_equal(np.asarray(unpack_int4(p)), np.asarray(q))


def test_int4_pack_rejects_odd_block():
    with pytest.raises(ValueError, match="even"):
        pack_int4(jnp.zeros((4, 255), jnp.int8))


def test_quantize_int4_grid():
    from hetu_tpu.comm.compress import (dequantize_blockwise,
                                        quantize_blockwise)
    x = _rand((1024,), 1)
    q, s = quantize_blockwise(x, 256, bits=4)
    assert int(jnp.max(jnp.abs(q))) <= 7
    err = np.abs(np.asarray(dequantize_blockwise(q, s)) - np.asarray(x))
    bound = np.repeat(np.asarray(s), 256) / 2 + 1e-9   # absmax/7 grid
    assert (err <= bound).all()


# ---------------------------------------------------------------------------
# the collectives match their exact lax twins
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,tol", [("int8", 0.02), ("int4", 0.2)])
def test_all_gather_q_matches_exact(mode, tol):
    mesh = _mesh()
    x = _rand((4, 8, 256))
    out = _run(mesh, lambda v: qc.all_gather_q(
        v[0], "dp", axis=0, tiled=True, mode=mode)[None], x)
    ref = _run(mesh, lambda v: jax.lax.all_gather(
        v[0], "dp", axis=0, tiled=True)[None], x)
    assert float(jnp.abs(out - ref).max()) <= tol * float(
        jnp.abs(ref).max())


@pytest.mark.parametrize("mode,tol", [("int8", 0.03), ("int4", 0.3)])
def test_reduce_scatter_q_matches_exact(mode, tol):
    mesh = _mesh()
    x = _rand((4, 8, 256), 2)
    out = _run(mesh, lambda v: qc.reduce_scatter_q(
        v[0], "dp", scatter_dimension=1, mode=mode)[None], x)
    ref = _run(mesh, lambda v: jax.lax.psum_scatter(
        v[0], "dp", scatter_dimension=1, tiled=True)[None], x)
    assert float(jnp.abs(out - ref).max()) <= tol * float(
        jnp.abs(ref).max())


def test_all_to_all_q_matches_exact():
    mesh = _mesh()
    x = _rand((4, 8, 256), 3)
    out = _run(mesh, lambda v: qc.all_to_all_q(
        v[0], "dp", split_axis=0, concat_axis=1, mode="int8")[None], x)
    ref = _run(mesh, lambda v: jax.lax.all_to_all(
        v[0], "dp", split_axis=0, concat_axis=1, tiled=True)[None], x)
    assert float(jnp.abs(out - ref).max()) <= 0.03 * float(
        jnp.abs(ref).max())


def test_all_reduce_q_matches_psum():
    mesh = _mesh()
    x = _rand((4, 8, 256), 4)
    out = _run(mesh, lambda v: qc.all_reduce_q(v[0], "dp",
                                               mode="int8")[None], x)
    ref = _run(mesh, lambda v: jax.lax.psum(v[0], "dp")[None], x)
    assert float(jnp.abs(out - ref).max()) <= 0.05 * float(
        jnp.abs(ref).max())


def test_non_float_and_small_payloads_stay_exact():
    mesh = _mesh()
    ints = jnp.arange(32, dtype=jnp.int32).reshape(4, 8)
    out = _run(mesh, lambda v: qc.all_gather_q(
        v[0], "dp", axis=0, tiled=True, mode="int8")[None], ints)
    ref = _run(mesh, lambda v: jax.lax.all_gather(
        v[0], "dp", axis=0, tiled=True)[None], ints)
    assert jnp.array_equal(out, ref)
    # sub-block float buffers too (quantizing would PAY bytes)
    tiny = _rand((4, 16))
    out = _run(mesh, lambda v: qc.all_gather_q(
        v[0], "dp", axis=0, tiled=True, mode="int8")[None], tiny)
    ref = _run(mesh, lambda v: jax.lax.all_gather(
        v[0], "dp", axis=0, tiled=True)[None], tiny)
    assert jnp.array_equal(out, ref)


def test_quantized_gather_backward_is_quantized_scatter():
    """The custom vjp: grads flow (no zero-gradient round()) and the
    backward matches the exact transpose within quantization error."""
    mesh = _mesh()
    x = _rand((4, 8, 256), 5)

    def make_grad(gather):
        def loss(v):
            y = gather(v[0])
            return jnp.sum(jnp.square(y))[None][0]
        return jax.grad(loss)

    g_q = _run(mesh, make_grad(lambda xl: qc.all_gather_q(
        xl, "dp", axis=0, tiled=True, mode="int8")), x)
    g_ref = _run(mesh, make_grad(lambda xl: jax.lax.all_gather(
        xl, "dp", axis=0, tiled=True)), x)
    assert float(jnp.abs(g_q).max()) > 0
    assert float(jnp.abs(g_q - g_ref).max()) <= 0.1 * float(
        jnp.abs(g_ref).max())


# ---------------------------------------------------------------------------
# topology: groups + the hierarchical grad sync
# ---------------------------------------------------------------------------

def test_topology_groups_and_classification():
    t = Topology(slice_devices=4, intra_gbps=45.0, inter_gbps=6.25)
    intra, inter = t.groups(8)
    assert intra == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert inter == ((0, 4), (1, 5), (2, 6), (3, 7))
    assert t.classify_group((0, 1, 2, 3)) == "intra"
    assert t.classify_group((0, 4)) == "inter"
    assert t.applies(8) and not t.applies(4) and not t.applies(6)
    with pytest.raises(ValueError, match="does not apply"):
        t.groups(6)


def test_two_level_sync_matches_psum():
    from hetu_tpu.comm import BucketPlan
    from hetu_tpu.comm.grad_sync import quantized_grad_sync
    dp = 8
    mesh = create_mesh(MeshConfig(dp=dp))
    topo = Topology(slice_devices=4, intra_gbps=45.0, inter_gbps=6.25)
    tree = {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
    plan = BucketPlan.build(tree, multiple=dp * 256)
    gw = _rand((dp, 64, 64), 6)

    def body(gw):
        out, _ = quantized_grad_sync({"w": gw[0]}, "dp", dp, plan,
                                     "int8", {}, topology=topo)
        return out["w"][None]

    out = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                            out_specs=P("dp"), check_vma=False))(gw)
    ref = np.asarray(gw).sum(0)
    # three quantize hops (intra-RS, inter-AR, intra-AG)
    np.testing.assert_allclose(np.asarray(out[0]), ref,
                               atol=0.08 * np.abs(ref).max())


def test_two_level_error_feedback_state_layout():
    """Two-level EF composes (the old stateless-only reject is
    retired): the state carries one residual per quantize point —
    ef_init's `topology=` arm adds the per-stage chunk residuals — and
    a flat-layout state still fails loudly (it cannot carry across the
    hierarchical schedule's extra quantize points)."""
    from hetu_tpu.comm import BucketPlan
    from hetu_tpu.comm.grad_sync import ef_init, ef_specs, quantized_grad_sync
    topo = Topology(slice_devices=4, intra_gbps=45.0, inter_gbps=6.25)
    plan = BucketPlan.build({"w": jax.ShapeDtypeStruct((64,), jnp.float32)},
                            multiple=8 * 256)
    st = ef_init(plan, 8, topology=topo)
    assert set(st) == {"a2a", "tl_inter", "ag", "tl_intra"}
    (L,) = plan.sizes
    assert st["tl_inter"][0].shape == (8, L // 4)
    assert st["tl_intra"][0].shape == (8, L // 4)
    sp = ef_specs(plan, topology=topo)
    assert set(sp) == set(st)
    with pytest.raises(ValueError, match="tl_inter"):
        quantized_grad_sync({"w": jnp.zeros((64,))}, "dp", 8, plan,
                            "int8-ef", {"a2a": [], "ag": []}, topology=topo)


def test_two_level_inter_slice_bytes_shrink():
    """The analyzer sees the hierarchy: the two-level sync's slice-
    spanning groups move ~1/slice_devices of the bytes a flat ring's
    spanning group moves — the HetCCL win, from real lowered HLO."""
    from hetu_tpu.comm import BucketPlan
    from hetu_tpu.comm.grad_sync import quantized_grad_sync
    from hetu_tpu.obs.comm import collective_table
    dp = 8
    mesh = create_mesh(MeshConfig(dp=dp))
    topo = Topology(slice_devices=4, intra_gbps=45.0, inter_gbps=6.25)
    tree = {"w": jax.ShapeDtypeStruct((128, 128), jnp.float32)}
    plan = BucketPlan.build(tree, multiple=dp * 256)

    def lower(topology):
        def body(gw):
            out, _ = quantized_grad_sync({"w": gw[0]}, "dp", dp, plan,
                                         "int8", {}, topology=topology)
            return out["w"][None]
        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                               out_specs=P("dp"), check_vma=False))
        return collective_table(
            fn.lower(jnp.zeros((dp, 128, 128), jnp.float32)).compile())

    def split(rows):
        intra = sum(r["wire_bytes"] for r in rows
                    if r["group_ranks"] and
                    topo.classify_group(r["group_ranks"]) == "intra")
        inter = sum(r["wire_bytes"] for r in rows
                    if r["group_ranks"] and
                    topo.classify_group(r["group_ranks"]) == "inter")
        return intra, inter

    flat_intra, flat_inter = split(lower(None))
    two_intra, two_inter = split(lower(topo))
    assert flat_inter > 0          # the flat ring spans slices
    assert two_inter > 0
    # inter-slice bytes drop by ~slice_devices (4): require >= 2.5x
    assert flat_inter >= 2.5 * two_inter, (flat_inter, two_inter)


# ---------------------------------------------------------------------------
# SP routing through dstates.convert (HETU_TPU_SP_COMPRESS)
# ---------------------------------------------------------------------------

def _sp_program(mesh):
    """x seq-sharded -> gather -> matmul -> (declared-partial)
    reduce-scatter: the Megatron-SP edge pair through convert()."""
    from hetu_tpu.dstates import DistributedStates as DS, convert
    seq_sh = DS.make(3, {1: "tp"})
    dup = DS.dup(3)
    part = DS.make(3, partial=("tp",))

    def run(x, w):
        full = convert(x, seq_sh, dup)
        y = full @ w
        return convert(y, part, seq_sh)

    return jax.jit(shard_map(run, mesh=mesh,
                             in_specs=(P(None, "tp"), P()),
                             out_specs=P(None, "tp"), check_vma=False))


def test_convert_sp_compress_cuts_bytes_3x(monkeypatch):
    """Acceptance: obs.comm reports >=3x fewer bytes on the SP
    all-gather/reduce-scatter path at int8 vs fp32 (real lowered HLO)."""
    from hetu_tpu.analysis.flag_identity import fingerprint
    from hetu_tpu.obs.comm import collective_report
    mesh = create_mesh(MeshConfig(tp=4))
    x = jnp.zeros((4, 256, 64), jnp.float32)
    w = jnp.zeros((64, 64), jnp.float32)

    def bytes_under(mode):
        if mode is None:
            monkeypatch.delenv("HETU_TPU_SP_COMPRESS", raising=False)
        else:
            monkeypatch.setenv("HETU_TPU_SP_COMPRESS", mode)
        compiled = _sp_program(mesh).lower(x, w).compile()
        return collective_report(compiled), compiled.as_text()

    rep32, txt_unset = bytes_under(None)
    rep_none, txt_none = bytes_under("none")
    # flag "none" compiles to the same program
    assert fingerprint(txt_unset) == fingerprint(txt_none)
    rep8, _ = bytes_under("int8")
    rep4, _ = bytes_under("int4")
    assert rep32["total_wire_bytes"] >= 3.0 * rep8["total_wire_bytes"], (
        rep32["total_wire_bytes"], rep8["total_wire_bytes"])
    assert rep8["total_wire_bytes"] > rep4["total_wire_bytes"]
    assert rep8["collectives"]["all-to-all"]["count"] >= 1  # quantized RS


def test_convert_sp_compress_roundtrip_close(monkeypatch):
    monkeypatch.setenv("HETU_TPU_SP_COMPRESS", "int8")
    mesh = create_mesh(MeshConfig(tp=4))
    x = _rand((4, 256, 64), 7)
    w = jnp.eye(64, dtype=jnp.float32)
    out = _sp_program(mesh)(x, w)
    monkeypatch.delenv("HETU_TPU_SP_COMPRESS")
    ref = _sp_program(mesh)(x, w)
    assert float(jnp.abs(out - ref).max()) <= 0.05 * float(
        jnp.abs(ref).max())


def test_sp_compress_loss_parity(monkeypatch):
    """Acceptance: an explicit-SP training loop (convert gather in,
    reduce-scatter out, quantized transposes in the backward) reaches the
    fp32 run's final loss within 1%."""
    from hetu_tpu.dstates import DistributedStates as DS, convert
    mesh = create_mesh(MeshConfig(tp=4))
    seq_sh = DS.make(3, {1: "tp"})
    dup = DS.dup(3)
    part = DS.make(3, partial=("tp",))
    H, tp = 256, 4
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(2, 64, H)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(2, 64, H)) * 0.1, jnp.float32)
    w1_0 = jnp.asarray(rng.normal(size=(H, H)) * 0.05, jnp.float32)
    w2_0 = jnp.asarray(rng.normal(size=(H, H)) * 0.05, jnp.float32)

    def train(mode, steps=40, lr=2.0):
        if mode is None:
            monkeypatch.delenv("HETU_TPU_SP_COMPRESS", raising=False)
        else:
            monkeypatch.setenv("HETU_TPU_SP_COMPRESS", mode)

        def loss_local(w1, w2, xl, yl):
            # column-parallel then row-parallel, SP edges via convert()
            full = convert(xl, seq_sh, dup)           # [b, s, H]
            h = jnp.tanh(full @ w1)                   # w1: [H, H/tp] local
            out_part = h @ w2                         # w2: [H/tp, H] local
            out = convert(out_part, part, seq_sh)     # RS onto seq
            return jnp.mean(jnp.square(out - yl))

        def step(w1, w2, xl, yl):
            l, g = jax.value_and_grad(
                lambda ws: loss_local(ws[0], ws[1], xl, yl))((w1, w2))
            l = jax.lax.psum(l, "tp") / tp
            return l, (w1 - lr * g[0], w2 - lr * g[1])

        fn = jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(P(None, "tp"), P("tp", None),
                      P(None, "tp"), P(None, "tp")),
            out_specs=(P(), (P(None, "tp"), P("tp", None))),
            check_vma=False))
        w1, w2 = w1_0, w2_0
        losses = []
        for _ in range(steps):
            l, (w1, w2) = fn(w1, w2, x, y)
            losses.append(float(l))
        return losses

    l32 = train(None)
    l8 = train("int8")
    assert l32[-1] < l32[0] * 0.7          # it actually trains
    assert l8[-1] < l8[0] * 0.7
    assert abs(l8[-1] - l32[-1]) / abs(l32[-1]) < 0.01, (l8[-1], l32[-1])
