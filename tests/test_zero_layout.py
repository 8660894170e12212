"""Where ZeRO splits the optimizer state (PR 59): a leaf stacked for the
scan over layers is split over dp on a dimension INSIDE the layer, so the
backward scan can reduce-scatter one layer's gradient into the shard this
rank updates; split on the layer dimension (the rule before), the whole
gradient was all-reduced inside the loop and sliced after it.

The layout is a matter of bytes on the wire, never of results: the same
steps, a checkpoint of either layout restores into the other, and the
trainer's gauges say which form the compiled sync took."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import hetu_tpu as ht
from hetu_tpu.core.mesh import MeshConfig, mesh_axis_group
from hetu_tpu.data import pad_batch
from hetu_tpu.engine import Trainer, TrainingConfig
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.nn.module import ParamSpec, stacked_spec
from hetu_tpu.obs.metrics import MetricsRegistry
from hetu_tpu.optim.optimizer import state_shardings, zero_shardings
from hetu_tpu.parallel import ParallelStrategy


def _spec(shape, ds_axes=None, stack=0):
    """A ParamSpec of `shape` whose dims in `ds_axes` ({dim: axis}) are
    taken, stacked `stack` times over 4 layers."""
    from hetu_tpu.dstates import DistributedStates
    ds = (DistributedStates.make(len(shape), ds_axes) if ds_axes else None)
    spec = ParamSpec(tuple(shape), jnp.float32, None, ds)
    for _ in range(stack):
        spec = stacked_spec(spec, 4)
    return spec


def _zero_spec(spec, dp=2, tp=2):
    mesh = ht.create_mesh(dp=dp, tp=tp)
    ns = (spec.ds.named_sharding(mesh) if spec.ds is not None
          else NamedSharding(mesh, P()))
    return tuple(zero_shardings({"w": ns}, {"w": spec}, mesh)["w"].spec)


@pytest.mark.parametrize("spec, want", [
    # unstacked leaves: the first free, divisible dim, as before
    (_spec((8, 6)), ("dp", None)),
    (_spec((8, 6), {0: "tp"}), ("tp", "dp")),
    (_spec((7, 6)), (None, "dp")),
    (_spec((7, 5)), ()),
    # stacked: the first free, divisible dim AFTER the stack's
    (_spec((8, 6), stack=1), (None, "dp", None)),
    (_spec((8, 6), {0: "tp"}, stack=1), (None, "tp", "dp")),
    (_spec((8, 6), {1: "tp"}, stack=1), (None, "dp", "tp")),
    (_spec((8,), stack=1), (None, "dp")),
    (_spec((8, 6), stack=2), (None, None, "dp", None)),
    # no such dim inside the layer: the stack's own, the split before
    (_spec((7, 5), stack=1), ("dp", None, None)),
    (_spec((7,), {0: "tp"}, stack=1), ("dp", "tp")),
], ids=["plain", "plain_tp_first", "plain_odd_first", "plain_indivisible",
        "stacked", "stacked_tp_first", "stacked_tp_second", "stacked_vector",
        "stacked_twice", "stacked_indivisible", "stacked_taken"])
def test_zero_splits_a_stacked_leaf_inside_the_layer(spec, want):
    assert _zero_spec(spec) == want


def test_shapes_alone_know_of_no_stack():
    """Params or ShapeDtypeStructs in place of the model's specs give the
    split on the first free dim, the layer dimension included: what a
    caller that cannot say which leaves are stacked gets, and the layout
    of every checkpoint written before PR 59."""
    mesh = ht.create_mesh(dp=2, tp=2)
    spec = _spec((8, 6), stack=1)
    z = zero_shardings({"w": NamedSharding(mesh, P())},
                       {"w": spec.abstract()}, mesh)
    assert tuple(z["w"].spec) == ("dp", None, None)


def test_a_stack_sharded_over_pp_is_split_inside_the_layer_as_before():
    mesh = ht.create_mesh(pp=2, dp=2)
    spec = stacked_spec(_spec((8, 6)), 4, lead_axis="pp")
    ns = spec.ds.named_sharding(mesh)
    for ref in (spec, spec.abstract()):
        z = zero_shardings({"w": ns}, {"w": ref}, mesh)
        assert tuple(z["w"].spec) == ("pp", "dp", None)


def test_zero_leaves_fsdp_and_dp1_alone():
    mesh = ht.create_mesh(dp=2, tp=2)
    spec = _spec((8, 6), {1: "dp"}, stack=1)       # FSDP took dp
    ns = spec.ds.named_sharding(mesh)
    assert zero_shardings({"w": ns}, {"w": spec}, mesh)["w"] is ns
    one = ht.create_mesh(tp=2)
    tree = {"w": NamedSharding(one, P())}
    assert zero_shardings(tree, {"w": spec}, one) is tree


def test_model_specs_mark_the_scanned_stack_and_nothing_else():
    model = LlamaLMHeadModel(LlamaConfig.tiny(),
                             ParallelStrategy(mesh=MeshConfig(dp=2, tp=2)))
    specs = model.param_specs()
    layers = specs["model"]["layers"]["layers"]
    assert {s.stack_dims for s in jax.tree.leaves(
        layers, is_leaf=lambda s: isinstance(s, ParamSpec))} == {1}
    rest = {k: v for k, v in specs["model"].items() if k != "layers"}
    rest["lm_head"] = specs.get("lm_head", {})
    assert {s.stack_dims for s in jax.tree.leaves(
        rest, is_leaf=lambda s: isinstance(s, ParamSpec))} == {0}
    # an unrolled stack has no layer dimension to skip
    unrolled = LlamaLMHeadModel(LlamaConfig.tiny(use_scan=False),
                                ParallelStrategy()).param_specs()
    assert {s.stack_dims for s in jax.tree.leaves(
        unrolled, is_leaf=lambda s: isinstance(s, ParamSpec))} == {0}


def test_state_shardings_of_the_four_chip_layout():
    """dp2 x tp2 + ZeRO on the model the four-chip cell trains: every
    matrix of a layer is split on a dim inside the layer — wqkv and
    gate|up on hidden, o_proj and down_proj on their output — and the
    unstacked leaves as before."""
    st = ParallelStrategy(mesh=MeshConfig(dp=2, tp=2),
                          sequence_parallel=True, zero=True)
    model = LlamaLMHeadModel(LlamaConfig.tiny(), st)
    mesh = st.build_mesh()
    pshard, sshard = state_shardings(model, mesh, zero=True)
    assert sshard["m"] is sshard["v"]
    layer = sshard["m"]["model"]["layers"]["layers"]
    got = {"wqkv": tuple(layer["attn"]["wqkv"].spec),
           "o_proj": tuple(layer["attn"]["o_proj"]["weight"].spec),
           "gate_up": tuple(layer["mlp"]["w_gate_up"].spec),
           "down": tuple(layer["mlp"]["down_proj"]["weight"].spec)}
    assert got == {"wqkv": (None, "dp", "tp", None, None),
                   "o_proj": (None, "tp", "dp"),
                   "gate_up": (None, "dp", None, "tp"),
                   "down": (None, "tp", "dp")}, got
    for ns in jax.tree.leaves(layer):
        assert ns.spec[0] is None, ns.spec
    old = zero_shardings(pshard, model.abstract_params(), mesh)
    outside = lambda t: [  # noqa: E731
        ns.spec for path, ns in jax.tree_util.tree_leaves_with_path(t)
        if "layers" not in jax.tree_util.keystr(path)]
    assert outside(sshard["m"]) == outside(old)
    pplain, plain = state_shardings(model, mesh, zero=False)
    assert plain["m"] is pplain and plain["step"].spec == P()
    from hetu_tpu.optim.zero_refresh import UNSHARDED, refresh_dims
    dims = refresh_dims(sshard["m"])["model"]["layers"]["layers"]
    assert dims["attn"]["wqkv"] == 1 and dims["mlp"]["down_proj"][
        "weight"] == 2 and UNSHARDED not in jax.tree.leaves(dims)


# ---------------------------------------------------------------------------
# the same steps under either layout
# ---------------------------------------------------------------------------

class _LayerSplitTrainer(Trainer):
    """The layout before PR 59: ZeRO splits the first free dim of every
    leaf, a stacked one's LAYER dimension (what `zero_shardings` gives
    shapes that know of no stack)."""

    def _make_shardings(self):
        pshard = self.model.shardings(self.mesh)
        z = zero_shardings(pshard, self.model.abstract_params(), self.mesh)
        return pshard, {"step": NamedSharding(self.mesh, P()),
                        "m": z, "v": z}


def _trainer(cls=Trainer, *, dp=2, tp=2, zero=True, ckpt_dir=None,
             registry=None, grad_clip=1.0, **cfg):
    st = ParallelStrategy(mesh=MeshConfig(dp=dp, tp=tp),
                          sequence_parallel=tp > 1, zero=zero)
    model = LlamaLMHeadModel(LlamaConfig.tiny(remat=False, **cfg), st)
    tc = TrainingConfig(global_batch_size=4, micro_batch_size=2, seq_len=32,
                        lr=3e-3, warmup_steps=0, total_steps=10,
                        log_every=100, ckpt_dir=ckpt_dir, grad_clip=grad_clip)
    trainer = cls(model, tc, st)
    if registry is not None:    # the process's, unless a test reads it
        trainer._registry = registry
    return trainer.build(jax.random.key(7))


def _batch(seed=0, n=4, seq=32):
    rng = np.random.default_rng(seed)
    return pad_batch([rng.integers(1, 250, size=seq - 4) for _ in range(n)],
                     seq)


def _layer_dim_of(trainer):
    wqkv = trainer.opt_state["m"]["model"]["layers"]["layers"]["attn"]["wqkv"]
    return tuple(wqkv.sharding.spec).index("dp")


@pytest.fixture(scope="module")
def two_layouts():
    """(new, old): the same model, seed and two steps at dp2 x tp2 + SP +
    ZeRO under the rule and under the layout before it.  The clip never
    binds: the global norm is a float32 sum of squares whose ORDER
    follows the layout, so it — and with it a binding clip's scale —
    differs in the last place (asserted below), which is no matter of
    the sync."""
    new, old = (_trainer(cls, grad_clip=1e9)
                for cls in (Trainer, _LayerSplitTrainer))
    assert _layer_dim_of(new) == 1 and _layer_dim_of(old) == 0
    for seed in (0, 1):
        a, b = (tr.train_step(_batch(seed)) for tr in (new, old))
        assert float(a["loss"]) == float(b["loss"])
        np.testing.assert_allclose(float(a["grad_norm"]),
                                   float(b["grad_norm"]), rtol=1e-6)
    return new, old


@pytest.mark.parametrize("what", ["params", "m", "v"])
def test_two_steps_at_dp2_are_bit_equal_under_either_layout(two_layouts,
                                                            what):
    """A two-term sum is the same in either order, and nothing else of
    the arithmetic depends on which rank holds which half."""
    new, old = two_layouts
    pick = (lambda t: t.params) if what == "params" else \
        (lambda t: t.opt_state[what])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(pick(new)),
                            jax.tree.leaves(pick(old))):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            err_msg=jax.tree_util.keystr(path))
    assert int(new.opt_state["step"]) == int(old.opt_state["step"]) == 2


def test_checkpoint_of_the_old_layout_restores_into_the_new(tmp_path):
    old = _trainer(_LayerSplitTrainer, ckpt_dir=str(tmp_path / "ck"))
    for seed in (0, 1):
        old.train_step(_batch(seed))
    old.save(wait=True)
    new = _trainer(ckpt_dir=str(tmp_path / "ck"))
    new.restore()
    assert new.global_step == old.global_step == 2
    assert _layer_dim_of(new) == 1 and _layer_dim_of(old) == 0
    for a, b in zip(jax.tree.leaves(new.state()),
                    jax.tree.leaves(old.state())):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # and the two go on alike
    la = float(new.train_step(_batch(2))["loss"])
    lb = float(old.train_step(_batch(2))["loss"])
    assert la == lb


# ---------------------------------------------------------------------------
# the gauges that say which form the compiled sync took
# ---------------------------------------------------------------------------

def _gauges(registry):
    return {(g["name"], g["labels"].get("form")): g["value"]
            for g in registry.snapshot()["gauges"]
            if g["name"].startswith("trainer.grad_sync")}


def test_grad_sync_gauges_agree_with_the_compiled_text():
    from hetu_tpu.obs.comm import collective_table, grad_sync_report
    reg = MetricsRegistry()
    tr = _trainer(registry=reg)
    hb = _batch()
    tr.train_step(hb)
    got = _gauges(reg)
    text = tr.lowered_step(hb, optimized=True)
    dp_group = mesh_axis_group(tr.mesh, "dp")
    assert dp_group == (0, 2)
    want = grad_sync_report(text, dp_group, default_world=4)
    assert got == {
        ("trainer.grad_sync_collectives", "all_reduce"): want["all_reduce"],
        ("trainer.grad_sync_collectives", "reduce_scatter"):
            want["reduce_scatter"],
        ("trainer.grad_sync_bytes_step", None): want["wire_bytes"]}
    # by hand from the table of every collective: the backward pass's
    # reductions over a group that holds the dp group
    backward = {ln.strip()[:200] for ln in text.splitlines()
                if "transpose(" in ln}      # a row keeps 200 characters
    rows = [r for r in collective_table(text, 4)
            if r["op"] in ("all-reduce", "reduce-scatter")
            and r["line"] in backward
            and (r["group_ranks"] is None
                 or set(dp_group) <= set(r["group_ranks"]))]
    assert rows, "a dp2 step with no gradient sync"
    assert sum(r["trip_count"] for r in rows) == (
        want["all_reduce"] + want["reduce_scatter"])
    assert sum(r["trip_count"] * r["wire_bytes"] for r in rows) == \
        pytest.approx(want["wire_bytes"])
    assert want["wire_bytes"] > 0


@pytest.mark.parametrize("dp, tp", [(1, 1), (1, 2)])
def test_grad_sync_gauges_are_absent_without_dp(dp, tp):
    reg = MetricsRegistry()
    tr = _trainer(dp=dp, tp=tp, zero=False, registry=reg)
    tr.train_step(_batch())
    assert reg.counter_value("trainer.compiles", pool="train_step") == 1
    assert _gauges(reg) == {}


_TPU_BODY = """\
%add (a: bf16[], b: bf16[]) -> bf16[] {
  ROOT %s = bf16[]{:T(256)} add(%a, %b)
}
%all-reduce-scatter.4 (input.1: bf16[1024,2048]) -> bf16[1024,1024] {
  %input.1 = bf16[1024,2048]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.9 = bf16[1024,2048]{1,0:T(8,128)(2,1)} all-reduce(%input.1), channel_id=9, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add
  ROOT %ds = bf16[1024,1024]{1,0:T(8,128)(2,1)} dynamic-slice(%all-reduce.9, %c, %o)
}
%cond (p: (s32[], bf16[1024,2048])) -> pred[] {
  %constant.3 = s32[]{:T(128)} constant(16)
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  ROOT %lt = pred[]{:T(512)} compare(%i, %constant.3), direction=LT
}
%body (p: (s32[], bf16[1024,2048])) -> (s32[], bf16[1024,2048]) {
  %g = bf16[1024,2048]{1,0:T(8,128)(2,1)} get-tuple-element(%p), index=1
  %fusion.1 = bf16[1024,1024]{1,0:T(8,128)(2,1)} fusion(%g), kind=kCustom, calls=%all-reduce-scatter.4, metadata={op_name="jit(step)/transpose(jvp())/while/body/layer/attn/dot_general"}
  %all-reduce.85 = (bf16[1,1024,2048]{2,1,0:T(8,128)(2,1)}, bf16[1,4096,2048]{2,1,0:T(8,128)(2,1)}) all-reduce(%g, %h), channel_id=15, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(step)/transpose(jvp())/while/body/layer/attn/dot_general"}
  %all-reduce.7 = bf16[2,4096,2048]{2,1,0:T(8,128)(2,1)} all-reduce(%x), channel_id=3, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(step)/transpose(jvp())/while/body/layer/mlp/dot_general"}
  %all-reduce.8 = bf16[2048]{0:T(1024)(128)(2,1)} all-reduce(%n), channel_id=4, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(step)/transpose(jvp())/while/body/layer/attn/reduce_sum"}
}
ENTRY %main (a: bf16[1024,2048]) -> bf16[1024,2048] {
  %w = (s32[], bf16[1024,2048]) while(%t), condition=%cond, body=%body
  %all-reduce.1 = f32[]{:T(128)} all-reduce(%l), channel_id=1, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(step)/jvp(loss)/reduce_sum"}
}
"""


def test_grad_sync_report_reads_the_tpu_compilers_text():
    """As the compiler for the described v5e writes it: a scalar with a
    layout and a compare of untyped operands in the loop's condition
    (16 trips), a reduce-scatter as an all-reduce inside a fusion whose
    output is the shard, the dp groups as an iota; the tp collective and
    the forward's loss sum are no gradient sync, the norm gain's
    all-reduce over dp x tp is."""
    from hetu_tpu.obs.comm import grad_sync_report
    got = grad_sync_report(_TPU_BODY, (0, 2), default_world=4)
    shard, both = 1024 * 1024 * 2, (1024 * 2048 + 4096 * 2048) * 2
    assert got == {"reduce_scatter": 16, "all_reduce": 32,
                   "wire_bytes": 16.0 * (shard + both + 1.5 * 2048 * 2)}
