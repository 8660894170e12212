"""What `remat_policy("dots_attn")` keeps of the flash kernel (nn/remat.py).

The kernel's `custom_vjp` backward reads the residuals its forward RULE
returns, `o` and `lse`; a `checkpoint_name` on the rule's RESULT is
another variable and saves neither, so a checkpointed block's backward
launched the forward kernel a second time (PERF.md s6, PR 61).  The rule
names both itself, and these cases count what that buys, all without
lowering: the `pallas_call` equations of
`jax.grad(jax.checkpoint(attention block, policy))`, for the Llama and the
GPT attention with the flash kernel forced on, alone and inside
`ops/pallas.per_shard`'s `shard_map` over dp2 x tp2 of the host's devices;
the residuals one block keeps; and, in interpret mode, that the gradients
are those of a block that recomputes everything.
"""
from __future__ import annotations

import contextlib
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from hetu_tpu import ops  # noqa: E402
from hetu_tpu.core.mesh import MeshConfig, use_mesh  # noqa: E402
from hetu_tpu.models.gpt.model import GPTAttention, GPTConfig  # noqa: E402
from hetu_tpu.models.llama import LlamaConfig  # noqa: E402
from hetu_tpu.models.llama.model import LlamaAttention  # noqa: E402
from hetu_tpu.nn.remat import remat_policy  # noqa: E402
from hetu_tpu.parallel import ParallelStrategy  # noqa: E402

B, S, HEADS, HD = 2, 128, 2, 128
HIDDEN = HEADS * HD

# forward, dq, dk/dv — and the forward again where nothing of it is kept
LAUNCHES = {"dots_attn": 3, "nothing": 4, "dots": 4}


@pytest.fixture(autouse=True)
def _flash_kernel_only(monkeypatch):
    """The flash kernel forced on (interpret mode on this backend) and no
    other, so that every `pallas_call` counted is one of its launches."""
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "flash")


@contextlib.contextmanager
def _block(family, sharded, devices):
    """-> (loss(policy) -> fn(params, x), params, x) of one attention
    block, under the mesh it runs in."""
    st = (ParallelStrategy(mesh=MeshConfig(dp=2, tp=2),
                           sequence_parallel=True, zero=True)
          if sharded else ParallelStrategy())
    mesh = st.build_mesh(devices[:4]) if sharded else None
    if family == "llama":
        attn = LlamaAttention(LlamaConfig.tiny(
            hidden_size=HIDDEN, num_attention_heads=HEADS,
            num_key_value_heads=HEADS, compute_dtype=jnp.float32), st)
        cos, sin = ops.build_rope_cache(S, HD)
        kw = {"cos": cos, "sin": sin}
    else:
        attn = GPTAttention(GPTConfig.tiny(
            hidden_size=HIDDEN, num_attention_heads=HEADS), st)
        kw = {}

    def loss(policy):
        return jax.checkpoint(
            lambda p, x: jnp.square(attn.forward(p, x, **kw)).sum(),
            policy=remat_policy(policy))
    with use_mesh(mesh) if sharded else contextlib.nullcontext():
        params = attn.init(jax.random.PRNGKey(0), mesh=mesh)
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, HIDDEN),
                              jnp.float32)
        yield loss, params, x


def _launches(loss, params, x):
    return str(jax.make_jaxpr(jax.grad(loss))(params, x)).count(
        "pallas_call[")


def _saved(loss, params, x, capsys):
    """[(shape, where it comes from)] of what the block keeps for its
    backward, arguments and constants apart."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(loss, params, x)
    rows = [re.match(r"\w+\[([\d,]*)\] (.*)", line).groups()
            for line in capsys.readouterr().out.splitlines()]
    return [(tuple(map(int, shape.split(","))), what) for shape, what in rows
            if not what.startswith(("from the argument", "from a constant"))]


CASES = [pytest.param(family, sharded, check, id=f"{family}-{where}-{check}")
         for family in ("llama", "gpt")
         for sharded, where in ((False, "one_device"), (True, "dp2tp2"))
         for check in (*LAUNCHES, "grads", "saved")
         # a mesh changes neither what a block keeps (the count says so)
         # nor its values (tests/test_pallas_kernels.py's per-shard cases)
         if not (sharded and check in ("grads", "saved"))]


@pytest.mark.parametrize("family,sharded,check", CASES)
def test_dots_attn_keeps_what_the_flash_backward_reads(
        family, sharded, check, devices, capsys):
    with _block(family, sharded, devices) as (loss, params, x):
        if check in LAUNCHES:
            assert _launches(loss(check), params, x) == LAUNCHES[check]
        elif check == "grads":
            got = jax.jit(jax.grad(loss("dots_attn"), argnums=(0, 1)))(
                params, x)
            want = jax.jit(jax.grad(loss("nothing"), argnums=(0, 1)))(
                params, x)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            saved = _saved(loss("dots_attn"), params, x, capsys)
            # `o` once, as the kernel laid it out: o_proj's backward
            # remakes its [b, s, heads, hd] operand from it
            kept_o = [shape for shape, _ in saved
                      if sorted(shape) == sorted((B, HEADS, S, HD))]
            assert kept_o == [(B, HEADS, S, HD)], saved
            assert [shape for shape, what in saved
                    if "attn_lse" in what] == [(B, HEADS, S)], saved
            # and nothing of it under a policy that names nothing
            assert not [s for s, _ in _saved(loss("nothing"), params, x,
                                             capsys)]
