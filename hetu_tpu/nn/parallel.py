"""Tensor/sequence-parallel layers.

TPU-native rebuild of the reference's multi-DS parallel modules
(reference: python/hetu/nn/modules/parallel_multi_ds.py:89-588).  The reference
inserts explicit `hetu.comm(tensor, ds)` ops where layouts mismatch; here the
layers run in *global view* under jit and express the same intent with
sharding constraints — GSPMD then inserts exactly the Megatron collectives
(all-gather before column, all-reduce/reduce-scatter after row) the reference
lowers CommOp to.  The DS algebra still documents/plans the comms
(hetu_tpu.dstates.deduce_comm) and drives the explicit shard_map paths used by
ring attention and MoE.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module
from hetu_tpu.parallel.strategy import ParallelStrategy


class ColumnParallelLinear(Module):
    """Y = X·W, W:[in, out] sharded on out over tp
    (reference: HtMultiColumnParallelLinear parallel_multi_ds.py:328)."""

    def __init__(self, in_features: int, out_features: int,
                 strategy: ParallelStrategy, bias: bool = True,
                 gather_output: bool = False, param_dtype=jnp.float32,
                 weight_init=None):
        super().__init__()
        self.strategy = strategy
        self.gather_output = gather_output
        if strategy.tp > 1 and out_features % strategy.tp:
            raise ValueError(f"out_features {out_features} must divide by "
                             f"tp={strategy.tp}")
        self.param("weight", (in_features, out_features),
                   weight_init or init.xavier_uniform(), dtype=param_dtype,
                   ds=strategy.col_weight())
        self.use_bias = bias
        if bias:
            self.param("bias", (out_features,), init.zeros, dtype=param_dtype,
                       ds=strategy.col_bias())

    def forward(self, params, x):
        y = x @ params["weight"].astype(x.dtype)
        if self.use_bias:
            y = y + params["bias"].astype(x.dtype)
        st = self.strategy
        if x.ndim == 3:
            y = st.constrain(y, st.act_hidden() if self.gather_output else st.act_inner())
        return y


class RowParallelLinear(Module):
    """Y = X·W, W:[in, out] sharded on in over tp; output needs a reduction —
    all-reduce (plain TP) or reduce-scatter onto the seq dim (SP)
    (reference: HtMultiRowParallelLinear, parallel_multi_ds.py)."""

    def __init__(self, in_features: int, out_features: int,
                 strategy: ParallelStrategy, bias: bool = True,
                 param_dtype=jnp.float32, weight_init=None):
        super().__init__()
        self.strategy = strategy
        if strategy.tp > 1 and in_features % strategy.tp:
            raise ValueError(f"in_features {in_features} must divide by "
                             f"tp={strategy.tp}")
        self.param("weight", (in_features, out_features),
                   weight_init or init.xavier_uniform(), dtype=param_dtype,
                   ds=strategy.row_weight())
        self.use_bias = bias
        if bias:
            # bias added after the reduction → replicated
            self.param("bias", (out_features,), init.zeros, dtype=param_dtype)

    def forward(self, params, x):
        y = x @ params["weight"].astype(x.dtype)
        st = self.strategy
        if x.ndim == 3:
            # Constraining the (partial) matmul result to the SP/replicated
            # layout makes GSPMD emit reduce-scatter (SP) or all-reduce (TP).
            y = st.constrain(y, st.act_hidden())
        if self.use_bias:
            y = y + params["bias"].astype(x.dtype)
        return y


class VocabParallelEmbedding(Module):
    """Embedding with the vocab dim sharded over tp
    (reference: HtMultiVocabParallelEmbedding, parallel_multi_ds.py:268)."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 strategy: ParallelStrategy, param_dtype=jnp.float32,
                 weight_init=None):
        super().__init__()
        self.strategy = strategy
        self.num_embeddings = num_embeddings
        if strategy.tp > 1 and num_embeddings % strategy.tp:
            raise ValueError(
                f"vocab size {num_embeddings} must divide by tp="
                f"{strategy.tp}; pad the vocab (e.g. 50257 -> 50304)")
        self.param("weight", (num_embeddings, embedding_dim),
                   weight_init or init.normal(0.02), dtype=param_dtype,
                   ds=strategy.vocab_weight())

    def forward(self, params, ids):
        y = jnp.take(params["weight"], ids, axis=0)
        st = self.strategy
        y = st.constrain(y, st.act_hidden())
        return y


class ParallelRMSNorm(Module):
    """RMSNorm that understands sequence parallelism: in SP the input/output
    stay seq-sharded over tp (norm is per-token so no comm is needed; the
    reference wires split0<->dup comms around it, parallel_multi_ds.py:89-162 —
    GSPMD places the equivalent gathers at the next matmul instead)."""

    def __init__(self, dim: int, strategy: ParallelStrategy, eps: float = 1e-5,
                 param_dtype=jnp.float32):
        super().__init__()
        self.strategy = strategy
        self.eps = eps
        self.param("weight", (dim,), init.ones, dtype=param_dtype)

    def forward(self, params, x):
        y = ops.rms_norm(x, params["weight"], self.eps)
        if x.ndim == 3:
            y = self.strategy.constrain(y, self.strategy.act_hidden())
        return y

    def residual(self, params, x, h):
        """Fused residual-add + norm (the pre-norm block's pair):
        returns (norm(x + h), x + h).  Routes to the Pallas fused_norm
        kernel under HETU_TPU_PALLAS; the fallback is exactly the seed
        composition `s = x + h; forward(s)`, same constrain."""
        y, s = ops.residual_rms_norm(
            x, h, params["weight"], self.eps,
            layout=self.strategy.act_hidden() if x.ndim == 3 else None)
        if x.ndim == 3:
            y = self.strategy.constrain(y, self.strategy.act_hidden())
        return y, s


class ParallelLayerNorm(Module):
    def __init__(self, dim: int, strategy: ParallelStrategy, eps: float = 1e-5,
                 bias: bool = True, param_dtype=jnp.float32):
        super().__init__()
        self.strategy = strategy
        self.eps = eps
        self.use_bias = bias
        self.param("weight", (dim,), init.ones, dtype=param_dtype)
        if bias:
            self.param("bias", (dim,), init.zeros, dtype=param_dtype)

    def forward(self, params, x):
        y = ops.layer_norm(x, params["weight"],
                           params["bias"] if self.use_bias else None, self.eps)
        if x.ndim == 3:
            y = self.strategy.constrain(y, self.strategy.act_hidden())
        return y

    def residual(self, params, x, h):
        """Fused residual-add + LayerNorm pair — see
        ParallelRMSNorm.residual."""
        y, s = ops.residual_layer_norm(
            x, h, params["weight"],
            params["bias"] if self.use_bias else None, self.eps,
            layout=self.strategy.act_hidden() if x.ndim == 3 else None)
        if x.ndim == 3:
            y = self.strategy.constrain(y, self.strategy.act_hidden())
        return y, s
