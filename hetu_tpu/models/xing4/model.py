"""Xing4.0: Kimi-K2's block (latent attention, leading dense layers, then
sigmoid-routed experts beside a shared one, YaRN) inside a residual
STREAM of `hc_mult` hidden vectors a token.  Serving only: `ServingEngine`
takes the model through the programs of `models/generation.py`.

The carry between layers is X [b, s, n, hidden].  Each of a layer's two
sublayers F (F_attn(h) = MLA(RMSNorm_in(h)), F_mlp(h) = MLP or
experts(RMSNorm_post(h)): `kimi_k2.KimiBlock`'s, reused) reads ONE vector
mixed from the stream and writes its output back into every stream
through its own `nn.hyper_connections.HyperConnection` (`hc_attn`,
`hc_mlp`): the block's `residual_pre` / `residual_post` hooks, which
`generation._layer` calls in place of `h + F(h)`.  Entry: the embedding
replicated into the n streams (`embed_tokens`); exit: the streams summed,
then the final norm (`final_hidden`).  A token's cache entry is Kimi's:
the stream changes nothing in the cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from hetu_tpu.models.kimi_k2.model import KimiBlock, KimiK2LMHeadModel
from hetu_tpu.models.xing4.config import Xing4Config
from hetu_tpu.nn.hyper_connections import HyperConnection, reading
from hetu_tpu.parallel.strategy import ParallelStrategy


class Xing4Block(KimiBlock):
    def __init__(self, config: Xing4Config, strategy: ParallelStrategy,
                 *, moe: bool):
        super().__init__(config, strategy, moe=moe)
        c = config
        for side in ("attn", "mlp"):
            setattr(self, f"hc_{side}", HyperConnection(
                c.hidden_size, c.hc_mult,
                sinkhorn_iters=c.hc_sinkhorn_iters, eps=c.hc_eps,
                rms_eps=c.rms_norm_eps,
                clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max),
                initializer_range=c.initializer_range))

    # -- generation._layer's residual hooks --------------------------------
    def residual_pre(self, lp, side, X):
        return getattr(self, f"hc_{side}").pre(lp[f"hc_{side}"], X)

    def residual_post(self, lp, side, mix, X, y):
        return getattr(self, f"hc_{side}").post(mix, X, y)

    def forward(self, params, X, rope, pos_ids):
        """Whole sequences: X [b, s, n, hidden] -> the same."""
        h, mix = self.residual_pre(params, "attn", X)
        with jax.named_scope("attn"):
            y = self.attn(params["attn"],
                          self.input_norm(params["input_norm"], h),
                          rope, pos_ids)
        X = self.residual_post(params, "attn", mix, X, y)
        h, mix = self.residual_pre(params, "mlp", X)
        with jax.named_scope("mlp"):
            y, _ = self.mlp_stats(params["mlp"],
                                  self.post_norm(params["post_norm"], h))
        return self.residual_post(params, "mlp", mix, X, y)


class Xing4LMHeadModel(KimiK2LMHeadModel):
    BLOCK = Xing4Block

    def param_specs(self):
        """Kimi's, with sublayer k of the walk (a layer's attention side,
        then its MLP side) initialised to read mostly stream k mod n
        (`nn.hyper_connections.reading`)."""
        specs = super().param_specs()
        c, k = self.config, 0
        for group, num in (("dense_layers", c.first_k_dense_replace),
                           ("moe_layers", c.num_moe_layers)):
            for i in range(num):
                for side in ("attn", "mlp"):
                    hc = specs["model"][group][f"layer_{i}"][f"hc_{side}"]
                    hc["b"] = reading(hc["b"], k % c.hc_mult, c.hc_mult)
                    k += 1
        return specs

    def embed_tokens(self, params, ids, pos_ids):
        x = super().embed_tokens(params, ids, pos_ids)
        return jnp.broadcast_to(
            x[..., None, :], x.shape[:-1] + (self.config.hc_mult,)
            + x.shape[-1:])

    def final_hidden(self, params, X):
        x = jnp.sum(X.astype(jnp.float32), axis=-2).astype(X.dtype)
        return super().final_hidden(params, x)
