"""Every model family through the ONE serving path, by its hooks: the
golden test of `ServingEngine` over the families of the package and two
defined here by the hooks alone (moved out of tests/test_serving.py: a
file is one worker's under `--dist loadfile`)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import generate
from hetu_tpu.models.cache_contract import KVAttention
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.obs.metrics import MetricsRegistry
from hetu_tpu.serving.request import Request

from test_serving import _engine


class _HooksOnlyFamily:
    """A K/V family that lives OUTSIDE the package, defined by the hooks
    `models/generation.py` lists and nothing else (no Module, no edit
    under hetu_tpu/): pre-norm blocks of rotary multi-query-group
    attention and a GELU MLP, the first layer with arrays of its own and
    the other two STACKED, so `ServingEngine` both calls and scans it.
    How a query attends the cached K/V is the package's `KVAttention`,
    as for llama and gpt; its own dense `forward` uses none of it."""

    STATS = ()

    class Attn(KVAttention):
        def __init__(self, config):
            self.config = config

        def project(self, p, hn, rope, pos_ids):
            from hetu_tpu import ops
            c, (b, s, _) = self.config, hn.shape
            q = (hn @ p["wq"]).reshape(b, s, c.num_attention_heads,
                                       c.head_dim)
            k = (hn @ p["wk"]).reshape(b, s, c.num_key_value_heads,
                                       c.head_dim)
            v = (hn @ p["wv"]).reshape(k.shape)
            return (ops.apply_rotary(q, *rope, pos_ids),
                    (ops.apply_rotary(k, *rope, pos_ids), v))

        def output(self, p, attn):
            return attn @ p["wo"]

    class Block:
        def __init__(self, config):
            self.attn = _HooksOnlyFamily.Attn(config)

        @staticmethod
        def input_norm(p, x):
            return x * jax.lax.rsqrt(
                jnp.mean(x * x, -1, keepdims=True) + 1e-6) * p
        post_norm = input_norm

        def mlp_stats(self, p, x):
            return jax.nn.gelu(x @ p["up"]) @ p["down"], None

    def __init__(self, head_dim=16):
        import types
        self.config = c = types.SimpleNamespace(
            vocab_size=256, hidden_size=2 * head_dim * 2,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=head_dim,
            max_position_embeddings=256, compute_dtype=jnp.float32,
            use_flash_attention=False)
        self.block = self.Block(c)

    def init(self, key):
        c = self.config
        h, kv = c.hidden_size, c.num_key_value_heads * c.head_dim
        shapes = {"input_norm": (h,), "post_norm": (h,),
                  "attn": {"wq": (h, h), "wk": (h, kv), "wv": (h, kv),
                           "wo": (h, h)},
                  "mlp": {"up": (h, 2 * h), "down": (2 * h, h)}}
        keys = iter(jax.random.split(key, 64))

        def make(shape, lead=()):
            if len(shape) == 1:
                return jnp.ones(lead + shape, jnp.float32)
            return 0.05 * jax.random.normal(next(keys), lead + shape)

        def layer(lead=()):
            return jax.tree.map(lambda sh: make(sh, lead), shapes,
                                is_leaf=lambda x: isinstance(x, tuple))
        return {"embed": make((c.vocab_size, h)), "first": layer(),
                "stack": layer((2,)), "final": make((h,)),
                "head": make((h, c.vocab_size))}

    # -- the hooks ---------------------------------------------------------
    def cache_contract(self):
        from hetu_tpu.models.cache_contract import kv_contract
        c = self.config
        return kv_contract(c.num_hidden_layers, c.num_key_value_heads,
                           c.head_dim, c.compute_dtype)

    def embed_tokens(self, params, ids, pos_ids):
        return params["embed"][ids]

    def rope_tables(self, max_len):
        from hetu_tpu import ops
        return ops.build_rope_cache(max_len, self.config.head_dim, 1e4)

    def serving_layers(self, params):
        return [(self.block, params["first"], None),
                (self.block, params["stack"], 2)]

    def final_hidden(self, params, x):
        return self.Block.input_norm(params["final"], x)

    def logits(self, params, hidden):
        return hidden @ params["head"]

    def lm_head_weight(self, params):
        return params["head"]

    # -- its own dense forward: no cache, no hook of attention -------------
    def forward(self, params, ids):
        """Logits [s, vocab] of ONE sequence ids [s]."""
        c, s = self.config, ids.shape[0]
        g = c.num_attention_heads // c.num_key_value_heads
        rope = self.rope_tables(s)
        pos = jnp.arange(s, dtype=jnp.int32)[None]
        x = params["embed"][ids][None]
        layers = [params["first"]] + [
            jax.tree.map(lambda a: a[i], params["stack"]) for i in range(2)]
        for lp in layers:
            q, (k, v) = self.block.attn.project(
                lp["attn"], self.Block.input_norm(lp["input_norm"], x),
                rope, pos)
            k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
            sc = jnp.einsum("bqnd,bknd->bnqk", q, k) * c.head_dim ** -0.5
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
            a = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(sc, -1), v)
            x = x + a.reshape(1, s, -1) @ lp["attn"]["wo"]
            x = x + self.block.mlp_stats(
                lp["mlp"], self.Block.input_norm(lp["post_norm"], x))[0]
        return self.logits(params, self.final_hidden(params, x))[0]


class _HooksWindowFamily(_HooksOnlyFamily):
    """`_HooksOnlyFamily` with layers that differ in how far back they
    read, written by the hooks alone: the first layer (its own arrays)
    reads everything, the two STACKED layers read the last `WINDOW`
    positions (`block.window`, and the contract's `windows`): a scanned
    run of window layers over pages of their own kind."""

    WINDOW = 12

    def __init__(self, head_dim=16):
        super().__init__(head_dim)
        self.window_block = self.Block(self.config)
        self.window_block.window = self.WINDOW
        self.window_block.attn_scope = "attn_window"

    def cache_contract(self):
        from hetu_tpu.models.cache_contract import kv_contract
        c = self.config
        return kv_contract(c.num_hidden_layers, c.num_key_value_heads,
                           c.head_dim, c.compute_dtype,
                           windows=(None, self.WINDOW, self.WINDOW))

    def serving_layers(self, params):
        return [(self.block, params["first"], None),
                (self.window_block, params["stack"], 2)]

    def forward(self, params, ids):
        c, s = self.config, ids.shape[0]
        g = c.num_attention_heads // c.num_key_value_heads
        rope = self.rope_tables(s)
        pos = jnp.arange(s, dtype=jnp.int32)[None]
        x = params["embed"][ids][None]
        layers = [params["first"]] + [
            jax.tree.map(lambda a: a[i], params["stack"]) for i in range(2)]
        t, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        for i, lp in enumerate(layers):
            q, (k, v) = self.block.attn.project(
                lp["attn"], self.Block.input_norm(lp["input_norm"], x),
                rope, pos)
            k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
            sc = jnp.einsum("bqnd,bknd->bnqk", q, k) * c.head_dim ** -0.5
            seen = (j <= t) & ((j > t - self.WINDOW) if i else True)
            sc = jnp.where(seen, sc, -jnp.inf)
            a = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(sc, -1), v)
            x = x + a.reshape(1, s, -1) @ lp["attn"]["wo"]
            x = x + self.block.mlp_stats(
                lp["mlp"], self.Block.input_norm(lp["post_norm"], x))[0]
        return self.logits(params, self.final_hidden(params, x))[0]


def _family_case(family, hd128):
    """(model, params, dense: (params, ids [s]) -> logits [s, vocab])."""
    if family in ("hooks", "hooks-window"):
        model = (_HooksOnlyFamily if family == "hooks"
                 else _HooksWindowFamily)(128 if hd128 else 16)
        return model, model.init(jax.random.key(3)), jax.jit(model.forward)
    if family == "trinity":
        from test_trinity import build, ref_logits
        cfg, model, params = build(head_dim=128 if hd128 else 16)
        return model, params, lambda p, ids: ref_logits(p, cfg, ids)
    if family == "kimi":
        from test_kimi_k2 import build, ref_logits
        cfg, model, params = build()
        return model, params, lambda p, ids: ref_logits(p, cfg, ids)
    if family == "mimo":
        from test_mimo_v2 import build, ref_logits
        cfg, model, params = build(**(dict(head_dim=192, v_head_dim=128)
                                      if hd128 else {}))
        return model, params, lambda p, ids: ref_logits(p, cfg, ids)
    if family == "ling":
        from test_bailing_hybrid import build, ref_logits
        cfg, model, params = build()
        return model, params, lambda p, ids: ref_logits(p, cfg, ids)
    if family == "longcat":
        from test_longcat import build, ref_logits
        cfg, model, params = build()
        return model, params, lambda p, ids: ref_logits(p, cfg, ids)
    if family == "xing4":
        from test_xing4 import build, ref_logits
        cfg, model, params = build()
        return model, params, lambda p, ids: ref_logits(p, cfg, ids)
    if family == "deepseek_v32":
        from test_deepseek_v32 import build, ref_logits
        cfg, model, params = build()
        return model, params, lambda p, ids: ref_logits(p, cfg, ids)
    if family == "lfm2":
        from test_lfm2_moe import build, ref_logits
        cfg, model, params = build(head_dim=64 if hd128 else None)
        return model, params, lambda p, ids: ref_logits(p, cfg, ids)
    if family == "gpt":
        from hetu_tpu.models.gpt import GPTConfig, GPTLMHeadModel
        kw = dict(hidden_size=256, num_attention_heads=2) if hd128 else {}
        model = GPTLMHeadModel(GPTConfig.tiny(
            remat=False, compute_dtype=jnp.float32, **kw))
    else:
        kw = dict(hidden_size=256, num_attention_heads=2,
                  num_key_value_heads=2) if hd128 else {}
        model = LlamaLMHeadModel(LlamaConfig.tiny(
            remat=False, compute_dtype=jnp.float32,
            use_flash_attention=False, use_scan=family != "llama-unstacked",
            **kw))
    return (model, model.init(jax.random.key(1)),
            jax.jit(lambda p, ids: model(p, ids[None])[0]))


#: the families whose cache is a latent a token (models/kimi_k2.MLAttention)
LATENT = ("kimi", "ling", "longcat", "xing4", "deepseek_v32")


#: the cases of `test_a_family_is_served_by_its_hooks` by the file that
#: runs them: a file is what one worker of the tier-1 run takes whole, and
#: all of them in this one made it the run's last, alone, for minutes
#: (tests/test_serving_families_latent.py, .._window.py)
HERE = [
    ("hooks", "composition"), ("hooks", "paged"),
    ("llama", "composition"), ("llama", "paged"),
    ("llama-unstacked", "composition"),
    ("gpt", "composition"), ("gpt", "paged"),
    ("hooks-window", "composition"), ("hooks-window", "paged"),
    ("lfm2", "composition"), ("lfm2", "paged")]
LATENT_CASES = [(f, r) for f in LATENT for r in ("xla", "kernel")]
WINDOW_CASES = [(f, r) for f in ("trinity", "mimo")
                for r in ("composition", "paged")]


@pytest.mark.parametrize("family,route", HERE)
def test_a_family_is_served_by_its_hooks(family, route, monkeypatch):
    """Golden, ONE body for every family: staggered continuous batching
    through the normal path (`run`: scheduler, allocator, page tables,
    chunked prefill — prompts straddle a page and a chunk — page write,
    decode) emits, token for token, the argmax of the model's own dense
    forward given the stream's own prefix (to 2e-4 of logit, what
    float32 attention in another order may move a near tie by); llama
    and gpt also `generate()`'s tokens exactly.

    ONE decode program for every family (`decode_step_paged`); the K/V
    families over both attentions a layer's `attend_paged` has there:
    `composition` (HETU_TPU_PALLAS=0: every K/V layer attends the slot's
    gathered pages by the XLA composition, `_attend_gathered`) and
    `paged` (the Pallas kernel, interpret mode, walks the page tables);
    kimi over its latent layer's two attentions likewise.  `hooks` is a K/V family defined HERE, outside the
    package, by the hooks alone: adding an architecture is new files
    only.  `hooks-window` is that family with two scanned layers that
    read a window of 12 positions beside one that reads everything, and
    `trinity` the package's own (window and full layers, each with its
    own arrays): pages by kind of layer, released behind the window
    while the request decodes, over both attentions.  `mimo` is the family
    whose kinds of layer also differ in what a token STORES (1 KV head
    against 2, keys wider than values; 192 / 128 under the kernel), with
    a sink in the window layers' softmax, a window smaller than a page
    pair and than the chunk, a sliding prefill scratch and no shared
    expert.  `ling` is the family with a STATE kind of layer: six
    linear-attention layers that store nothing a token and a fixed state
    a sequence, held by slot beside the one latent layer's pages and
    carried by the chunk and the decode program (`state_chunk`,
    `state_step`), over the latent layer's two attentions as kimi.
    `longcat` is the family whose published layer is TWO latent cache
    layers with ONE expert branch handed from the first sublayer's MLP
    side to the second's (`mlp_hands_on` / `mlp_takes_handed`), routed by
    softmax over routed and identity experts, over the two latent
    attentions as kimi.
    `xing4` is the family whose carry between layers is a STREAM of four
    hidden vectors a token, read and written through the block's
    `residual_pre` / `residual_post` hooks around Kimi's sublayers.
    `deepseek_v32` is the family whose latent layers SELECT what they
    attend: a token stores two arrays (the latent and an indexer's key)
    under one page table, every decode step and most chunk rows attend
    the 24 best-scored of the positions they see, and the decode step
    gathers them (neither paged latent attention: its route is not
    asked).
    `lfm2` is the family whose state is a short convolution's TAIL alone
    (five layers, two positions a sequence, held by slot) beside K/V
    pages whose heads of 64 are stored two a lane row (`paged`: heads of
    64 in rows of 128 under the kernel), with every expert held.
    `llama-unstacked` is the Llama block built with
    use_scan=False: a layer's own arrays, called, never scanned."""
    monkeypatch.setenv("HETU_TPU_PALLAS",
                       "1" if route in ("paged", "kernel") else "0")
    if route == "kernel":
        monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "paged_latent")
    if route == "paged":
        monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "paged_attn")
    model, params, dense = _family_case(family, hd128=route == "paged")
    vocab = model.config.vocab_size
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, vocab, size=n)
                    .astype(np.int32), max_new_tokens=m, arrival_t=t)
            for i, (n, m, t) in enumerate(
                [(5, 6, 0.0), (16, 4, 0.0), (23, 6, 0.02), (40, 5, 0.05),
                 (17, 6, 0.07), (9, 3, 0.3)])]
    reg = MetricsRegistry()
    eng = _engine(model, params, registry=reg, num_slots=4, page_size=8,
                  max_len=128, prefill_chunk=16, num_pages=64)
    results = {r.rid: r for r in eng.run(reqs)}
    if family not in LATENT:
        # what every traced K/V layer chose, and why
        took = eng.kernel_routes["paged_attn"]
        if route == "paged":
            assert took["pallas"] and not took["xla"]
        else:
            assert took["xla"] and not took["pallas"]
            assert list(took["why"]) == [
                "switched off by HETU_TPU_PALLAS / HETU_TPU_PALLAS_KERNELS"]
    if family == "deepseek_v32":
        assert "paged_latent" not in eng.kernel_routes
        assert not eng.windowed and len(eng.pool.arrays.tree()) == 2
        assert 0 < reg.counter_value("serve.decode_selected_tokens") \
            < reg.counter_value("serve.decode_selectable_tokens")
    elif family in LATENT:
        assert eng.kernel_routes["paged_latent"][
            "pallas" if route == "kernel" else "xla"]
    if family in ("ling", "lfm2"):
        assert eng.stateful and len(eng.pool.state) == (
            2 if family == "ling" else 1)
        assert reg.counter_value("serve.state_resets") == len(reqs)
    assert sorted(results) == list(range(len(reqs)))
    for req in reqs:
        toks = np.asarray(results[req.rid].tokens)
        assert len(toks) == req.max_new_tokens
        lg = np.asarray(dense(params, jnp.asarray(
            np.concatenate([req.prompt, toks[:-1]]))))[req.prompt_len - 1:]
        gap = lg.max(-1) - lg[np.arange(len(toks)), toks]
        assert (gap <= 2e-4).all(), (req.rid, gap)
        if family not in LATENT + ("hooks", "lfm2") \
                and route == "composition":
            # (one program a prompt length: run op by op, `generate()`'s
            # prefill was most of these cases' seconds)
            gold = generate(model, params, jnp.asarray(req.prompt[None]),
                            max_new_tokens=req.max_new_tokens)
            assert list(toks) == list(np.asarray(gold)[0, req.prompt_len:])
    eng.scheduler.check_invariants()
    assert eng.pool.free_count == eng.pool.num_pages
    assert reg.counter_value("serve.decode_context_tokens") > 0
    if eng.windowed:
        assert reg.counter_value("serve.window_pages_released") > 0
        assert 0 < reg.counter_value("serve.decode_window_context_tokens") \
            < reg.counter_value("serve.decode_context_tokens")
    if model.STATS:
        # the programs' stats came back with the tokens
        n = {k[len("serve.moe_"):]: reg.counter_value(k)
             for k, _ in model.STATS}
        assert n["extra_row_blocks"] >= 0
        assert n["row_blocks"] >= n["extra_row_blocks"]
        # (xing4 and lfm2 hold every expert of a layer, 8 here: every
        # pair is local; the others hold 4 of their router's 16)
        held = 8 if family in ("xing4", "lfm2") else 4
        assert n["layer_steps"] > 0 and (
            n["assignments"] == n["local_assignments"] if held == 8
            else n["assignments"] > n["local_assignments"])
        assert n["expert_hits"] <= held * n["layer_steps"]
        assert 0 < n["max_expert_load"] <= 16 * 4




def test_each_kind_of_layer_answers_the_kernels_gate_for_itself(monkeypatch):
    """A window model ONE of whose kinds of layer the paged kernel's gate
    refuses serves with the kernel in the other kind: the route is a
    layer's own (`KVAttention.attend_paged`), not one answer for the
    whole model.  MiMo in bfloat16 at widths the kernel reads (keys 192
    in 256 lanes, values 128): a layer that reads everything stores ONE
    KV head a token, which the kernel reads as pages of [page_size, hd]
    (it refused two-byte pages of one head until PR 47; interpret mode
    here, behind a gate that is asked as on a TPU); a window layer stores
    two, and here a page of them is over the kernel's VMEM limit, set
    between the two kinds' pages (refused: the composition over gathered
    pages).  Both routes stand on the one `kernel_routes` line, each
    with its reason, and the tokens' first (the chunk program's, the same
    under both) are those of the engine all of whose layers take the
    composition."""
    from hetu_tpu.ops.pallas import paged_attention as pa
    from test_mimo_v2 import build, tiny_cfg
    cfg, model, params = build(
        head_dim=192, v_head_dim=128,
        serving=dict(tiny_cfg()["serving"], param_dtype="bfloat16"))
    assert model.config.compute_dtype == jnp.bfloat16
    rng = np.random.default_rng(2)
    reqs = lambda: [Request(  # noqa: E731
        rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
        .astype(np.int32), max_new_tokens=5)
        for i, n in enumerate((5, 23, 40))]
    kw = dict(num_slots=4, page_size=8, max_len=128, prefill_chunk=16,
              num_pages=64)
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    rng = np.random.default_rng(2)
    want = _engine(model, params, **kw).run(reqs())
    # auto, asked as on a TPU; the kernel itself still interpreted
    monkeypatch.delenv("HETU_TPU_PALLAS")
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "paged_attn")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pa, "_interpret", lambda: True)
    c = model.config
    page = [8 * pa._token_vmem_bytes(c.num_attention_heads, n_kv, 256, 2,
                                     "none", 128)
            for n_kv in (c.num_key_value_heads, c.swa_num_key_value_heads)]
    assert c.num_key_value_heads == 1 and page[0] < page[1]
    monkeypatch.setattr(pa, "_VMEM_LIMIT", sum(page) // 2)
    rng = np.random.default_rng(2)
    eng = _engine(model, params, **kw)
    got = eng.run(reqs())
    took = eng.kernel_routes["paged_attn"]
    assert took["pallas"] and took["xla"], took
    assert sorted(why.split(":")[0] for why in took["why"]) == [
        "shape gate", "shape gate passes"], took
    assert any("bytes of VMEM" in why for why in took["why"])
    # the kernel took the full kind's ONE head (keys wider than values),
    # and no window layer
    assert eng.kernel_routes["paged_attn_shapes"]["pallas"]
    assert "paged_attn_window" not in eng.kernel_routes
    assert [len(r.tokens) for r in got] == [5, 5, 5]
    assert [r.tokens[0] for r in got] == [r.tokens[0] for r in want]
    eng.scheduler.check_invariants()
    assert eng.pool.free_count == eng.pool.num_pages
