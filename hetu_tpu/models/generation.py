"""Autoregressive generation with a KV cache, and the serving programs.

Rebuild of the reference's model-generation surface (reference:
python/hetu/models/utils/model_utils.py PreTrainedModel generate path; the
reference is training-first and so are we — this is the functional decode
loop for eval/demo, TPU-shaped: static max length, lax.scan decode, cache as
a pytree carried through the scan).

ONE set of programs, written against hooks the model brings: no program
here knows a family.  What a token stores is the model's cache contract
(models/cache_contract.py); how its entry is made and how a query attends
it are the model's hooks:

  embed_tokens(params, ids, pos_ids)   -> x [b, s, hidden]
      (or whatever the model carries between its layers: a block with
      the residual hooks below may carry a stream [b, s, n, hidden],
      which `final_hidden` collapses)
  rope_tables(max_len)                 -> whatever its `project` takes
  serving_params(params)               -> params     (OPTIONAL)
      the parameters as these programs read them, where serving wants
      another layout than training: llama's fused qkv and gate/up
      weights as matrices a product takes where they lie in the layers'
      stack (`_walk_layers` says why).  An engine calls it once, at its
      build, and hands the programs the result; a family without it is
      served with its parameters as they come, by the same programs.
  serving_layers(params)               -> runs (block, parameters, count)
      in the cache's layer order.  count = n: the parameters are n
      layers' STACKED [n, ...], and the run is scanned; count None: one
      layer's own arrays, and the layer is called.  `_walk_layers`, the
      one walk over the layers, chooses by that and by nothing else.
  block.input_norm / post_norm / mlp_stats(params, x) -> (y, stats)
  block.residual_pre(lp, side, carry) -> (x, mix),
  block.residual_post(lp, side, mix, carry, y) -> carry   (OPTIONAL)
      THE BLOCK'S RESIDUAL PATH around each of its two sublayers (`side`
      "attn" or "mlp"): what the sublayer reads of the carry and how its
      output is folded back (`_layer`).  Absent: x is the carry and y is
      added to it.
  block.window, block.attn_scope         (OPTIONAL)
      how far back the layer's queries read, their own position counted
      (None or absent: everything), as the cache contract's `windows`
      says it per layer: `_layer` hands it to the three attend hooks as
      `window=`; `_walk_layers` finds the layer's KIND in the contract
      (`kind_of(layer)`: layers that read as far back AND store the same
      shapes share cache arrays, page arrays and a page table:
      serving/kv_pool.py); and the scope the layer's attention runs
      under inside `attn`, so that a trace tells the kinds apart
  block.attn.project(p, hn, rope, pos_ids) -> (q, entries[, aux])
      HOW A TOKEN'S CACHE ENTRY IS MADE: one array per array of the
      contract, [b, s, *stored shape]; what it returns beyond that (a
      gate on the attention's output) is handed to `output`
  block.attn.attend_paged(p, q, pools, table, positions, base)
  block.attn.attend_dense(p, q, caches, start[, first=])
  block.attn.attend_prompt(p, q, entries)
      HOW A QUERY ATTENDS IT: over pages, over a dense per-slot cache
      (which begins at position `first` where the chunk program's
      sliding scratch says so), and a whole prompt over its own entries
      (for the K/V kind all three are `cache_contract.KVAttention`,
      which also asks the family for a `sink`)
  block.attn.output(p, attn[, aux]), final_hidden(params, x),
  logits(params, h), lm_head_weight(params)
  block.attn.state_chunk(p, hn, state, start, valid) -> (out, state')
  block.attn.state_step(p, hn, state, live)          -> (out, state')
      A STATE LAYER'S WHOLE ATTENTION, in place of `project` /
      `attend_*` / `output`: a layer whose kind, by the cache contract,
      stores a fixed state a SEQUENCE and nothing a token (`state_shapes`:
      a linear-attention layer).  hn [b, s, hidden] (normed); `state`
      one array [b, *shape] per state array of the kind, the rows'
      own; `out` [b, s, hidden], the residual's addend.  `state_chunk`
      advances each row by the first `valid[b]` of its s positions, which
      begin at position start[b]: the rows past `valid` (a chunk's
      padding) must leave the state as the last valid row left it.
      `state_step` advances the rows where `live[b]` by ONE position
      and leaves the others' state untouched (the decode pass's idle
      slots, and slots whose prompt is still being prefilled).  The
      programs hand a chunk that starts at position 0 zeros for its
      state (`extend_cache`), slice the rows out of the carried state
      arrays and write them back in place; `_walk_layers` finds the
      layer's kind in the contract and nothing else chooses.  A hook may
      return ONE value more: what the layer hands on (below).
  block.attn.mix(p, hn[, handed=])                   -> out
      THE WHOLE ATTENTION OF A LAYER THAT KEEPS AND READS NO CACHE (the
      contract's `reads`: NO_CACHE), in place of the hooks above.
  block.hands_on, block.takes_handed                 (OPTIONAL)
      An activation carried across layers beside h (`_layer`'s `handed`):
      a state block that says `hands_on` replaces it by the third value
      its `state_chunk` / `state_step` returned; a block that says
      `takes_handed` is given it as `handed=`.
  block.mlp_hands_on, block.mlp_takes_handed         (OPTIONAL)
      The same carry from the MLP side: a block that says `mlp_hands_on`
      has `mlp_stats` return (y, stats, what it hands on), one that says
      `mlp_takes_handed` has it given what was handed, as `handed=` (a
      branch computed from one layer's post-attention norm and added
      after a later layer's MLP: a shortcut-connected expert layer).
  A LAYER THAT READS ANOTHER LAYER'S ENTRIES (the contract's `reads[l]` =
      k) has `project` return no entries, `()`: the programs write none
      and hand its `attend_*` hooks layer k's cache arrays, or its pages
      and page table.
  serving_layers: A RUN MAY BE A PERIOD: block and parameters tuples, one
      entry a layer of the period, each entry's parameters stacked
      [count, ...] (`_walk_layers`).
  model.read_rows_from                                (OPTIONAL)
      The layer from whose attention on only the rows whose logits are
      read need computing (`extend_cache(read_row=)`).  Unnamed, it is
      the number of layers: every layer runs for every row, and only
      the final norm and the head run for the read row alone.

  STATS, zero_stats(), add_stats(a, b)
      `stats` is a small int32 vector a layer counts of itself (an
      expert layer's assignments: nn/moe.MOE_STATS); `STATS`
      names each entry's counter and says whether executions add up or
      take the maximum, and is EMPTY for a model that counts nothing
      (llama, gpt: `mlp_stats` returns None for it, and the other two
      are not asked for).  A program takes the running vector in and
      hands it on only when it is given one, so the engine reads it with
      the tokens and nowhere else, and the programs of a model that
      counts nothing have no such argument.

The programs: `prefill` (a whole prompt into a fresh dense cache),
`decode_step_slots` (one token a row over a dense cache; `decode_step`
at one position for all rows: `generate()`'s loop), `extend_cache` (a
chunk of tokens a row over a dense cache: chunked prefill), and
`decode_step_paged` / `verify_step_paged` (one token, or a block, a
slot, over a paged pool where it lies: THE decode and verify step of
the serving engine, for every cache kind; which attention a layer runs
there, a kernel that walks the page table or the XLA composition over
the slot's gathered pages, is its `attend_paged` hook's choice).
`verify_step_slots` (a block a row over a dense cache) is the tests'
reference of `verify_step_paged`.  Every one runs `_layer`, the one
decoder layer, and differs in the two lines it hands it: where the entry
is written and how the query attends (docs/serving.md).
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu.models.cache_contract import cache_contract


#: float32 score bytes `_attend_cached_chunk` computes at once; over
#: this it goes KV head by KV head
_SCORES_AT_ONCE = 256 << 20


def _attend_cached(q, ck, cv, pos, scale):
    """q: [b, 1, nq, hd]; ck/cv: [b, M, n_kv, hd]; attend over
    cache[:pos+1] (pos scalar, or [b] for per-slot depths).

    GQA attends in the GROUPED layout — q reshaped [b, C, n_kv, g, hd]
    and contracted against the cache's n_kv heads directly — instead of
    materializing a group-repeated copy of the whole cache every step
    (the old jnp.repeat path copied M*n_kv*hd*(g-1) elements per layer
    per token).  Head ordering matches the fused-QKV layout (q head
    j = kv head j // g): the q·k scores are bit-identical to the repeat
    path and the p·v output matches to float32-ulp (the weighted sum
    over the cache axis reassociates without the materialized copy) —
    regression-tested in tests/test_generation.py.

    This is exactly the single-query case of `_attend_cached_chunk`
    (one query at offset 0 from `pos`) — ONE implementation of the
    grouped contraction + causal mask, so decode and chunked prefill
    can never drift numerically."""
    return _attend_cached_chunk(q, ck, cv, pos, scale)


def _attend_cached_chunk(q, ck, cv, start, scale, window=None, first=0,
                         sink=None):
    """Multi-query cached attention for chunked prefill.  q: [b, C, nq,
    hd] sits at absolute positions start..start+C-1 (start scalar or
    [b]); key position k is visible to query i iff k <= start + i
    (causal within the chunk, full visibility of the already-cached
    prefix) and, under a `window`, k > start + i - window.  ck [b, M,
    n_kv, hd] / cv [b, M, n_kv, hd_v] (the values' width is their own)
    hold the positions first..first+M-1 (`first` = 0: the whole cache; a
    window layer's chunk hands in the slice it reads:
    cache_contract.KVAttention.attend_dense).  `sink` [nq]: a scalar a
    query head that stands in the softmax's denominator as one more key
    and adds no value.  Same grouped-GQA contraction as
    `_attend_cached`.  -> [b, C, nq, hd_v].

    THE XLA composition of attention over a dense cache, and the
    reference of the blockwise kernel (ops/pallas/chunk_attention) that
    `attend_dense` routes ONE row's chunk of C > 1 queries to on a TPU:
    what stays here is every other backend, every shape the kernel's
    gate refuses (head_dim % 128, a chunk or a cache length that does
    not tile), a single query (`_attend_cached`, the decode step over a
    dense cache), rows at depths of their own (start [b > 1]: a layer's
    gathered pages in the paged decode and verify steps,
    `KVAttention._attend_gathered`) and whole prompts under a
    window (`attend_prompt`).  It forms the float32 scores of every
    position it is handed, in HBM."""
    b, M, n_kv, hd = ck.shape
    C, nq = q.shape[1], q.shape[2]
    group = nq // n_kv
    qg = q.reshape(b, C, n_kv, group, hd)
    start = jnp.asarray(start)
    if start.ndim == 0:
        start = start[None]
    qpos = start[:, None] + jnp.arange(C)[None, :]            # [b, C]
    kpos = jnp.arange(M)[None, None, :]
    if not (isinstance(first, int) and first == 0):
        kpos = kpos + first
    mask = kpos <= qpos[..., None]                            # [b, C, M]
    if window is not None:
        mask = mask & (kpos > qpos[..., None] - window)

    def attend(qg, ck, cv, sink=None):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                       ck.astype(jnp.float32)) * scale
        s = jnp.where(mask[:, None, None, :, :], s, -1e30)
        if sink is None:
            p = jax.nn.softmax(s, axis=-1)
        else:
            sk = sink.astype(jnp.float32)[None, :, :, None, None]  # [1,h,g,1,1]
            m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sk)
            e = jnp.exp(s - m)
            p = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sk - m))
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, cv.astype(jnp.float32))

    heads = (qg, ck, cv) if sink is None else (
        qg, ck, cv, jnp.reshape(sink, (n_kv, group)))
    if b * nq * C * M * 4 > _SCORES_AT_ONCE and n_kv > 1:
        # one KV head's group of query heads at a time: the float32
        # scores of all heads at once would be the program's largest
        # temporary (0.54 GB, twice, for 32 heads x 512 x 8,192)
        out = lax.map(
            lambda x: attend(x[0][:, :, None], x[1][:, :, None],
                             x[2][:, :, None], *(y[None] for y in x[3:])
                             )[:, :, 0],
            tuple(jnp.moveaxis(a, 2, 0) for a in heads[:3]) + heads[3:])
        out = jnp.moveaxis(out, 0, 2)
    else:
        out = attend(*heads)
    return out.reshape(b, C, nq, cv.shape[-1]).astype(q.dtype)



def lm_head_weight(model, params):
    """The lm_head slice as a [hidden, vocab] matrix — the weight
    operand of the fused sampling epilogue (serving/sampling.
    sample_hidden).  Matches `model.logits`: tied embeddings transpose
    the token-embedding table, untied models carry an explicit head."""
    return model.lm_head_weight(params)


def _check_context_length(config, max_len: int):
    """Past the trained context, GPT's jnp.take on wpe (and LLaMA's RoPE
    table lookup) would silently clamp to the last position — fail loudly
    instead.  One guard shared by every cache-building entry point."""
    if max_len > config.max_position_embeddings:
        raise ValueError(
            f"cache length {max_len} exceeds max_position_embeddings "
            f"{config.max_position_embeddings}")



def window_reach(window: int, chunk: int, page: int = 1) -> int:
    """Positions a window layer's SLIDING scratch holds for chunks of
    `chunk` tokens: the window, up to whole pages (so that a page of the
    pool is a block of the scratch), before the chunk, and the chunk."""
    return -(-window // page) * page + chunk


def init_cache(model, batch: int, max_len: int, chunk: Optional[int] = None,
               page: int = 1):
    """Empty dense cache: per KIND of layer of the model's cache contract
    (one kind for most models), one array [layers of the kind, b,
    positions, *stored shape] per array a token stores there ((k, v) of
    [.., n_kv, hd] for the K/V kind), kind after kind.  `max_len`
    positions, but for a window kind given `chunk` (the chunk program's
    scratch, which SLIDES: `extend_cache(slide=True)`): `window_reach`
    positions, where that is fewer."""
    c = model.config
    _check_context_length(c, max_len)
    contract = cache_contract(model)

    def positions(w):
        return max_len if w is None or chunk is None else min(
            max_len, window_reach(w, chunk, page))
    return tuple(
        jnp.zeros((len(contract.layers_of(k)), batch, positions(w))
                  + tuple(shape), c.compute_dtype)
        for k, w in enumerate(contract.kinds)
        for shape in contract.stored_shapes_of(k))


def _of_kind(arrays, kind: int, n: int):
    """The `n` arrays of `kind` in a tuple that holds `n` a kind, kind
    after kind (a dense cache, a pool's pages), and that tuple with
    others in their place."""
    return _at(arrays, slice(kind * n, (kind + 1) * n))


def _at(arrays, where: slice):
    """The arrays at `where` of a tuple, and that tuple with others in
    their place."""
    def put(new):
        return arrays[:where.start] + tuple(new) + arrays[where.stop:]
    return arrays[where], put


def _state_rows(arrays, at, row0, b: int):
    """Rows row0 .. row0 + b - 1 of ONE layer (`at` = (j,)) of a state
    kind's carried arrays [layers, rows, ...], and the arrays with these
    rows written back where they lay."""
    rows = tuple(lax.dynamic_slice_in_dim(a[at], row0, b, axis=0)
                 for a in arrays)

    def put(new):
        return tuple(
            lax.dynamic_update_slice(
                a, n.astype(a.dtype)[None],
                at + (row0,) + (0,) * (a.ndim - 2))
            for a, n in zip(arrays, new))
    return rows, put


def unpaged_layers(contract) -> str:
    """The layers of a contract that do not keep pages of their own, by
    kind of layer and by name: "" for a model all of whose layers do,
    else "layers 0, 2 keep a state a sequence; layers 19, 21 read layer
    17's entries; layers 18, 20 keep and read no cache"."""
    from hetu_tpu.models.cache_contract import NO_CACHE
    by = {}
    for l in range(contract.num_layers):
        r = contract.reads[l]
        what = ("keep a state a sequence" if contract.state_shapes[l]
                is not None else None if r is None else
                "keep and read no cache" if r == NO_CACHE else
                f"read layer {r}'s entries")
        if what:
            by.setdefault(what, []).append(str(l))
    return "; ".join(f"layers {', '.join(ls)} {what}"
                     for what, ls in by.items())


def _refuse_state(model, what: str):
    """The programs over a dense cache that `generate()` runs are built
    for layers that each keep pages of their own: not for a state kind
    of layer, nor for a layer that keeps nothing and reads another's."""
    unpaged = unpaged_layers(cache_contract(model))
    if unpaged:
        raise NotImplementedError(
            f"{type(model).__name__}: {unpaged} (the cache contract's "
            f"state_shapes / reads); {what} is not built for them: the "
            "chunk program (extend_cache) and the paged decode step carry "
            "the state and hand a reading layer the pages it reads")


# ---------------------------------------------------------------------------
# One decoder layer, one walk over the layers
# ---------------------------------------------------------------------------

def _attn_scopes(block):
    """The scopes a layer's attention runs under: `attn` and, inside it,
    the one the block names for its kind of layer (`block.attn_scope`)."""
    stack = contextlib.ExitStack()
    stack.enter_context(jax.named_scope("attn"))
    scope = getattr(block, "attn_scope", None)
    if scope:
        stack.enter_context(jax.named_scope(scope))
    return stack


class _Add:
    """The residual path of a block that brings none: a sublayer reads
    the carry as it is, and its output is added to it under the
    sublayer's own scopes, where the add has always stood (so the
    programs of every such block lower to the text they lowered to)."""

    def __init__(self, block):
        self.block = block

    def residual_pre(self, lp, side, h):
        return h, None

    def residual_post(self, lp, side, mix, h, y):
        with (_attn_scopes(self.block) if side == "attn"
              else jax.named_scope(side)):
            return h + y


def _residual_path(block):
    """The block's pair of residual hooks (`_layer`), or `_Add`."""
    return block if hasattr(block, "residual_pre") else _Add(block)


def _layer(block, lp, h, rope, pos_ids, cache_step, state_step=None,
           handed=None):
    """THE decoder layer of every program below: norm, projection,
    attention over the cache, output and residual, then the MLP and its
    residual, under the scopes a device trace is summed by
    (obs.scope_map: `attn`, `attn/kv_write`, `mlp`).

    THE RESIDUAL PATH IS THE BLOCK'S, by one pair of hooks around each
    of the two sublayers (`side` is "attn" or "mlp", `lp` the layer's
    parameters):

      block.residual_pre(lp, side, carry)  -> (x, mix)
          the sublayer's input x [b, s, hidden] from what is carried
          between layers, and whatever `residual_post` takes
      block.residual_post(lp, side, mix, carry, y) -> carry
          the sublayer's output y folded back into the carry

    Both run OUTSIDE the sublayer's scopes, so that a block's own scopes
    for them (nn/hyper_connections: `mhc_pre`, `mhc_sinkhorn`,
    `mhc_post`) are siblings of `attn` and `mlp` and those keep meaning
    what they mean in every other program.  What is carried is the
    model's business (`embed_tokens` makes it, `final_hidden` takes it):
    [b, s, hidden], or a stream [b, s, n, hidden]; the programs only
    require its first two dimensions.  A block without the hooks has
    `_Add`: x is the carry, and y is added to it.

    cache_step(attn module, its params, q, entries, win) -> (attention
    output [b, s, n_q * hd], *rest) is what a program differs by: where
    the token's entries are written and how the query attends them.
    `win` is the layer's window as the attend hooks take it: {} for a
    layer that reads everything (`block.window` None or absent), else
    {"window": w}; such a block names the scope its attention runs
    under inside `attn` (`block.attn_scope`), so that a trace tells the
    kinds of layer apart.  What `project` returns beyond (q, entries)
    (a gate on the attention's output) is handed to `output`.

    A STATE layer (the walk's caller saw its kind in the contract) is
    given `state_step(attn module, its params, hn) -> (the residual's
    addend [b, s, hidden], what the layer hands on or None, *rest)`
    instead: the model's `state_chunk` or `state_step` hook over the rows
    of the carried state, in place of projection, cache and output.

    A layer that keeps and reads NO cache (the contract's `NO_CACHE`) is
    given neither: its `mix` hook is its whole attention.

    `handed` is what an earlier layer handed on (None where none has): a
    block that says `takes_handed` is given it (`handed=` of `mix`), a
    state block that says `hands_on` replaces it by what its hook returns
    third, every other block passes it on untouched.  The MLP side may
    do both as well: `mlp_takes_handed` gives `mlp_stats` what was handed
    (`handed=`), `mlp_hands_on` replaces it by what `mlp_stats` returns
    third.
    Returns (h, the layer's stats, handed, *rest)."""
    window = getattr(block, "window", None)
    win = {} if window is None else {"window": window}
    path = _residual_path(block)
    x, mix = path.residual_pre(lp, "attn", h)
    with _attn_scopes(block):
        hn = block.input_norm(lp["input_norm"], x)
        if state_step is not None:
            out, new, *rest = state_step(block.attn, lp["attn"], hn)
            if getattr(block, "hands_on", False):
                handed = new
        elif cache_step is None:
            rest = []
            out = block.attn.mix(
                lp["attn"], hn, **({"handed": handed} if getattr(
                    block, "takes_handed", False) else {}))
        else:
            q, entries, *aux = block.attn.project(lp["attn"], hn, rope,
                                                  pos_ids)
            attn, *rest = cache_step(block.attn, lp["attn"], q, entries, win)
            out = block.attn.output(lp["attn"], attn, *aux)
    h = path.residual_post(lp, "attn", mix, h, out)
    x, mix = path.residual_pre(lp, "mlp", h)
    with jax.named_scope("mlp"):
        y, st, *new = block.mlp_stats(
            lp["mlp"], block.post_norm(lp["post_norm"], x),
            **({"handed": handed} if getattr(
                block, "mlp_takes_handed", False) else {}))
        if getattr(block, "mlp_hands_on", False):
            (handed,) = new
    h = path.residual_post(lp, "mlp", mix, h, y)
    return (h, st, handed, *rest)


def _walk_layers(model, params, x, state, stats, layer, *, handed=None,
                 layers=None):
    """THE walk over `model.serving_layers(params)`, in the cache's layer
    order.  A run of stacked parameters [n, ...] is scanned, a layer with
    arrays of its own is called.  The walk moves no bytes a layer does
    not read, of the weights or of the cache:

    * the weights.  A scanned layer's are the scan's xs, and a product
      takes its matrix out of the stack [n, h, w] inside its own fusion:
      a stacked run of plain matrices costs the scan's index arithmetic
      (0.008 ms of the 5.94 ms InternLM2 decode program: PERF.md s5).
      A fused weight in the training layout (`[.., 2, I]`, `[.., n_kv,
      g + 2, hd]`) is instead copied out whole, every layer of every
      execution, before the product reads it again (2.82 ms of that
      program before PR 32), which is why a model may hand the serving
      programs a view of its own (`serving_params`).  A family whose
      layers differ gives each its own arrays (models/kimi_k2), at the
      price of a program that grows with the depth, or, where they
      differ with a PERIOD (a state layer, then a window layer, eight
      times), hands a run whose block and parameters are TUPLES, one
      entry a layer of the period, each entry's parameters stacked
      [periods, ...]: the scan's body is then the period's layers one
      after the other, each with its own kind and place in the
      contract, and the program holds one body a distinct layer of the
      period (models/phi4_flash).  The model's parameters say which;
      nothing else does.
    * the cache.  `state`, arrays whose leading dim is the layer (a
      paged pool, a dense cache [L, b, M, ...]), is handed to every
      layer WHOLE with the layer's index, `at` = (l,), and handed on,
      updated in place: `c[at]` reads and `c.at[at + (...)]` writes the
      layer's part.  In a scan it is a loop CARRY, never an xs or a ys,
      which would slice every layer's slab out and stack it back into a
      fresh buffer (three full-pool copies a step: PERF.md s6, PR 25;
      the chunk program's dense scratch read and written whole to
      change one chunk: PR 32).  A caller that donates it gets its own
      buffers back.

    layer(block, lp, h, state, at, page_at, handed) -> (h, stats of the
    layer, state, out, handed).  `at` = (l,) is the layer among all
    layers; `page_at` = (kind, (j,)) is the layer's kind by the cache
    contract (layers that read as far back and store the same shapes are
    one kind, with cache arrays, page arrays and a page table of their
    own) and its place j among the layers of that kind (the leading dim
    of a dense cache's and of a paged pool's arrays, and of a state
    kind's state arrays: the state kinds are numbered behind the kinds
    that hold pages, and `contract.is_state(kind)` is how a program's
    `layer` knows to give `_layer` a `state_step`); with one kind j is l.
    A layer that stores NOTHING and reads another layer's entries (the
    contract's `reads`) is handed the kind and place of THAT layer, and
    hands nothing out; one that reads nobody's is handed `page_at` None.
    `handed` is the activation a layer hands on to later layers beside
    h (`_layer`), a carry of a scanned run like h.  `out` is
    whatever a layer hands out besides (a token's entries for a paged
    pool to scatter; None): stacked over the layers of a kind, and with
    several kinds the kinds' tuples one after the other, as a dense
    cache lays its arrays out.  `layers` = (lo, hi) walks only the runs
    that lie in layers lo .. hi - 1 (a run may not straddle an end).
    Returns (x, stats, state, out, handed).  The caller opens the
    `layer` scope (a trace's name for the stack: obs.scope_map) around
    the walk and what it does to the state before and after."""
    l0 = 0
    contract = cache_contract(model)
    lo, hi = layers or (0, contract.num_layers)
    outs = [[] for _ in contract.kinds]      # (of the kinds with pages)

    def add(stats, st):
        return stats if stats is None else model.add_stats(stats, st)

    def page_at(l: int):
        kind = contract.kind_of(l)
        return kind, (None if kind is None else contract.place_of(l))

    def note(l: int, out):
        """A storing page layer's `out`, in the layer order of its
        kind."""
        kind = contract.kind_of(l)
        if contract.stores(l) and not contract.is_state(kind):
            outs[kind].append(out)

    for block, lp, count in model.serving_layers(params):
        n = (count or 1) * (len(block) if isinstance(block, tuple) else 1)
        first, l0 = l0, l0 + n
        if first >= hi or l0 <= lo:
            continue
        if first < lo or l0 > hi:
            raise ValueError(f"layers {lo}..{hi - 1} cut the run of layers "
                             f"{first}..{l0 - 1}")
        if count is None:
            kind, j0 = page_at(first)
            x, st, state, out, handed = layer(
                block, lp, x, state, (jnp.int32(first),),
                None if kind is None else (kind, (jnp.int32(j0),)), handed)
            stats = add(stats, st)
            note(first, jax.tree.map(lambda a: a[None], out))
        elif isinstance(block, tuple):
            # a period of layers, scanned over the periods: layer p of
            # period i is layer first + i * P + p, of the kind of layer
            # first + p, at a place that advances evenly with i
            P = len(block)
            where = []
            for p in range(P):
                at = [page_at(first + i * P + p) for i in range(count)]
                kind, j0 = at[0]
                step = at[1][1] - j0 if count > 1 and kind is not None else 0
                if any(a != (kind, None if kind is None else j0 + i * step)
                       for i, a in enumerate(at)):
                    raise ValueError(
                        f"layer {p} of the period at layer {first} changes "
                        f"its kind or steps unevenly over the periods: {at}")
                where.append((kind, j0, step))

            def body(carry, xs, block=block, where=where, first=first, P=P):
                h, state, stats, handed = carry
                lps, i = xs
                got = []
                for p, (blk, lp, (kind, j0, step)) in enumerate(
                        zip(block, lps, where)):
                    h, st, state, out, handed = layer(
                        blk, lp, h, state, (first + i * P + p,),
                        None if kind is None else
                        (kind, (j0 + i * step if step else jnp.int32(j0),)),
                        handed)
                    stats = add(stats, st)
                    got.append(out)
                return (h, state, stats, handed), tuple(got)

            (x, state, stats, handed), got = lax.scan(
                body, (x, state, stats, handed),
                (tuple(lp), jnp.arange(count, dtype=jnp.int32)))
            paged = [contract.kind_of(first + p) for p in range(P)
                     if contract.stores(first + p)
                     and not contract.is_state(contract.kind_of(first + p))]
            if len(set(paged)) < len(paged) and any(
                    g is not None for g in got):
                raise NotImplementedError(
                    "two storing layers of one kind in a period hand "
                    "entries out: their order among the kind's is not kept")
            for p in range(P):
                note(first + p, got[p])
        else:
            kind, j0 = page_at(first)

            def body(carry, xs, block=block, kind=kind, shift=j0 - first):
                h, state, stats, handed = carry
                lp, l = xs
                h, st, state, out, handed = layer(
                    block, lp, h, state, (l,),
                    (kind, (l + shift if shift else l,)), handed)
                return (h, state, add(stats, st), handed), out

            (x, state, stats, handed), out = lax.scan(
                body, (x, state, stats, handed),
                (lp, jnp.arange(first, first + count, dtype=jnp.int32)))
            note(first, out)
    outs = [None if not o else o[0] if len(o) == 1 else jax.tree.map(
        lambda *a: jnp.concatenate(a), *o) for o in outs]
    out = outs[0] if len(outs) == 1 else (
        None if outs[0] is None else sum((tuple(o) for o in outs), ()))
    return x, stats, state, out, handed


# ---------------------------------------------------------------------------
# Dense caches: prefill, the single-token step, the chunk
# ---------------------------------------------------------------------------

def prefill(model, params, input_ids, max_len: int):
    """Run whole prompts [b, plen] through the layers, each attending its
    own entries (`attend_prompt`: the flash path for the K/V kind), and
    return (last_logits [b, vocab], cache): the entries of every layer,
    padded to `max_len` positions (`init_cache`'s arrays)."""
    _check_context_length(model.config, max_len)
    _refuse_state(model, "prefill of whole prompts into a dense cache")
    b, plen = input_ids.shape
    pos_ids = jnp.broadcast_to(jnp.arange(plen, dtype=jnp.int32), (b, plen))
    rope = model.rope_tables(max_len)
    x = model.embed_tokens(params, input_ids, pos_ids)

    def layer(block, lp, h, state, at, page_at, handed):
        h, st, handed, entries = _layer(
            block, lp, h, rope, pos_ids,
            lambda attn, p, q, entries, win: (
                attn.attend_prompt(p, q, entries, **win), entries))
        return h, st, state, entries, handed

    with jax.named_scope("layer"):
        x, _, _, entries, _ = _walk_layers(model, params, x, None, None,
                                           layer)
    logits = model.logits(params, model.final_hidden(params, x))[:, -1, :]
    pad = ((0, 0), (0, 0), (0, max_len - plen))
    return logits, tuple(jnp.pad(e, pad + ((0, 0),) * (e.ndim - 3))
                         for e in entries)


def _cache_write_token(c, e, positions, uniform: bool, at):
    """Write one token's entry e [b, 1, ...] into layer `at` = (l,) of a
    cache array [L, b, M, ...] at `positions`.  Uniform (scalar)
    positions keep the contiguous dynamic_update_slice lowering — the
    generate() hot loop must not pay batched-scatter cost for a
    broadcast index — per-slot vectors scatter per row (the serving
    form)."""
    if uniform:
        return lax.dynamic_update_slice(
            c, e.astype(c.dtype)[None],
            at + (0, positions) + (0,) * (e.ndim - 2))
    return c.at[at + (jnp.arange(e.shape[0]), positions)].set(
        e[:, 0].astype(c.dtype))


def decode_step_slots(model, params, tokens, cache, positions):
    """One token step with PER-SLOT positions (each batch row is an
    independent sequence at its own depth) over a dense cache
    (`init_cache`'s arrays): `generate()`'s step, and with
    `PagePool.gather` the tests' reference of `decode_step_paged`; no
    program of the serving engine calls it.

    tokens: [b] int32; positions: [b] int32 (this token's absolute
    position per slot) — or a scalar, which keeps the contiguous
    dynamic_update_slice cache lowering for the uniform-position
    generate() hot loop.  Returns (logits [b, vocab], new_cache, token
    entries): THIS step's entries per layer, one array [L, b, *stored
    shape] per array of the cache ((k_toks, v_toks) for the K/V kind;
    with several kinds of layer a kind's after a kind's, as the cache
    is laid out) — a paged cache scatters them into its pool instead of
    carrying the dense cache."""
    _refuse_state(model, "the decode step over a dense cache")
    b = tokens.shape[0]
    uniform = jnp.ndim(positions) == 0
    pos_ids = (jnp.broadcast_to(positions, (b, 1)) if uniform
               else positions[:, None])
    rope = model.rope_tables(cache[0].shape[2])
    n = len(cache_contract(model).token_shapes)
    x = model.embed_tokens(params, tokens[:, None], pos_ids)

    def layer(block, lp, h, cache, at, page_at, handed):
        kind, at = page_at
        mine, put = _of_kind(cache, kind, n)

        def step(attn, p, q, entries, win):
            new = tuple(_cache_write_token(c, e, positions, uniform, at)
                        for c, e in zip(mine, entries))
            return (attn.attend_dense(p, q, tuple(c[at] for c in new),
                                      positions, **win),
                    put(new), tuple(e[:, 0] for e in entries))
        h, st, handed, cache, toks = _layer(block, lp, h, rope, pos_ids,
                                            step)
        return h, st, cache, toks, handed

    with jax.named_scope("layer"):
        x, _, cache, toks, _ = _walk_layers(model, params, x, tuple(cache),
                                            None, layer)
    logits = model.logits(params, model.final_hidden(params, x))[:, 0, :]
    return logits, cache, toks


def decode_step(model, params, token, cache, pos):
    """One token step. token: [b] int32; pos: scalar current position.
    Returns (logits [b, vocab], new_cache).  Delegates to the slot-masked
    form; the scalar position keeps the contiguous cache-update
    lowering."""
    logits, new_cache, _ = decode_step_slots(
        model, params, token, cache, jnp.asarray(pos, jnp.int32))
    return logits, new_cache


def extend_cache(model, params, tokens, cache, start, stats=None, *,
                 collect_token_kv: bool = False, slide: bool = False,
                 max_len: Optional[int] = None, state_row=0, valid=None,
                 read_row=None):
    """Advance a dense cache by a whole token block (chunked prefill).

    tokens: [b, C] int32 at absolute positions start..start+C-1 (start
    scalar or [b]); the chunk's entries are written into the cache, in
    place where the caller donates it (the cache is a carry of the layer
    walk: a chunk changes C tokens a layer and moves no others), and
    each query attends causally over cache[:start+i+1].  Returns
    (logits [b, C, vocab], new_cache).  Running consecutive chunks
    through this is numerically the incremental form of `prefill` — the
    serving engine uses it so one long prompt never stalls the decode
    batch (docs/serving.md).

    ``slide=True`` (the engine's chunk program: ONE row, consecutive
    chunks of C tokens from position 0 on): a window kind's arrays hold
    M = `init_cache(.., chunk=C)`'s positions, not `max_len`, and SLIDE:
    they hold the positions base .. base + M - 1 with base = max(0,
    start - (M - C)), the M - C positions before the chunk and the
    chunk.  At each launch the M - C positions that stay are moved down
    by what base advanced since the launch before (base(start) -
    base(start - C), at most C), the chunk is written behind them, and
    the queries attend the array with `first=base`.  A kind that reads
    everything keeps `max_len` positions and moves nothing; `max_len`
    (for the rotation tables) is then that kind's length, and must be
    given where every kind slides.

    **A model with STATE layers** (the contract's `state_shapes`): the
    state arrays [layers of the kind, rows, *shape] follow the page
    kinds' arrays in `cache` (the engine hands in the pool's own,
    `serving/kv_pool.PagePool.state`, donated with the scratch) and are carried, read and
    written in place like them: the b rows from `state_row` on are the
    sequences' (the engine: one row, the slot's).  `valid` [b] (default
    C) is how many of a row's C tokens are the prompt's: the rows past it
    are the chunk's padding, which a page layer's causal mask keeps from
    the rows before them and which a state layer must not take into its
    state (`state_chunk`).  A row whose chunk starts at position 0 starts
    from ZERO state, whatever the arrays held: a slot is reset by its
    next prompt's first chunk and by nothing else.

    **Rows whose logits nobody reads** (``read_row``, a traced scalar:
    the ONE row of every sequence's chunk whose logits the caller reads,
    or negative: none).  The final norm and the head then run for that
    row alone, inside a `lax.cond` on read_row >= 0 (a chunk that does
    not end its prompt runs neither), and the call returns logits
    [b, 1, vocab], zeros where read_row < 0: the head's scope stays
    `lm_head` at the program's top level.  A model all of whose layers
    from some layer k on keep no cache of their own says so
    (`model.read_rows_from` = k: layer k itself holds pages, every later
    layer reads another's or none; the default is the number of layers:
    every layer stores or carries what later rows need, so every layer
    runs for every row): those layers too are then worth running only
    where logits are read.  The walk runs layers 0 .. k - 1 for every
    row, layer k's norm, projection and cache write for every row, and
    inside the same conditional, under the scope `layer/tail`, layer k's
    attention, output and MLP and layers k + 1 .. for that row alone, one
    query at position start + read_row over the cache as the chunk left
    it.  A call without `read_row` (`verify_step_slots`,
    serving/disagg.py) runs every layer and the head for every row and
    returns [b, C, vocab].

    ``collect_token_kv=True`` (`verify_step_slots`, the tests'
    reference of `verify_step_paged`: no program of the engine asks) also
    returns the chunk's entries per layer ((k, v) [L, b, C, n_kv, hd])
    so a paged cache can scatter them into its pool; given the running
    `stats` vector of a model that counts (`model.STATS`), the advanced
    one is returned last."""
    b, C = tokens.shape
    rows = jnp.arange(b)
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
    qpos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # [b, C]
    contract = cache_contract(model)
    n = len(contract.token_shapes)
    n_paged = n * len(contract.kinds)
    rope = model.rope_tables(
        max_len or max(c.shape[2] for c in cache[:n_paged]))
    if contract.state_kinds:
        valid = jnp.broadcast_to(
            jnp.asarray(C if valid is None else valid, jnp.int32), (b,))
    # the scopes the training programs carry, so that a device trace of
    # the serving programs is summed under the same names (obs.scope_map)
    with jax.named_scope("embed"):
        x = model.embed_tokens(params, tokens, qpos)

    # the layer from whose attention on only the read row is computed:
    # the model's, or no layer (the norm and the head alone)
    stop = None if read_row is None else getattr(
        model, "read_rows_from", contract.num_layers)

    def layer_at(start, qpos, write: bool = True):
        """`_walk_layers`'s layer for queries at `qpos` [b, s], the
        first at `start` [b].  `write` False (the tail: the rows' entries
        are in the cache): a layer attends and writes nothing."""
        def layer(block, lp, h, cache, at, page_at, handed):
            if page_at is None:     # keeps and reads no cache
                h, st, handed = _layer(block, lp, h, rope, qpos, None,
                                       handed=handed)
                return h, st, cache, None, handed
            kind, at = page_at
            if contract.is_state(kind):
                if not write:
                    raise NotImplementedError(
                        "a state layer behind `read_rows_from`: its state "
                        "needs every row")
                arrays, put_kind = _at(cache, contract.arrays_of(kind))

                def state_step(attn, p, hn):
                    mine, put = _state_rows(arrays, at, state_row, b)
                    fresh = start == 0
                    mine = tuple(
                        jnp.where(fresh.reshape((b,) + (1,) * (a.ndim - 1)),
                                  jnp.zeros((), a.dtype), a) for a in mine)
                    out, new, *more = attn.state_chunk(p, hn, mine, start,
                                                       valid)
                    return (out, more[0] if more else None,
                            put_kind(put(new)), None)
                h, st, handed, cache, out = _layer(
                    block, lp, h, rope, qpos, None, state_step, handed)
                return h, st, cache, out, handed
            mine, put = _of_kind(cache, kind, n)
            sliding = slide and getattr(block, "window", None) is not None

            def step(attn, p, q, entries, win):
                # (a layer that reads another layer's entries projects
                # none of its own: nothing to write)
                where, first, new = qpos, {}, mine
                if write and entries:
                    with jax.named_scope("kv_write"):
                        if sliding:
                            keep = mine[0].shape[2] - C
                            base = jnp.maximum(start[0] - keep, 0)
                            shift = base - jnp.maximum(
                                start[0] - C - keep, 0)
                            new = tuple(
                                lax.dynamic_update_slice(
                                    c, lax.dynamic_slice_in_dim(
                                        c[at], shift, keep, axis=1)[None],
                                    at + (0,) * (c.ndim - 1)) if keep else c
                                for c in mine)
                            where, first = qpos - base, {"first": base}
                        new = tuple(
                            c.at[at + (rows[:, None], where)].set(
                                e.astype(c.dtype))
                            for c, e in zip(new, entries))
                elif sliding:
                    raise NotImplementedError(
                        "a window layer that attends a sliding scratch "
                        "without writing its chunk")
                return (attn.attend_dense(p, q, tuple(c[at] for c in new),
                                          start, **win, **first),
                        put(new), entries if collect_token_kv and write
                        and entries else None)
            h, st, handed, cache, out = _layer(block, lp, h, rope, qpos,
                                               step, handed=handed)
            return h, st, cache, out, handed
        return layer

    def entries_only(block, lp, h, cache, at, page_at, handed):
        """Layer `stop` for the rows nobody reads: the norm, the
        projection and the cache write; h goes on as it came."""
        kind, at = page_at
        mine, put = _of_kind(cache, kind, n)
        x, _ = _residual_path(block).residual_pre(lp, "attn", h)
        with _attn_scopes(block):
            entries = block.attn.project(
                lp["attn"], block.input_norm(lp["input_norm"], x), rope,
                qpos)[1]
            with jax.named_scope("kv_write"):
                new = tuple(
                    c.at[at + (rows[:, None], qpos)].set(e.astype(c.dtype))
                    for c, e in zip(mine, entries))
        return (h, None if stats is None else model.zero_stats(), put(new),
                None, handed)

    cache = tuple(cache)
    with jax.named_scope("layer"):
        if stop in (None, contract.num_layers):
            x, stats, cache, chunk, _ = _walk_layers(
                model, params, x, cache, stats, layer_at(start, qpos))
            handed = None               # (no layer is left to take it)
        else:
            if ((slide and contract.windows[stop] is not None)
                    or collect_token_kv
                    or any(contract.stores(l) for l in range(
                        stop + 1, contract.num_layers))):
                raise NotImplementedError(
                    f"read_rows_from {stop}: layer {stop} has to hold pages "
                    "of every position and no later layer may store")
            x, stats, cache, _, handed = _walk_layers(
                model, params, x, cache, stats, layer_at(start, qpos),
                layers=(0, stop))
            x, stats, cache, _, handed = _walk_layers(
                model, params, x, cache, stats, entries_only, handed=handed,
                layers=(stop, stop + 1))
            chunk = None
    if stop is None:
        logits = model.logits(params, model.final_hidden(params, x))
    else:
        def tail(x, handed, stats):
            r = jnp.maximum(jnp.asarray(read_row, jnp.int32), 0)
            x, handed = jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, r, 1, axis=1),
                (x, handed))
            if stop < contract.num_layers:
                at = start + r
                with jax.named_scope("layer"), jax.named_scope("tail"):
                    x, stats, _, _, _ = _walk_layers(
                        model, params, x, cache, stats,
                        layer_at(at, at[:, None], write=False),
                        handed=handed, layers=(stop, contract.num_layers))
            return (model.logits(params, model.final_hidden(params, x)),
                    stats)
        none = jax.eval_shape(tail, x, handed, stats)[0]
        logits, stats = lax.cond(
            jnp.asarray(read_row) >= 0, tail,
            lambda x, handed, stats: (jnp.zeros(none.shape, none.dtype),
                                      stats),
            x, handed, stats)
    return ((logits, cache) + ((chunk,) if collect_token_kv else ())
            + (() if stats is None else (stats,)))


def verify_step_slots(model, params, tokens, cache, positions):
    """The tests' reference of `verify_step_paged` (over
    `PagePool.gather`'s dense views, with `PagePool.write_tokens`); no
    program of the engine calls it.  The speculative-decoding VERIFY
    step: advance every slot by a whole [k+1]-token block in ONE forward
    (serving/spec_decode.py).

    tokens: [S, k+1] int32 — per slot, the last emitted token followed
    by the k draft tokens; positions: [S] int32 — the slot's current
    write position (token i of the block sits at positions[s] + i).
    This is exactly `extend_cache` with PER-SLOT start positions (each
    batch row an independent sequence at its own depth, the
    `decode_step_slots` convention) plus the block's per-layer K/V
    handed out for the paged-pool scatter.

    Returns (logits [S, k+1, vocab], new_cache, (k_chunk, v_chunk))
    with k_chunk/v_chunk [L, S, k+1, n_kv, hd].  logits[:, i] is the
    next-token distribution AFTER input token i — the verification
    targets: greedy acceptance compares draft i+1 against
    argmax(logits[:, i]), bit-identical to what the sequential
    single-token path would have computed at that depth (same
    chunk-causal grouped-GQA attention as chunked prefill — one
    implementation, so spec-decode and sequential decode cannot drift
    numerically)."""
    return extend_cache(model, params, tokens, cache,
                        positions.astype(jnp.int32),
                        collect_token_kv=True)


# ---------------------------------------------------------------------------
# Paged pools: the decode step and the verify step
# ---------------------------------------------------------------------------

def _paged_write(pool, scale, table, positions, t, layer, base, bits):
    """Scatter a block's entries t [S, C, *stored shape], token i of slot
    s at (table[s, (positions[s] + i) // ps], (positions[s] + i) % ps),
    into ONE layer's pages of the carried pool: the payload into the
    flat page array [L * P, ps, ...] at page `base + page` (base =
    l * P, added after any null-page redirect), and for quantized pages
    (`scale` not None: int8, or int4 nibble payloads with ``bits=4``)
    the per-head-vector f32 scale into the layer's plane of
    [L, P, ps, n_kv].  Inactive slots' tables point at the null page
    (the layer's id 0) — their write lands there harmlessly
    (serving/kv_pool.py) — and so do a block's positions past the
    table's reach (the redirect `serving/kv_pool.write_tokens` applies;
    a single token's position never is).  Returns (pool, scale)."""
    S, C = t.shape[:2]
    ps, mp = pool.shape[1], table.shape[1]
    if C == 1:
        t = t[:, 0]
        page, off = table[jnp.arange(S), positions // ps], positions % ps
    else:
        pos = positions[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        pidx = pos // ps
        page = jnp.where(
            pidx < mp,
            table[jnp.arange(S)[:, None], jnp.clip(pidx, 0, mp - 1)], 0)
        off = pos % ps
    if scale is None:
        return pool.at[base + page, off].set(t.astype(pool.dtype)), None
    # the SAME primitives as the page write's and the tests' reference
    # (kv_pool.write_*): pool contents are bit-identical to theirs
    from hetu_tpu.serving.kv_pool import quantize_heads
    q, s = quantize_heads(t.astype(jnp.float32), bits)
    return pool.at[base + page, off].set(q.astype(pool.dtype)), \
        scale.at[layer, page, off].set(s)


def _paged_forward(model, params, tokens, pool_tree, table, positions,
                   stats):
    """A block of C tokens a slot (tokens [S, C], token i at
    positions[s] + i; [S] and C = 1 for the decode step) through the
    layers, attending a paged pool where it lies.

    pool_tree: the pool's arrays as it lays them out
    (`serving/kv_pool.PoolArrays.tree()`): one page array
    [L, P, page_size, *stored shape] per array of the model's cache
    contract, then, for quantized pages (int8, or uint8 nibble pairs of
    int4: the K/V kind only), a plane of per-head-vector f32 scales
    [L, P, page_size, n_kv] for each.  A model whose layers differ in
    how far back they read (the contract's `windows`) has one such set
    of page arrays a KIND of layer, one after the other in the tree,
    each [layers of the kind, pages of the kind + 1, ...], and `table`
    is [kinds, S, max_pages]: a layer writes and reads its kind's pages
    through its kind's table, at its place among the layers of its
    kind; exact pages only.  The block's entries are scattered
    into each slot's pages BEFORE the query attends (write-then-attend:
    the token sees itself, exactly like the dense path).

    The pool is carried whole through the layers and written in place
    (`_walk_layers`): a caller that donates it (the engine does) gets
    its own buffers back, with no second pool among the program's
    temporaries.  The page arrays are carried as their flat views
    [L * P, ps, ...] (a bitcast): layer l's page p is page l * P + p,
    so with `table + l * P` as its page table the same scatter and the
    same kernel walk the same bytes, and l * P is the layer's null
    page.  The scale planes stay [L, P, ps, n_kv]: the kernel reads a
    page's scales as a block of a page-major array whose rows are padded
    to 128 lanes, which is not how the planes are stored, so it is
    handed ONE layer's plane, `scale[l]`, to read by the engine's own
    page ids (`scale_table`), and what is converted for it is P pages a
    layer, never L * P.

    Returns (final-norm hidden [S, C, hidden], pool tree, stats)."""
    positions = positions.astype(jnp.int32)
    table = table.astype(jnp.int32)
    C = 1 if tokens.ndim == 1 else tokens.shape[1]
    contract = cache_contract(model)
    n, K = len(contract.token_shapes), len(contract.kinds)
    # one page table a kind: [S, max_pages], or [K, S, max_pages]
    tables = (table,) if table.ndim == 2 else tuple(table)
    n_state = sum(len(s) for s in contract.state_kinds)
    pool_tree = tuple(pool_tree)
    pool_tree, states = (pool_tree[:len(pool_tree) - n_state],
                         pool_tree[len(pool_tree) - n_state:])
    pools, scales = pool_tree[:n * K], pool_tree[n * K:]
    if (n_state or contract.borrows) and (C > 1 or scales):
        raise NotImplementedError(
            "a block of tokens a slot (the verify step) and quantized "
            "pages are not built for a model with state layers or layers "
            f"that keep no cache ({unpaged_layers(contract)})")
    # a slot is live where it holds a page
    live = jnp.any(tables[0] != 0, axis=-1) if n_state else None
    if len(tables) != K or (scales and K > 1):
        raise ValueError(
            f"{K} kinds of layer need {K} page tables and exact pages, "
            f"got {len(tables)} and {len(scales)} scale planes")
    ps = pools[0].shape[2]
    pages = tuple(pools[k * n].shape[1] for k in range(K))  # +1: the null
    quant = (None if not scales
             else "int4" if pools[0].dtype == jnp.uint8 else "int8")
    bits = 4 if quant == "int4" else 8
    pos_ids = positions[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    with jax.named_scope("embed"):
        x = model.embed_tokens(
            params, tokens[:, None] if tokens.ndim == 1 else tokens, pos_ids)
    rope = model.rope_tables(table.shape[-1] * ps)

    def layer(block, lp, h, state, at, page_at, handed):
        flats, scales, states = state
        if page_at is None:         # keeps and reads no cache
            h, st, handed = _layer(block, lp, h, rope, pos_ids, None,
                                   handed=handed)
            return h, st, state, None, handed
        kind, (l,) = page_at
        if contract.is_state(kind):
            arrays, put_kind = _at(states, contract.state_arrays_of(kind))

            def state_step(attn, p, hn):
                mine, put = _state_rows(arrays, (l,), 0, hn.shape[0])
                out, new, *more = attn.state_step(p, hn, mine, live)
                return (out, more[0] if more else None,
                        (flats, scales, put_kind(put(new))))
            h, st, handed, state = _layer(block, lp, h, rope, pos_ids, None,
                                          state_step, handed)
            return h, st, state, None, handed
        flat, tbl = flats[kind], tables[kind]
        base = l * pages[kind]

        def step(attn, p, q, entries, win):
            if not entries:
                # a layer that reads another layer's entries: that
                # layer's pages, through that layer's table, as they lie
                return (attn.attend_paged(p, q, flat, tbl, positions, base,
                                          **win), state)
            with jax.named_scope("kv_write"):
                new = [_paged_write(pool, sc, tbl, positions, e, l, base,
                                    bits)
                       for pool, sc, e in zip(flat, scales or (None,) * n,
                                              entries)]
            flat_, scales_ = (tuple(w[0] for w in new),
                              tuple(w[1] for w in new) if scales else ())
            quantized = (dict(scales=scales_, layer=l, quant=quant)
                         if scales else {})
            return (attn.attend_paged(p, q, flat_, tbl, positions, base,
                                      **quantized, **win),
                    (flats[:kind] + (flat_,) + flats[kind + 1:], scales_,
                     states))
        h, st, handed, state = _layer(block, lp, h, rope, pos_ids, step,
                                      handed=handed)
        return h, st, state, None, handed

    with jax.named_scope("layer"):
        flats = tuple(
            tuple(p.reshape((p.shape[0] * p.shape[1],) + p.shape[2:])
                  for p in pools[k * n:(k + 1) * n]) for k in range(K))
        x, stats, (flats, scales, states), _, _ = _walk_layers(
            model, params, x, (flats, scales, states), stats, layer)
        pools = tuple(f.reshape(p.shape)
                      for f, p in zip(sum(flats, ()), pools))
    return model.final_hidden(params, x), pools + scales + states, stats


def decode_step_paged(model, params, tokens, pool_tree, table, positions,
                      stats=None):
    """One decode step over a paged pool where it lies: the serving
    engine's decode program, for every cache kind.  Each layer scatters
    the token's entries into the slot's page and attends by its own
    `attend_paged` (a kernel that walks the page table,
    ops/pallas/paged_attention, or the composition over the slot's
    gathered pages: cache_contract.KVAttention says which and why).

    tokens: [S] int32; pool_tree: the pool's arrays (`_paged_forward`
    says which), page 0 of a layer the null page; table: [S, max_pages]
    int32; positions: [S] int32 — slot s's current token sits at
    positions[s] and attends over everything at or before it.  Returns
    (logits [S, vocab], the updated pool tree) and, given the running
    `stats` vector of a model that counts, the advanced one last."""
    hidden, pool_tree, stats = _paged_forward(
        model, params, tokens, pool_tree, table, positions, stats)
    logits = model.logits(params, hidden)[:, 0, :]
    return (logits, pool_tree) + (() if stats is None else (stats,))


def verify_step_paged(model, params, tokens, pool_tree, table, positions,
                      *, return_hidden: bool = False):
    """The speculative VERIFY step over a paged KV pool where it lies:
    the engine's verify program (ops/pallas/paged_attention.paged_verify
    where a layer's `attend_paged` takes the kernel: all k+1 query
    positions walk the slot's pages in one launch with per-position
    causal masks; else the composition over the slot's gathered
    pages).

    tokens: [S, C] int32 (last emitted token + k drafts per slot);
    positions: [S] int32 — token i of the block sits at positions[s]+i;
    the pool tree as `decode_step_paged`.  Returns
    (logits [S, C, vocab], the updated pool tree).

    ``return_hidden=True`` returns the final-norm HIDDEN states
    [S, C, hidden] instead of logits — the fused sampling epilogue
    (serving/sampling.sample_hidden_grid) consumes them directly so the
    [S, C, vocab] logits plane never materializes in HBM."""
    hidden, pool_tree, _ = _paged_forward(model, params, tokens, pool_tree,
                                          table, positions, None)
    return (hidden if return_hidden else model.logits(params, hidden),
            pool_tree)


def generate(model, params, input_ids, *, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None,
             eos_token_id: Optional[int] = None,
             pad_token_id: Optional[int] = None):
    """Autoregressive generation (greedy when temperature == 0; top_k
    and/or top_p (nucleus) filtering when sampling).
    input_ids: [b, plen] int32 -> [b, plen + max_new_tokens].

    EOS handling: with eos_token_id (alias: eos_id) set, a sequence that
    emits EOS is done — it keeps emitting `pad_token_id` (default: the
    EOS id itself, the pre-serving behavior) and, once EVERY sequence in
    the batch is done, the remaining scan iterations skip the decode
    computation entirely via lax.cond (the same active-mask early-exit
    the serving scheduler uses per slot)."""
    b, plen = input_ids.shape
    max_len = plen + max_new_tokens
    # context-length validation happens in prefill (_check_context_length)
    logits, cache = prefill(model, params, input_ids, max_len)
    rng = rng if rng is not None else jax.random.key(0)
    eos = eos_token_id if eos_token_id is not None else eos_id
    fill = pad_token_id if pad_token_id is not None else eos

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k is not None or (top_p is not None and top_p > 0.0):
            # ONE descending full-vocab sort serves both filters (the sort
            # is the sampler's dominant cost inside the decode scan)
            desc = jnp.sort(logits, axis=-1)[:, ::-1]
        if top_k is not None:
            kth = desc[:, top_k - 1][:, None]
            logits = jnp.where(logits < kth, -1e30, logits)
        if top_p is not None and top_p > 0.0:
            # nucleus: keep the smallest prefix of the sorted distribution
            # whose mass exceeds top_p; the max-prob token always survives
            # (its preceding mass is 0 < top_p), so small top_p degenerates
            # to greedy.  top_p in (None, 0.0) = filter disabled.  With
            # top_k set, the nucleus is computed over the RENORMALIZED
            # top-k distribution (HF semantics: top_k filters first); the
            # filtered descending view is just the top-k prefix of `desc`,
            # so no second sort is needed.
            desc_f = desc if top_k is None else jnp.where(
                jnp.arange(desc.shape[-1]) < top_k, desc, -1e30)
            probs = jax.nn.softmax(desc_f, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep = cum - probs < top_p          # mass BEFORE this token
            cutoff = jnp.min(jnp.where(keep, desc_f, jnp.inf),
                             axis=-1, keepdims=True)
            logits = jnp.where(logits < cutoff, -1e30, logits)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    def step(carry, i):
        logits, cache, key, done = carry
        key, sub = jax.random.split(key)
        tok = sample(logits, sub)
        if eos is not None:
            tok = jnp.where(done, fill, tok)
            done = done | (tok == eos)
            # all sequences finished -> skip the whole decode computation
            # (a real branch under the scan: only the taken side runs)
            logits, cache = lax.cond(
                jnp.all(done),
                lambda c: c,
                lambda c: decode_step(model, params, tok, c[1], plen + i),
                (logits, cache))
        else:
            logits, cache = decode_step(model, params, tok, cache, plen + i)
        return (logits, cache, key, done), tok

    done0 = jnp.zeros((b,), bool)
    (_, _, _, _), toks = lax.scan(
        step, (logits, cache, rng, done0), jnp.arange(max_new_tokens))
    return jnp.concatenate([input_ids, toks.T], axis=1)
