"""What the check of the DeepSeek-V3.2 cell can tell apart, at the timed
sizes (the cell's own configuration file: run it on the chip; a tiny
configuration runs on the CPU).  Nothing here is run by the benchmark or
by the tests: it is how the readings of PERF.md s6 and of the
configuration's `assumed` are made again.

    python3 benchmarks/tests/controls_deepseek_v32.py controls --seed N
    python3 benchmarks/tests/controls_deepseek_v32.py boundary --seed N \
        --length 16384 --stride 8 [--embed-scale 0.02]
    python3 benchmarks/tests/controls_deepseek_v32.py scorer --seed N

`controls`: the first `check_requests` requests of the cell's traffic
plan are served by `ServingEngine` (the cell's slots, pages and chunk)
and each stream is held by the HARNESS'S comparison,
`reference.check_stream`, to the family's reference and to the reference
with one thing done wrongly (`families/deepseek_v32.CONTROLS`), and to
the sound reference over the weights rounded to e4m3 (the nearest
precision below the configuration's).  `correct` is what `run.py` says of
a run: every stream ok.  Exit 0 where the sound reference is `correct`
and no control is.

`boundary`: where a sound program's largest gaps come from.  One sequence
through the program's chunk launches; the reference's logits at every
`stride`-th row; then (1) for the rows of largest gap, how many positions
the program's selection of LAYER 0 (both sides read the same embedding
rows there) swaps against the reference's, and (2) the program again with
the REFERENCE'S selected sets fed to every layer's attention in place of
its own: the gaps that remain are not the selection's.  `--embed-scale`
multiplies the embedding to the given std (the configuration file says
which it was made with).

`scorer`: the program's scorer alone (`ops/sparse_attention.index_scores`,
one launch's rows at the cell's chunk against 8k, 16k and 32k visible
keys), many dispatches behind one another so that the host's time to
dispatch one hides behind the device's: seconds a 1,024 x 1,024 block and
the share of the chip's peak, for `dsv32.indexer_score_roofline`.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import reference, traffic  # noqa: E402
from benchmarks.families import deepseek_v32 as fam  # noqa: E402

F32 = jnp.float32


def say(**record):
    print(json.dumps(record), flush=True)


def build(args):
    cfg = traffic.load_json("configs", args.config)
    model = fam.build_model(cfg, cfg["serving"])
    params = jax.jit(model.init)(jax.random.key(traffic.jax_seed(args.seed)))
    return cfg, model, params


def round_to_e4m3(leaves: list):
    """Every matrix of the list rounded to e4m3 with one scale a tensor,
    in place and a leaf at a time (two copies of the weights do not fit
    the chip beside the reference: the caller holds no other)."""
    for i, a in enumerate(leaves):
        if a.ndim >= 2:
            a32 = a.astype(F32)
            scale = jnp.max(jnp.abs(a32)) / 448.0
            leaves[i] = ((a32 / scale).astype(jnp.float8_e4m3fn).astype(F32)
                         * scale).astype(a.dtype)
        del a


# ---------------------------------------------------------------------------
# controls
# ---------------------------------------------------------------------------

def serve(cfg, model, params, plan):
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.engine import ServingEngine
    from hetu_tpu.serving.request import Request
    engine = ServingEngine(model, params, fam.serve_config(cfg),
                           registry=MetricsRegistry())
    for pr in plan:
        engine.submit(Request(rid=pr.rid, prompt=pr.prompt,
                              max_new_tokens=pr.max_new, arrival_t=0.0))
    tokens = {}
    while len(tokens) < len(plan):
        for r in engine.step(0.0):
            tokens[r.rid] = list(r.tokens)
    engine.close()
    del engine
    gc.collect()
    return tokens


def controls(args):
    cfg, model, params = build(args)
    tf = traffic.load_traffic(args.traffic)
    n = args.streams or int(tf.get("check_requests", 4))
    plan = traffic.plan_requests(tf, args.seed, cfg["vocab_size"],
                                 count=n)[:n]
    tokens = serve(cfg, model, params, plan)
    del model
    names = [c for c in args.controls.split(",") if c]
    correct = {}
    for name in ["sound"] + names:
        forward = fam.logits_at if name in ("sound", "e4m3") else \
            functools.partial(fam.logits_at, control=name)
        if name == "e4m3":                      # the last: `params` goes
            leaves, tree = jax.tree.flatten(params)
            del params
            round_to_e4m3(leaves)
            params = jax.tree.unflatten(tree, leaves)
            del leaves
        # a wrong-thing control is NOT correct with its first stream that
        # is not ok: `--control-streams` bounds how many it is shown
        some = plan[:args.control_streams or n] if name in fam.CONTROLS \
            else plan
        streams = [dict(prompt=len(pr.prompt), **reference.check_stream(
            forward, params, cfg, pr.prompt, tokens[pr.rid],
            cfg["serving"]["max_len"])) for pr in some]
        correct[name] = all(s["ok"] for s in streams)
        say(control=name, correct=correct[name], streams=streams)
    ok = correct.pop("sound") and not any(correct.values())
    say(ok=ok, controls_correct=correct, seed=args.seed, config=args.config,
        traffic=args.traffic)
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# boundary
# ---------------------------------------------------------------------------

def reference_sets(params, ids, cfg):
    """The reference's selected sets of every layer, a bit a position
    [layers, s, s / 8]: `fam.hidden_states`' walk by the family's own
    functions, with each layer's selection kept."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(ids.shape[0])
        x = params["model"]["embed"]["weight"][ids].astype(F32)
        sets = []
        for lp, moe in fam._layers(params):
            ap = lp["attn"]
            hn = fam._rms_norm(x, lp["input_norm"]["weight"], eps)
            ent = fam.entries(hn, pos, ap, cfg)
            cq = fam._rms_norm(hn @ ap["wq_a"].astype(F32),
                               ap["q_norm"]["weight"], eps)
            sets.append(fam._by_blocks(
                lambda cq_b, h_b, at, ap=ap, ent=ent: fam._pack(fam.selected(
                    fam.index_scores(cq_b, h_b, at, ent[2], ap["indexer"],
                                     cfg), at, cfg)),
                fam.Q_BLOCK, cq, hn, pos))
            x = x + fam.attend(hn, pos, *ent, ap, cfg, whole=True)
            x = x + fam._by_blocks(
                lambda hb, lp=lp, moe=moe: fam._mlp(hb, lp, cfg, moe),
                fam.ROW_BLOCK, x)
        return jnp.stack(sets)


def program_layer0_sets(model, params, ids, rows):
    """The selection of the PROGRAM's layer 0 for the queries at `rows`,
    bool [len(rows), s]: its own projections, scores and bisection."""
    s = ids.shape[0]
    block, lp, _ = model.serving_layers(params)[0]
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    hn = block.input_norm(lp["input_norm"],
                          model.embed_tokens(params, ids[None], pos))
    q, (_, keys) = block.attn.project(lp["attn"], hn, model.rope_tables(s),
                                      pos)
    take = lambda a: a[:, rows]  # noqa: E731
    return block.attn._keep(tuple(map(take, q)), keys, pos[:, rows])[0]


def boundary(args):
    from hetu_tpu.models import generation as gen
    from hetu_tpu.models.deepseek_v32 import model as dsm
    from hetu_tpu.models.kimi_k2.model import MLAttention
    cfg, model, params = build(args)
    S, C = args.length, cfg["serving"]["prefill_chunk"]
    made_with = cfg.get("embed_initializer_range") or cfg.get(
        "initializer_range", 0.02)
    if args.embed_scale and args.embed_scale != made_with:
        emb = params["model"]["embed"]["weight"]
        params = dict(params, model=dict(params["model"], embed={"weight": (
            emb.astype(F32) * (args.embed_scale / made_with)).astype(
                emb.dtype)}))
    ids = traffic.rng_for(args.seed, "ids").integers(
        0, cfg["vocab_size"], size=S).astype(np.int32)
    rows = np.arange(args.stride - 1, S, args.stride).astype(np.int32)
    contract = model.cache_contract()
    feed = {}

    def fed_attend_dense(self, params, q, caches, start):
        """`DSAttention.attend_dense` under the set it is GIVEN."""
        keep = fam._unpack(feed["bits"][feed["layer"]], caches[1].shape[1])
        feed["layer"] += 1
        with jax.named_scope("dsa_attend"):
            return MLAttention.attend_dense(self, params, q[:2], caches[:1],
                                            start, keep=keep[None])

    def launch(p, t, cache, start, bits=None):
        feed.update(layer=0, bits=bits)
        return gen.extend_cache(model, p, t, cache, start,
                                model.zero_stats())[:2]

    def program_tokens(bits=None):
        own = dsm.DSAttention.attend_dense
        if bits is not None:
            dsm.DSAttention.attend_dense = fed_attend_dense
        try:
            chunk = jax.jit(launch, donate_argnums=2)
            cache = tuple(jnp.zeros((contract.num_layers, 1, S) + s,
                                    jnp.bfloat16)
                          for s in contract.stored_shapes)
            out = []
            for s in range(0, S, C):
                extra = () if bits is None else (bits[:, s:s + C],)
                lg, cache = chunk(params, jnp.asarray(ids[None, s:s + C]),
                                  cache, jnp.int32(s), *extra)
                out.append(np.asarray(jnp.argmax(
                    lg[0, args.stride - 1::args.stride], -1)))
            return np.concatenate(out)
        finally:
            dsm.DSAttention.attend_dense = own

    def held(tokens, lg):
        top = lg.max(-1)
        gaps = top - lg[np.arange(len(tokens)), tokens]
        tols = np.asarray([reference.logit_gap_tolerance(m) for m in top])
        return gaps, {"rows": len(tokens), "over": int((gaps > tols).sum()),
                      "max_gap": float(gaps.max()),
                      "p99_gap": float(np.quantile(gaps, 0.99)),
                      "argmax_pct": float(100 * (lg.argmax(-1)
                                                 == tokens).mean())}

    own_tokens = program_tokens()
    lg = np.asarray(jax.jit(lambda p, i, r: fam.logits_at(p, i, r, cfg))(
        params, jnp.asarray(ids), jnp.asarray(rows)))
    gaps, own = held(own_tokens, lg)
    say(program="its own selection", embed_scale=args.embed_scale
        or made_with, **own)
    bits = jax.jit(lambda p, i: reference_sets(p, i, cfg))(
        params, jnp.asarray(ids))
    fed_gaps, fed = held(program_tokens(bits), lg)
    say(program="the reference's sets fed to every layer", **fed)
    # the rows of largest gap: what layer 0 swapped there
    worst = np.argsort(-gaps)[:args.worst]
    spread = np.arange(0, len(rows), max(1, len(rows) // 256))
    picked = np.concatenate([worst, spread])
    got = np.asarray(jax.jit(lambda p, i, r: program_layer0_sets(
        model, p, i, r))(params, jnp.asarray(ids), jnp.asarray(rows[picked])))
    want = np.asarray(fam._unpack(bits[0][rows[picked]], S))
    swapped = (got & ~want).sum(-1)
    assert ((want & ~got).sum(-1) == swapped).all()
    say(layer0_swaps_of_selected={
        "over_256_rows_spread": {"mean": float(swapped[len(worst):].mean()),
                                 "max": int(swapped[len(worst):].max())},
        "worst_rows": [{"position": int(rows[i]), "gap": round(float(
            gaps[i]), 3), "gap_fed": round(float(fed_gaps[i]), 3),
            "selected": int(want[k].sum()), "swapped": int(swapped[k])}
            for k, i in enumerate(worst)]})
    return 0


def scorer(args):
    import time
    from benchmarks import peaks
    from hetu_tpu.ops import sparse_attention as dsa
    cfg = traffic.load_json("configs", args.config)
    C, M = cfg["serving"]["prefill_chunk"], cfg["serving"]["max_len"]
    H, D = cfg["index_n_heads"], cfg["index_head_dim"]
    peak = peaks.peaks_for(jax.devices()[0].device_kind)["flops_per_s"] \
        if jax.default_backend() == "tpu" else None
    k = jax.random.split(jax.random.key(traffic.jax_seed(args.seed)), 3)
    q = jax.random.normal(k[0], (1, C, H, D), jnp.bfloat16)
    w = jax.random.normal(k[1], (1, C, H), F32)
    keys = jax.random.normal(k[2], (1, M, D), jnp.bfloat16)
    fn = jax.jit(dsa.index_scores)
    for seen in (8192, 16384, 32768):
        if seen > M:
            continue
        qpos = (seen - C + jnp.arange(C, dtype=jnp.int32))[None]
        jax.block_until_ready(fn(q, w, keys, qpos))
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            out = fn(q, w, keys, qpos)
        jax.block_until_ready(out)
        call_s = (time.perf_counter() - t0) / args.repeats
        blocks = seen // 1024 * (C / 1024)
        say(rows=C, keys_seen=seen, call_ms=1e3 * call_s,
            block_1024x1024_ms=1e3 * call_s / blocks,
            pct_of_peak=None if peak is None else 100 * (
                2.0 * H * D * C * seen / call_s / peak))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("controls", "boundary", "scorer"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--config", default="deepseek-v3.2-ep32-depth5")
    ap.add_argument("--traffic", default="sparse-long-context-closed")
    ap.add_argument("--streams", type=int, default=0)
    ap.add_argument("--control-streams", type=int, default=0)
    ap.add_argument("--controls", default=",".join(fam.CONTROLS + ("e4m3",)))
    ap.add_argument("--length", type=int, default=16384)
    ap.add_argument("--stride", type=int, default=8)
    ap.add_argument("--embed-scale", type=float, default=0.0)
    ap.add_argument("--worst", type=int, default=12)
    ap.add_argument("--repeats", type=int, default=20)
    args = ap.parse_args(argv)
    return {"controls": controls, "boundary": boundary,
            "scorer": scorer}[args.what](args)


if __name__ == "__main__":
    sys.exit(main())
