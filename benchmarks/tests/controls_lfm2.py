"""What the check of the LFM2-8B-A1B cell can tell apart, at the timed
sizes (the cell's own configuration file: run it on the chip; the tiny
configuration runs on the CPU).  Nothing here is run by the benchmark or
by the tests: it is how the readings of PERF.md s6 and of the
configuration's `assumed` are made again.

    python3 benchmarks/tests/controls_lfm2.py controls --seed N
    python3 benchmarks/tests/controls_lfm2.py passes --seed N --streams 8 \
        --margins 0.05,0.1

`controls`: the first `check_requests` requests of the cell's traffic
plan are served by `ServingEngine` (the cell's slots, pages and chunk)
and each stream is held by the HARNESS'S comparison,
`reference.check_stream`, to the family's reference, to the reference
with one thing done wrongly (`families/lfm2_moe.CONTROLS`: the
convolution's tail in the precision below, a dropped tap, a tail from a
padding row, the bias in the weights, a wrong fourth expert), and to the
sound reference over
the weights rounded to e4m3 (the nearest precision below the
configuration's).  `correct` is what `run.py` says of a run: every stream
ok.  Exit 0 where the sound reference is `correct` and no control is.

`passes`: what the near-tie passes are needed for.  `--streams` requests
of the plan are served; every generated position is then held to the
comparison's limit under the plain forward alone and under the family's
near-tie passes at each `--margins` value of `router_tie_logit`: how
many rows stay over the limit, and the largest gap in units of it.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import reference, traffic  # noqa: E402
from benchmarks.families import lfm2_moe as fam  # noqa: E402

F32 = jnp.float32


def say(**record):
    print(json.dumps(record), flush=True)


def build(args):
    cfg = dict(traffic.load_json("configs", args.config),
               **{k: json.loads(v) for k, v in
                  (kv.split("=", 1) for kv in args.set)})
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
    routes = {k: {a: v[a] for a in ("pallas", "xla")}
              for k, v in engine.kernel_routes.items()
              if isinstance(v, dict) and "pallas" in v}
    engine.close()
    del engine
    gc.collect()
    return tokens, routes


def planned(args, cfg, n):
    tf = traffic.load_traffic(args.traffic)
    n = n or int(tf.get("check_requests", 4))
    return traffic.plan_requests(tf, args.seed, cfg["vocab_size"],
                                 count=n)[:n]


def controls(args):
    cfg, model, params = build(args)
    plan = planned(args, cfg, args.streams)
    tokens, routes = serve(cfg, model, params, plan)
    say(routes=routes)
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
        t0 = time.perf_counter()
        streams = [dict(prompt=len(pr.prompt), **reference.check_stream(
            forward, params, cfg, pr.prompt, tokens[pr.rid],
            cfg["serving"]["max_len"])) for pr in plan]
        correct[name] = all(s["ok"] for s in streams)
        say(control=name, correct=correct[name], streams=streams,
            seconds=time.perf_counter() - t0)
    ok = correct.pop("sound") and not any(correct.values())
    say(ok=ok, controls_correct=correct, seed=args.seed, config=args.config,
        traffic=args.traffic)
    return 0 if ok else 1


def rows_over(forward, params, cfg, prompt, tokens, pad_to):
    """`reference.check_stream`'s comparison, row by row: (rows over the
    limit, the largest gap in units of the limit, rows that are the
    reference's argmax, rows)."""
    plen, n = len(prompt), len(tokens)
    stream = np.zeros(pad_to, np.int32)
    stream[:plen] = prompt
    stream[plen: plen + n - 1] = tokens[:-1]
    rows = np.arange(plen - 1, plen - 1 + n)
    lg = np.asarray(reference._logits_jit(forward, cfg)(
        params, jnp.asarray(stream),
        jnp.asarray(reference._pad_rows(rows))))[:n]
    top = lg.max(axis=-1)
    gaps = top - lg[np.arange(n), np.asarray(tokens)]
    tols = np.asarray([reference.logit_gap_tolerance(m) for m in top])
    # of the rows over the limit: (row, gap in limits, how many values
    # stand above the served token)
    over = [(int(r), float(gaps[r] / tols[r]),
             int((lg[r] > lg[r, tokens[r]]).sum()))
            for r in np.nonzero(gaps > tols)[0]]
    return (int((gaps > tols).sum()), float((gaps / tols).max()),
            int((lg.argmax(-1) == np.asarray(tokens)).sum()), n, over)


def passes(args):
    cfg, model, params = build(args)
    plan = planned(args, cfg, args.streams or 8)
    tokens, _ = serve(cfg, model, params, plan)
    del model
    pad_to = cfg["serving"]["max_len"]
    variants = [("plain", dict(cfg, router_tie_logit=0.0))] + [
        (f"margin {m}", dict(cfg, router_tie_logit=float(m)))
        for m in args.margins.split(",") if m]
    for name, c in variants:
        # a forward of its own a variant: `reference._logits_jit` keeps a
        # program by the forward and the configuration's numbers
        forward = functools.partial(fam.logits_at)
        t0 = time.perf_counter()
        got = [rows_over(forward, params, c, pr.prompt, tokens[pr.rid],
                         pad_to) for pr in plan]
        say(variant=name, rows_over=sum(g[0] for g in got),
            streams_not_ok=sum(g[0] > 0 for g in got),
            largest_gap_in_limits=max(g[1] for g in got),
            argmax_pct=100.0 * sum(g[2] for g in got) / sum(g[3] for g in got),
            rows=sum(g[3] for g in got), per_stream=[g[:2] for g in got],
            over=[g[4] for g in got],
            seconds=time.perf_counter() - t0)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("controls", "passes"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--config", default="lfm2-8b-a1b-depth12")
    ap.add_argument("--traffic", default="many-turns-closed")
    ap.add_argument("--streams", type=int, default=0)
    ap.add_argument("--controls", default=",".join(fam.CONTROLS + ("e4m3",)))
    ap.add_argument("--margins", default="0.1")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON", help="a key of the configuration "
                    "read as this value (a reading at another seeding)")
    args = ap.parse_args(argv)
    from hetu_tpu.utils.device import enable_compile_cache
    enable_compile_cache()
    return {"controls": controls, "passes": passes}[args.what](args)


if __name__ == "__main__":
    sys.exit(main())
