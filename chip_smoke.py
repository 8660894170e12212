#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that hetu_tpu's main path runs on a TPU.

    python chip_smoke.py             # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4   # four chips: the sharded train step and
                                     # the single-device step it is compared
                                     # with, and no other phase

One process, one import of JAX, no child that needs the chip.  It drives
`Trainer` and `ServingEngine` through their normal entry points at the
full width of Llama-2-7B (hidden 4096, intermediate 11008, 32 heads x 128,
vocab 32000); only DEPTH is cut, and each phase says by how much.  Weights
and tokens are random, made from --seed.

There is no CPU mode: without a TPU the script exits non-zero and prints
no result.  (tests/test_chip_smoke.py calls the phase functions at a tiny
size under JAX_PLATFORMS=cpu, only to check their control flow, and
tests/test_chip_compile.py compiles the same programs for a described
v5e.)

Every output line is one JSON object.  Timings on the earlier lines are
smoke timings of a handful of steps, not metrics.  The last line is the
verdict the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
import traceback

import numpy as np

#: the scope each dispatcher wraps its Pallas call in (ops/attention.py,
#: ops/norms.py, ops/activations.py, ops/rotary.py, optim/optimizer.py,
#: models/generation.py) — how a `tpu_custom_call` in compiled text is
#: told apart from the others
KERNEL_SCOPES = {
    "flash": "pallas_flash_attention",
    "norm": "pallas_residual_rmsnorm",
    "swiglu": "pallas_swiglu",
    "rotary": "pallas_rotary",
    "paged_attn": "pallas_paged_attention",
}

#: the kernels the train step runs on a TPU, under any mesh (the AdamW
#: update is an op chain XLA fuses)
TRAIN_KERNELS = ("flash", "norm", "rotary", "swiglu")

#: sharded-vs-single first-step loss: the bound __graft_entry__'s
#: multichip dry run uses.  Both programs run the same kernels (the
#: sharded one once per shard), but sum the row-parallel matmul partials
#: and the per-shard loss terms in a different order, so in bf16 they
#: agree to rounding accumulated over the layers, not bitwise; a sharding
#: bug (a wrong slice, a reduction counted twice) moves the loss by O(1).
SHARDED_LOSS_RTOL = 1e-2


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def emit(**record):
    print(json.dumps(record), flush=True)


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# what the compiled programs contain
# ---------------------------------------------------------------------------

def kernels_in(hlo_text: str) -> dict:
    """{kernel: number of tpu_custom_call instructions under its scope}
    in a compiled program's text."""
    lines = [ln for ln in hlo_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    return {name: sum(scope in ln for ln in lines)
            for name, scope in KERNEL_SCOPES.items()}


def check_routes(routes: dict, hlo_text: str, program: str):
    """`routes` is what the program's owner recorded while it was traced
    (`Trainer.kernel_routes` / `ServingEngine.kernel_routes`: per kernel,
    how many dispatches took Pallas and how many XLA, and why).  Every
    kernel it says took Pallas has a tpu_custom_call in the compiled
    program, and no kernel that only took XLA has one."""
    found = kernels_in(hlo_text)
    for name, rec in routes.items():
        if name not in found:
            continue
        check(bool(found[name]) == bool(rec["pallas"]),
              f"{program}: kernel {name!r} routed {rec} but the compiled "
              f"program has {found[name]} tpu_custom_call(s) under "
              f"{KERNEL_SCOPES[name]}")


# ---------------------------------------------------------------------------
# device and cache reporting
# ---------------------------------------------------------------------------

class CacheCounter:
    """Counts JAX's persistent-compilation-cache traffic in this process:
    `requests` = compiles that consulted the cache, `hits` = served from
    it, `writes` = compiled here and slow enough to be kept."""

    _EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
               "/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "writes"}

    def __init__(self):
        import jax.monitoring
        self.counts = {"requests": 0, "hits": 0, "writes": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        key = self._EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def device_memory(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({"device": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit")})
    return out


def shard_bytes(tree, devices) -> dict:
    """{device id: bytes of `tree` resident there}, from each leaf's
    addressable shards."""
    import jax
    per = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            per[sh.device.id] += sh.data.nbytes
    return per


def tree_bytes(tree) -> int:
    import jax
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(cfg, *, batch: int, seq: int, steps: int, seed: int,
                strategy=None, name: str = "train"):
    """`steps` steps of `Trainer` on one fixed batch of seeded random
    tokens.  Returns the record it emitted (losses, timings, routes)."""
    import jax
    from hetu_tpu.engine.trainer import Trainer
    from hetu_tpu.engine.trainer_config import TrainingConfig
    from hetu_tpu.models.llama import LlamaLMHeadModel
    from hetu_tpu.parallel import ParallelStrategy

    strategy = strategy or ParallelStrategy()
    dp = max(strategy.dp, 1)
    check(batch % dp == 0, f"batch {batch} must divide by dp={dp}")
    model = LlamaLMHeadModel(cfg, strategy)
    tc = TrainingConfig(global_batch_size=batch, micro_batch_size=batch // dp,
                        seq_len=seq, lr=3e-4, warmup_steps=1,
                        total_steps=max(steps, 2), seed=seed,
                        log_every=10 ** 9)
    trainer = Trainer(model, tc, strategy)
    devices = list(trainer.mesh.devices.flat)

    t0 = time.perf_counter()
    trainer.build()
    jax.block_until_ready((trainer.params, trainer.opt_state))
    build_s = time.perf_counter() - t0

    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(batch, seq), dtype=np.int32)
    host_batch = {"input_ids": ids, "labels": ids}

    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        metrics = trainer.train_step(host_batch)
        jax.block_until_ready(metrics["loss"])
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))

    params_b, opt_b = tree_bytes(trainer.params), tree_bytes(trainer.opt_state)
    per_dev = {"params": shard_bytes(trainer.params, devices),
               "opt_state": shard_bytes(trainer.opt_state, devices)}
    # the step program's compiled text (the pool keeps its executables
    # to itself, so this compiles it a second time: a compile-cache hit)
    hlo_text = trainer.lowered_step(host_batch, optimized=True)
    routes = trainer.kernel_routes
    c = cfg
    # a random-init LM's logits are N(0, sigma^2) with sigma =
    # initializer_range * sqrt(hidden), so its cross entropy starts at
    # ln(vocab) + sigma^2 / 2
    expect0 = (math.log(c.vocab_size)
               + c.initializer_range ** 2 * c.hidden_size / 2)
    record = dict(
        phase=name, strategy=strategy.describe(),
        layers_kept=c.num_hidden_layers, batch=batch, seq=seq, steps=steps,
        losses=losses, expected_first_loss=expect0,
        smoke_timing_s={"build": build_s,
                        "first_step_incl_compile": step_s[0],
                        "later_steps": step_s[1:]},
        routes=routes, tpu_custom_calls=kernels_in(hlo_text),
        bytes={"params": params_b, "opt_state": opt_b, "per_device": per_dev},
        memory=device_memory(devices))
    emit(**record)

    check_routes(routes, hlo_text, name)
    if jax.default_backend() == "tpu":
        on = sorted(k for k, r in routes.items() if r["pallas"])
        check(on == sorted(TRAIN_KERNELS),
              f"{name}: on a TPU the step should run {TRAIN_KERNELS} as "
              f"Pallas kernels, under any mesh; it ran {on}: {routes}")
    check(all(math.isfinite(x) for x in losses), f"{name}: loss {losses}")
    check(abs(losses[0] - expect0) < 0.02 * expect0,
          f"{name}: first loss {losses[0]} is not ~{expect0:.3f} "
          f"(ln vocab + sigma^2/2 of a random init)")
    if steps > 1:
        check(losses[-1] < losses[0],
              f"{name}: loss did not fall over {steps} steps: {losses}")
    trainer.close()
    return record


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_layers(cfg, serve_cfg, n_requests: int, bytes_limit: int) -> dict:
    """As many layers as fit beside the KV pool, from the sizes the
    compiled programs show (AOT compile for the described chip, PR 21):
    the decode program updates the donated pool in place (PR 25: it held
    it twice while the pool was an xs -> ys of the layer scan), every
    prefilling request holds one dense [max_len] scratch cache of its
    own, the engine holds the fused qkv and gate/up weights a second
    time in its serving layout while this script keeps the training
    tree for its references (`LlamaLMHeadModel.serving_params`, PR 32),
    and ~1 GiB goes to the programs' other temporaries.  15% of the
    device is left free."""
    c, s = cfg, serve_cfg
    item = np.dtype(c.compute_dtype).itemsize
    kv = c.num_key_value_heads * c.head_dim
    fused = (c.hidden_size * (c.hidden_size + 2 * kv)
             + 2 * c.hidden_size * c.intermediate_size)
    per_layer_params = (2 * fused + c.hidden_size * c.hidden_size
                        + c.hidden_size * c.intermediate_size
                        + 2 * c.hidden_size) * np.dtype(c.param_dtype).itemsize
    pool = 2 * (s.num_pages + 1) * s.page_size * kv * item
    scratch = 2 * s.max_len * kv * item
    per_layer = per_layer_params + pool + n_requests * scratch
    fixed = (2 * c.vocab_size * c.hidden_size
             * np.dtype(c.param_dtype).itemsize) + (1 << 30)
    layers = int((0.85 * bytes_limit - fixed) // per_layer)
    return {"layers": max(1, min(layers, 32)), "per_layer_bytes": per_layer,
            "pool_bytes_per_layer": pool, "fixed_bytes": fixed,
            "bytes_limit": bytes_limit}


def logit_gap_tolerance(max_logit: float) -> float:
    """4 bf16 ulps at the magnitude of the winning logit.  The engine
    (chunked prefill over a dense scratch, paged-attention decode) and
    the reference (one flash-attention forward) round differently in
    bf16, and bf16 logits are coarse enough (spacing 2^-5 at 4..8) that
    the top two of 32000 often tie — so a served token must be the
    reference's argmax OR within this gap of it."""
    return 4.0 * 2.0 ** (math.floor(math.log2(max(abs(max_logit), 1e-6))) - 7)


def serve_phase(cfg, serve_cfg, *, prompt_lens, max_new: int, seed: int,
                name: str = "serve"):
    """Serve seeded requests of mixed prompt lengths through
    `ServingEngine.run` and hold each greedy stream to the reference:
    `models/generation.generate` token for token, and — where bf16 ties
    let the two diverge — to the reference forward's logits given the
    stream's own prefix (`logit_gap_tolerance`)."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models.generation import generate
    from hetu_tpu.models.llama import LlamaLMHeadModel
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.ops import pallas as kernels
    from hetu_tpu.ops.pallas import sample
    from hetu_tpu.serving.engine import ServingEngine
    from hetu_tpu.serving.request import Request

    c, sc = cfg, serve_cfg
    device = jax.devices()[0]
    model = LlamaLMHeadModel(c)
    t0 = time.perf_counter()
    # one compiled program: eager init of a 7B-width stack dispatches
    # every initializer op by op and took 70 s on the chip
    params = jax.jit(model.init)(jax.random.key(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0

    registry = MetricsRegistry()
    engine = ServingEngine(model, params, sc, registry=registry)
    S = sc.num_slots
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    requests = [Request(rid=i, prompt=rng.integers(0, c.vocab_size, size=n,
                                                   dtype=np.int32),
                        max_new_tokens=max_new, arrival_t=0.01 * i)
                for i, n in enumerate(prompt_lens)]
    t0 = time.perf_counter()
    results = engine.run(requests)
    run_s = time.perf_counter() - t0
    check(len(results) == len(requests),
          f"{name}: {len(results)} of {len(requests)} requests finished")
    for r in results:
        check(r.finished_reason == "length" and len(r.tokens) == max_new,
              f"{name}: request {r.rid} ended {r.finished_reason!r} after "
              f"{len(r.tokens)} tokens")
    chunks = {r.rid: r.stats.prefill_chunks for r in results}
    check(max(chunks.values()) > 1,
          f"{name}: no prompt took several launches of the chunk program: "
          f"{chunks}")

    # the decode program's compiled text: lowered for the arguments the
    # engine calls it with, so this is the executable `warmup` built
    decode_text = engine.lower_programs()["decode"].compile().as_text()
    # what the engine recorded while its programs were traced, and one
    # question nobody asked: the fused sampling epilogue's gate, as the
    # engine puts it for a verify block when speculative decoding is on
    routes = dict(engine.kernel_routes)
    with kernels.record_routes(routes):
        kernels.resolve_route(
            "sample", sample.check_shapes,
            (S * (sc.spec_k + 1), c.hidden_size),
            (c.hidden_size, c.vocab_size))
    # which attention each traced K/V layer of the decode program took
    # (`KVAttention.attend_paged`: the route is the layer's own)
    paged = routes["paged_attn"]
    check(paged["pallas"] + paged["xla"] > 0,
          f"{name}: no layer of the decode program asked for its route")
    if jax.default_backend() == "tpu":
        check(paged["pallas"] and not paged["xla"],
              f"{name}: on a TPU every decode layer should run the "
              f"paged-attention kernel: {paged}")
    step_hist = registry.histogram("serve.token_latency_s")
    decode_steps = int(registry.counter_value("serve.decode_steps"))
    memory_serving = device_memory([device])
    # free the pool and the scratch caches before the reference runs
    engine.close()
    del engine

    # reference 1: generate(), one jitted call per distinct prompt length
    by_len = {}
    for req in requests:
        by_len.setdefault(req.prompt_len, []).append(req)
    ref_tokens = {}
    t0 = time.perf_counter()
    for plen, reqs in sorted(by_len.items()):
        out = jax.jit(lambda p, ids: generate(
            model, p, ids, max_new_tokens=max_new))(
                params, jnp.asarray(np.stack([r.prompt for r in reqs])))
        out = np.asarray(out)
        for req, row in zip(reqs, out):
            ref_tokens[req.rid] = row[plen:].tolist()
    reference_s = time.perf_counter() - t0

    # reference 2: the training forward over each served stream (padded
    # on the right to a multiple of 128 — causal, so the pad is inert)
    forward = jax.jit(lambda p, ids: model(p, ids))
    streams, failures = [], []
    for req, res in zip(requests, results):
        check(req.rid == res.rid, f"{name}: results out of order")
        stream = np.concatenate([req.prompt, np.asarray(res.tokens[:-1],
                                                        np.int32)])
        padded = np.zeros(-(-len(stream) // 128) * 128, np.int32)
        padded[: len(stream)] = stream
        logits = forward(params, jnp.asarray(padded[None]))
        rows = np.asarray(logits[0, req.prompt_len - 1: len(stream)],
                          np.float32)
        check(rows.shape == (max_new, c.vocab_size) and np.isfinite(
            rows).all(), f"{name}: reference logits {rows.shape}")
        gaps = rows.max(axis=-1) - rows[np.arange(max_new), res.tokens]
        tols = np.asarray([logit_gap_tolerance(m) for m in rows.max(axis=-1)])
        if not (gaps <= tols).all():
            failures.append(
                f"request {req.rid} (prompt {req.prompt_len}) served "
                f"{res.tokens}; reference generate {ref_tokens[req.rid]}; "
                f"logit gaps {gaps.tolist()} exceed {tols.tolist()}")
        streams.append({"rid": req.rid, "prompt_len": req.prompt_len,
                        "prefill_chunks": chunks[req.rid],
                        "matches_generate":
                            res.tokens == ref_tokens[req.rid],
                        "max_logit_gap": float(gaps.max()),
                        "tolerance_there": float(tols[gaps.argmax()])})

    record = dict(
        phase=name, layers_kept=c.num_hidden_layers, slots=S,
        max_len=sc.max_len, page_size=sc.page_size,
        prefill_chunk=sc.prefill_chunk, kv_quant=sc.kv_quant,
        requests=len(requests), max_new_tokens=max_new, streams=streams,
        streams_matching_generate=sum(s["matches_generate"]
                                      for s in streams),
        tolerance="4 bf16 ulps at the reference's max logit",
        smoke_timing_s={"init_params": init_s, "warmup_incl_compile": warmup_s,
                        "run": run_s, "reference": reference_s,
                        "decode_step_median": step_hist.percentile(50.0),
                        "decode_steps": decode_steps},
        routes=routes,
        never_asked={k: "no dispatcher of this kernel on the serving path"
                     for k in kernels.KERNEL_NAMES if k not in routes},
        tpu_custom_calls=kernels_in(decode_text),
        memory_while_serving=memory_serving)
    emit(**record)
    check_routes(routes, decode_text, f"{name}: decode program")
    check(not failures, f"{name}: " + " | ".join(failures))
    return record


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def sharded_phase(cfg, *, batch: int, seq: int, steps: int, seed: int,
                  mesh_config):
    """The train step under dp x tp + sequence parallel + ZeRO on the
    real devices, held to the single-device step of the same seed."""
    from hetu_tpu.parallel import ParallelStrategy

    single = train_phase(cfg, batch=batch, seq=seq, steps=steps, seed=seed,
                         name="train_single_device")
    gc.collect()   # the first trainer's 12 GB must be gone from device 0
    strategy = ParallelStrategy(mesh=mesh_config, sequence_parallel=True,
                                zero=True)
    sharded = train_phase(cfg, batch=batch, seq=seq, steps=steps, seed=seed,
                          strategy=strategy, name="train_sharded")

    n = mesh_config.num_devices
    rel = abs(sharded["losses"][0] - single["losses"][0]) / abs(
        single["losses"][0])
    check(rel < SHARDED_LOSS_RTOL,
          f"sharded first-step loss {sharded['losses'][0]} vs single-device "
          f"{single['losses'][0]}: rel {rel:.2e} >= {SHARDED_LOSS_RTOL}")
    # really split: every device holds a share, and no device holds the
    # whole of the parameters (tp) or of the optimizer state (tp x ZeRO)
    b = sharded["bytes"]
    for kind, whole_frac in (("params", 1.0 / mesh_config.tp),
                             ("opt_state", 1.0 / n)):
        per = b["per_device"][kind]
        check(len(per) == n and all(v > 0 for v in per.values()),
              f"{kind} not on every device: {per}")
        # replicated small leaves (norm gains) ride on top of the even share
        check(max(per.values()) <= 1.05 * whole_frac * b[kind],
              f"{kind} not split {whole_frac:.2f}-ways: per device {per} "
              f"of {b[kind]} bytes")
    emit(phase="sharded_vs_single", mesh=str(mesh_config),
         first_step_loss={"single": single["losses"][0],
                          "sharded": sharded["losses"][0], "rel_err": rel},
         tolerance=SHARDED_LOSS_RTOL,
         per_device_bytes=b["per_device"])


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform!r}); "
              f"this script has no CPU mode", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    # count = the chips this run exercises (== len(jax.devices()) on the
    # one-chip and the four-chip machine it is meant for)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": args.chips}

    import jaxlib
    from importlib.metadata import version

    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.models.llama import LlamaConfig
    from hetu_tpu.serving.engine import ServeConfig
    from hetu_tpu.utils.device import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    emit(phase="start", chips=args.chips, seed=args.seed, device=device,
         model="Llama-2-7B widths (hidden 4096, intermediate 11008, 32 heads "
               "x 128, vocab 32000); depth cut from the published 32 layers "
               "to each phase's layers_kept, nothing else",
         versions={"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                   "libtpu": version("libtpu")},
         compile_cache_dir=cache_dir)

    train_cfg = LlamaConfig.llama2_7b(
        num_hidden_layers=2, param_dtype=jnp.bfloat16,
        remat_policy="dots_attn")
    try:
        if args.chips == 4:
            sharded_phase(train_cfg, batch=2, seq=4096, steps=2,
                          seed=args.seed, mesh_config=MeshConfig(dp=2, tp=2))
        else:
            train_phase(train_cfg, batch=2, seq=4096, steps=4,
                        seed=args.seed)
            emit(phase="compile_cache", after="train", **cache.snapshot())
            gc.collect()
            serve_cfg = ServeConfig(num_slots=8, page_size=16, max_len=2048,
                                    prefill_chunk=128)
            prompt_lens = (40, 300, 1100, 300, 40, 1100)
            fit = serve_layers(LlamaConfig.llama2_7b(
                param_dtype=jnp.bfloat16), serve_cfg, len(prompt_lens),
                dev.memory_stats()["bytes_limit"])
            emit(phase="serve_sizing", **fit)
            serve_phase(LlamaConfig.llama2_7b(
                num_hidden_layers=fit["layers"], param_dtype=jnp.bfloat16),
                serve_cfg, prompt_lens=prompt_lens, max_new=12,
                seed=args.seed)
    except Exception as e:   # the boundary: report, then fail the run
        traceback.print_exc()
        emit(phase="compile_cache", **cache.snapshot())
        emit(ok=False, error=f"{type(e).__name__}: {e}"[:4000], device=device)
        return 1
    emit(phase="compile_cache", after="all", **cache.snapshot())
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
