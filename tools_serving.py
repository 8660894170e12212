"""Serving load generator: drive the continuous-batching engine with a
synthetic arrival trace and print an SLO report.

    JAX_PLATFORMS=cpu python tools_serving.py --requests 16 --rate 20
    python tools_serving.py --trace bursty --burst 6 --quant int8
    python tools_serving.py --requests 32 --runlog /tmp/serve.jsonl
    python tools_serving.py --trace poisson --requests 16 \
        --slo-class gold:0.2:0.05 --slo-class bulk \
        --runlog /tmp/serve.jsonl --chrome-trace /tmp/serve_trace.json
    python tools_serving.py --sample --temperature 0.8 --top-k 40
    python tools_serving.py --spec ngram --spec-k 4 --runlog /tmp/s.jsonl
    python tools_serving.py --shared-prefix 64 --max-len 128 \
        --runlog /tmp/s.jsonl

Seeded and CPU-safe (tiny LLaMA by default): the same trace replays to
the same tokens every run.  The report is one JSON object — request
count, TTFT / e2e latency percentiles, tokens/s, slot occupancy and
cache-page utilization — plus RunLog ``serve`` events when --runlog is
given (summarize those with `python tools_obs_report.py <runlog>`).

`--slo-class name[:ttft_s[:token_gap_s[:priority]]]` (repeatable)
assigns latency classes round-robin; per-class attainment/goodput come
from `python tools_serving_report.py <runlog>`.  `--chrome-trace
OUT.json` turns on the flight recorder (the HETU_TPU_SERVE_TRACE path)
and renders the per-slot span timeline for Perfetto.

Decoding-subsystem trace modes (docs/serving.md):
`--sample` builds the in-graph sampling decode program
(HETU_TPU_SERVE_SAMPLE) and stamps seeded per-request SamplingParams;
`--spec ngram` runs speculative decoding (the report gains draft
acceptance counts; tools_serving_report prints the acceptance-rate
section); `--shared-prefix N` prepends one N-token system prompt to
every request and turns on the radix prefix cache — the report's
prefix_cache keys (and tools_serving_report's cache-hit section) show
the prefill tokens eliminated; `--preempt` arms SLO-class preemptive
admission (pair with prioritized --slo-class specs, e.g. gold:0.2:-:2).
"""
from __future__ import annotations

import argparse
import json
import sys


def build_model(family: str):
    import jax
    import jax.numpy as jnp
    if family == "llama":
        from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
        cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                               use_flash_attention=False)
        model = LlamaLMHeadModel(cfg)
    elif family == "gpt":
        from hetu_tpu.models.gpt import GPTConfig, GPTLMHeadModel
        cfg = GPTConfig.tiny(remat=False, compute_dtype=jnp.float32)
        model = GPTLMHeadModel(cfg)
    else:
        raise SystemExit(f"unknown --model {family!r} (llama | gpt)")
    return model, model.init(jax.random.key(0))


def slo_report(results, registry) -> dict:
    from hetu_tpu.obs.metrics import percentile_of_sorted
    ttfts = sorted(r.stats.ttft_s for r in results
                   if r.stats.ttft_s is not None)
    e2es = sorted(r.stats.e2e_s for r in results
                  if r.stats.e2e_s is not None)
    waits = sorted(r.stats.queue_wait_s for r in results
                   if r.stats.queue_wait_s is not None)
    tokens = sum(len(r.tokens) for r in results)
    span = max((r.stats.done_t for r in results if r.stats.done_t), default=0.0)
    rep = {
        "requests": len(results),
        "tokens_out": tokens,
        "tokens_per_s": round(tokens / span, 2) if span > 0 else None,
        "finished_by": {},
        "ttft_s": {"p50": percentile_of_sorted(ttfts, 50),
                   "p95": percentile_of_sorted(ttfts, 95)},
        "e2e_s": {"p50": percentile_of_sorted(e2es, 50),
                  "p95": percentile_of_sorted(e2es, 95)},
        "queue_wait_s": {"p50": percentile_of_sorted(waits, 50),
                         "p95": percentile_of_sorted(waits, 95)},
    }
    for r in results:
        rep["finished_by"][r.finished_reason] = \
            rep["finished_by"].get(r.finished_reason, 0) + 1
    # token_latency_s = user-visible inter-token gap (decode-step wall);
    # token_cost_s = amortized per-token engine cost (wall / active)
    for name in ("serve.token_latency_s", "serve.token_cost_s"):
        h = registry.histogram(name)
        if h is not None:
            rep[name.split(".", 1)[1]] = {"p50": h.percentile(50),
                                          "p95": h.percentile(95)}
    for g in ("serve.queue_depth", "serve.slot_occupancy",
              "serve.page_util"):
        v = registry.gauge_value(g)
        if v is not None:
            rep[g.split(".", 1)[1] + "_last"] = v
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Drive the serving engine with a synthetic arrival "
                    "trace and print an SLO report (docs/serving.md).")
    ap.add_argument("--model", default="llama", help="llama | gpt")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--trace", default="poisson",
                    help="arrival process: poisson | bursty | closed "
                         "(all at t=0)")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="mean arrival rate, requests/s")
    ap.add_argument("--burst", type=int, default=4,
                    help="bursty trace: requests per burst")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=8,
                    help="prefill chunk budget (tokens)")
    ap.add_argument("--pages", type=int, default=0,
                    help="usable KV pages (0 = full reservation)")
    ap.add_argument("--quant", default=None,
                    help="KV page mode: none | int8 (default: the "
                         "HETU_TPU_KV_QUANT flag)")
    ap.add_argument("--prompt-lens", default="4,24",
                    help="uniform prompt-length range 'lo,hi'")
    ap.add_argument("--max-new", default="4,12",
                    help="uniform decode-budget range 'lo,hi'")
    ap.add_argument("--eos", type=int, default=None,
                    help="per-request EOS token id")
    ap.add_argument("--runlog", default=None,
                    help="also write RunLog `serve` events here")
    ap.add_argument("--slo-class", action="append", default=None,
                    metavar="NAME[:TTFT_S[:GAP_S]]",
                    help="SLO class spec, repeatable; classes assign "
                         "round-robin over the request stream ('-' or "
                         "empty target = uncontracted)")
    ap.add_argument("--chrome-trace", metavar="OUT.json", default=None,
                    help="record request spans (the HETU_TPU_SERVE_TRACE "
                         "flight recorder) and render the per-slot "
                         "timeline here (open in Perfetto)")
    ap.add_argument("--per-request", action="store_true",
                    help="include the per-request table in the report")
    ap.add_argument("--sample", action="store_true",
                    help="build the sampling decode program "
                         "(HETU_TPU_SERVE_SAMPLE) and stamp seeded "
                         "SamplingParams on every request")
    ap.add_argument("--temperature", type=float, default=0.8,
                    help="--sample: sampling temperature")
    ap.add_argument("--top-k", type=int, default=0,
                    help="--sample: top-k filter (0 = off)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="--sample: nucleus filter (0 = off)")
    ap.add_argument("--spec", default=None, metavar="MODE",
                    help="speculative decoding mode (ngram); the report "
                         "gains draft acceptance counts")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="--spec: draft tokens per verify step")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend one N-token system prompt to every "
                         "request and enable the radix prefix cache")
    ap.add_argument("--preempt", action="store_true",
                    help="SLO-class preemptive admission (pair with "
                         "prioritized --slo-class specs)")
    args = ap.parse_args(argv)

    from hetu_tpu import serving
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.obs.runlog import RunLog
    from hetu_tpu.utils import flags as _flags

    model, params = build_model(args.model)
    n = args.requests
    if args.trace == "poisson":
        arrivals = serving.poisson_arrivals(n, args.rate, seed=args.seed)
    elif args.trace == "bursty":
        arrivals = serving.bursty_arrivals(n, args.rate, burst=args.burst,
                                           seed=args.seed)
    elif args.trace == "closed":
        arrivals = None
    else:
        raise SystemExit(f"unknown --trace {args.trace!r}")
    lo, hi = (int(x) for x in args.prompt_lens.split(","))
    mlo, mhi = (int(x) for x in args.max_new.split(","))
    slo_classes = ([serving.SLOClass.parse(s) for s in args.slo_class]
                   if args.slo_class else None)
    sampling = (serving.SamplingParams(
        temperature=args.temperature, top_k=args.top_k,
        top_p=args.top_p, seed=args.seed) if args.sample else None)
    reqs = serving.synthetic_requests(
        n, vocab_size=model.config.vocab_size, prompt_lens=(lo, hi),
        max_new=(mlo, mhi), eos_token_id=args.eos, arrivals=arrivals,
        slo_classes=slo_classes, shared_prefix_len=args.shared_prefix,
        sampling=sampling, seed=args.seed)
    if args.shared_prefix and args.max_len < args.shared_prefix + hi + mhi:
        raise SystemExit(
            f"--max-len {args.max_len} cannot hold the {args.shared_prefix}"
            f"-token shared prefix + suffix {hi} + decode budget {mhi}")

    cfg_kw = dict(num_slots=args.slots, page_size=args.page,
                  max_len=args.max_len, prefill_chunk=args.chunk,
                  num_pages=args.pages, sampling=args.sample,
                  preempt=args.preempt,
                  prefix_cache=bool(args.shared_prefix))
    if args.spec is not None:
        cfg_kw.update(spec_decode=args.spec, spec_k=args.spec_k)
    if args.quant is not None:
        cfg_kw["kv_quant"] = args.quant
    cfg = serving.ServeConfig.from_flags(**cfg_kw)

    registry = MetricsRegistry()
    runlog_path = args.runlog
    if args.chrome_trace and not runlog_path:
        # the span renderer reads records back from a RunLog; without an
        # explicit one, record into a scratch file next to the trace
        runlog_path = args.chrome_trace + ".runlog.jsonl"
    run_log = RunLog(runlog_path) if runlog_path else None
    tracer = None
    if args.chrome_trace or _flags.bool_flag("HETU_TPU_SERVE_TRACE"):
        tracer = serving.RequestTracer(run_log=run_log, registry=registry)
    eng = serving.ServingEngine(model, params, cfg, registry=registry,
                                run_log=run_log, tracer=tracer)
    print(f"# warmup (compiling {args.model} prefill/decode programs)...",
          file=sys.stderr)
    eng.warmup()
    results = eng.run(reqs)

    rep = slo_report(results, registry)
    rep["trace"] = args.trace
    rep["kv_quant"] = cfg.kv_quant
    if slo_classes:
        rep["slo_classes"] = [c.to_dict() for c in slo_classes]
    if cfg.spec_decode != "none":
        proposed = sum(r.stats.spec_proposed for r in results)
        accepted = sum(r.stats.spec_accepted for r in results)
        rep["spec_decode"] = {
            "mode": cfg.spec_decode, "k": cfg.spec_k,
            "drafts_proposed": proposed, "drafts_accepted": accepted,
            "acceptance_rate": round(accepted / proposed, 4)
            if proposed else 0.0,
        }
    if eng.prefix_cache is not None:
        rep["prefix_cache"] = eng.prefix_cache.stats()
    if cfg.preempt:
        rep["preemptions"] = eng.scheduler.preempted
    if args.per_request:
        rep["per_request"] = [
            {"rid": r.rid, "tokens": len(r.tokens),
             "reason": r.finished_reason, "slo_class": reqs[r.rid].slo.name,
             "ttft_s": r.stats.ttft_s, "e2e_s": r.stats.e2e_s}
            for r in results]
    print(json.dumps(rep, indent=2))
    if run_log is not None:
        run_log.close()
        print(f"# serve events written to {runlog_path} "
              f"(summarize: python tools_obs_report.py {runlog_path}; "
              f"per-class SLO: python tools_serving_report.py "
              f"{runlog_path})", file=sys.stderr)
    if args.chrome_trace:
        from hetu_tpu.obs.trace import serving_trace
        records = RunLog.read(runlog_path)
        serving_trace(records).save(args.chrome_trace)
        print(f"# per-slot span timeline written to {args.chrome_trace} "
              f"(open at https://ui.perfetto.dev)", file=sys.stderr)
    return 0 if len(results) == len(reqs) else 1


if __name__ == "__main__":
    from hetu_tpu.utils.device import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
