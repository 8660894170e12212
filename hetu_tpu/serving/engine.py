"""Serving engine: continuous batching + paged KV cache over the
training stack.

The engine runs THREE jitted programs, all static-shape (TPU-shaped —
one compile each, no shape-bucket churn):

  * prefill chunk   — `models/generation.extend_cache` over a
                      [1, k x prefill_chunk] token block into a
                      per-request scratch cache.  Prefill is its OWN
                      program (disaggregated from decode), interleaved
                      with the decode batch: a step computes one chunk's
                      rows a prefilling slot and no more, so a long
                      prompt costs extra engine steps, never a
                      multi-chunk stall in the other requests'
                      inter-token gap.  Those rows go to the OLDEST
                      prefilling slot first, in launches of k = 1 to 4
                      chunks (`plan_prefill_launches`; one compile a
                      launch shape, at most `PREFILL_LAUNCH_ROWS` rows).
  * prefill write   — scatter the scratch K/V into the slot's pool pages
                      (quantizing in the int8 page mode).
  * decode step     — `models/generation.decode_step_paged` over the
                      full slot batch with per-slot positions: each
                      layer scatters the token's K/V into the slot's
                      page and attends the pool where it lies, through
                      the page table; argmax.  Inactive slots ride along
                      pointing at the null page.  ONE program for every
                      cache kind; which attention a layer calls there is
                      the layer's own hook's choice (`attend_paged`: the
                      Pallas paged-attention kernel where HETU_TPU_PALLAS
                      and its shape gate allow, else the XLA composition
                      over the slot's gathered pages; `kernel_routes`
                      says which, a traced layer; docs/kernels.md).

Between device steps the host-side `Scheduler` admits/evicts at token
granularity and the engine stamps SLO metrics into the `obs` registry
(serve.* counters/gauges/histograms) and RunLog ``serve`` events — the
same observability spine training runs use, so `tools_obs_report.py`
reads a serving run like any other.

Decoding is greedy by default (per-request EOS, length budgets); the
production decoding subsystem layers on top, all default-off with
registered decode-program byte-identity contracts: in-graph seeded
sampling (HETU_TPU_SERVE_SAMPLE, serving/sampling.py), the radix
prefix cache (HETU_TPU_SERVE_PREFIX_CACHE, serving/prefix_cache.py —
shared prompts admit with their KV pages resident), speculative
decoding (HETU_TPU_SPEC_DECODE, serving/spec_decode.py — the decode
program becomes a batched k+1-token verify), and SLO-class preemptive
admission (HETU_TPU_SERVE_PREEMPT).  The programs know no model family:
`models/generation` writes them against hooks the model brings (llama,
gpt, kimi_k2; a family of one's own needs no edit here).

The optional `reshard` hook (`serving/reshard.LoadAdaptiveMesh`) is the
Hetis move: queue-depth tier changes re-shard the serving params through
the hot-switch ParamSlice machinery.

See docs/serving.md for the architecture and known limits.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import math
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.models.cache_contract import cache_contract
from hetu_tpu.models.generation import (_check_context_length,
                                        decode_step_paged, extend_cache,
                                        init_cache, lm_head_weight,
                                        verify_step_paged)
from hetu_tpu.obs.health import maybe_serving_health_monitor
from hetu_tpu.obs.hlo_text import INSTR_PAT
from hetu_tpu.obs.metrics import MetricsRegistry, get_registry
from hetu_tpu.obs.runlog import RunLog, default_runlog_path
from hetu_tpu.ops.pallas import record_routes
from hetu_tpu.serving.kv_pool import PagePool, PoolArrays
from hetu_tpu.serving.request import (Request, RequestResult,
                                      RequestStats, rid_sampled)
from hetu_tpu.serving.scheduler import Scheduler
from hetu_tpu.serving.tracing import maybe_tracer
from hetu_tpu.utils.logging import get_logger
from hetu_tpu.utils.profiling import StepRecorder, phase_span

logger = get_logger("serving.engine")

#: the host phase spans of one `ServingEngine.step`, in the order a step
#: enters them, all nested in `serve.step`.  The two marked SYNC wait
#: for the device; the others only dispatch to it or work on the host.
#: Both waits come AFTER the step's dispatches (docs/serving.md).
STEP_PHASES = (
    "serve.admit",            # fault results, deadlines, admission, stalls
    "serve.prefill_chunk",    # per prefilling slot: ids + chunk dispatch
    "serve.page_write",       # scratch -> pages dispatch, prefix insert
    "serve.decode_build",     # positions, tokens, page table, sampling
    "serve.decode_dispatch",  # the decode (or verify) program
    "serve.token_fetch",      # SYNC: the decode BEFORE this step's, to the host
    "serve.first_token",      # SYNC: this step's prompts' first tokens
    "serve.emit",             # per-token bookkeeping, finishes
    "serve.housekeeping",     # numerics, gauges, health, brownout, reshard
)


#: the most prompt rows ONE launch of the chunk program carries.  A launch
#: streams the whole stack's weights whatever its rows, so on the chip
#: `benchmarks/peaks.py` describes (TPU v5e: 197 TFLOP/s bf16, 819 GB/s)
#: its matrix products take longer than its weights' streaming only from
#: 197e12 x 2 B / (2 x 819e9) = ~240 rows on: under that the MXU waits
#: for weights.  Twice that, and no more: Phi-4-mini-flash's chunk at
#: 1,024 rows read WORSE than two of 512 (PERF.md s5), the attention's
#: scores and the scan's temporaries grow with the rows.
PREFILL_LAUNCH_ROWS = 512


def launch_multiples(chunk: int, slides: bool) -> tuple:
    """(the numbers of chunks k ONE launch of the chunk program may carry,
    ascending; why).  1 to 4 chunks while k x `chunk` rows stay within
    `PREFILL_LAUNCH_ROWS` (a program a shape, so a few shapes: of
    InternLM2's, alone on a v5e, 128 rows take 6.2 ms, 256 7.7, 384 10.9
    and 512 13.4: PERF.md s6, PR 48); (1,) where one chunk already fills a
    launch, and where a window kind's scratch is sized by the chunk and
    slides by it (`extend_cache(slide=True)`: a launch of other rows
    would need a scratch of its own)."""
    if slides:
        return (1,), ("a window kind's prefill scratch holds the window "
                      "and ONE chunk and slides by it")
    ks = tuple(k for k in (1, 2, 3, 4)
               if k == 1 or k * chunk <= PREFILL_LAUNCH_ROWS)
    if ks == (1,):
        return ks, (f"two chunks of {chunk} rows pass the "
                    f"{PREFILL_LAUNCH_ROWS} rows a launch carries")
    return ks, (f"up to {PREFILL_LAUNCH_ROWS} rows a launch: its weights "
                "are streamed once for them")


def plan_prefill_launches(chunks_left: Sequence[int],
                          multiples: Sequence[int]) -> List[int]:
    """How one step spends its prompt rows: the chunks each prefilling
    slot's ONE launch carries, given the chunks each padded prompt has
    left, OLDEST ADMISSION FIRST.  The step's budget is one chunk a
    prefilling slot (what a launch a slot computed); the slot at the
    head takes the largest k of `multiples` within what it has left and
    what is left of the budget, then the next, until the budget is spent;
    a slot that gets 0 waits a step.  Every slot has a chunk left and 1
    is a multiple, so the budget is always spent exactly; with
    `multiples` = (1,) the plan is one chunk a slot."""
    budget = len(chunks_left)
    plan = []
    for left in chunks_left:
        k = max((m for m in multiples if m <= min(left, budget)), default=0)
        plan.append(k)
        budget -= k
    return plan


@dataclasses.dataclass
class _InFlight:
    """A decode (or verify) program dispatched whose tokens the host has
    not fetched: its output on the device, the (slot index, SlotState)
    rows of its batch, and when the host began to build it."""
    out: tuple
    rows: list
    t0: float


@dataclasses.dataclass(frozen=True)
class _Program:
    """One row of the table of an engine's programs
    (`ServingEngine._build_programs`): the body, the argument it
    donates, what builds its arguments aimed at the null page, and which
    of `ServingEngine._program`'s wrappers can apply to it."""
    body: Callable
    donate: int
    null_args: Callable
    #: its first argument is the parameters (resident int experts are
    #: dequantized there)
    takes_params: bool = True
    #: it has quantize sites for the numerics observatory to collect
    numerics: bool = True


def _chunk_program(k: int) -> str:
    """The table's name of the chunk program that carries k chunks."""
    return "prefill_chunk" if k == 1 else f"prefill_chunk_x{k}"


@dataclasses.dataclass
class _PromptEnd:
    """A prompt whose last chunk is dispatched and whose first token the
    host has not fetched: the chunk program's greedy token and, for a
    sampled one, the logits row it is drawn from, on the device."""
    slot: int
    st: object
    first: object
    logits_row: object


def first_token_from_logits(req, logits_row, position: int, *,
                            sampling: bool) -> int:
    """The TTFT token from a final prefill chunk's logits row: argmax
    (the default), or the seeded sampler for sampling requests — the
    (seed, position) key derivation every sampling site shares.  A pure
    function of (request, logits, position): the engine's colocated
    prefill and the disaggregated prefill tier (serving/disagg.py) both
    call it, which is what makes the two paths token-identical."""
    if not (sampling and req.sampling.temperature > 0):
        return int(np.argmax(np.asarray(logits_row)))
    from hetu_tpu.serving.sampling import sample_tokens
    sp = req.sampling
    tok = sample_tokens(
        jnp.asarray(logits_row)[None],
        jnp.asarray([sp.seed & 0xFFFFFFFF], jnp.uint32),
        jnp.asarray([position], jnp.int32),
        jnp.asarray([sp.temperature], jnp.float32),
        jnp.asarray([sp.top_k], jnp.int32),
        jnp.asarray([sp.top_p], jnp.float32))
    return int(np.asarray(tok)[0])


@dataclasses.dataclass
class ServeConfig:
    """Engine shape knobs (all static: they pick the compiled programs).

    num_pages=0 sizes the pool for FULL reservation —
    num_slots * (max_len / page_size) usable pages, so admission never
    waits on pages, only on slots.  Smaller pools trade queueing delay
    for memory (the scheduler's reserve-on-admit keeps it deadlock-free
    either way)."""
    num_slots: int = 8
    page_size: int = 16
    max_len: int = 256
    prefill_chunk: int = 32
    num_pages: int = 0
    kv_quant: str = "none"      # "none" (exact, default) | "int8" | "int4"
    #: at most so many slots are in prefill at once, each holding a
    #: prefill scratch of its own (a dense cache of max_len positions in
    #: every layer that reads everything: 126 MB a request where one
    #: layer of 10 x 128 K and V rows reads 24,576 positions); a request
    #: beyond that waits in the queue (stall reason "prefill_scratch").
    #: 0 (default): as many as there are slots
    max_prefilling: int = 0
    # MoE serving (HETU_TPU_MOE_DISPATCH, serving/experts.py): int8/int4
    # store the stacked [E, ...] expert weights resident-quantized
    # (KV-pool-style blockwise payloads + f32 scales, dequantized inside
    # the decode/prefill programs); gspmd (default) and fp32 leave the
    # params untouched.  Ignored for dense models.
    moe_dispatch: str = "gspmd"
    # -- the production decoding subsystem (all default-off: the unset
    #    programs are byte-identical to the pre-subsystem engine,
    #    enforced by the flag-identity sweep) -------------------------
    #: in-graph temperature/top-k/top-p sampling (HETU_TPU_SERVE_SAMPLE,
    #: serving/sampling.py): the decode program takes per-slot seeded
    #: PRNG keys; greedy rows stay argmax bit-for-bit
    sampling: bool = False
    #: speculative decoding (HETU_TPU_SPEC_DECODE, spec_decode.py):
    #: "none" | "ngram" | "model" — verify spec_k drafts + 1 in one
    #: batched step; "model" runs a resident-quantized small draft
    #: model (pass draft_model=/draft_params= to the engine) and
    #: verifies with the full stochastic p/q rejection rule
    spec_decode: str = "none"
    spec_k: int = 4
    #: radix prefix cache (HETU_TPU_SERVE_PREFIX_CACHE,
    #: prefix_cache.py): shared page-aligned prompt prefixes admit with
    #: their KV pages already resident (COW refcounts in kv_pool.py)
    prefix_cache: bool = False
    prefix_cache_pages: int = 0      # 0 = bounded by pool pressure only
    #: SLO-class-aware preemptive admission (HETU_TPU_SERVE_PREEMPT):
    #: under slot/page pressure a strictly-higher-priority queued
    #: request evicts-and-requeues the lowest-priority live slot
    preempt: bool = False
    #: per-tenant admission quotas (HETU_TPU_SERVE_QUOTAS,
    #: serving/request.py TenantQuota): caps each tenant's LIVE
    #: slots/pages at admission; {} (default) = quota-free — the
    #: admission path is byte-identical to the pre-tenant engine
    quotas: dict = dataclasses.field(default_factory=dict)
    #: serve-event RunLog sampling (HETU_TPU_RUNLOG_SERVE_SAMPLE): only
    #: a deterministic hashed 1-in-N of rids (request.py rid_sampled)
    #: emit admit/done/preempt events,
    #: stamped sample_weight=N (slo_report re-weights).  Registry
    #: counters stay exact.  1 (default) = every event, byte-identical
    #: RunLog to the pre-sampling engine
    serve_sample: int = 1
    # -- the fault-tolerance layer (docs/fault_tolerance.md; all
    #    default-off, all host-side policy: the compiled programs are
    #    byte-identical at any setting — registered identity contracts)
    #: per-request retry budget after a replica death
    #: (HETU_TPU_SERVE_RETRY): fail_over() requeues each in-flight
    #: request up to this many times ('replica_lost' stall reason);
    #: past the budget it terminates as 'retry_exhausted'.  0 = no
    #: retries
    retry_budget: int = 0
    #: enforce SLOClass.deadline_s (HETU_TPU_SERVE_DEADLINE): each step
    #: sweeps queued and live requests, expiring any older than its
    #: class deadline as 'deadline_exceeded'
    deadline: bool = False
    #: sustained-pressure brownout shedding (HETU_TPU_SERVE_BROWNOUT):
    #: page utilization >= brownout_page_high with >= brownout_queue_min
    #: queued for brownout_streak consecutive steps sheds the
    #: lowest-priority queued band ('brownout_shed')
    brownout: bool = False
    brownout_page_high: float = 0.95
    brownout_queue_min: int = 1
    brownout_streak: int = 4
    #: migrate the KV pool through LoadAdaptiveMesh tier changes
    #: (HETU_TPU_SERVE_KV_REPAGE, serving/reshard.py reshard_pool)
    kv_repage: bool = False

    def __post_init__(self):
        if isinstance(self.num_pages, list):
            self.num_pages = tuple(self.num_pages)
        if self.max_len % self.page_size:
            raise ValueError(f"max_len {self.max_len} must be a multiple "
                             f"of page_size {self.page_size}")
        if self.max_len % self.prefill_chunk:
            # the chunk program pads prompts to a chunk multiple; an
            # uneven tail would scatter past the [.., max_len, ..]
            # scratch cache (silently dropped by XLA — refuse instead of
            # leaning on out-of-bounds semantics)
            raise ValueError(f"max_len {self.max_len} must be a multiple "
                             f"of prefill_chunk {self.prefill_chunk}")
        if self.kv_quant not in ("none", "int8", "int4"):
            raise ValueError(f"kv_quant {self.kv_quant!r} invalid; "
                             "choices: ('none', 'int8', 'int4')")
        if self.moe_dispatch not in ("gspmd", "fp32", "int8", "int4"):
            raise ValueError(
                f"moe_dispatch {self.moe_dispatch!r} invalid; choices: "
                "('gspmd', 'fp32', 'int8', 'int4')")
        if self.spec_decode not in ("none", "ngram", "model"):
            raise ValueError(
                f"spec_decode {self.spec_decode!r} invalid; choices: "
                "('none', 'ngram', 'model')")
        if self.spec_decode != "none" and self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if self.serve_sample < 1:
            raise ValueError(f"serve_sample must be >= 1, "
                             f"got {self.serve_sample}")
        if self.retry_budget < 0:
            raise ValueError(f"retry_budget must be >= 0, "
                             f"got {self.retry_budget}")
        if not 0.0 < self.brownout_page_high <= 1.0:
            raise ValueError(f"brownout_page_high must be in (0, 1], "
                             f"got {self.brownout_page_high}")
        if self.brownout_streak < 1 or self.brownout_queue_min < 1:
            raise ValueError(
                "brownout_streak and brownout_queue_min must be >= 1, "
                f"got {self.brownout_streak}/{self.brownout_queue_min}")
        if self.num_pages == 0:
            self.num_pages = self.num_slots * (self.max_len
                                               // self.page_size)

    @property
    def lookahead(self) -> int:
        """Extra cache positions a verify step may write past the
        sequence head (0 without speculative decoding) — widens every
        page reservation (scheduler.py)."""
        return self.spec_k if self.spec_decode != "none" else 0

    @staticmethod
    def from_flags(**overrides) -> "ServeConfig":
        """Defaults from the serving flag surface (utils/flags.py:
        HETU_TPU_KV_QUANT + the serve-shape flags); explicit kwargs
        win."""
        from hetu_tpu.serving.request import parse_quotas
        from hetu_tpu.utils import flags
        vals = dict(
            num_slots=flags.int_flag("HETU_TPU_SERVE_SLOTS"),
            page_size=flags.int_flag("HETU_TPU_SERVE_PAGE"),
            max_len=flags.int_flag("HETU_TPU_SERVE_MAX_LEN"),
            prefill_chunk=flags.int_flag("HETU_TPU_SERVE_PREFILL_CHUNK"),
            num_pages=flags.int_flag("HETU_TPU_SERVE_PAGES"),
            kv_quant=flags.str_flag("HETU_TPU_KV_QUANT"),
            moe_dispatch=flags.str_flag("HETU_TPU_MOE_DISPATCH"),
            sampling=flags.bool_flag("HETU_TPU_SERVE_SAMPLE"),
            spec_decode=flags.str_flag("HETU_TPU_SPEC_DECODE"),
            spec_k=flags.int_flag("HETU_TPU_SPEC_K"),
            prefix_cache=flags.bool_flag("HETU_TPU_SERVE_PREFIX_CACHE"),
            prefix_cache_pages=flags.int_flag("HETU_TPU_SERVE_PREFIX_PAGES"),
            preempt=flags.bool_flag("HETU_TPU_SERVE_PREEMPT"),
            quotas=parse_quotas(flags.str_flag("HETU_TPU_SERVE_QUOTAS")),
            serve_sample=flags.int_flag("HETU_TPU_RUNLOG_SERVE_SAMPLE"),
            retry_budget=flags.int_flag("HETU_TPU_SERVE_RETRY"),
            deadline=flags.bool_flag("HETU_TPU_SERVE_DEADLINE"),
            brownout=flags.bool_flag("HETU_TPU_SERVE_BROWNOUT"),
            kv_repage=flags.bool_flag("HETU_TPU_SERVE_KV_REPAGE"),
        )
        vals.update(overrides)
        return ServeConfig(**vals)


def serving_view(model, params, reshard=None):
    """(the parameters the serving programs read, the bytes relaid for
    them, why).  A model may hold its fused weights for serving in
    another layout than training's (`serving_params`, the optional hook
    of models/generation.py): taken ONCE, at an engine's build, the
    leaves it does not relay shared with the caller's tree, so that no
    weight is held twice.  The view has no sharding specs: with a
    `reshard` hook (which re-shards the engine's parameters by the
    training specs) or a model built for tp > 1 (whose layers constrain
    by them), as for a family without the hook, the parameters are
    served as they came and the bytes are 0."""
    hook = getattr(model, "serving_params", None)
    tp = getattr(getattr(model, "strategy", None), "tp", 1)
    if hook is None:
        why = "the model brings no serving_params hook"
    elif reshard is not None:
        why = "the reshard hook re-shards by the training specs"
    elif tp > 1:
        why = f"tp = {tp}: the layers constrain by the training specs"
    else:
        own = dict(jax.tree.leaves_with_path(params))
        if any(isinstance(a, jax.ShapeDtypeStruct) for a in own.values()):
            # abstract parameters (a compile for a described chip) give
            # an abstract view: a leaf is the caller's where its shape is
            view = jax.eval_shape(hook, params)
            same = lambda a, b: b is not None and a.shape == b.shape  # noqa: E731
        else:
            view, same = hook(params), lambda a, b: a is b
        relaid = [a for path, a in jax.tree.leaves_with_path(view)
                  if not same(a, own.get(path))]
        return (view, sum(a.size * a.dtype.itemsize for a in relaid),
                f"{len(relaid)} fused weights held as matrices a product "
                f"takes in place ({type(model).__name__}.serving_params)")
    return params, 0, "served as they came: " + why


class ServingEngine:
    """Continuous-batching facade over (model, params)."""

    def __init__(self, model, params, config: Optional[ServeConfig] = None,
                 *, run_log: Optional[RunLog] = None,
                 registry: Optional[MetricsRegistry] = None,
                 reshard=None, tracer=None, health=None,
                 telemetry=None, drafter=None, draft_model=None,
                 draft_params=None, cost_model=None):
        self.model = model
        self.config = config or ServeConfig.from_flags()
        c = model.config
        _check_context_length(c, self.config.max_len)
        # what a token stores a layer is the MODEL's to say
        # (models/cache_contract.py): the pool, the prefill scratch and
        # the cache-byte gauges are all sized from this one contract
        self.cache = cache_contract(model)
        #: some layer reads a window only, or the layers differ in what a
        #: token stores (or K is wider than V): pages by kind of layer
        self.windowed = self.cache.by_kind
        #: some layer keeps a state a sequence and no pages (the
        #: contract's `state_shapes`): the pool holds it by slot, and the
        #: chunk and decode programs carry it
        self.stateful = bool(self.cache.state_kinds)
        #: some layer keeps NO cache of its own (the contract's `reads`):
        #: it attends the pages of the layer it names through that
        #: layer's table, or attends nothing; the pool holds nothing for it
        self.borrows = self.cache.borrows
        #: the kinds of page layer that attend for the ONE row of a chunk
        #: whose logits are read (`extend_cache`'s `read_row`): those of a
        #: model whose later layers keep no cache (`read_rows_from`; none
        #: where every layer runs for every row and the head alone for
        #: the one), and, a reading layer each, how far back the layer it
        #: reads does
        tail_from = getattr(model, "read_rows_from", self.cache.num_layers)
        self._tail_kinds = frozenset(
            k for k in range(len(self.cache.kinds))
            if min(self.cache.layers_of(k)) >= tail_from)
        self._read_windows = tuple(
            self.cache.windows[r] for r in self.cache.reads
            if r is not None and r >= 0)
        #: a layer each that SELECTS what it attends (the contract's
        #: `selects`), the most positions a query of it attends
        self._selects = tuple(k for k in self.cache.selects
                              if k is not None)
        if (self.cache.kind != "kv" or self.windowed or self.stateful
                or self.borrows):
            self._refuse_unbuilt(reshard, draft_model, drafter)
        self.pool = PagePool.for_contract(
            self.cache, num_pages=self.config.num_pages,
            page_size=self.config.page_size, quant=self.config.kv_quant,
            num_slots=self.config.num_slots)
        # radix prefix cache (serving/prefix_cache.py): shared prompt
        # prefixes admit with their pages already resident
        self.prefix_cache = None
        if self.config.prefix_cache:
            from hetu_tpu.serving.prefix_cache import RadixPrefixCache
            self.prefix_cache = RadixPrefixCache(
                self.pool, max_pages=self.config.prefix_cache_pages)
        self.scheduler = Scheduler(num_slots=self.config.num_slots,
                                   pool=self.pool,
                                   max_len=self.config.max_len,
                                   prefix_cache=self.prefix_cache,
                                   lookahead=self.config.lookahead,
                                   quotas=self.config.quotas,
                                   retry_budget=self.config.retry_budget)
        # per-request cost ledger (serving/costs.py): when a CostModel
        # rides along, every done event carries analytic cost_* fields
        # (prefill/decode FLOPs, page-seconds, KV byte-seconds, wire
        # bytes) for slo_report's per-tenant cost attribution
        self.ledger = None
        if cost_model is not None:
            from hetu_tpu.serving.costs import CostLedger
            self.ledger = CostLedger(cost_model)
        # speculative decoding (serving/spec_decode.py): host drafter +
        # the batched verify program built below; `drafter=` overrides
        # the config mode with any Drafter instance.  spec_decode=
        # 'model' builds a ModelDrafter from draft_model/draft_params
        # (resident-quantized; verified with the stochastic p/q rule)
        from hetu_tpu.serving.spec_decode import make_drafter
        if drafter is not None and self.config.spec_decode == "none":
            # the reservation lookahead and the verify program are both
            # sized by the config — a drafter without them would write
            # past reservations
            raise ValueError("a custom drafter needs spec_decode set "
                             "(e.g. ServeConfig(spec_decode='ngram')) so "
                             "the verify program and page lookahead exist")
        draft_kw = ({"model": draft_model, "params": draft_params}
                    if self.config.spec_decode == "model"
                    and draft_model is not None else {})
        self.drafter = (drafter if drafter is not None
                        else make_drafter(self.config.spec_decode,
                                          **draft_kw))
        self.spec = self.drafter is not None
        #: stochastic drafters report their proposal distribution and
        #: are verified with the full p/q rejection rule in-graph
        self.spec_stochastic = bool(
            self.spec and getattr(self.drafter, "stochastic", False))
        #: per-rid preemption counts + the work counters accrued before
        #: each requeue (requests survive requeues; their SlotState —
        #: and its RequestStats — does not): folded back into the final
        #: done event so acceptance-rate/chunk accounting describes the
        #: whole run, not just the last incarnation
        self._preempt_counts = {}
        self._carried_stats = {}
        #: fault-termination results produced OUTSIDE step() — fail_over
        #: runs between steps (the run() on_step hook), so its
        #: retry-exhausted casualties park here until the next step
        #: drains them into its finished list
        self._fault_results: List[RequestResult] = []
        #: consecutive steps at brownout pressure (the shed streak)
        self._brownout_hot = 0
        #: driver-clock time at the end of the last step — the default
        #: timestamp for between-step fault events (fail_over)
        self._last_clock = 0.0
        self.reshard = reshard
        self._registry = registry if registry is not None else get_registry()
        #: the record `step()` keeps of itself (utils/profiling.
        #: StepRecorder: counters `serve.step_wall_s`, `.phase_s{phase}`,
        #: `.empty_s`, `.stalled_steps{phase}`, ...), the full records of
        #: its last stalled steps, and the slowest `step()` so far:
        #: {"step", "now", "step_s", "phases": seconds per host phase,
        #: "cpu_s", "gap_s", "gc", "compiles", "compile_s", "dispatched",
        #: "fetch_wait_s", "median_s", "stalled", "bytes_in_use", ...}
        #: (None before the first; a caller may set it to None again)
        self._step_record = StepRecorder("serve", self._registry)
        self.slow_steps = self._step_record.slow_steps
        self.slowest_step: Optional[dict] = None
        #: seconds this step's deferred fetches waited for the device
        self._fetch_wait = 0.0
        #: the parameters as the programs read them: the model's serving
        #: view (`serving_params`) where it brings one, else the caller's
        self.params, self.relaid_weight_bytes, why = serving_view(
            model, params, reshard)
        self._registry.set_gauge("serve.relaid_weight_bytes",
                                 self.relaid_weight_bytes)
        # every kernel routing decision of this engine — the static ones
        # _build_programs takes, then each program's as it is traced —
        # with its reason (ops/pallas.record_routes), beside what was
        # relaid for the programs and why
        self.kernel_routes: dict = {
            "relaid_weight_bytes": self.relaid_weight_bytes,
            "relaid_weight_why": why}
        if run_log is None:
            path = default_runlog_path(None)
            run_log = RunLog(path) if path else None
            self._owns_runlog = run_log is not None
        else:
            self._owns_runlog = False
        self.run_log = run_log
        #: timestamp basis every serve event/span declares (the engine
        #: drives a virtual DRIVER clock in run()/tests; a live server
        #: embedding the engine on wall time sets "wall" so the fleet
        #: stitcher refuses to mix the two)
        self.clock_basis = "driver"
        # the flight recorder (HETU_TPU_SERVE_TRACE) and the serving
        # health detectors (HETU_TPU_HEALTH) — both host-side only, both
        # a single None check when their flag is unset; explicit
        # instances win over the flag gates (tests, tools)
        self.tracer = tracer if tracer is not None else \
            maybe_tracer(run_log=self.run_log, registry=self._registry)
        self.health = health if health is not None else \
            maybe_serving_health_monitor(runlog=self.run_log,
                                         registry=self._registry)
        #: optional obs.aggregate.TelemetrySource: serve events ride the
        #: cluster telemetry push so tools_cluster.py sees this worker
        self.telemetry = telemetry
        self.steps_done = 0
        # numerics observatory (obs/numerics.py, HETU_TPU_NUMERICS):
        # read once at build — unset means the decode/write programs
        # below are byte-identical to the flag not existing (registered
        # identity contract).  When on, the int8 KV-page quantize sites
        # tap their exact roundtrip SNR into a stats pytree the wrapped
        # programs return alongside their outputs.
        from hetu_tpu.obs.numerics import numerics_enabled, record_every
        self._numerics = numerics_enabled()
        self._numerics_every = record_every()
        self._numerics_stats = None
        # the numerics detectors (quant_snr_collapse on kv_pages, etc.)
        # ride the same HETU_TPU_HEALTH gate as the serving monitor
        # above — without this the serving side would RECORD SNR but
        # never watch it
        from hetu_tpu.obs.health import maybe_numerics_health_monitor
        self._num_health = (maybe_numerics_health_monitor(
            runlog=self.run_log, registry=self._registry,
            source=self.telemetry) if self._numerics else None)

        # MoE: resident quantized expert weights (serving/experts.py).
        # Quantized ONCE here, dequantized inside the compiled programs
        # — the params tree the engine holds stays int8/int4 on the
        # expert share.  The reshard hook moves fp params; composing it
        # with the quantized tree would reshard int payloads it cannot
        # re-slice — refuse loudly.
        n_exp = getattr(c, "num_experts", 0) or 0
        self._moe_spec = None
        if n_exp > 0 and self.config.moe_dispatch in ("int8", "int4"):
            if self.reshard is not None:
                raise ValueError(
                    "resident-quantized MoE experts (moe_dispatch="
                    f"{self.config.moe_dispatch!r}) do not compose with "
                    "the reshard hook — use gspmd/fp32 dispatch or drop "
                    "the hook")
            from hetu_tpu.serving.experts import (expert_bytes,
                                                  quantize_expert_tree)
            bits = 8 if self.config.moe_dispatch == "int8" else 4
            self.params, self._moe_spec = quantize_expert_tree(
                self.params, n_exp, bits=bits)
            eb = expert_bytes(self._moe_spec)
            self._registry.set_gauge("serve.moe_expert_bytes",
                                     eb["quantized_bytes"])
            self._registry.set_gauge("serve.moe_expert_bytes_fp",
                                     eb["fp_bytes"])

        # per-request prefill scratch: a dense [L, 1, max_len] cache the
        # chunk program advances IN PLACE (it is donated), one array per
        # array of the contract; every request is handed zeros of its
        # own at its first launch, so no call can consume another's
        # buffer
        # A WINDOW kind's scratch holds the window and one chunk, and
        # slides (`extend_cache(slide=True)`), where chunks and pages
        # line up (a page of the pool is then a block of the scratch)
        C, ps = self.config.prefill_chunk, self.config.page_size
        self._slide = self.windowed and C % ps == 0 \
            and self.config.max_len % ps == 0
        self._fresh_scratch = jax.jit(functools.partial(
            init_cache, model, 1, self.config.max_len,
            **(dict(chunk=C, page=ps) if self._slide else {})))
        #: the chunks ONE launch of the chunk program may carry (a
        #: program a shape: `_chunk_program`), by what is observed here
        #: alone: the chunk's rows and whether the scratch slides
        self._launch_multiples, why = launch_multiples(C, self._slide)
        self.kernel_routes["prefill_launch_rows"] = {
            "rows": [k * C for k in self._launch_multiples], "why": why}
        from hetu_tpu.serving.kv_pool import contract_bytes_per_token
        itemsize = jnp.dtype(c.compute_dtype).itemsize
        mode = (self.config.kv_quant if self.config.kv_quant != "none" else
                {2: "bf16", 4: "fp32"}[itemsize])
        self._registry.set_gauge(
            "serve.kv_bytes_per_token",
            contract_bytes_per_token(self.cache, mode))
        #: per kind of layer, the label of its series and the positions
        #: its prefill scratch holds
        self._kind_names = tuple("full" if w is None else f"window_{w}"
                                 for w in self.cache.kinds)
        self._scratch_positions = tuple(
            a.shape[2] for a in jax.eval_shape(self._fresh_scratch)[
                ::len(self.cache.token_shapes)])
        for k, kind in enumerate(self._kind_names
                                 if self.windowed or self.stateful else ()):
            # what a token costs in each kind of layer, from the kind's
            # own shapes: a window kind's share is paid for the last
            # `window` positions only; and what a prefilling request's
            # scratch takes of each kind
            layers = len(self.cache.layers_of(k))
            self._registry.set_gauge(
                "serve.kv_bytes_per_token",
                itemsize * layers * sum(
                    math.prod(x) for x in self.cache.token_shapes_of(k)),
                kind=kind)
            if self.windowed:
                self._registry.set_gauge(
                    "serve.prefill_scratch_bytes",
                    itemsize * layers * self._scratch_positions[k]
                    * sum(math.prod(x)
                          for x in self.cache.stored_shapes_of(k)),
                    kind=kind)
        if self.stateful:
            # a state kind costs a token nothing and a slot its state,
            # however long the sequence
            K = len(self.cache.kinds)
            per_slot = [self.cache.state_bytes_per_slot(K + i)
                        for i in range(len(self.cache.state_kinds))]
            self._state_bytes_per_slot = sum(per_slot)
            for i, nbytes in enumerate(per_slot):
                kind = "state" if i == 0 else f"state_{i}"
                self._registry.set_gauge("serve.kv_bytes_per_token", 0,
                                         kind=kind)
                self._registry.set_gauge("serve.state_bytes_per_slot",
                                         nbytes, kind=kind)
        #: the running stats vector of the programs of a model that
        #: counts (what `model.STATS` names: an expert model's assignment
        #: counts), on the device between fetches; None for a model whose
        #: STATS is empty: its programs carry none
        self._stats_zero = model.zero_stats() if model.STATS else None
        self._stats_acc = self._stats_zero
        #: the decode program dispatched last whose tokens the host has
        #: not fetched (`step`: one step stays queued on the device), and
        #: what the next program reads in its place when there is none
        self._inflight: Optional[_InFlight] = None
        self._no_tokens = jnp.zeros(
            self.config.num_slots + len(model.STATS), jnp.int32)
        #: perf_counter at the last emit of a decode program's tokens
        self._landed_t = 0.0
        # what the engine holds AT REST, to lay a stalled step's
        # `bytes_in_use` and a run's peak against: the parameters as the
        # programs read them, the page pool, and the prefill scratch ONE
        # prefilling request holds (a slot in prefill has its own)
        for what, tree in (("weights", self.params),
                           ("pool", self.pool.arrays.tree()),
                           ("scratch", jax.eval_shape(self._fresh_scratch)),
                           *([("state", self.pool.state)]
                             if self.stateful else [])):
            self._registry.set_gauge(
                "serve.held_bytes",
                sum(a.size * a.dtype.itemsize
                    for a in jax.tree.leaves(tree)), what=what)
        with record_routes(self.kernel_routes):
            self._build_programs()

    def _refuse_unbuilt(self, reshard, draft_model, drafter):
        """A model whose cache is not of the K/V kind (latent attention),
        or some of whose layers read a window only, runs the normal path;
        what this engine has only for K/V pools every layer of which
        keeps everything is refused here by name, never run as something
        else.  (The disaggregated prefill tier refuses such a model
        itself: serving/disagg.PrefillWorker, `adopt_prefilled`.)"""
        cfg, name = self.config, type(self.model).__name__
        unbuilt = {
            "speculative decoding (spec_decode / verify_step_*)":
                cfg.spec_decode != "none" or drafter is not None
                or draft_model is not None,
            "the radix prefix cache (prefix_cache)": cfg.prefix_cache,
            "int8 / int4 pages (kv_quant)": cfg.kv_quant != "none",
            "the reshard hook (serving/reshard.py)": reshard is not None,
        }
        if self.cache.kind != "kv":
            # (over K/V pages, window layers or not, the store is built)
            unbuilt["resident quantized experts (moe_dispatch int8 / int4, "
                    "serving/experts.py)"] = \
                cfg.moe_dispatch in ("int8", "int4")
        if self.stateful or self.borrows:
            # no page holds a state layer's past: a shared prefix has no
            # state to start from, a draft block none to fall back to;
            # a layer that reads another's pages has no table of its own
            # to share, verify or move
            unbuilt["moving the pool with the parameters (kv_repage)"] = \
                cfg.kv_repage
        asked = [what for what, on in unbuilt.items() if on]
        if asked:
            from hetu_tpu.models.generation import unpaged_layers
            keeps = "; ".join(
                [f"{len(self.cache.layers_of(k))} layers keep "
                 + ("every position" if w is None
                    else f"the last {w} positions")
                 for k, w in enumerate(self.cache.kinds)]
                + [f"{len(self.cache.layers_of(len(self.cache.kinds) + i))} "
                   f"layers keep a state a sequence {shapes}"
                   for i, shapes in enumerate(self.cache.state_kinds)]
                + ([unpaged_layers(self.cache)]
                   if self.stateful or self.borrows else []))
            raise NotImplementedError(
                f"{name} stores {self.cache.token_shapes} a token a layer "
                f"({self.cache.kind!r}: {keeps}); not built for it: "
                + "; ".join(asked))

    # ------------------------------------------------------------ build
    def _program(self, name: str):
        """The jitted program of `self._table[name]`: its body and what
        the engine does to EVERY body, written once, in this order from
        the body outwards: resident int experts dequantized on entry (so
        only the transient working copy is fp: the decode step's expert
        HBM read is the quantized payload), the numerics collector (the
        stats pytree rides out as one more output, LAST: `_call` peels
        it), the kernel routes the trace takes noted in
        `self.kernel_routes`, and the donation.  Nothing here names an
        argument but the first.  Where neither wrapper applies the body
        IS the program (the HETU_TPU_NUMERICS=0 and moe_dispatch="gspmd"
        identity contracts hold by construction), and the compiled
        module keeps the body's name (`jit_decode_fn`: what a device
        trace finds a program's events by, benchmarks/metrics)."""
        from hetu_tpu.obs.numerics import collecting
        from hetu_tpu.serving.experts import dequantize_expert_tree
        p = self._table[name]
        spec = self._moe_spec if p.takes_params else None
        collect = self._numerics and p.numerics

        @functools.wraps(p.body)
        def program(*args):
            with record_routes(self.kernel_routes), (
                    collecting() if collect
                    else contextlib.nullcontext()) as col:
                if spec is not None:
                    args = (dequantize_expert_tree(args[0], spec), *args[1:])
                out = p.body(*args)
                return (out, col.finalize()) if collect else out
        return jax.jit(program, donate_argnums=(p.donate,))

    def _call(self, name: str, *args):
        """Dispatch one of the engine's programs, peeling the numerics
        stats output where `_program` added it (latest wins until
        recorded)."""
        out = self._jits[name](*args)
        if self._numerics and self._table[name].numerics:
            out, stats = out
            if stats:
                self._numerics_stats = stats
        return out

    def _build_programs(self):
        model, pool = self.model, self.pool
        sampling_on = self.config.sampling

        def pick_token(logits, positions, sample_args):
            """Next token per slot: plain argmax (the byte-identical
            default), or the in-graph sampler when the engine was built
            with HETU_TPU_SERVE_SAMPLE (serving/sampling.py; greedy
            rows still argmax inside it)."""
            if not sampling_on:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            from hetu_tpu.serving.sampling import sample_tokens
            seeds, temps, top_ks, top_ps = sample_args
            # the emitted token's sequence position is positions + 1
            # (its input rides at `positions`) — the (seed, position)
            # key derivation every sampling site in the engine shares
            return sample_tokens(logits, seeds, positions + 1,
                                 temps, top_ks, top_ps)

        #: 1 where the programs carry the model's running stats vector
        #: (`model.STATS`), as one more argument and result; else 0
        counts = int(bool(model.STATS))

        def decode_fn(params, pool_tree, table, tokens, positions, prev,
                      *rest):
            stats, sample_args = rest[:counts], rest[counts:]
            # a slot's token is the host's where the host knows it; -1
            # where it is what the decode program before this one gave
            # the slot (`prev`, that program's output, never fetched in
            # between: `ServingEngine.step`)
            tokens = jnp.where(tokens < 0, prev[:tokens.shape[0]], tokens)
            # each layer scatters this token's entries into the slot's
            # page and attends the pool where it lies, by the attention
            # its own `attend_paged` chooses.  The tree is the pool's
            # own: int8/int4 pools carry (k, v, k_scale, v_scale)
            logits, pool_tree, *stats = decode_step_paged(
                model, params, tokens, pool_tree, table, positions, *stats)
            nxt = pick_token(logits, positions, sample_args)
            # the stats ride out behind the tokens: one fetch
            return (jnp.concatenate([nxt, *stats]) if stats else nxt,
                    pool_tree)

        slide = (dict(slide=True, max_len=self.config.max_len)
                 if self._slide else {})

        # with state layers `cache` is the request's scratch and, behind
        # it, the pool's state arrays (both donated, both carried), and
        # two more arguments say which row of them is the slot's and how
        # many of the chunk's rows are the prompt's
        state_args = ("state_row", "valid") if self.stateful else ()

        # the head (and the layers of a model whose later layers keep no
        # cache: `read_rows_from`) runs for the ONE row a finished prompt
        # samples from (`row`; negative: the chunk does not end its
        # prompt and runs none of it), and the program hands back that
        # row's logits alone, [1, 1, vocab].  `chunk` is [1, k x C]: one
        # program a launch shape (the table below), this one body
        def chunk_fn(params, chunk, cache, start, row, *rest):
            state = dict(zip(state_args, rest))
            logits, cache, *stats = extend_cache(
                model, params, chunk, cache, start, *rest[len(state):],
                **slide, **state, read_row=row)
            # the greedy first token of a prompt that ends on `row` of
            # this chunk: taken here, fetched at the step's end
            with jax.named_scope("lm_head"):
                first = jnp.argmax(logits[0, 0], axis=-1).astype(jnp.int32)
            return (logits, first, cache, *stats)

        by_kind = self.windowed

        def write_fn(pool_tree, pages_row, *caches):
            with jax.named_scope("kv_write"):
                if by_kind:
                    # the scratch as the chunk program carries it,
                    # [layers, 1, positions, ...]: its one row is taken
                    # HERE, not by an eager slice (and a copy) an array
                    # at every prompt's end (`_scratch_rows`)
                    caches = tuple(c[:, 0] for c in caches)
                return pool.write_pages(pool_tree, pages_row, *caches)

        # speculative-decoding verify (serving/spec_decode.py): score
        # the last token + k drafts in one multi-query forward
        # (`verify_step_paged`: each layer scatters the block's K/V and
        # attends by its own `attend_paged`, the multi-query kernel or
        # the composition), and compute the acceptance in-graph; the
        # host only reads [S, k+1] target tokens and [S] emit counts,
        # never the logits.  When the fused `sample` kernel routes, the
        # forward returns last-layer HIDDEN rows and the lm_head matmul +
        # filter + draw fuse into one epilogue launch — the
        # [S, k+1, vocab] logits plane never touches HBM.
        K1 = self.config.spec_k + 1
        stochastic = self.spec_stochastic
        self.verify_fused_sample = False
        if self.spec and not stochastic:
            from hetu_tpu.ops.pallas import resolve_route
            from hetu_tpu.ops.pallas import sample as _psample
            mc = model.config
            self.verify_fused_sample = resolve_route(
                "sample", _psample.check_shapes,
                (self.config.num_slots * K1, mc.hidden_size),
                (mc.hidden_size, mc.vocab_size))
        fused_sample = self.verify_fused_sample

        def full_sample_args(tokens, sample_args):
            """The per-slot sampling vectors, or the all-greedy ones
            when the engine runs without HETU_TPU_SERVE_SAMPLE (the
            fused/stochastic epilogues take them unconditionally;
            temp 0 rows argmax, so greedy stays greedy)."""
            if sampling_on:
                return sample_args
            S = tokens.shape[0]
            return (jnp.zeros((S,), jnp.uint32),
                    jnp.zeros((S,), jnp.float32),
                    jnp.zeros((S,), jnp.int32),
                    jnp.zeros((S,), jnp.float32))

        def verify_fn(params, pool_tree, table, tokens, positions, *rest):
            # (stochastic acceptance: the drafter's distributions ride
            # in before the sampling vectors)
            q_probs, sample_args = (rest[:int(stochastic)],
                                    rest[int(stochastic):])
            pos_grid = positions[:, None] + jnp.arange(K1, dtype=jnp.int32)
            out, new_tree = verify_step_paged(
                model, params, tokens, pool_tree, table, positions,
                return_hidden=fused_sample)
            if stochastic:
                from hetu_tpu.serving.spec_decode import stochastic_verify
                seeds, temps, top_ks, top_ps = full_sample_args(
                    tokens, sample_args)
                targets, n_emit = stochastic_verify(
                    out, *q_probs, tokens[:, 1:], seeds, pos_grid + 1,
                    temps, top_ks, top_ps)
                return targets, n_emit, new_tree
            if fused_sample:
                from hetu_tpu.serving.sampling import sample_hidden_grid
                seeds, temps, top_ks, top_ps = full_sample_args(
                    tokens, sample_args)
                targets = sample_hidden_grid(
                    out, lm_head_weight(model, params), seeds,
                    pos_grid + 1, temps, top_ks, top_ps)
            elif sampling_on:
                from hetu_tpu.serving.sampling import sample_token_grid
                seeds, temps, top_ks, top_ps = sample_args
                targets = sample_token_grid(out, seeds, pos_grid + 1,
                                            temps, top_ks, top_ps)
            else:
                targets = jnp.argmax(out, axis=-1).astype(jnp.int32)
            match = (targets[:, :-1] == tokens[:, 1:]).astype(jnp.int32)
            n_emit = jnp.cumprod(match, axis=1).sum(axis=1) + 1      # [S]
            return targets, n_emit.astype(jnp.int32), new_tree

        # prefix-cache prime (serving/prefix_cache.py): gather a slot's
        # resident shared-prefix pages into the dense prefill scratch so
        # suffix chunks attend over them (read-only — not donated)
        def prime_fn(pool_tree, pages_row):
            return pool.gather(pool_tree, pages_row[None])

        # THE table of the engine's programs: `_program` builds each
        # once, `_dummy_args` / `lower_programs` / `warmup` read it; an
        # argument a family brings is added at the body and at the call.
        # The pool tree is donated: the KV pool is the engine's dominant
        # allocation and it flows through every step — without donation
        # XLA would copy the whole pool to update one token per slot
        # (the engine always reassigns self.pool.arrays from the
        # returned tree, so the donated input is never reused).  The
        # paged programs keep that promise inside too: the pool is a
        # carry of their layer loop, written in place, one buffer from
        # argument to result (models/generation._walk_layers;
        # tests/test_chip_compile.py holds the compiled program to it).
        # With speculative decoding on, the verify program IS the
        # decode-step program (there is no single-token decode to build).
        # The scratch is donated too, and a carry of the chunk program's
        # layer walk: a launch changes one chunk's tokens a layer where
        # they lie (as an xs -> ys it read and wrote all 201 MB of the
        # InternLM2 cells' scratch; donated but not carried, or carried
        # but not donated, a copy stayed: PERF.md s6, PR 30).  The chunk
        # program has no quantize site to collect; the page write takes
        # no parameters.
        self._table = {
            "verify" if self.spec else "decode": _Program(
                verify_fn if self.spec else decode_fn, 1,
                self._null_step_args),
            "write_pages": _Program(write_fn, 0, self._null_write_args,
                                    takes_params=False),
        }
        for k in self._launch_multiples:
            # a launch of k chunks is a row, and a compile, of its own;
            # the module keeps the body's name at every shape
            # (`jit_chunk_fn`: a device trace's median of the chunk
            # program is over all its launches), the largest LAST: of two
            # texts of one name benchmarks/trace.py joins the later
            self._table[_chunk_program(k)] = _Program(
                chunk_fn, 2, functools.partial(self._null_chunk_args, k),
                numerics=False)
        self._jits = {name: self._program(name) for name in self._table}
        self._prime_jit = (jax.jit(prime_fn)
                           if self.prefix_cache is not None else None)

    # ---------------------------------------------------- numerics taps
    def _maybe_record_numerics(self):
        """Every HETU_TPU_NUMERICS_EVERY engine steps, host-fetch the
        latest stats pytree and fan it out through the one numerics
        sink (RunLog record + registry gauges + telemetry)."""
        if (not self._numerics or self._numerics_stats is None
                or self.steps_done % self._numerics_every):
            return
        from hetu_tpu.obs import numerics as _numerics
        try:
            host = jax.device_get(self._numerics_stats)
        except Exception:   # telemetry never kills an engine step
            self._numerics_stats = None
            return
        self._numerics_stats = None
        _numerics.record(host, step=self.steps_done,
                         registry=self._registry, runlog=self.run_log)
        if self._num_health is not None:
            self._num_health.observe(self.steps_done, host)

    # ------------------------------------- arguments at the null page
    def _null_step_args(self):
        """The decode program's or, with speculative decoding, the verify
        program's: every slot at the null page (a zero table), the
        sampling vectors of no request."""
        S, K1 = self.config.num_slots, self.config.spec_k + 1
        head = (self.params, self.pool.tree(),
                jnp.zeros(self.scheduler.page_table.shape, jnp.int32))
        pos = jnp.zeros(S, jnp.int32)
        sample_args = self._sample_args([]) if self.config.sampling else ()
        if not self.spec:
            return (*head, jnp.zeros(S, jnp.int32), pos, self._no_tokens,
                    *self._stats_args(), *sample_args)
        vocab = self.model.config.vocab_size
        return (*head, jnp.zeros((S, K1), jnp.int32), pos,
                *((jnp.full((S, K1 - 1, vocab), 1.0 / vocab, jnp.float32),)
                  if self.spec_stochastic else ()), *sample_args)

    def _null_chunk_args(self, k: int):
        # (a launch of k chunks; state layers: the null slot's row, every
        # row the prompt's)
        R = k * self.config.prefill_chunk
        return (self.params, jnp.zeros((1, R), jnp.int32),
                tuple(self._fresh_scratch()) + self.pool.state,
                jnp.int32(0), jnp.int32(0),
                *((jnp.int32(self.pool.null_slot), jnp.int32(R))
                  if self.stateful else ()), *self._stats_args())

    def _null_write_args(self):
        return (self.pool.arrays.tree(),
                jax.tree.map(jnp.asarray, self.scheduler.null_write_rows()),
                *self._scratch_rows(self._fresh_scratch()))

    def _dummy_args(self, program: str):
        """Arguments of the engine's own shapes for one of its programs
        ("decode" | "verify", "write_pages", "prefill_chunk" and, a
        launch of k > 1 chunks, "prefill_chunk_x<k>"), all aimed at the
        null page (zero table/row): what `warmup` runs and
        `lower_programs` abstracts."""
        if program not in self._table:
            raise ValueError(f"unknown program {program!r}")
        return self._table[program].null_args()

    def lower_programs(self, sharding=None) -> dict:
        """{program: jax.stages.Lowered} for the decode step ("verify"
        with speculative decoding on), the page write and the prefill
        chunk at every launch shape the engine can issue
        ("prefill_chunk", "prefill_chunk_x2", ...: `launch_multiples`).
        By default lowered for the arguments `warmup` called the
        programs with, as they are: `.compile()` is then the executable
        the engine's own jitted function built and runs (one lowering in
        JAX's caches, no second compile), and `.as_text()` names its
        instructions as a device trace of the engine does.  (Abstract
        arguments that state a placement are another lowering: the
        compiler numbered its fusions otherwise, and a trace's events
        missed the text: PERF.md s6, PR 53.)  `sharding` instead places
        ABSTRACT arguments of the engine's shapes on a described device
        and compiles the programs for a chip that is not attached
        (tests/test_chip_compile.py)."""
        def abstract(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=sharding), tree)
        return {name: fn.lower(*(
            self._dummy_args(name) if sharding is None
            else abstract(self._dummy_args(name))))
            for name, fn in self._jits.items()}

    def _note_programs(self):
        """Which text a reader of this engine's device trace joins: per
        program its instructions and the text's fingerprint (the first
        12 hex digits of its SHA-1), on the `kernel_routes` line; with
        HETU_TPU_PROFILE and a RunLog, each program's `profile` record
        (obs.hlo_profile.profile_record: the static profile and how
        `scope_map` placed its instructions)."""
        from hetu_tpu.utils import flags as _flags
        profile = (self.run_log is not None
                   and _flags.bool_flag("HETU_TPU_PROFILE"))
        noted = self.kernel_routes["programs"] = {}
        for name, low in self.lower_programs().items():
            compiled = low.compile()
            text = compiled.as_text()
            noted[name] = {
                "instructions": sum(
                    INSTR_PAT.match(ln) is not None
                    for ln in text.splitlines()),
                "fingerprint": hashlib.sha1(text.encode()).hexdigest()[:12]}
            if profile:
                from hetu_tpu.obs.hlo_profile import profile_record
                self.run_log.log("profile", name=name, **noted[name],
                                 **profile_record(compiled, text=text))

    def warmup(self):
        """Compile every program, the chunk program at every launch shape
        it can be issued at, so that no request's TTFT is a compile.
        The dummy decode/write still target the null page
        (zero table/row), so pool CONTENT is untouched — but the pool
        trees are donated through the calls, so the returned trees must
        be committed back (discarding them would leave self.pool.arrays
        pointing at deleted buffers on donating backends)."""
        step = "verify" if self.spec else "decode"
        nxt, *_, tree = self._call(step, *self._dummy_args(step))
        self.pool.commit(tree)
        if not self.spec:
            # and as the queued step dispatches it: the tokens of the
            # decode before it an output still on the device
            args = list(self._dummy_args("decode"))
            args[5] = nxt
            nxt, tree = self._call("decode", *args)
            self.pool.commit(tree)
        for name in map(_chunk_program, self._launch_multiples):
            lg, _, cache = self._call(name, *self._dummy_args(name))[:3]
            if self.stateful:
                # (the state arrays were donated behind the scratch)
                self.pool.state = tuple(
                    cache[len(cache) - len(self.pool.state):])
        tree = self._call("write_pages", *self._dummy_args("write_pages"))
        self.pool.arrays = PoolArrays.from_tree(tree)
        if self._prime_jit is not None:
            jax.block_until_ready(self._prime_jit(
                self.pool.arrays.tree(),
                jnp.zeros(self.scheduler.max_pages, jnp.int32)))
        jax.block_until_ready((nxt, lg, cache))
        self._note_programs()
        return self

    # ----------------------------------------------------------- intake
    def submit(self, req: Request, now: Optional[float] = None):
        if req.sampling.temperature > 0 and not self.config.sampling:
            raise ValueError(
                f"request {req.rid} asks for sampling (temperature "
                f"{req.sampling.temperature}) but the engine was built "
                "greedy-only — set HETU_TPU_SERVE_SAMPLE=1 / "
                "ServeConfig(sampling=True)")
        if now is not None:
            req.arrival_t = now
        self.scheduler.submit(req)
        self._registry.inc("serve.requests_submitted")
        self._registry.inc("serve.requests_submitted_class",
                           slo_class=req.slo.name)
        if self.tracer is not None:
            self.tracer.on_submit(req)

    def note_remote_submit(self, req: Request,
                           now: Optional[float] = None):
        """Account a request whose PREFILL runs on a remote tier
        (serving/disagg.py): the submission counters and the tracer's
        queued span open here — on the decode replica that will own the
        request — but the request does NOT enter the scheduler queue
        (it admits via `adopt_prefilled` when its KV shipment lands, or
        re-enters through `submit` on colocation fallback)."""
        if req.sampling.temperature > 0 and not self.config.sampling:
            raise ValueError(
                f"request {req.rid} asks for sampling (temperature "
                f"{req.sampling.temperature}) but the engine was built "
                "greedy-only — set HETU_TPU_SERVE_SAMPLE=1 / "
                "ServeConfig(sampling=True)")
        if now is not None:
            req.arrival_t = now
        self._registry.inc("serve.requests_submitted")
        self._registry.inc("serve.requests_submitted_class",
                           slo_class=req.slo.name)
        if self.tracer is not None:
            self.tracer.on_submit(req)

    def adopt_prefilled(self, req: Request, ks, vs, t1: int,
                        now: float) -> bool:
        """Adopt a prefill-tier KV shipment (serving/disagg.py): admit
        `req` straight into a free slot (`admit_direct` — it never
        queues), scatter the shipped scratch K/V into its pages through
        the SAME write program colocated prefill uses, seed the stream
        with the shipped first token, and join the decode batch.  The
        shipment carries the full [L, max_len, n_kv, hd] scratch the
        prefill tier computed with the identical chunk program, so pool
        content — and therefore every subsequent decode token — is
        byte-identical to the single-engine run.  False = no slot/
        reservation/quota headroom right now; the caller retries next
        step (the shipment stays pending, the dedupe seq unburned)."""
        if self.windowed or self.stateful or self.borrows:
            from hetu_tpu.models.generation import unpaged_layers
            raise NotImplementedError(
                f"{type(self.model).__name__} has layers that read a window "
                "only, keep a state a sequence or keep no cache of their "
                f"own ({unpaged_layers(self.cache) or 'window layers'}); the "
                "disaggregated prefill tier (adopt_prefilled, "
                "serving/disagg.py) is not built for them")
        # the shipment's pages and first token are written from the host:
        # with nothing queued, and every finish seen (a slot may be free)
        self._drain("adopt", lambda: now)
        adm = self.scheduler.admit_direct(req, now)
        if adm is None:
            reason = self.scheduler.last_stall or "none"
            self._registry.inc("serve.admission_stalls", reason=reason)
            if self.tracer is not None:
                self.tracer.on_stall([req.rid], reason)
            return False
        slot_idx, st = adm
        if self.ledger is not None:
            self.ledger.on_admit(req.rid, len(st.pages), now)
        if self.tracer is not None:
            self.tracer.on_admit(req, slot_idx, now, shared_tokens=0)
        pages_row = np.full(self.scheduler.max_pages, PagePool.NULL_PAGE,
                            np.int32)
        pages_row[: len(st.pages)] = st.pages
        tree = self._call("write_pages", self.pool.arrays.tree(),
                               jnp.asarray(pages_row),
                               jnp.asarray(ks), jnp.asarray(vs))
        self.pool.arrays = PoolArrays.from_tree(tree)
        st.prefilling = False
        st.pos = req.prompt_len
        st.generated.append(int(t1))
        st.stats.first_token_t = now
        st.stats.token_ts.append(now)
        ttft = st.stats.ttft_s
        self._registry.observe("serve.ttft_s", ttft)
        self._registry.observe("serve.ttft_s_class", ttft,
                               slo_class=req.slo.name)
        if st.stats.queue_wait_s is not None:
            self._registry.observe("serve.queue_wait_s",
                                   st.stats.queue_wait_s)
        self._registry.inc("serve.tokens_out")
        self._registry.inc("serve.disagg_adoptions")
        if self.tracer is not None:
            self.tracer.on_first_token(req, slot_idx, now, chunk=0)
        if self.health is not None:
            self.health.observe_ttft(ttft, step=self.steps_done, t=now)
        if self._sampled(req.rid):
            self._log_serve(event="admit", req=req.rid,
                            slot=slot_idx, prompt_len=req.prompt_len,
                            chunks=0, ttft_s=ttft,
                            queue_wait_s=st.stats.queue_wait_s, now=now,
                            slo_class=req.slo.name, tenant=req.tenant,
                            shared_tokens=0, disagg=True,
                            queue_depth=self.scheduler.queue_depth,
                            page_util=self.pool.utilization,
                            **self._weight_fields())
        # a max_new=1 request finishes at adoption: park its result with
        # the between-step fault results; the next step() drains them
        self._maybe_finish(slot_idx, st, int(t1), now,
                           self._fault_results)
        return True

    def _sampled(self, rid: int) -> bool:
        """Does `rid` emit per-request serve events?  Deterministic
        hashed 1-in-N (HETU_TPU_RUNLOG_SERVE_SAMPLE, request.py
        `rid_sampled`) — the same requests are sampled on every replay,
        and N=1 (the default) keeps the RunLog byte-identical to the
        pre-sampling engine.  Registry counters are never sampled."""
        return rid_sampled(rid, self.config.serve_sample)

    def _weight_fields(self) -> dict:
        """The sample_weight stamp for sampled per-request events (only
        when sampling is actually on — the N=1 record shape is
        unchanged)."""
        n = self.config.serve_sample
        return {"sample_weight": n} if n > 1 else {}

    def _log_serve(self, **fields):
        """One serve event to every attached sink: the RunLog and (when
        a TelemetrySource rides along) the cluster telemetry push.
        Every record declares its ``clock`` basis (driver|wall — the
        engine drives a virtual driver clock; see obs/spans.py) so the
        fleet stitcher can refuse mixed-basis inputs."""
        fields.setdefault("clock", self.clock_basis)
        rec = None
        if self.run_log is not None:
            rec = self.run_log.log("serve", **fields)
        if self.telemetry is not None:
            if rec is None:
                rec = dict(fields, kind="serve", t=time.time())
            self.telemetry.note_event(rec)

    # ------------------------------------------------------------- step
    def step(self, now: float) -> List[RequestResult]:
        """One engine iteration at driver time `now`: admit every
        admissible queued request (reservation only), spend the step's
        prompt rows on the PREFILLING slots, dispatch one decode step
        over the slots whose prefill is complete, and only then wait for
        the device.  Returns requests that finished this step.

        **A step's prompt rows: one chunk's a prefilling slot, spent
        oldest first.**  With n slots in prefill a step computes n x
        `prefill_chunk` prompt rows, never more: that budget is the
        disaggregation contract (a long prompt adds engine steps, never
        a multi-chunk stall to the decode batch's inter-token gap).  It
        is not spent one chunk a slot: the slots are taken in admission
        order (`SlotState.admit_seq`), and each takes ONE launch of the
        largest k of `launch_multiples` chunks within what its padded
        prompt has left and what is left of the budget
        (`plan_prefill_launches`), until the budget is spent; a slot left
        with nothing waits a step.  A launch streams the whole stack's
        weights whatever its rows, so the same rows in fewer, larger
        launches (at most `PREFILL_LAUNCH_ROWS`: the derivation is at the
        constant) are the same work in less time; and first come, first
        served inside a step lowers the mean time to a first token and
        raises none past what the budget already implied.  Refused, by
        what the engine observes and with the reason on the
        `kernel_routes` line (`"prefill_launch_rows"`): a chunk of more
        than `PREFILL_LAUNCH_ROWS` / 2 rows (k = 1: one chunk a slot a
        step, in admission order), and a window kind's sliding scratch,
        which holds the window and ONE chunk.  `serve.prefill_chunks`
        counts the LAUNCHES, `serve.prefill_launches{rows}` each shape's,
        `serve.prefill_tokens` the prompt rows.

        **One step stays queued on the device.**  Step k dispatches its
        chunk programs and decode k, and then fetches the tokens of
        decode k-1 (`serve.token_fetch`) and the first tokens of the
        prompts that ended in step k (`serve.first_token`): while the
        host emits, tidies, returns to its caller, admits and builds
        step k+1, the device runs decode k.  Decode k needs nothing the
        host has not seen: a slot's position is the host's own count
        (`SlotState.pos + inflight`), and its token is decode k-1's
        output, selected per slot ON THE DEVICE against the host's
        vector (a slot whose token the host knows: a first token; -1
        otherwise).  A slot whose tokens are all dispatched
        (`len(generated) + inflight == max_new_tokens`) is not in the
        next batch, so no row is computed past a length finish; an EOS is
        seen one fetch late, and the one row already dispatched for that
        slot is discarded (`serve.overrun_rows`): it wrote at a position
        the slot's own reservation covers.  A prompt's first token is the
        chunk program's own argmax of the row the prompt ends on, fetched
        at the step's end; its slot joins the NEXT step's decode from
        the host's vector.  A slot is in the scheduler until its last
        token is emitted, and past its prefill only with a first token.

        **The pool needs no ordering of its own.**  Every program takes
        the pool tree and returns it (donated), so the device runs them
        in dispatch order; pages the host releases (`_maybe_finish`,
        `_release_behind_windows`) while a program that reads them is in
        flight can only be written by programs dispatched later.

        **The same loop drains** (`_drain`: fetch and emit at once;
        `serve.pipeline_drains{why}`) where what comes next depends on
        values the host has not seen, or rewrites slots, pages or
        parameters under the programs: speculative decoding (the accepted
        count sets the positions: every step), a sampled first token,
        a preemption, `fail_over`, a deadline that expires a live slot,
        brownout pressure, a reshard, `adopt_prefilled`, a step with
        nothing to dispatch, `close` and the end of `run`.

        Every dispatch, sync and bookkeeping block runs inside one of the
        host phase spans of `STEP_PHASES` (utils/profiling.phase_span): a
        TraceAnnotation on the profiler's clock, nested in `serve.step`,
        and the phase's seconds in this step's record (`self._step_record`,
        utils/profiling.StepRecorder), which ends in the counters and
        histograms of `_note_step_phases`, in `self.slow_steps` for a
        stalled step and, for the slowest step so far, in
        `self.slowest_step` (docs/serving.md).  The plan and the loop
        over the slots between the blocks are in no phase, so the phases
        sum to a little under the step."""
        t0 = time.perf_counter()

        def clock() -> float:
            return now + (time.perf_counter() - t0)

        self._fetch_wait = 0.0
        finished: List[RequestResult] = []
        phases = self._step_record.begin()
        with jax.profiler.TraceAnnotation("serve.step"):
            while True:
                with phase_span("serve.admit", phases):
                    why = self._admit(clock, finished)
                if why is None:
                    break
                self._drain(why, clock, finished, phases)

            ends: List[_PromptEnd] = []
            prefilling = sorted(
                ((i, st) for i, st in enumerate(self.scheduler.slots)
                 if st is not None and st.prefilling),
                key=lambda slot: slot[1].admit_seq)
            plan = plan_prefill_launches(
                [self._chunks_left(st) for _, st in prefilling],
                self._launch_multiples)
            for (i, st), k in zip(prefilling, plan):
                if k:
                    self._advance_prefill(i, st, k, clock, ends, phases)

            # every slot past its prefill whose tokens are not all
            # dispatched: a length finish is known here, without a fetch
            batch = [i for i, st in enumerate(self.scheduler.slots)
                     if st is not None and not st.prefilling
                     and len(st.generated) + st.inflight
                     < st.request.max_new_tokens]
            older, self._inflight = self._inflight, None
            if batch:
                self._inflight = self._dispatch_decode(batch, older, phases)
            if older is not None:
                if self._inflight is None:
                    self._registry.inc("serve.pipeline_drains", why="idle")
                self._land(older, clock, finished, phases,
                           behind=self._inflight is not None)
            if ends:
                self._land_first_tokens(ends, clock, finished, phases)
            if self.spec:
                self._drain("spec", clock, finished, phases)

            with phase_span("serve.housekeeping", phases):
                self.steps_done += 1
                self._maybe_record_numerics()
                self._registry.set_gauge("serve.queue_depth",
                                         self.scheduler.queue_depth)
                self._registry.set_gauge("serve.slot_occupancy",
                                         self.scheduler.occupancy)
                self._registry.set_gauge("serve.page_util",
                                         self.pool.utilization)
                for t in self.config.quotas:
                    # quota gauges: each quota'd tenant's live usage, so
                    # a registry snapshot shows who is pinned at their cap
                    self._registry.set_gauge(
                        "serve.tenant_slots",
                        self.scheduler.tenant_slots.get(t, 0), tenant=t)
                    self._registry.set_gauge(
                        "serve.tenant_pages",
                        self.scheduler.tenant_pages.get(t, 0), tenant=t)
                if self.health is not None:
                    self.health.observe_step(
                        self.steps_done,
                        queue_depth=self.scheduler.queue_depth,
                        page_util=self.pool.utilization, t=clock())
                if self.config.brownout:
                    self._maybe_brownout(clock, finished, phases)

                if self.reshard is not None:
                    tier = self.reshard.observe(self.scheduler.queue_depth)
                    if tier is not None:
                        self._drain("reshard", clock, finished, phases)
                        t_pause0 = clock()
                        with self._registry.timer("serve.reshard_s"):
                            self.params = self.reshard.reshard(
                                self.params, tier)
                            if self.config.kv_repage:
                                # the KV pool rides the same hot switch
                                # (HETU_TPU_SERVE_KV_REPAGE): in-flight
                                # requests keep their cache across the
                                # tier change
                                self.pool.arrays = \
                                    self.reshard.reshard_pool(
                                        self.pool.arrays, tier)
                                self._registry.inc("serve.kv_repages")
                        t_pause1 = clock()
                        self._registry.inc("serve.reshards")
                        if self.tracer is not None:
                            paused = [
                                self.scheduler.slots[i].request.rid
                                for i in self.scheduler.active_slots()
                                if not self.scheduler.slots[i].prefilling]
                            self.tracer.on_pause(paused, t_pause0,
                                                 t_pause1, tier=tier)
                        self._log_serve(
                            event="reshard", tier=tier,
                            strategy=self.reshard.describe(tier),
                            now=t_pause1, pause_s=t_pause1 - t_pause0,
                            queue_depth=self.scheduler.queue_depth,
                            **({"kv_repage": True}
                               if self.config.kv_repage else {}))
                # what the step's record takes of the engine: read here,
                # inside a phase, so that closing the record is no part
                # of the step
                sched = self.scheduler
                empty = (self._inflight is None and not sched.queue
                         and all(st is None for st in sched.slots))
                dispatched = {
                    "chunk_launches": sum(map(bool, plan)),
                    "prefill_chunks": sum(plan),
                    "decode_batch": len(batch),
                    "fetch_behind": bool(batch) and older is not None}
                self._last_clock = clock()
        self._note_step_phases(now, empty, dispatched)
        return finished

    def _admit(self, clock, finished) -> Optional[str]:
        """The step's `serve.admit` phase: between-step fault results,
        deadline expiry, the admission loop, the stall stamp.  Returns
        why the step has to drain first (a deadline is about to expire a
        live slot, a preemption to evict one: each leaves with every
        token it was given, as if nothing had been queued), to be called
        again after the drain; else None."""
        if self._fault_results:
            finished.extend(self._fault_results)
            self._fault_results.clear()
        if self.config.deadline:
            # before admissions: an expired queued request must
            # not grab a slot on the step it dies
            t_dead = clock()
            if self._inflight is not None and self._overdue_slots(t_dead):
                return "deadline"
            self._expire_deadlines(t_dead, finished)
        while True:
            t_adm = clock()
            cap = self.config.max_prefilling
            if cap and self.scheduler.queue and sum(
                    st is not None and st.prefilling
                    for st in self.scheduler.slots) >= cap:
                # every scratch the configuration allows is in use
                self.scheduler.last_stall = "prefill_scratch"
                break
            adm = self.scheduler.admit_next(t_adm)
            if adm is None:
                # SLO-class preemption (HETU_TPU_SERVE_PREEMPT):
                # a stalled strictly-higher-priority head may
                # evict the lowest-priority live slot and retry
                # the admission
                if self.config.preempt and self.scheduler.queue:
                    if (self._inflight is not None
                            and self.scheduler.preempt_victim(
                                self.scheduler.queue[0].slo.priority)
                            is not None):
                        return "preempt"
                    if self._try_preempt(clock()):
                        continue
                break
            slot_idx, st = adm
            st.prefilling = True
            if self.ledger is not None:
                self.ledger.on_admit(st.request.rid, len(st.pages), t_adm)
            self._start_prefill(slot_idx, st, t_adm)
            if self.tracer is not None:
                self.tracer.on_admit(st.request, slot_idx, t_adm,
                                     shared_tokens=st.shared_tokens)
        if self.scheduler.queue:
            # admission declined with work queued: count the
            # stall and stamp the scheduler's reserve-on-admit
            # attribution on every waiting request (the counter
            # must not depend on the tracing flag — it is the
            # registry's stall signal)
            reason = self.scheduler.last_stall or "none"
            self._registry.inc("serve.admission_stalls", reason=reason)
            if self.tracer is not None:
                self.tracer.on_stall(
                    [r.rid for r in self.scheduler.queue], reason)
        return None

    def _dispatch_decode(self, batch, older, phases) -> _InFlight:
        """Build and dispatch one decode (or verify) program over the
        slots of `batch`, behind `older` (the decode before it, unfetched,
        or None): nothing here waits for the device."""
        t0 = time.perf_counter()
        slots = self.scheduler.slots
        with phase_span("serve.decode_build", phases):
            # the decode batch's inputs are DERIVED from scheduler
            # state every step (single source of truth): next write
            # position per decoding slot, by the host's own count of the
            # rows it has dispatched; empty/prefilling rows ride along at
            # (0, 0) writing into their masked region
            S = self.config.num_slots
            positions = np.zeros(S, np.int32)
            for i in batch:
                positions[i] = slots[i].pos + slots[i].inflight
            if self.windowed:
                self._release_behind_windows(batch, positions)
            # what this decode step's attention reads: every
            # slot's cached tokens, the one it writes included
            self._registry.inc("serve.decode_steps")
            if older is not None:
                self._registry.inc("serve.decode_steps_overlapped")
            self._registry.inc("serve.decode_slot_steps", len(batch))
            self._registry.inc("serve.decode_context_tokens",
                               int(positions.sum()) + len(batch))
            if self._selects:
                # the layers that SELECT, summed over them: the cached
                # tokens they select FROM, and what their attention reads
                # of those: per slot min(context, the layer's `selects`)
                context = positions[batch] + 1
                self._registry.inc("serve.decode_selectable_tokens",
                                   int(context.sum()) * len(self._selects))
                self._registry.inc(
                    "serve.decode_selected_tokens",
                    sum(int(np.minimum(context, k).sum())
                        for k in self._selects))
            sample_args = (self._sample_args(batch)
                           if self.config.sampling else ())
        if self.spec:
            out = self._spec_dispatch(batch, positions, sample_args, phases)
        else:
            with phase_span("serve.decode_build", phases):
                # last emitted token per slot; -1 where that token is
                # `older`'s, on the device: the program takes it there
                tokens = np.zeros(S, np.int32)
                for i in batch:
                    tokens[i] = (-1 if slots[i].inflight
                                 else slots[i].generated[-1])
                if self.stateful:
                    # what the state layers read and write for the rows
                    # that decode: a slot's whole state, once each (under
                    # the name the model gives: `state_counter`)
                    self._registry.inc(
                        getattr(self.model, "state_counter",
                                "serve.kda_state_bytes"),
                        2 * len(batch) * self._state_bytes_per_slot)
                if self.borrows:
                    # positions read from a page set by layers that keep
                    # none of their own: each reader reads what the layer
                    # it names holds for the slot
                    self._registry.inc(
                        "serve.shared_kv_positions",
                        self._borrowed_positions(positions[batch] + 1))
                decode_args = (
                    self.params, self.pool.tree(),
                    self._decode_table(batch),
                    jnp.asarray(tokens), jnp.asarray(positions),
                    self._no_tokens if older is None else older.out[0],
                    *self._stats_args(), *sample_args)
            with phase_span("serve.decode_dispatch", phases):
                nxt, pool_tree = self._call("decode", *decode_args)
                self.pool.commit(pool_tree)
                # the stats ride out behind this program's tokens: what
                # is dispatched after it counts from zero again
                self._stats_acc = self._stats_zero
            out = (nxt,)
        rows = [(i, slots[i]) for i in batch]
        for _, st in rows:
            st.inflight += 1
        return _InFlight(out, rows, t0)

    def _land(self, flight: _InFlight, clock, finished, phases,
              behind: bool = False):
        """Fetch a dispatched decode program's tokens and emit them to
        their requests.  `behind`: a later decode is dispatched behind it
        (the deferred fetch: `serve.fetch_wait_s` is what the host waits
        there; near zero, the host sets the pace)."""
        with phase_span("serve.token_fetch", phases):
            tw = time.perf_counter()
            host = jax.device_get(flight.out)
            if behind:
                waited = time.perf_counter() - tw
                self._fetch_wait += waited
                self._registry.inc("serve.fetch_wait_total_s", waited)
        with phase_span("serve.emit", phases):
            if self.spec:
                emitted = self._spec_accept(flight.rows, *host)
            else:
                nxt, = host
                self._note_program_stats(nxt[self.config.num_slots:])
                emitted = {i: [int(nxt[i])] for i, _ in flight.rows}
            # token_latency_s is the USER-visible inter-token
            # gap: every active slot advances >= one token per
            # decode step, so the gap IS the time from the emit before
            # this one (or from this program's build, if later).  The
            # amortized per-token engine cost (wall / tokens
            # emitted — the throughput number) is its own
            # series; conflating them would understate latency
            # by up to num_slots x.
            t = time.perf_counter()
            decode_wall = t - max(flight.t0, self._landed_t)
            self._landed_t = t
            n_emitted = sum(len(v) for v in emitted.values())
            self._registry.observe("serve.token_latency_s", decode_wall)
            self._registry.observe("serve.token_cost_s",
                                   decode_wall / max(n_emitted, 1))
            tnow = clock()
            n_done0 = len(finished)
            for i, st in flight.rows:
                if self.scheduler.slots[i] is not st:
                    # the slot ended by an EOS the host saw after this
                    # row was dispatched: computed, counted, discarded
                    self._registry.inc("serve.overrun_rows")
                    continue
                st.inflight -= 1
                for tok in emitted[i]:
                    st.generated.append(tok)
                    st.pos += 1
                    st.stats.token_ts.append(tnow)
                    self._registry.inc("serve.tokens_out")
                    if self.tracer is not None:
                        self.tracer.on_token(st.request, tnow)
                    self._maybe_finish(i, st, tok, tnow, finished)
                    if self.scheduler.slots[i] is None:
                        break    # finished: drop surplus drafts
            if self.tracer is not None and len(finished) > n_done0:
                # an eviction changed the batch composition:
                # split the survivors' decode segments so the
                # boundary is visible
                survivors = [
                    self.scheduler.slots[i].request.rid
                    for i in self.scheduler.active_slots()
                    if not self.scheduler.slots[i].prefilling]
                if survivors:
                    self.tracer.on_split(survivors, tnow, "evict")

    def _drain(self, why: str, clock, finished=None, phases=None):
        """Fetch and emit what is queued on the device, now (nothing
        queued: nothing done, nothing counted).  Between steps
        (`finished` None) a finish parks with the between-step fault
        results, for the next step to return."""
        flight, self._inflight = self._inflight, None
        if flight is None:
            return
        self._registry.inc("serve.pipeline_drains", why=why)
        self._land(flight, clock,
                   self._fault_results if finished is None else finished,
                   {} if phases is None else phases)

    def _release_behind_windows(self, active, positions):
        """Before a decode step: every window layer's pages that have
        fallen wholly behind their slot's window go back to the free
        list (`Scheduler.advance`), and what the step's window layers
        read is counted beside `serve.decode_context_tokens`: per slot
        min(context, window), of the widest window."""
        released = sum(self.scheduler.advance(i) for i in active)
        if released:
            self._registry.inc("serve.window_pages_released", released)
        w = max(w for w in self.cache.kinds if w is not None)
        self._registry.inc(
            "serve.decode_window_context_tokens",
            int(np.minimum(positions[active] + 1, w).sum()))

    def _borrowed_positions(self, contexts) -> int:
        """Positions the reading layers of one decode pass attend in
        pages that are not theirs, for slots at `contexts` (cached
        tokens, the one written included): a reader of a layer that
        reads everything reads the context, of a window layer
        min(context, window)."""
        return sum(int((contexts if w is None
                        else np.minimum(contexts, w)).sum())
                   for w in self._read_windows)

    def _scratch_rows(self, scratch):
        """A request's prefill scratch as the page-write program takes
        it: by kind of layer whole (the program takes the one row), else
        the row of each array [L, positions, ...], as `adopt_prefilled`
        ships it."""
        return scratch if self.windowed else tuple(a[:, 0] for a in scratch)

    def _scratch_bases(self, start: int):
        """Per kind of layer, the position its prefill scratch begins at
        when the chunk program has run the chunk at `start`
        (`models/generation.extend_cache`: a window kind's slides)."""
        C = self.config.prefill_chunk
        return [0 if w is None or not self._slide else max(0, start - (M - C))
                for w, M in zip(self.cache.kinds, self._scratch_positions)]

    def _count_attended_keys(self, start: int, C: int, row: int = 0):
        """`serve.prefill_attended_keys{kind}`: the (query, key) pairs
        ONE layer of the kind attends in a chunk launch of C rows at
        `start`: C * start + C (C + 1) / 2 where the kind reads
        everything, sum_i min(start + i + 1, window) under a window (a
        chunk's padding rows count: the program computes them).  A kind
        whose layers attend for the read row alone (`read_rows_from`):
        that row's start + row + 1 keys, none where `row` is negative.
        `serve.prefill_selected_keys`: of those pairs, the ones the
        layers that SELECT attend, summed over those layers: sum_i
        min(start + i + 1, the layer's `selects`)."""
        def capped(cap):
            # the first `a` rows see start + i + 1 keys, the rest `cap`
            a = min(max(cap - start - 1, 0), C)
            return a * (start + 1) + a * (a - 1) // 2 + (C - a) * cap
        for k, (kind, w) in enumerate(zip(self._kind_names,
                                          self.cache.kinds)):
            if k in self._tail_kinds:
                pairs = start + row + 1 if row >= 0 else 0
            elif w is None:
                pairs = C * start + C * (C + 1) // 2
            else:
                pairs = capped(w)
            self._registry.inc("serve.prefill_attended_keys", pairs,
                               kind=kind)
        if self._selects:
            self._registry.inc("serve.prefill_selected_keys",
                               sum(capped(k) for k in self._selects))

    def _stats_args(self) -> tuple:
        """The extra argument of the programs of a model that counts
        (`model.STATS`): the running stats vector; none otherwise."""
        return () if self._stats_acc is None else (self._stats_acc,)

    def _note_program_stats(self, values):
        """The programs' running stats vector, fetched behind a decode
        program's tokens (empty for a model that counts nothing), into
        the counters the MODEL names
        (`model.STATS`: (counter, "sum" | "max") per entry): a sum as an
        increment, a maximum as the running maximum.  The device-side
        vector started again from zero when that program was DISPATCHED
        (`_dispatch_decode`), so each fetched vector holds what ran since
        the decode before it."""
        for (name, how), v in zip(self.model.STATS, values):
            more = int(v)
            if how == "max":
                more -= self._registry.counter_value(name)
            if more > 0:
                self._registry.inc(name, more)

    def _note_step_phases(self, now: float, empty: bool, dispatched: dict):
        """Close the step's record (`StepRecorder.end`: the counters and
        histograms of the step, the stall rule), with what the engine
        alone knows: what the step dispatched, what its deferred fetch
        waited, and whether it leaves the engine EMPTY (no slot active,
        nothing queued, nothing in flight: the gap to the next step is
        then `serve.empty_s`, else the caller's, `serve.caller_s`).  A
        step is judged stalled among the steps that spent as many chunks
        of prompt rows as it has (however many launches carried them): a
        chunk program can take five decode programs' time (MiMo: 39 ms
        beside 8).  The
        record becomes `slowest_step` if no step since that was last
        cleared took longer: so the slowest step of any run, traced or
        not, names its phase and what held it (docs/serving.md)."""
        self.slowest_step = self._step_record.end(
            self.steps_done, now, self.slowest_step, empty=empty,
            kind=dispatched["prefill_chunks"], dispatched=dispatched,
            fetch_wait_s=self._fetch_wait)

    # ----------------------------------------------------------- faults
    def _finish_faulted(self, req, now: float, finished, *, reason: str,
                        event: str, tokens, st=None, slot=None):
        """Terminate `req` with a fault outcome (`deadline_exceeded`,
        `brownout_shed`, `retry_exhausted`): the _maybe_finish
        bookkeeping — carried-stats folding, ledger cost, counters, a
        sampled serve event — for a request the model did not finish.
        `st`/`slot` identify a live incarnation (whose ledger entry
        closes); queued casualties pass neither and cost nothing."""
        stats = st.stats if st is not None else RequestStats(
            arrival_t=req.arrival_t)
        stats.done_t = now
        stats.preemptions = self._preempt_counts.pop(req.rid, 0)
        stats.retries = self.scheduler.retries.pop(req.rid, 0)
        carried = self._carried_stats.pop(req.rid, None)
        if carried is not None:
            stats.spec_proposed += carried["spec_proposed"]
            stats.spec_accepted += carried["spec_accepted"]
            stats.prefill_chunks += carried["prefill_chunks"]
        res = RequestResult(rid=req.rid, tokens=list(tokens),
                            finished_reason=reason, stats=stats)
        self._registry.inc(f"serve.{reason}")
        self._registry.inc(f"serve.{reason}_class",
                           slo_class=req.slo.name)
        cost = {}
        if self.ledger is not None and st is not None:
            cost = self.ledger.finish(
                req.rid, now, prompt_len=req.prompt_len,
                shared_tokens=stats.shared_prefix_tokens,
                tokens_out=len(res.tokens))
        if self._sampled(req.rid):
            self._log_serve(
                event=event, req=req.rid, reason=reason,
                tokens=len(res.tokens), e2e_s=stats.e2e_s, now=now,
                slo_class=req.slo.name, tenant=req.tenant,
                retries=stats.retries, preemptions=stats.preemptions,
                queue_depth=self.scheduler.queue_depth,
                **({"slot": slot} if slot is not None else {}),
                **cost, **self._weight_fields())
        finished.append(res)
        return res

    def fail_over(self, now: Optional[float] = None) -> dict:
        """The serving replica dies and a recovery replica takes over
        on the spot (the chaos `engine_kill` injection point, called
        between steps from the run() on_step hook): every in-flight
        request loses its slot, pages, and partial output.  A request
        with retry budget left (HETU_TPU_SERVE_RETRY) re-enters the
        queue behind a `replica_lost` stall span and re-prefills on
        re-admission (cheap under a warm radix prefix cache); greedy
        argmax and the (seed, position)-keyed sampler are pure
        functions of the prompt, so the replayed stream is
        token-identical to the undisturbed run — the same purity the
        preempt path already relies on.  Over-budget requests
        terminate as `retry_exhausted`, surfacing through the next
        step()'s results.  Params, pool, and compiled programs
        survive (the recovery replica inherits them); what is tested
        is the REQUEST-state recovery.  Returns
        ``{"requeued": [rids], "exhausted": [rids]}``."""
        now = self._last_clock if now is None else now
        # what the dying replica had queued on its device is fetched
        # first: a request is discarded with every token it was given
        self._drain("fail_over", lambda: now)
        requeued: List[int] = []
        exhausted: List[int] = []
        self._registry.inc("serve.failovers")
        for i in list(self.scheduler.active_slots()):
            st = self.scheduler.slots[i]
            req = st.request
            if (self.scheduler.retries.get(req.rid, 0)
                    < self.config.retry_budget):
                # the accrued work counters survive the requeue (the
                # preempt carry discipline); the ledger bills the
                # discarded incarnation — it re-runs on re-admission
                carried = self._carried_stats.setdefault(
                    req.rid, {"spec_proposed": 0, "spec_accepted": 0,
                              "prefill_chunks": 0})
                carried["spec_proposed"] += st.stats.spec_proposed
                carried["spec_accepted"] += st.stats.spec_accepted
                carried["prefill_chunks"] += st.stats.prefill_chunks
                if self.ledger is not None:
                    self.ledger.on_preempt(req.rid, now,
                                           ctx_start=st.shared_tokens,
                                           tokens_cached=st.pos)
                self.scheduler.requeue_lost(i)
                self._registry.inc("serve.replica_requeues")
                self._registry.inc("serve.replica_requeues_class",
                                   slo_class=req.slo.name)
                if self.tracer is not None:
                    self.tracer.on_replica_lost(req, i, now)
                if self._sampled(req.rid):
                    self._log_serve(
                        event="retry", req=req.rid, slot=i, now=now,
                        attempt=self.scheduler.retries[req.rid] + 1,
                        slo_class=req.slo.name, tenant=req.tenant,
                        tokens_discarded=len(st.generated),
                        **self._weight_fields())
                requeued.append(req.rid)
            else:
                if self.tracer is not None:
                    self.tracer.on_finish(
                        req, i, "retry_exhausted", now,
                        tokens=len(st.generated),
                        e2e_s=now - float(req.arrival_t), evicted=True)
                tokens = list(st.generated)
                self.scheduler.release(i)
                self._finish_faulted(req, now, self._fault_results,
                                     reason="retry_exhausted",
                                     event="evict", tokens=tokens,
                                     st=st, slot=i)
                exhausted.append(req.rid)
        self._log_serve(event="failover", now=now,
                        requeued=len(requeued),
                        exhausted=len(exhausted),
                        queue_depth=self.scheduler.queue_depth)
        return {"requeued": requeued, "exhausted": exhausted}

    def _overdue_slots(self, now: float) -> List[int]:
        """The live slots whose request is older than its SLO class
        deadline."""
        return [i for i in self.scheduler.active_slots()
                if (d := self.scheduler.slots[i].request.slo.deadline_s)
                is not None
                and now - self.scheduler.slots[i].request.arrival_t > d]

    def _expire_deadlines(self, now: float, finished):
        """Terminate every queued or live request older than its SLO
        class deadline (HETU_TPU_SERVE_DEADLINE) as `deadline_exceeded`
        — a real terminal outcome: traced, costed, counted, and
        returned through run() like any finish."""
        for req in [r for r in self.scheduler.queue
                    if r.slo.deadline_s is not None
                    and now - r.arrival_t > r.slo.deadline_s]:
            if not self.scheduler.drop_queued(req):
                continue
            if self.tracer is not None:
                self.tracer.on_expire(req, now,
                                      e2e_s=now - float(req.arrival_t))
            self._finish_faulted(req, now, finished,
                                 reason="deadline_exceeded",
                                 event="expired", tokens=[])
        for i in self._overdue_slots(now):
            st = self.scheduler.slots[i]
            req = st.request
            if self.tracer is not None:
                self.tracer.on_expire(req, now,
                                      tokens=len(st.generated),
                                      e2e_s=now - float(req.arrival_t))
            tokens = list(st.generated)
            self.scheduler.release(i)
            self._finish_faulted(req, now, finished,
                                 reason="deadline_exceeded",
                                 event="expired", tokens=tokens,
                                 st=st, slot=i)

    def _maybe_brownout(self, clock, finished, phases):
        """Sustained-pressure shedding (HETU_TPU_SERVE_BROWNOUT): page
        utilization >= brownout_page_high with >= brownout_queue_min
        queued for brownout_streak consecutive steps sheds the
        LOWEST-priority queued band as `brownout_shed` (the preempt
        priority order: smaller SLOClass.priority = less important),
        metered through the health monitor when one is attached.
        Deterministic by construction — driven only by pool and queue
        state, never the wall clock: under pressure the step drains
        first, so that the pool is judged with every finish in it."""
        c = self.config

        def hot():
            return (self.pool.utilization >= c.brownout_page_high
                    and self.scheduler.queue_depth >= c.brownout_queue_min)
        if hot():
            self._drain("brownout", clock, finished, phases)
        if not hot():
            self._brownout_hot = 0
            return
        now = clock()
        self._brownout_hot += 1
        if self._brownout_hot < c.brownout_streak:
            return
        self._brownout_hot = 0
        lowest = min(r.slo.priority for r in self.scheduler.queue)
        shed = [r for r in self.scheduler.queue
                if r.slo.priority == lowest]
        for req in shed:
            if not self.scheduler.drop_queued(req):
                continue
            if self.tracer is not None:
                self.tracer.on_shed(req, now)
            self._finish_faulted(req, now, finished,
                                 reason="brownout_shed", event="shed",
                                 tokens=[])
        if self.health is not None:
            self.health.note_brownout(self.steps_done, shed=len(shed),
                                      page_util=self.pool.utilization,
                                      t=now)

    # --------------------------------------------------------- sampling
    def _sample_args(self, active):
        """Per-slot sampling-parameter vectors for the jitted programs
        (inactive rows ride along greedy at seed 0)."""
        S = self.config.num_slots
        seeds = np.zeros(S, np.uint32)
        temps = np.zeros(S, np.float32)
        top_ks = np.zeros(S, np.int32)
        top_ps = np.zeros(S, np.float32)
        for i in active:
            sp = self.scheduler.slots[i].request.sampling
            seeds[i] = sp.seed & 0xFFFFFFFF
            temps[i] = sp.temperature
            top_ks[i] = sp.top_k
            top_ps[i] = sp.top_p
        return (jnp.asarray(seeds), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps))

    def _decode_table(self, active):
        """Page-table input for the decode batch: only decoding slots'
        rows are real; prefilling/empty rows are pinned to the null
        page.  The scheduler's table is populated at ADMISSION, so a
        still-prefilling slot's row already points at live pages — and
        under the radix prefix cache its first page is a COW-shared
        prefix page.  The ride-along (token 0, position 0) write for
        such a row must land in the null page, not in `table[slot][0]`
        row 0, or it silently corrupts position 0 of the shared prefix
        for every reader."""
        table = np.zeros_like(self.scheduler.page_table)
        for i in active:
            # [S, max_pages], or [kinds, S, max_pages]
            table[..., i, :] = self.scheduler.page_table[..., i, :]
        return jnp.asarray(table)

    # ------------------------------------------------------ spec decode
    def _spec_dispatch(self, active, positions, sample_args, phases):
        """One speculative decode step over the active slots: draft k
        tokens per slot on the host, verify all k+1 in ONE batched
        forward, accept by sample-then-match — or by the full
        stochastic p/q rejection rule when the drafter reports its
        proposal distribution (serving/spec_decode.py).  Returns the
        verify program's (targets, emit counts), on the device: the
        accepted count sets the next step's positions, so `step` drains
        them at once (`_spec_accept`).  Drafting is this step's
        `serve.decode_build` phase, acceptance part of its
        `serve.emit`."""
        with phase_span("serve.decode_build", phases):
            S, k = self.config.num_slots, self.config.spec_k
            w = getattr(self.drafter, "window", None)
            tokens = np.zeros((S, k + 1), np.int32)
            q_probs = (np.zeros((S, k, self.model.config.vocab_size),
                                np.float32)
                       if self.spec_stochastic else None)
            for i in active:
                st = self.scheduler.slots[i]
                # hand the drafter only the trailing window it reads —
                # O(window) per step, not O(prompt + generated)
                if w:
                    from_prompt = max(0, w - len(st.generated))
                    ctx = (st.request.prompt[st.request.prompt_len
                                             - from_prompt:].tolist()
                           + st.generated[-w:])
                else:
                    ctx = st.request.prompt.tolist() + st.generated
                tokens[i, 0] = st.generated[-1]
                if q_probs is not None:
                    sp = st.request.sampling
                    tokens[i, 1:], q_probs[i] = \
                        self.drafter.propose_with_probs(
                            ctx, k, seed=sp.seed & 0xFFFFFFFF,
                            start_pos=int(positions[i]) + 1)
                else:
                    tokens[i, 1:] = self.drafter.propose(ctx, k)
            extra = ((jnp.asarray(q_probs),) if q_probs is not None
                     else ())
            verify_args = (
                self.params, self.pool.arrays.tree(),
                self._decode_table(active),
                jnp.asarray(tokens), jnp.asarray(positions), *extra,
                *sample_args)
        with phase_span("serve.decode_dispatch", phases):
            targets, n_emit, pool_tree = self._call("verify", *verify_args)
            self.pool.arrays = PoolArrays.from_tree(pool_tree)
        return targets, n_emit

    def _spec_accept(self, rows, targets, n_emit) -> dict:
        """{slot: emitted tokens} (>= 1 per slot) of a fetched verify
        step, and its acceptance counts."""
        k = self.config.spec_k
        emitted = {}
        for i, st in rows:
            n = int(n_emit[i])
            emitted[i] = [int(t) for t in targets[i, :n]]
            st.stats.spec_proposed += k
            st.stats.spec_accepted += n - 1
            self._registry.inc("serve.spec_proposed", value=k)
            self._registry.inc("serve.spec_accepted", value=n - 1)
            self._registry.observe("serve.spec_emitted", float(n))
        return emitted

    # ------------------------------------------------------- preemption
    def _try_preempt(self, now: float) -> bool:
        """Evict-and-requeue the lowest-priority live slot when the
        stalled queue head outranks it (HETU_TPU_SERVE_PREEMPT).
        Returns True when a slot was freed (the caller retries
        admission)."""
        head = self.scheduler.queue[0]
        victim = self.scheduler.preempt_victim(head.slo.priority)
        if victim is None:
            return False
        st = self.scheduler.slots[victim]
        req = st.request
        self._preempt_counts[req.rid] = \
            self._preempt_counts.get(req.rid, 0) + 1
        carried = self._carried_stats.setdefault(
            req.rid, {"spec_proposed": 0, "spec_accepted": 0,
                      "prefill_chunks": 0})
        carried["spec_proposed"] += st.stats.spec_proposed
        carried["spec_accepted"] += st.stats.spec_accepted
        carried["prefill_chunks"] += st.stats.prefill_chunks
        if self.ledger is not None:
            # the victim's computed-but-discarded work is part of what
            # the request truly cost (it re-runs on re-admission)
            self.ledger.on_preempt(req.rid, now,
                                   ctx_start=st.shared_tokens,
                                   tokens_cached=st.pos)
        self.scheduler.preempt(victim)
        self._registry.inc("serve.preemptions")
        self._registry.inc("serve.preemptions_class",
                           slo_class=req.slo.name)
        if self.tracer is not None:
            self.tracer.on_preempt(req, victim, now, by=head.rid)
        if self._sampled(req.rid):
            self._log_serve(event="preempt", req=req.rid, slot=victim,
                            by=head.rid, by_class=head.slo.name,
                            slo_class=req.slo.name, tenant=req.tenant,
                            now=now,
                            tokens_discarded=len(st.generated),
                            queue_depth=self.scheduler.queue_depth,
                            **self._weight_fields())
        return True

    def _first_token(self, req, logits_row, position: int) -> int:
        """The TTFT token from the final prefill chunk's logits — the
        shared pure helper, keyed by this engine's sampling config."""
        return first_token_from_logits(req, logits_row, position,
                                       sampling=self.config.sampling)

    # ---------------------------------------------------------- prefill
    def _start_prefill(self, slot_idx: int, st, now: float):
        """A freshly admitted slot's prefill scratch.  With a
        radix-cache hit the scratch is PRIMED here: the shared pages
        gather into positions [0, shared_tokens) (exact in the fp page
        mode — the bytes written at caching time), so suffix chunks
        attend over the resident prefix and prefill FLOPs drop to the
        unshared suffix.  Without one the slot holds NO scratch until its
        first launch (`_advance_prefill`): a step's rows go to the oldest
        slots first, and one that waits its turn waits without 201 MB
        (the InternLM2 cells' scratch) of zeros."""
        if st.shared_tokens:
            row = np.full(self.scheduler.max_pages, PagePool.NULL_PAGE,
                          np.int32)
            shared_pages = st.shared_tokens // self.pool.page_size
            row[:shared_pages] = st.pages[:shared_pages]
            st.prefill_cache = self._prime_jit(self.pool.arrays.tree(),
                                               jnp.asarray(row))
            self._registry.inc("serve.prefix_hits")
            self._registry.inc("serve.prefix_shared_tokens",
                               value=st.shared_tokens)
        elif self.prefix_cache is not None:
            self._registry.inc("serve.prefix_misses")
        if self.prefix_cache is not None:
            self._registry.set_gauge("serve.prefix_cache_pages",
                                     self.prefix_cache.num_pages)

    def _chunks_left(self, st) -> int:
        """The chunks a prefilling slot's padded prompt has left."""
        left = st.request.prompt_len - st.shared_tokens
        return math.ceil(left / self.config.prefill_chunk) - st.chunks_done

    def _advance_prefill(self, slot_idx: int, st, k: int, clock, ends,
                         phases):
        """Run ONE launch of the chunk program, the next `k` chunks of a
        prefilling slot's prompt (`plan_prefill_launches`); where they
        end the prompt, scatter the scratch K/V into the slot's pages and
        leave the prompt's end in `ends`: its first token is fetched at
        the step's end (`_land_first_tokens`), where the slot joins the
        decode batch.  A radix-cache hit
        starts chunking at the shared boundary (`st.shared_tokens` —
        the primed prefix is already in the scratch) and never
        re-writes the shared pages."""
        with phase_span("serve.prefill_chunk", phases):
            req = st.request
            plen = req.prompt_len
            # the launch's rows: C where a chunk stood
            C = k * self.config.prefill_chunk
            s = st.shared_tokens + st.chunks_done * self.config.prefill_chunk
            last = k == self._chunks_left(st)
            # the last VALID prompt position of the final chunk (padding
            # tail positions carry garbage): the row the program runs the
            # head for and takes the first token at; negative: this chunk
            # has none (a prefix-cache hit leaves the last chunk a row:
            # `RadixPrefixCache.match` stops at plen - 1)
            row = plen - 1 - s if last else -1
            ids = np.zeros(C, np.int32)
            seg = req.prompt[s: min(s + C, plen)]
            ids[: len(seg)] = seg
            # (with state layers the pool's state arrays ride behind the
            # scratch, and the chunk at position 0 starts the slot's row
            # of them from zeros)
            if st.prefill_cache is None:    # its first launch: zeros
                st.prefill_cache = self._fresh_scratch()
            scratch = tuple(st.prefill_cache)
            out = self._call(
                _chunk_program(k), self.params, jnp.asarray(ids[None]),
                scratch + self.pool.state, jnp.int32(s), jnp.int32(row),
                *((jnp.int32(slot_idx), jnp.int32(len(seg)))
                  if self.stateful else ()), *self._stats_args())
            logits, first, cache, *stats = out
            st.prefill_cache, self.pool.state = (cache[:len(scratch)],
                                                 cache[len(scratch):])
            if self.stateful:
                if s == 0:
                    self._registry.inc("serve.state_resets")
                self._registry.inc("serve.chunk_padded_rows", C - len(seg))
            if stats:
                (self._stats_acc,) = stats
            # rows the head (and the layers from `read_rows_from` on)
            # ran for
            self._registry.inc("serve.prefill_tail_rows", int(last))
            st.chunks_done += k
            # (launches, the request's and the engine's: rows a launch is
            # `serve.prefill_tokens` over `serve.prefill_chunks`)
            st.stats.prefill_chunks += 1
            self._registry.inc("serve.prefill_chunks")
            self._registry.inc("serve.prefill_launches", rows=C)
            self._registry.inc("serve.prefill_tokens", len(seg))
            self._count_attended_keys(s, C, row)
            if not last:
                if self.tracer is not None:
                    self.tracer.on_chunk(req, clock(), st.chunks_done)
                return                    # more chunks: next engine step

        with phase_span("serve.page_write", phases):
            # scatter only the FRESHLY prefilled pages; shared-prefix
            # pages already hold these tokens' K/V (they are what the
            # scratch was primed from) and are read-only to this slot
            # (COW) — their row entries point at the null page so the
            # write lands harmlessly
            pages_row = self.scheduler.write_rows(
                slot_idx, st.shared_tokens // self.pool.page_size,
                bases=self._scratch_bases(s))
            tree = self._call("write_pages", self.pool.arrays.tree(),
                                   jax.tree.map(jnp.asarray, pages_row),
                                   *self._scratch_rows(st.prefill_cache))
            self.pool.arrays = PoolArrays.from_tree(tree)
            st.prefill_cache = None
            if self.prefix_cache is not None:
                # index the finished prompt: full page-blocks not yet
                # cached adopt this request's pages (incref — the slot
                # keeps its own reference and releases it on finish)
                self.prefix_cache.insert(req.prompt, st.pages, clock())
            # the row's logits are kept where the first token is SAMPLED
            # from them (on the host), not the chunk program's argmax
            drawn = self.config.sampling and req.sampling.temperature > 0
            ends.append(_PromptEnd(slot_idx, st, first,
                                   logits[0, 0] if drawn else None))

    def _land_first_tokens(self, ends, clock, finished, phases):
        """The step's second wait for the device, after its dispatches:
        the first generated token of every prompt that ended in this
        step — the chunk program's argmax, or the seeded sampler for
        sampling requests (same key derivation as the decode program:
        position plen) — emitted to its request, whose slot is past its
        prefill from here on."""
        if any(e.logits_row is not None for e in ends):
            # the sampler runs eagerly, behind everything dispatched: the
            # same wait under its name, and what it fetched emitted first
            self._drain("sampling", clock, finished, phases)
        with phase_span("serve.first_token", phases):
            firsts = [
                self._first_token(e.st.request, e.logits_row,
                                  e.st.request.prompt_len)
                if e.logits_row is not None else int(t)
                for e, t in zip(ends, jax.device_get(
                    [e.first for e in ends]))]
        with phase_span("serve.emit", phases):
            for e, t1 in zip(ends, firsts):
                self._emit_first_token(e.slot, e.st, t1, clock(), finished)

    def _emit_first_token(self, slot_idx: int, st, t1: int, tnow: float,
                          finished):
        """A prompt's first generated token to its request: from here the
        slot is past its prefill, and has its `first_token_t`."""
        req = st.request
        st.prefilling = False
        st.pos = req.prompt_len
        st.generated.append(t1)
        st.stats.first_token_t = tnow
        st.stats.token_ts.append(tnow)
        ttft = st.stats.ttft_s
        self._registry.observe("serve.ttft_s", ttft)
        self._registry.observe("serve.ttft_s_class", ttft,
                               slo_class=req.slo.name)
        if st.stats.queue_wait_s is not None:
            self._registry.observe("serve.queue_wait_s",
                                   st.stats.queue_wait_s)
        self._registry.inc("serve.tokens_out")
        if self.tracer is not None:
            self.tracer.on_first_token(req, slot_idx, tnow,
                                       chunk=st.chunks_done)
        if self.health is not None:
            self.health.observe_ttft(ttft, step=self.steps_done, t=tnow)
        if self._sampled(req.rid):
            self._log_serve(event="admit", req=req.rid,
                            slot=slot_idx, prompt_len=req.prompt_len,
                            chunks=st.stats.prefill_chunks,
                            ttft_s=ttft,
                            queue_wait_s=st.stats.queue_wait_s,
                            now=tnow,
                            slo_class=req.slo.name, tenant=req.tenant,
                            shared_tokens=st.shared_tokens,
                            queue_depth=self.scheduler.queue_depth,
                            page_util=self.pool.utilization,
                            **self._weight_fields())
        self._maybe_finish(slot_idx, st, t1, tnow, finished)

    # ----------------------------------------------------------- finish
    def _maybe_finish(self, slot_idx: int, st, tok: int, tnow: float,
                      finished):
        req = st.request
        reason = None
        if req.eos_token_id is not None and tok == req.eos_token_id:
            reason = "eos"
        elif len(st.generated) >= req.max_new_tokens:
            reason = "length"
        if reason is None:
            return
        st.stats.done_t = tnow
        res = RequestResult(rid=req.rid, tokens=list(st.generated),
                            finished_reason=reason, stats=st.stats)
        self.scheduler.release(slot_idx)
        self._registry.inc("serve.requests_done")
        self._registry.inc("serve.requests_done_class",
                           slo_class=req.slo.name)
        if st.stats.e2e_s is not None:
            self._registry.observe("serve.e2e_s", st.stats.e2e_s)
            self._registry.observe("serve.e2e_s_class", st.stats.e2e_s,
                                   slo_class=req.slo.name)
        if self.tracer is not None:
            self.tracer.on_finish(req, slot_idx, reason, tnow,
                                  tokens=len(res.tokens),
                                  e2e_s=st.stats.e2e_s)
        st.stats.preemptions = self._preempt_counts.pop(req.rid, 0)
        st.stats.retries = self.scheduler.retries.pop(req.rid, 0)
        carried = self._carried_stats.pop(req.rid, None)
        if carried is not None:
            # work spent before each preemption belongs to this run
            st.stats.spec_proposed += carried["spec_proposed"]
            st.stats.spec_accepted += carried["spec_accepted"]
            st.stats.prefill_chunks += carried["prefill_chunks"]
        cost = {}
        if self.ledger is not None:
            cost = self.ledger.finish(
                req.rid, tnow, prompt_len=req.prompt_len,
                shared_tokens=st.stats.shared_prefix_tokens,
                tokens_out=len(res.tokens))
        if self._sampled(req.rid):
            self._log_serve(
                event="done", req=req.rid, slot=slot_idx,
                reason=reason, tokens=len(res.tokens),
                ttft_s=st.stats.ttft_s, e2e_s=st.stats.e2e_s,
                tokens_per_s=res.tokens_per_s, now=tnow,
                slo_class=req.slo.name, tenant=req.tenant,
                slo_ttft_s=req.slo.ttft_s,
                slo_token_gap_s=req.slo.token_gap_s,
                spec_proposed=st.stats.spec_proposed,
                spec_accepted=st.stats.spec_accepted,
                shared_prefix_tokens=st.stats.shared_prefix_tokens,
                prompt_len=req.prompt_len,
                preemptions=st.stats.preemptions,
                queue_depth=self.scheduler.queue_depth,
                slot_occupancy=self.scheduler.occupancy,
                page_util=self.pool.utilization,
                **({"retries": st.stats.retries}
                   if st.stats.retries else {}),
                **cost, **self._weight_fields())
        finished.append(res)

    # -------------------------------------------------------------- run
    def run(self, requests: Sequence[Request], *, start: float = 0.0,
            on_step=None) -> List[RequestResult]:
        """Drive the engine over a request trace to completion under a
        virtual clock: arrivals come from each request's `arrival_t`,
        and time advances by the real wall cost of each engine step —
        deterministic token output, realistic latency accounting.

        ``on_step(step_index)`` (optional) runs at each step boundary
        INSIDE the timed window, so any wall time it spends (a chaos
        slow-decode injection, a host-side stall) inflates the virtual
        clock exactly like a slow engine step would — the hook the
        chaos harness drives instead of forking this loop."""
        pending = sorted(requests, key=lambda r: (r.arrival_t, r.rid))
        now = start
        results: List[RequestResult] = []
        i = 0
        step_idx = 0
        while True:
            while i < len(pending) and pending[i].arrival_t <= now + 1e-12:
                self.submit(pending[i])
                i += 1
            if not self.scheduler.active_slots() and not self.scheduler.queue:
                if i >= len(pending):
                    break
                now = max(now, pending[i].arrival_t)   # idle-skip to next
                continue
            t0 = time.perf_counter()
            if on_step is not None:
                # chaos hooks fire here (maybe_chaos_serving /
                # maybe_slow_step); give between-step fault events a
                # current driver timestamp
                self._last_clock = max(self._last_clock, now)
                on_step(step_idx)
            results.extend(self.step(now))
            now += time.perf_counter() - t0
            step_idx += 1
        # (what is left queued is a row past an EOS: no slot holds it)
        self._drain("idle", lambda: now, results)
        if self.run_log is not None or self.telemetry is not None:
            n_tokens = sum(len(r.tokens) for r in results)
            elapsed = max(now - start, 1e-9)
            self._log_serve(event="report",
                            requests=len(results), tokens=n_tokens,
                            elapsed_s=elapsed, now=now,
                            tokens_per_s=n_tokens / elapsed,
                            kernel_routes=self.kernel_routes)
        return sorted(results, key=lambda r: r.rid)

    def close(self):
        self._drain("close", lambda: self._last_clock)
        if self._owns_runlog and self.run_log is not None:
            self.run_log.close()
