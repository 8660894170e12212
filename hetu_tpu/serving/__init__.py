"""Serving subsystem: continuous batching + paged KV cache over the
training stack (docs/serving.md).

    from hetu_tpu import serving
    eng = serving.ServingEngine(model, params,
                                serving.ServeConfig(num_slots=8))
    results = eng.run(serving.synthetic_requests(16, vocab_size=256,
                                                 seed=0))

Deliberately NOT imported from the package root: training paths never
pay for (or lower differently because of) the serving stack — the
serving flags (HETU_TPU_KV_QUANT, HETU_TPU_SERVE_TRACE + the
serve-shape flags) are read only inside this package, so leaving them
unset cannot perturb any training program.

The reverse does not hold yet: this package imports the trainer
(`reshard` -> `engine.hot_switch.param_handle` -> `hetu_tpu.engine` ->
`engine.trainer`).  That is hetu_tpu's own modules alone, tens of
milliseconds, since `utils.checkpoint` loads orbax at the first save or
restore (PR 56; tests/test_import_cost.py keeps it so); cutting the edge
itself is ROADMAP queue 3's.
"""
from hetu_tpu.serving.costs import (COST_FIELDS,  # noqa: F401
                                    CostLedger, CostModel,
                                    aggregate_costs)
from hetu_tpu.serving.disagg import (DisaggCoordinator,  # noqa: F401
                                     PrefillWorker, Shipment,
                                     ShipmentChannel, pack_shipment,
                                     unpack_shipment)
from hetu_tpu.serving.engine import (ServeConfig,  # noqa: F401
                                     ServingEngine,
                                     first_token_from_logits)
from hetu_tpu.serving.frontend import Frontend  # noqa: F401
from hetu_tpu.serving.fleet import (FleetConfig,  # noqa: F401
                                    FleetSimulator, ServiceModel,
                                    analytic_models, attainment_delta,
                                    fleet_workload)
from hetu_tpu.serving.kv_pool import (PagePool,  # noqa: F401
                                      PoolArrays, kv_bytes_per_token)
from hetu_tpu.serving.prefix_cache import (RadixPrefixCache,  # noqa: F401
                                           maybe_prefix_cache)
from hetu_tpu.serving.request import (DEFAULT_SLO, GREEDY,  # noqa: F401
                                      Request, RequestResult,
                                      RequestStats, SamplingParams,
                                      SLOClass, TenantQuota,
                                      parse_quotas, rid_sampled)
from hetu_tpu.serving.reshard import LoadAdaptiveMesh  # noqa: F401
from hetu_tpu.serving.scheduler import Scheduler, SlotState  # noqa: F401
from hetu_tpu.serving.slo_report import (serving_report,  # noqa: F401
                                         render_text)
from hetu_tpu.serving.spec_decode import (CallableDrafter,  # noqa: F401
                                          Drafter, NGramDrafter,
                                          make_drafter)
from hetu_tpu.serving.traces import (bursty_arrivals,  # noqa: F401
                                     poisson_arrivals, synthetic_requests)
from hetu_tpu.serving.tracing import (RequestTracer,  # noqa: F401
                                      maybe_tracer)

__all__ = [
    "ServingEngine", "ServeConfig", "first_token_from_logits",
    "DisaggCoordinator", "PrefillWorker", "Shipment", "ShipmentChannel",
    "pack_shipment", "unpack_shipment", "Frontend",
    "FleetSimulator", "FleetConfig", "ServiceModel", "analytic_models",
    "attainment_delta", "fleet_workload",
    "CostModel", "CostLedger", "COST_FIELDS", "aggregate_costs",
    "PagePool", "PoolArrays", "kv_bytes_per_token",
    "RadixPrefixCache", "maybe_prefix_cache",
    "Request", "RequestResult", "RequestStats", "SLOClass", "DEFAULT_SLO",
    "SamplingParams", "GREEDY",
    "TenantQuota", "parse_quotas", "rid_sampled",
    "Scheduler", "SlotState",
    "LoadAdaptiveMesh",
    "Drafter", "NGramDrafter", "CallableDrafter", "make_drafter",
    "RequestTracer", "maybe_tracer",
    "serving_report", "render_text",
    "poisson_arrivals", "bursty_arrivals", "synthetic_requests",
]
