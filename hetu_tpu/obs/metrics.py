"""Process-local metrics registry.

Rebuild of the reference's profiler cost records as an always-on surface
(reference: hetu/impl/profiler/profiler.h:25 per-op cost records,
SURVEY §5.1 HETU_EVENT_TIMING) — but instead of env-gated log lines, a
typed registry the whole runtime writes into and any exit point (trainer
close, bench, tools_obs_report) can snapshot:

    reg = get_registry()
    reg.inc("elastic.replans")
    reg.set_gauge("rpc.worker_last_seen_s", 0.0, rank=3)
    reg.observe("trainer.step_time_s", 0.412)

Counters are monotonic, gauges are last-write-wins, histograms keep a
bounded reservoir and report count/sum/min/max/percentiles.  Every series
is keyed by (name, sorted label items) so per-rank / per-strategy series
coexist under one name.  All operations are thread-safe: the rpc server's
connection threads and the trainer loop write concurrently.
"""
from __future__ import annotations

import json
import math
import random
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

_LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, Any]) -> _LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def percentile_of_sorted(sorted_vals: List[float],
                         p: float) -> Optional[float]:
    """Nearest-rank percentile (p in [0, 100]) over an ascending list —
    THE percentile definition every obs surface shares (Histogram,
    cluster aggregation), so worker-side and cluster-side p50/p95 can
    never diverge on rounding semantics."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(p / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class Histogram:
    """Bounded-reservoir timing histogram.

    Keeps the first `cap` observations verbatim plus running count/sum/
    min/max for everything; past the cap, the reservoir is maintained by
    uniform sampling (Vitter's Algorithm R): observation number k > cap
    replaces a random slot with probability cap/k, so the sample stays a
    uniform draw over the WHOLE run, not a sliding window of the tail —
    whole-run percentiles over 10^6 observations still see early-run
    outliers.  The RNG is seeded per-histogram (deterministic; the
    unseeded-rng lint and the golden tests both rely on that)."""

    __slots__ = ("cap", "count", "total", "vmin", "vmax", "_sample", "_rng",
                 "nonfinite")

    def __init__(self, cap: int = 2048, seed: int = 0):
        self.cap = cap
        self.count = 0
        self.total = 0.0
        self.vmin: Optional[float] = None
        self.vmax: Optional[float] = None
        self._sample: List[float] = []
        self._rng = random.Random(seed)
        self.nonfinite = 0

    def observe(self, value: float):
        v = float(value)
        if not math.isfinite(v):
            # a single NaN/inf observation must not poison the running
            # sum/min/max or the reservoir percentiles (one poisoned
            # export would blind every downstream consumer) — count it
            # separately and keep the finite statistics exact
            self.nonfinite += 1
            return
        self.count += 1
        self.total += v
        self.vmin = v if self.vmin is None else min(self.vmin, v)
        self.vmax = v if self.vmax is None else max(self.vmax, v)
        if len(self._sample) < self.cap:
            self._sample.append(v)
        else:
            # Algorithm R: keep this value with probability cap/count,
            # evicting a uniformly random resident
            j = self._rng.randrange(self.count)
            if j < self.cap:
                self._sample[j] = v

    def percentile(self, p: float) -> Optional[float]:
        """p in [0, 100] over the reservoir (exact until `cap` samples)."""
        return percentile_of_sorted(sorted(self._sample), p)

    def summary(self) -> Dict[str, Any]:
        out = {"count": self.count, "sum": self.total,
               "min": self.vmin, "max": self.vmax,
               "mean": (self.total / self.count) if self.count else None}
        for p in (50, 95, 99):
            out[f"p{p}"] = self.percentile(p)
        if self.nonfinite:
            # only surfaced when present so existing summary consumers
            # see an unchanged shape on healthy histograms
            out["nonfinite"] = self.nonfinite
        return out


class MetricsRegistry:
    """One process-local registry of counters/gauges/histograms."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, _LabelKey], float] = {}
        self._gauges: Dict[Tuple[str, _LabelKey], float] = {}
        self._hists: Dict[Tuple[str, _LabelKey], Histogram] = {}

    # ------------------------------------------------------------- write
    def inc(self, name: str, value: float = 1.0, **labels):
        key = (name, _label_key(labels))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def set_gauge(self, name: str, value: float, **labels):
        key = (name, _label_key(labels))
        with self._lock:
            self._gauges[key] = float(value)

    def observe(self, name: str, value: float, **labels):
        key = (name, _label_key(labels))
        with self._lock:
            self._observe(key, value)

    def _observe(self, key, value: float):
        """One observation into the histogram of `key`; the lock held."""
        h = self._hists.get(key)
        if h is None:
            h = self._hists[key] = Histogram()
        h.observe(value)

    @staticmethod
    def series(name: str, **labels) -> Tuple[str, _LabelKey]:
        """The key of one series, for `record()`: a writer of many series
        a step forms each key once."""
        return (name, _label_key(labels))

    def record(self, counts=(), observations=()):
        """`inc` and `observe` for many series under ONE lock, each named
        by its `series()` key: `counts` and `observations` are iterables
        of (key, value).  What a step loop's recorder writes at the end
        of every step (utils/profiling.StepRecorder: 11 counters and 8
        histograms of an engine step), at half of what as many single
        calls cost (PERF.md s6)."""
        with self._lock:
            counters = self._counters
            for key, value in counts:
                counters[key] = counters.get(key, 0.0) + value
            for key, value in observations:
                self._observe(key, value)

    def timer(self, name: str, **labels):
        """Context manager observing wall seconds into histogram `name`."""
        return _Timer(self, name, labels)

    # -------------------------------------------------------------- read
    def counter_value(self, name: str, **labels) -> float:
        return self._counters.get((name, _label_key(labels)), 0.0)

    def gauge_value(self, name: str, **labels) -> Optional[float]:
        return self._gauges.get((name, _label_key(labels)))

    def histogram(self, name: str, **labels) -> Optional[Histogram]:
        return self._hists.get((name, _label_key(labels)))

    def snapshot(self) -> Dict[str, Any]:
        """{'counters': [...], 'gauges': [...], 'histograms': [...]} with
        each series as {'name', 'labels', ...} — JSON-serializable."""
        with self._lock:
            counters = [{"name": n, "labels": dict(lk), "value": v}
                        for (n, lk), v in sorted(self._counters.items())]
            gauges = [{"name": n, "labels": dict(lk), "value": v}
                      for (n, lk), v in sorted(self._gauges.items())]
            hists = [dict({"name": n, "labels": dict(lk)}, **h.summary())
                     for (n, lk), h in sorted(self._hists.items())]
        return {"counters": counters, "gauges": gauges, "histograms": hists}

    def export_jsonl(self, path: str):
        """One JSONL line per series (kind-tagged) — greppable, appendable."""
        snap = self.snapshot()
        with open(path, "w") as f:
            for kind in ("counters", "gauges", "histograms"):
                for rec in snap[kind]:
                    f.write(json.dumps(dict(rec, kind=kind[:-1])) + "\n")

    def reset(self):
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


class _Timer:
    def __init__(self, reg: MetricsRegistry, name: str, labels: Dict):
        self.reg, self.name, self.labels = reg, name, labels
        self.elapsed: Optional[float] = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.elapsed = time.perf_counter() - self._t0
        self.reg.observe(self.name, self.elapsed, **self.labels)
        return False


_default = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-global default registry (what the trainer/rpc/elastic
    layers write into unless handed an explicit one)."""
    return _default
