"""Hardware + model profiling for the strategy search.

Rebuild of the Galvatron profiler (reference: tools/Galvatron/galvatron/core/
profiler.py:8-530 — per-layer time/memory profiling and allreduce/p2p
bandwidth measurement, persisted as hardware_configs/*.json).  TPU version:
measures MXU matmul throughput and per-axis collective bandwidth on whatever
mesh is available, and ships calibrated defaults for the chips we know.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class HardwareProfile:
    """The TPU analog of hardware_configs/*.json."""
    chip: str = "v5e"
    bf16_tflops: float = 197.0          # per chip peak
    hbm_gbytes: float = 16.0
    hbm_gbps: float = 820.0
    ici_allreduce_gbps: float = 45.0    # bus bandwidth per chip (1D ring)
    ici_p2p_gbps: float = 90.0
    dcn_gbps: float = 6.25
    # optional slice topology section (comm/topology.py Topology):
    # {slice_devices, slice_shape?, intra_gbps, inter_gbps}
    topology: Optional[Dict[str, object]] = None
    measured: Dict[str, float] = dataclasses.field(default_factory=dict)

    PRESETS = {
        "v5e": dict(bf16_tflops=197.0, hbm_gbytes=16.0, hbm_gbps=820.0,
                    ici_allreduce_gbps=45.0, ici_p2p_gbps=90.0),
        "v5p": dict(bf16_tflops=459.0, hbm_gbytes=95.0, hbm_gbps=2765.0,
                    ici_allreduce_gbps=90.0, ici_p2p_gbps=180.0),
        "v4": dict(bf16_tflops=275.0, hbm_gbytes=32.0, hbm_gbps=1228.0,
                   ici_allreduce_gbps=50.0, ici_p2p_gbps=100.0),
    }

    #: `jax.Device.device_kind` -> preset name; a kind that is not here
    #: has no peak to assume ("TPU v5 lite" is what a v5e reports)
    DEVICE_KINDS = {"TPU v5 lite": "v5e", "TPU v5e": "v5e",
                    "TPU v5p": "v5p", "TPU v5": "v5p", "TPU v4": "v4"}

    @staticmethod
    def preset(chip: str) -> "HardwareProfile":
        return HardwareProfile(chip=chip, **HardwareProfile.PRESETS[chip])

    @staticmethod
    def for_device_kind(kind: str) -> "HardwareProfile":
        """The preset of the chip a `device_kind` names; an unknown kind
        raises — there is no peak to assume for it."""
        try:
            return HardwareProfile.preset(HardwareProfile.DEVICE_KINDS[kind])
        except KeyError:
            raise ValueError(
                f"no hardware preset for device_kind {kind!r} (known: "
                f"{sorted(HardwareProfile.DEVICE_KINDS)})") from None

    def save(self, path: str):
        with open(path, "w") as f:
            json.dump(dataclasses.asdict(self), f, indent=2)

    @staticmethod
    def load(path: str) -> "HardwareProfile":
        with open(path) as f:
            return HardwareProfile(**json.load(f))


def _sync(x):
    # host fetch of one element: waits for the device like
    # block_until_ready, whatever pytree the probe returns
    return float(np.asarray(jax.tree.leaves(x)[0]).reshape(-1)[0])


def _diff_time(f_full, f_half, iters: int):
    """Differential timing: run the probe at two rep counts and use the
    TIME DIFFERENCE, which cancels every constant cost (dispatch, host
    fetch) exactly — regardless of how much of it
    overlaps device compute.  Plain subtraction of a measured scalar
    round-trip is wrong in both directions here (round-2 captures: 73
    TFLOP/s uncorrected, 209 > 197-peak fully-corrected); the two-point
    scheme read 189-196 on the same chip.  Returns seconds per
    work_diff_units of extra work."""
    _sync(f_full()); _sync(f_half())        # compile both
    t_full, t_half = [], []
    for _ in range(iters):
        t = time.perf_counter()
        _sync(f_half())
        t_half.append(time.perf_counter() - t)
        t = time.perf_counter()
        _sync(f_full())
        t_full.append(time.perf_counter() - t)
    dt = min(t_full) - min(t_half)
    if dt <= 0.05 * min(t_full):
        raise RuntimeError(
            f"differential probe too noisy: t_full={min(t_full):.4f}s "
            f"t_half={min(t_half):.4f}s")
    return dt


def measure_matmul_tflops(n: int = 4096, iters: int = 8,
                          dtype=jnp.bfloat16) -> float:
    """Measured MXU throughput (the per-layer compute calibration input)."""
    reps = 512
    if jax.default_backend() == "cpu":   # keep the CPU smoke path fast
        n, iters, reps = min(n, 1024), min(iters, 3), 8
    a = jnp.ones((n, n), dtype)
    b = jnp.ones((n, n), dtype)

    def body(reps):
        def run(a, b):
            x = jax.lax.fori_loop(
                0, reps, lambda i, x: (x @ b).astype(dtype), a)
            return jnp.sum(x.astype(jnp.float32))
        g = jax.jit(run)
        return lambda: g(a, b)

    dt = _diff_time(body(reps), body(reps // 2), iters)
    return (reps // 2) * 2 * n ** 3 / dt / 1e12


def measure_hbm_gbps(mbytes: int = 256, iters: int = 8) -> float:
    """Measured HBM read+write bandwidth via a big elementwise copy-scale
    (reference: galvatron profiles comm bandwidth; HBM is the TPU analog
    bottleneck).  Bytes counted = read + write of the buffer."""
    n = mbytes * 1024 * 1024 // 4
    reps = 64
    if jax.default_backend() == "cpu":
        n, reps, iters = n // 8, 8, min(iters, 3)
    x0 = jnp.ones((n,), jnp.float32)

    def body(reps):
        def run(x):
            # scan (not an unrolled chain): each step is a sequential full
            # read+write pass — an unrolled x*c+d chain would fuse into ONE
            # pass and overreport bandwidth by reps x
            def step(x, _):
                return x * 1.0000001 + 1e-9, None
            x, _ = jax.lax.scan(step, x, None, length=reps)
            return x[:1]
        g = jax.jit(run)
        return lambda: g(x0)

    dt = _diff_time(body(reps), body(reps // 2), iters)
    return (reps // 2) * 2 * n * 4 / dt / 1e9


def measure_collective_gbps(mesh, axis: str = "tp",
                            mbytes: int = 64) -> Optional[float]:
    """psum bus bandwidth over one mesh axis (reference: allreduce_bandwidth
    json files). Returns None when the axis has a single member."""
    size = int(mesh.shape.get(axis, 1))
    if size <= 1:
        return None
    n = mbytes * 1024 * 1024 // 4
    x0 = jnp.ones((n,), jnp.float32)
    from jax.sharding import PartitionSpec as P

    def body(reps):
        def run(v):
            def step(i, v):
                # fresh dependency each round so XLA cannot collapse the
                # loop into a single psum
                return jax.lax.psum(v, axis) * (1.0 / size)
            return jax.lax.fori_loop(0, reps, step, v)[:1]
        g = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P(),
                                  out_specs=P(), check_vma=False))
        return lambda: g(x0)

    dt = _diff_time(body(8), body(4), iters=5)
    # bus bytes for ring allreduce: 2 * (size-1)/size * payload, per round
    bus = 4 * 2 * (size - 1) / size * n * 4
    return bus / dt / 1e9


def measure_overlap_coef(mesh=None, axis: Optional[str] = None,
                         n: int = 2048, iters: int = 5) -> float:
    """Compute-vs-communication overlap slowdown coefficient (reference:
    tools/Galvatron/.../overlap_coefficient.json:2 — they measure how much
    compute slows when comm overlaps it and feed the factor to the search).

    Stream A = an MXU matmul chain.  Stream B = a psum chain over `axis`
    when a mesh axis with >1 members is available (real pod); on a single
    chip, an HBM-streaming chain — the same memory/DMA subsystem a real
    ICI transfer contends on, which is what makes overlap non-free.
    Each stream and the joint program are timed DIFFERENTIALLY (reps vs
    reps/2) so dispatch constants cancel.

    Returns k = t_joint / max(t_A, t_B), clipped to [1.0, 2.0]:
    1.0 = perfect overlap, 2.0 = fully serialized."""
    dtype = jnp.bfloat16
    mm_reps, mem_reps = 64, 32
    if jax.default_backend() == "cpu":
        n, mm_reps, mem_reps, iters = 512, 32, 16, 3
    a0 = jnp.ones((n, n), dtype)
    b0 = jnp.ones((n, n), dtype)
    m0 = jnp.ones((8 * n * n,), jnp.float32)

    def mm_chain(x, reps):
        x = jax.lax.fori_loop(0, reps, lambda i, x: (x @ b0).astype(dtype), x)
        return jnp.sum(x.astype(jnp.float32))

    use_psum = (mesh is not None and axis is not None
                and int(mesh.shape.get(axis, 1)) > 1)
    if use_psum:
        from jax.sharding import PartitionSpec as P
        size = int(mesh.shape[axis])

        def comm_chain(v, reps):
            def run(v):
                return jax.lax.fori_loop(
                    0, reps, lambda i, v: jax.lax.psum(v, axis) * (1.0 / size),
                    v)
            return jnp.sum(jax.shard_map(run, mesh=mesh, in_specs=P(),
                                         out_specs=P())(v)[:1])
    else:
        def comm_chain(v, reps):
            def step(v, _):
                return v * 1.0000001 + 1e-9, None
            v, _ = jax.lax.scan(step, v, None, length=reps)
            return jnp.sum(v[:1])

    def f_mm(reps):
        g = jax.jit(lambda a: mm_chain(a, reps))
        return lambda: g(a0)

    def f_comm(reps):
        g = jax.jit(lambda v: comm_chain(v, reps))
        return lambda: g(m0)

    def f_joint(mmr, cmr):
        g = jax.jit(lambda a, v: mm_chain(a, mmr) + comm_chain(v, cmr))
        return lambda: g(a0, m0)

    t_mm = _diff_time(f_mm(mm_reps), f_mm(mm_reps // 2), iters)
    t_cm = _diff_time(f_comm(mem_reps), f_comm(mem_reps // 2), iters)
    t_j = _diff_time(f_joint(mm_reps, mem_reps),
                     f_joint(mm_reps // 2, mem_reps // 2), iters)
    return float(np.clip(t_j / max(t_mm, t_cm), 1.0, 2.0))


def profile_hardware(mesh=None, chip: Optional[str] = None,
                     measure: bool = True) -> HardwareProfile:
    """Measure what is measurable on the current devices, fill the rest from
    the chip preset (reference: galvatron profile_hardware scripts).
    measure=False skips device benchmarks (preset-only — e.g. when planning
    for a different pod than the one running the search)."""
    prof = (HardwareProfile.preset(chip) if chip is not None else
            HardwareProfile.for_device_kind(jax.devices()[0].device_kind))
    if not measure:
        return prof
    # a probe that fails raises: a profile with a silently missing
    # measurement would be read as "the preset is what was measured"
    prof.measured["matmul_tflops"] = round(measure_matmul_tflops(), 1)
    prof.measured["hbm_gbps"] = round(measure_hbm_gbps(), 1)
    ov_axis = None
    if mesh is not None:   # first >1 axis: the psum path needs a ring
        ov_axis = next((a for a in mesh.axis_names
                        if int(mesh.shape[a]) > 1), None)
    prof.measured["overlap_coef"] = round(
        measure_overlap_coef(mesh=mesh, axis=ov_axis), 3)
    if mesh is not None:
        for axis in mesh.axis_names:
            bw = measure_collective_gbps(mesh, axis)
            if bw is not None:
                prof.measured[f"allreduce_gbps_{axis}{mesh.shape[axis]}"] = \
                    round(bw, 2)
    return prof


def profile_model_layer(block_fn, params, x, iters: int = 5) -> Dict[str, float]:
    """Per-layer fwd+bwd wall time (reference: galvatron per-layer profiling).
    block_fn(params, x) -> y with y.shape == x.shape."""
    def loss(p, x):
        return jnp.sum(block_fn(p, x).astype(jnp.float32))

    g = jax.jit(jax.grad(loss))
    _sync(g(params, x))
    times = []
    for _ in range(iters):
        t = time.perf_counter()
        _sync(g(params, x))
        times.append(time.perf_counter() - t)
    return {"fwd_bwd_s": min(times)}
