"""Straggler injection tool.

Rebuild of the reference's straggler workloads (reference: workloads/cuda/
workload_{heavy_compute,heavy_communicate,stall_communicate}.cu — standalone
binaries that occupy/stall GPUs to simulate stragglers for the Malleus
experiments, examples/malleus/test_straggler_workload.py).

TPU version: a competing process that burns MXU cycles (heavy_compute) or
sleeps in bursts (stall) on the local chip, degrading a co-located trainer
so Malleus planning / elastic behavior can be exercised.

    python tools_straggler.py --mode compute --duty 0.5 --seconds 60
"""
import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["compute", "stall", "transfer"],
                    default="compute")
    ap.add_argument("--duty", type=float, default=0.5,
                    help="fraction of each second spent burning")
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--size", type=int, default=4096)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    x = jnp.ones((args.size, args.size), jnp.bfloat16)

    @jax.jit
    def burn(x):
        for _ in range(8):
            x = (x @ x) * (1.0 / args.size)
        return jnp.sum(x.astype(jnp.float32))

    import numpy as np
    host_buf = (np.ones((args.size, args.size), np.float32)
                if args.mode == "transfer" else None)

    t_end = time.time() + args.seconds
    print(f"straggler[{args.mode}] duty={args.duty} for {args.seconds}s")
    while time.time() < t_end:
        t0 = time.time()
        if args.mode == "transfer":
            # heavy_communicate analog: saturate the host<->device link
            # (the single-chip stand-in for contended ICI/NCCL bandwidth)
            while time.time() - t0 < args.duty:
                d = jax.device_put(host_buf)
                np.asarray(d[:1, :1])   # round trip forces the copy back
            time.sleep(max(0.0, 1.0 - args.duty))
        elif args.mode == "compute":
            # occupy the device for `duty` of each second
            while time.time() - t0 < args.duty:
                float(burn(x))
            time.sleep(max(0.0, 1.0 - args.duty))
        else:
            # stall: one short device burst per cycle, then idle for the rest
            # — duty stays 'fraction of the cycle busy' in BOTH modes; the
            # burst keeps the device claimed (queue pressure), the shape of
            # the reference's stall_communicate workload
            burst_t = time.time()
            float(burn(x))
            busy = time.time() - burst_t
            time.sleep(max(busy * (1.0 - args.duty) / max(args.duty, 0.05),
                           0.01))
    print("straggler done")


if __name__ == "__main__":
    main()
