"""hetu_tpu — a TPU-native distributed deep-learning framework.

A ground-up rebuild of the capabilities of PKU-DAIR/Hetu (reference surveyed in
/root/repo/SURVEY.md) designed for TPU hardware: JAX/XLA/pjit for the compute
path, GSPMD shardings driven by a first-class distributed-layout algebra
(`DistributedStates`), Pallas kernels for the hot ops, and shard_map +
collective-permute for ring-attention context parallelism and pipelining.

Top-level namespaces mirror the reference's Python framework
(reference: python/hetu/__init__.py):

- ``hetu_tpu.core``     — mesh/device model, dtypes, symbolic ints
- ``hetu_tpu.dstates``  — DistributedStates sharding algebra (the heart)
- ``hetu_tpu.nn``       — Module system + layers (incl. parallel layers)
- ``hetu_tpu.ops``      — functional ops & Pallas kernels
- ``hetu_tpu.models``   — model families (llama, gpt, ...)
- ``hetu_tpu.parallel`` — pipeline / context / expert parallel engines
- ``hetu_tpu.optim``    — optimizers (Adam/SGD w/ ZeRO sharding)
- ``hetu_tpu.engine``   — Trainer, plan pool, strategy handling
- ``hetu_tpu.data``     — datasets, tokenizers, bucketing/packing
- ``hetu_tpu.utils``    — checkpoint, parallel-config (ds JSON), logging
"""

__version__ = "0.1.0"

from hetu_tpu.core.mesh import (
    MeshConfig,
    create_mesh,
    current_mesh,
    use_mesh,
    mesh_axis_size,
)
from hetu_tpu.core import dtypes
from hetu_tpu.core.symbol import IntSymbol
from hetu_tpu.dstates import (
    DistributedStates,
    CommType,
    deduce_comm,
    convert,
)
from hetu_tpu import nn
from hetu_tpu import ops
from hetu_tpu import optim

# Short aliases mirroring the reference API surface.
ds = DistributedStates
