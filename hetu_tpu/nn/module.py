"""Functional Module system.

The reference exposes a torch-like stateful `hetu.nn.Module`
(reference: python/hetu/nn/modules/module.py) whose parameters are graph
variables.  On TPU the idiomatic form is functional: a Module instance is a
*static description* (architecture + parameter specs + layouts) and parameters
live in a pytree threaded through jit-compiled functions.  The API keeps the
torch-ish construction style (attribute assignment auto-registers children,
`ModuleList`, `Sequential`) while init/apply are pure:

    model = Linear(4, 8)
    params = model.init(jax.random.key(0))       # pytree of arrays
    y = model.apply(params, x)                   # == model(params, x)

Parameter layouts are `DistributedStates`; `model.shardings(mesh)` yields the
matching NamedSharding pytree, and `model.init(key, mesh=mesh)` materializes
parameters already sharded (via jit out_shardings), so trillion-parameter
models never fully exist on one host.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from hetu_tpu.dstates import DistributedStates


@dataclasses.dataclass
class ParamSpec:
    """Declaration of one parameter (shape/dtype/init/distributed layout)."""

    shape: Tuple[int, ...]
    dtype: Any
    init: Callable[[jax.Array, Tuple[int, ...], Any], jax.Array]
    ds: Optional[DistributedStates] = None
    #: leading dims that index the copies of a stack (`stacked_spec`): a
    #: scan over them sees, and its backward makes, one copy at a time
    stack_dims: int = 0

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


class Module:
    """Base module. Subclasses declare params/children in __init__ and
    implement `forward(self, params, *args, **kwargs)`."""

    def __init__(self):
        object.__setattr__(self, "_params", {})
        object.__setattr__(self, "_children", {})

    # -- registration -------------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Module):
            self._children[name] = value
        object.__setattr__(self, name, value)

    def param(self, name: str, shape: Tuple[int, ...], init: Callable,
              dtype=jnp.float32, ds: Optional[DistributedStates] = None) -> str:
        """Declare a parameter; returns its key into the params pytree."""
        self._params[name] = ParamSpec(tuple(int(s) for s in shape), dtype, init, ds)
        return name

    def add_module(self, name: str, module: "Module") -> "Module":
        self._children[name] = module
        object.__setattr__(self, name, module)
        return module

    # -- traversal ----------------------------------------------------------
    def param_specs(self) -> Dict[str, Any]:
        """Nested dict of ParamSpec mirroring the params pytree."""
        out: Dict[str, Any] = dict(self._params)
        for cname, child in self._children.items():
            sub = child.param_specs()
            if sub:
                out[cname] = sub
        return out

    def named_modules(self, prefix: str = ""):
        yield prefix or "", self
        for cname, child in self._children.items():
            yield from child.named_modules(f"{prefix}.{cname}" if prefix else cname)

    # -- init / shardings ---------------------------------------------------
    def abstract_params(self):
        return jax.tree.map(
            lambda spec: spec.abstract(), self.param_specs(),
            is_leaf=lambda s: isinstance(s, ParamSpec))

    def shardings(self, mesh):
        """NamedSharding pytree for all params (replicated when no ds).
        Axes that do not divide a dim are dropped (e.g. FSDP on an odd-sized
        norm weight) — sharding is an optimization, never a correctness
        requirement here."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        def one(spec: ParamSpec):
            if spec.ds is None:
                return NamedSharding(mesh, P())
            ds = spec.ds
            for d, axes in enumerate(ds.spec):
                if not axes:
                    continue
                size = 1
                for a in axes:
                    size *= int(mesh.shape.get(a, 1))
                if spec.shape[d] % size:
                    ds = ds.without_split(d)
            return ds.named_sharding(mesh)

        return jax.tree.map(one, self.param_specs(),
                            is_leaf=lambda s: isinstance(s, ParamSpec))

    def partition_specs(self):
        from jax.sharding import PartitionSpec as P

        def one(spec: ParamSpec):
            return spec.ds.partition_spec() if spec.ds is not None else P()

        return jax.tree.map(one, self.param_specs(),
                            is_leaf=lambda s: isinstance(s, ParamSpec))

    def init(self, key: jax.Array, mesh=None):
        """Materialize parameters. With a mesh, init runs under jit with
        sharded outputs so each device only materializes its shard
        (the analog of reference ParallelVariableOp local init,
        reference: hetu/graph/ops/variable.cc)."""
        specs = self.param_specs()
        leaves, treedef = jax.tree.flatten(
            specs, is_leaf=lambda s: isinstance(s, ParamSpec))

        def build(key):
            keys = jax.random.split(key, len(leaves))
            return treedef.unflatten([
                spec.init(k, spec.shape, spec.dtype)
                for k, spec in zip(keys, leaves)
            ])

        if mesh is None:
            return build(key)
        shardings = self.shardings(mesh)
        with mesh:
            return jax.jit(build, out_shardings=shardings)(key)

    def num_params(self) -> int:
        total = 0
        for leaf in jax.tree.leaves(self.param_specs(),
                                    is_leaf=lambda s: isinstance(s, ParamSpec)):
            n = 1
            for s in leaf.shape:
                n *= s
            total += n
        return total

    # -- forward ------------------------------------------------------------
    def forward(self, params, *args, **kwargs):
        raise NotImplementedError

    def apply(self, params, *args, **kwargs):
        return self.forward(params, *args, **kwargs)

    def __call__(self, params, *args, **kwargs):
        return self.forward(params, *args, **kwargs)


def stacked_spec(spec: ParamSpec, num: int,
                 lead_axis: Optional[str] = None) -> ParamSpec:
    """Lift a ParamSpec to a stack of `num` independent copies with a leading
    layer dim — used by scan-over-layers decoder stacks.  Init vmaps the base
    initializer over per-layer keys.  `lead_axis` shards the layer dim (the
    pipeline-stage placement: each pp rank holds its own layer slice)."""
    base_init = spec.init

    def init(key, shape, dtype):
        keys = jax.random.split(key, shape[0])
        return jax.vmap(lambda k: base_init(k, shape[1:], dtype))(keys)

    lead = ((lead_axis,) if lead_axis else (),)
    if spec.ds is not None:
        ds = spec.ds.shifted(1, lead=lead)
    elif lead_axis:
        from hetu_tpu.dstates import DistributedStates
        ds = DistributedStates.make(len(spec.shape) + 1, {0: lead_axis})
    else:
        ds = None
    return ParamSpec((num,) + spec.shape, spec.dtype, init, ds,
                     stack_dims=spec.stack_dims + 1)


def stack_param_specs(specs, num: int, lead_axis: Optional[str] = None):
    """Map stacked_spec over a nested spec dict."""
    return jax.tree.map(lambda s: stacked_spec(s, num, lead_axis), specs,
                        is_leaf=lambda s: isinstance(s, ParamSpec))


class ModuleList(Module):
    def __init__(self, modules: Optional[List[Module]] = None):
        super().__init__()
        self._list: List[Module] = []
        for m in modules or []:
            self.append(m)

    def append(self, module: Module):
        name = str(len(self._list))
        self._list.append(module)
        self._children[name] = module
        return self

    def __iter__(self):
        return iter(self._list)

    def __len__(self):
        return len(self._list)

    def __getitem__(self, i):
        return self._list[i]

    def items(self):
        return [(str(i), m) for i, m in enumerate(self._list)]


class Sequential(ModuleList):
    def forward(self, params, x, **kwargs):
        for name, m in self.items():
            # param-less children (activations, pooling) have no subtree
            x = m(params.get(name, {}), x, **kwargs)
        return x


class ModuleDict(Module):
    def __init__(self, modules: Optional[Dict[str, Module]] = None):
        super().__init__()
        for k, v in (modules or {}).items():
            self.add_module(k, v)

    def __getitem__(self, k):
        return self._children[k]

    def items(self):
        return self._children.items()
