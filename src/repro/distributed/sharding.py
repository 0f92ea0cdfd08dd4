"""Logical-axis sharding rules (MaxText-style, shape-aware).

Parameters and activations are annotated with *logical* axis names
("batch", "heads", "ff", ...).  ``pspec`` greedily maps logical names onto
mesh axes, honoring divisibility — so the same model code serves the
single-pod (16,16) mesh, the multi-pod (2,16,16) mesh, and a 1-device CPU
smoke test without edits.  Greedy multi-assignment lets e.g. batch=256
shard over ("pod","data") while kv_heads=8 falls back from "model" to
sharding head_dim instead (the decode-KV memory fix; see DESIGN.md §6).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, ContextManager, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax._src import config as _jax_config
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Per logical axis: ordered mesh-axis candidates (first match wins).
AXIS_CANDIDATES = {
    "batch": ("pod", "data"),            # training/prefill activations
    "batch_full": ("pod", "data", "model"),  # decode batches spill to model
    "seq": ("seq",),                     # reserved (SP uses explicit rules)
    "seq_sp": ("model",),                # Megatron-SP residual stream
    "kv_seq": ("data",),                 # long-context decode KV sharding
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head": ("model",),                  # fallback when kv_heads indivisible
    "ff": ("model",),
    "experts": ("model",),
    "lru": ("model",),
    "embed": (),
    None: (),
}


def pspec(shape: Sequence[int], axes: Sequence[Optional[str]],
          mesh_axis_names: Sequence[str],
          mesh_shape: Optional[dict] = None) -> P:
    """Greedy shape-aware logical→mesh mapping.

    Each mesh axis is used at most once per tensor; a dim takes as many of
    its candidate axes as divide it (in order).
    """
    if mesh_shape is None:
        mesh_shape = {}
    used = set()
    out = []
    for size, name in zip(shape, axes):
        assigned: list = []
        rem = size
        for cand in AXIS_CANDIDATES.get(name, ()):
            if cand in used or cand not in mesh_axis_names:
                continue
            ax_size = mesh_shape.get(cand, 1)
            if ax_size > 1 and rem % ax_size == 0:
                assigned.append(cand)
                used.add(cand)
                rem //= ax_size
        out.append(tuple(assigned) if len(assigned) > 1
                   else (assigned[0] if assigned else None))
    return P(*out)


# ---------------------------------------------------------------------------
# fleet pspecs: stacked study-axis state (engine/fleet.py)
# ---------------------------------------------------------------------------
# The fleet ask plane stacks whole studies along ONE leading axis; unlike
# model parameters there is no logical-name negotiation — every leaf of the
# stacked state (X (S, b, D), y (S, b), θ (S, P), factors (S, b, b), PRNG
# keys (S, 2)) shards its leading axis over the mesh's study dimension and
# replicates the rest.  These helpers are the fleet-facing analogue of
# ``param_pspecs``.

FLEET_AXIS = "study"


def fleet_pspec(ndim: int, axis: str = FLEET_AXIS) -> P:
    """Leading-study-axis spec: ``P(axis, None, ...)`` for an ndim-leaf."""
    if ndim < 1:
        raise ValueError("fleet state leaves must have a leading study axis")
    return P(axis, *([None] * (ndim - 1)))


def fleet_sharding(mesh: Mesh, ndim: int = 1,
                   axis: Optional[str] = None) -> NamedSharding:
    """NamedSharding splitting the leading study axis of an ndim-leaf over
    a 1-D fleet mesh (``make_fleet_mesh``).  A P() with fewer axes than the
    array rank replicates the trailing dims, so ndim=1 serves every leaf."""
    if axis is None:
        axis = mesh.axis_names[0]
    return NamedSharding(mesh, fleet_pspec(ndim, axis))


def fleet_shardings(mesh: Mesh, tree, axis: Optional[str] = None):
    """Same-structure pytree of leading-study-axis NamedShardings."""
    if axis is None:
        axis = mesh.axis_names[0]
    return jax.tree.map(
        lambda x: fleet_sharding(mesh, jnp.ndim(x), axis), tree)


def gspmd_lowering() -> ContextManager:
    """Lower the programs traced inside this context through GSPMD, not
    Shardy.  The TPU compiler (libtpu 0.0.34) refuses a float64 Cholesky
    partitioned over more than one device under Shardy ("A tuple parameter
    that is being flattened shouldn't have frontend attributes").  JAX has
    no public scoped switch for the partitioner, only the process-wide
    ``jax_use_shardy_partitioner``; this is its thread-local context.  The
    setting is part of the jit cache key."""
    return _jax_config.use_shardy_partitioner(False)


# ---------------------------------------------------------------------------
# boxed parameters: value + logical axes travel together through init
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclass
class Boxed:
    """A parameter leaf annotated with logical axis names."""
    value: Any
    axes: Tuple[Optional[str], ...]

    def tree_flatten(self):
        return ((self.value,), self.axes)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)


def box(value, *axes) -> Boxed:
    return Boxed(value, tuple(axes))


def is_boxed(x) -> bool:
    return isinstance(x, Boxed)


def unbox(tree):
    """Strip Boxed wrappers → plain array pytree."""
    return jax.tree.map(lambda b: b.value, tree, is_leaf=is_boxed)


def boxed_axes(tree):
    """Same-structure pytree of logical-axes tuples."""
    return jax.tree.map(lambda b: b.axes, tree, is_leaf=is_boxed)


def param_pspecs(tree, mesh: Mesh):
    """PartitionSpec pytree for a Boxed param tree on ``mesh``."""
    names = mesh.axis_names
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(b: Boxed):
        v = b.value
        return pspec(v.shape, b.axes, names, shape)

    return jax.tree.map(one, tree, is_leaf=is_boxed)


def param_shardings(tree, mesh: Mesh):
    specs = param_pspecs(tree, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# activation constraints
# ---------------------------------------------------------------------------

def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint via logical axes; no-op off-mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or not mesh.axis_names:
        return x
    shape = dict(zip(mesh.axis_names, mesh.axis_sizes))
    spec = pspec(x.shape, axes, mesh.axis_names, shape)
    return jax.lax.with_sharding_constraint(x, spec)
