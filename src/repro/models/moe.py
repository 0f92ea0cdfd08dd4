"""Mixture-of-Experts block: top-k routing, capacity-based dispatch,
expert parallelism over the mesh "model" axis via shard_map.

TPU adaptation notes (DESIGN.md §6): activations arrive data-sharded and
model-replicated (the dense-TP convention), so *dispatch needs no
all-to-all* — every model shard already holds the tokens and gathers the
ones routed to its own experts through index-gather into an (E_loc, C, D)
capacity buffer (gather, not one-hot einsum: the buffer is the only
HBM-resident intermediate).  The combine is one psum over "model" — the
honest EP collective that shows up in the roofline's collective term.

Capacity semantics are GShard-style: per shard, each expert accepts at most
``C = ceil(N_loc·k/E · capacity_factor)`` tokens; overflow tokens drop (their
gate mass is simply lost, renormalization keeps the rest).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import Boxed, box, constrain
from repro.models.config import ModelConfig
from repro.models.layers import _dense_init

Array = jax.Array


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    kr, k1, k2, k3 = jax.random.split(key, 4)
    p = {
        "router": box(_dense_init(kr, (d, E), jnp.float32, d),
                      "embed", None),
        "w_up": box(_dense_init(k1, (E, d, ff), dtype, d),
                    "experts", "embed", None),
        "w_gate": box(_dense_init(k2, (E, d, ff), dtype, d),
                      "experts", "embed", None),
        "w_down": box(_dense_init(k3, (E, ff, d), dtype, ff),
                      "experts", None, "embed"),
    }
    return p


def _expert_ffn(w_up, w_gate, w_down, xs):
    """xs: (E_loc, C, D) → (E_loc, C, D)."""
    h = jnp.einsum("ecd,edf->ecf", xs, w_up)
    g = jnp.einsum("ecd,edf->ecf", xs, w_gate)
    h = jax.nn.silu(g) * h
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _local_moe(x_flat: Array, router_w: Array, w_up, w_gate, w_down,
               *, k: int, n_experts_global: int, e_start: int,
               capacity: int) -> Tuple[Array, Array]:
    """Per-shard MoE: dispatch local tokens to this shard's experts.

    x_flat: (N, D) local tokens (model-replicated);
    w_*: (E_loc, ...) this shard's experts covering global expert ids
    [e_start, e_start + E_loc).  Returns (partial y (N, D), aux loss).
    """
    N, D = x_flat.shape
    E_loc = w_up.shape[0]
    E = n_experts_global

    logits = (x_flat.astype(jnp.float32) @ router_w)           # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, k)                # (N, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # aux load-balance loss (computed once per shard, identical everywhere)
    me = jnp.mean(probs, axis=0)                               # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), 1), 0)
    aux = E * jnp.sum(me * ce)

    # ---- dispatch to the local expert range --------------------------------
    flat_e = expert_idx.reshape(-1)                            # (N*k,)
    flat_g = gate_vals.reshape(-1)
    local_e = flat_e - e_start
    mine = (local_e >= 0) & (local_e < E_loc)
    local_e = jnp.clip(local_e, 0, E_loc - 1)

    # position of each routed pair within its expert (rank over N*k)
    onehot = (jax.nn.one_hot(local_e, E_loc, dtype=jnp.int32)
              * mine[:, None].astype(jnp.int32))               # (N*k, E_loc)
    pos = jnp.cumsum(onehot, axis=0) - onehot                  # exclusive
    pos = jnp.sum(pos * onehot, axis=1)                        # (N*k,)
    keep = mine & (pos < capacity)
    slot = local_e * capacity + pos                            # (N*k,)
    slot = jnp.where(keep, slot, E_loc * capacity)             # spill row

    token_id = jnp.arange(N * k, dtype=jnp.int32) // k         # (N*k,)

    # gather tokens into the capacity buffer (spill row is dropped)
    src = jnp.zeros((E_loc * capacity + 1,), jnp.int32) \
        .at[slot].set(token_id, mode="drop")
    filled = jnp.zeros((E_loc * capacity + 1,), jnp.bool_) \
        .at[slot].set(keep, mode="drop")
    xs = x_flat[src[:-1]] * filled[:-1, None].astype(x_flat.dtype)
    xs = xs.reshape(E_loc, capacity, D)

    ys = _expert_ffn(w_up, w_gate, w_down, xs)                 # (E_loc, C, D)
    ys = ys.reshape(E_loc * capacity, D)

    # combine: scatter-add expert outputs back to tokens, gate-weighted
    contrib = jnp.where(keep, flat_g, 0.0).astype(ys.dtype)
    y = jnp.zeros((N, D), ys.dtype).at[jnp.where(keep, token_id, N)].add(
        ys[jnp.where(keep, slot, 0)] * contrib[:, None], mode="drop")
    return y, aux


def apply_moe(p: dict, cfg: ModelConfig, x: Array) -> Tuple[Array, Array]:
    """x: (B, S, D) → (y, aux_loss).  EP over the mesh "model" axis."""
    B, S, D = x.shape
    k = cfg.experts_per_token
    E = cfg.n_experts

    mesh = jax.sharding.get_abstract_mesh()
    router_w = p["router"].value
    w_up, w_gate, w_down = (p["w_up"].value, p["w_gate"].value,
                            p["w_down"].value)

    if mesh is None or mesh.empty or "model" not in mesh.axis_names:
        x_flat = x.reshape(B * S, D)
        cap = max(int(math.ceil(B * S * k / E * cfg.moe_capacity_factor)), 1)
        y, aux = _local_moe(x_flat, router_w, w_up, w_gate, w_down,
                            k=k, n_experts_global=E, e_start=0,
                            capacity=cap)
        return y.reshape(B, S, D), aux

    axis_sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    m_size = axis_sizes["model"]
    if E % m_size != 0:
        raise ValueError(f"n_experts={E} not divisible by model={m_size}")
    E_loc = E // m_size
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names
                       and B % axis_sizes[a] == 0 and axis_sizes[a] > 1)
    b_shards = math.prod(axis_sizes[a] for a in batch_axes) if batch_axes \
        else 1
    n_loc = (B // b_shards) * S
    cap = max(int(math.ceil(n_loc * k / E * cfg.moe_capacity_factor)), 1)

    def shard_fn(xs, rw, wu, wg, wd):
        # xs: (B_loc, S, D); wu/wg/wd: (E_loc, ...)
        m_idx = lax.axis_index("model")
        e_start = m_idx * E_loc
        y, aux = _local_moe(xs.reshape(-1, D), rw, wu, wg, wd,
                            k=k, n_experts_global=E, e_start=e_start,
                            capacity=cap)
        # combine across expert shards (each shard holds partial sums for
        # all of its local tokens) — the EP collective.
        y = lax.psum(y, "model")
        aux = lax.pmean(aux, "model")
        return y.reshape(xs.shape), aux

    bspec = batch_axes if len(batch_axes) > 1 else \
        (batch_axes[0] if batch_axes else None)
    y, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(x, router_w, w_up, w_gate, w_down)
    return y, aux
