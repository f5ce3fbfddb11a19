"""Mixture-of-Experts layer built on the DX100 bulk-access pipeline.

Token->expert routing *is* the paper's indirection pattern:

  reorder   : tokens sorted by expert id (sort_indices) so each expert's
              rows form one contiguous run — a "DRAM row" opened once;
  coalesce  : capacity-bounded contiguous expert buffers, one scatter with
              unique destinations (single-writer, no atomics);
  interleave: expert buffers sharded over the `model`/expert mesh axis —
              GSPMD routes the dispatch as all-to-all across chips
              (address-range partitioning, paper §6.6);
  combine   : IRMW ADD — weighted scatter-add back to token order via
              sort+segment-sum (bulk_rmw), the RMW microbenchmark embedded
              in a real model.

Experts run as one batched einsum over (n_experts, capacity, d_model).
When n_experts < model-axis size, expert weights carry an inner TP factor
(`ep_tp`) so the (experts x tp) product fills the axis (grok-1: 8e x 2).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bulk_ops, reorder
from repro.models.layers import _dense_init


def init_moe(key, d_model: int, d_ff: int, n_experts: int,
             dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d_model, n_experts), jnp.float32),
        "w_gate": _dense_init(ks[1], (n_experts, d_model, d_ff), dtype),
        "w_up": _dense_init(ks[2], (n_experts, d_model, d_ff), dtype),
        "w_down": _dense_init(ks[3], (n_experts, d_ff, d_model), dtype),
    }


def moe_ffn(p: dict, x: jax.Array, *, n_experts: int, top_k: int,
            capacity_factor: float = 1.25,
            dx100_combine: bool = True) -> jax.Array:
    """x: (B, S, D) -> (B, S, D)."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)

    # --- routing -----------------------------------------------------------
    logits = (xt.astype(jnp.float32) @ p["router"])           # (T, E)
    weights, experts = jax.lax.top_k(logits, top_k)           # (T, K)
    weights = jax.nn.softmax(weights, axis=-1)

    # --- reorder: sort the T*K (token, expert) pairs by expert -------------
    flat_e = experts.reshape(-1).astype(jnp.int32)            # (T*K,)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    flat_w = weights.reshape(-1)
    sorted_e, perm = reorder.sort_indices(flat_e)
    sorted_tok = flat_tok[perm]
    sorted_w = flat_w[perm]

    # --- coalesce into capacity-bounded contiguous expert buffers ----------
    capacity = int(capacity_factor * t * top_k / n_experts)
    capacity = max(8, -(-capacity // 8) * 8)                  # sublane align
    counts = jax.ops.segment_sum(jnp.ones_like(sorted_e), sorted_e,
                                 num_segments=n_experts)
    estart = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos_in_e = jnp.arange(t * top_k, dtype=jnp.int32) - estart[sorted_e]
    keep = pos_in_e < capacity                                # overflow drop
    dest = jnp.where(keep, sorted_e * capacity + pos_in_e,
                     n_experts * capacity)
    buf = jnp.zeros((n_experts * capacity, d), x.dtype)
    buf = buf.at[dest].set(xt[sorted_tok], mode="drop",
                           unique_indices=True)
    buf = buf.reshape(n_experts, capacity, d)

    # --- expert FFN: one batched einsum (each expert = one opened "row") ---
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"])            # (E, C, D)
    y = y.reshape(n_experts * capacity, d)

    # --- combine: IRMW ADD back to token order ------------------------------
    gathered = y[jnp.clip(dest, 0, n_experts * capacity - 1)]
    contrib = gathered * sorted_w[:, None].astype(y.dtype)
    contrib = jnp.where(keep[:, None], contrib, 0)
    if dx100_combine:
        out = bulk_ops.bulk_rmw(jnp.zeros((t, d), y.dtype), sorted_tok,
                                contrib, op="ADD")
    else:  # naive duplicate-index scatter (serializing baseline)
        out = jnp.zeros((t, d), y.dtype).at[sorted_tok].add(contrib)
    return out.reshape(b, s, d).astype(x.dtype), logits


def _ambient_model_axis():
    """Size of the 'model' axis of the ambient (jit) mesh, or 0."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is not None and "model" in (mesh.axis_names or ()):
            return int(dict(zip(mesh.axis_names, mesh.axis_sizes))["model"])
    except Exception:  # noqa: BLE001
        pass
    return 0


def moe_ffn_ep(p: dict, x: jax.Array, *, n_experts: int, top_k: int,
               capacity_factor: float = 1.25):
    """Expert-parallel MoE via shard_map (beyond-paper opt, §Perf).

    Key observation: activations are replicated across the `model` axis
    (they are sharded only over `data`), so every model-column device can
    *locally* select the tokens routed to ITS expert — dispatch costs ZERO
    collective bytes. Only the combine needs communication: one psum of the
    (T/dp, D) output partial-sums over `model`. This replaces GSPMD's
    all-gather of the full (T*top_k, D) update stream into the
    expert-sharded buffer (the dominant collective of the baseline).

    This is the paper's §6.6 "core multiplexing" realized on a mesh: each
    engine instance (device column) owns one expert's address range and is
    its single writer.

    Requires n_experts == model-axis size and T % data-axis == 0; callers
    fall back to `moe_ffn` otherwise.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    model_size = _ambient_model_axis()
    b, s, d = x.shape
    t = b * s
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    dp = 1
    for a in dp_axes:
        dp *= int(sizes[a])
    tl = t // dp
    cap = int(capacity_factor * tl * top_k / n_experts)
    cap = max(8, -(-cap // 8) * 8)

    def local(xt, router, w_gate, w_up, w_down):
        # xt: (Tl, D); w_*: (1, D, F) — this device's expert
        logits = xt.astype(jnp.float32) @ router            # (Tl, E)
        weights, experts = jax.lax.top_k(logits, top_k)
        weights = jax.nn.softmax(weights, axis=-1)
        my_e = jax.lax.axis_index("model")
        flat_e = experts.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(tl, dtype=jnp.int32), top_k)
        flat_w = weights.reshape(-1)
        mine = flat_e == my_e
        pos = jnp.cumsum(mine.astype(jnp.int32)) - 1
        keep = mine & (pos < cap)
        dest = jnp.where(keep, pos, cap)
        buf = jnp.zeros((cap + 1, d), xt.dtype)
        buf = buf.at[dest].set(xt[flat_tok], mode="drop",
                               unique_indices=True)[:cap]
        h = jax.nn.silu(buf @ w_gate[0]) * (buf @ w_up[0])
        y = (h @ w_down[0]).astype(jnp.float32)             # (cap, D)
        # combine: local scatter-add in token order, psum over experts
        contrib = jnp.zeros((tl, d), jnp.float32)
        src = jnp.where(keep, pos, cap - 1)
        val = y[src] * jnp.where(keep, flat_w, 0.0)[:, None]
        tok = jnp.where(keep, flat_tok, tl)
        contrib = contrib.at[tok].add(val, mode="drop")
        out = jax.lax.psum(contrib, "model")
        return out.astype(xt.dtype), logits

    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    out, logits = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp_spec, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp_spec, None), P(dp_spec, None)),
    )(x.reshape(t, d), p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out.reshape(b, s, d), logits


def moe_ffn_auto(p, x, *, n_experts, top_k, capacity_factor=1.25,
                 use_ep: bool = False):
    """Dispatch to the EP fast path when legal, else the GSPMD baseline."""
    if use_ep:
        model_size = _ambient_model_axis()
        b, s, _ = x.shape
        mesh = jax.sharding.get_abstract_mesh()
        if model_size == n_experts and mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
            dp = 1
            for a, n in sizes.items():
                if a != "model":
                    dp *= int(n)
            if (b * s) % dp == 0:
                return moe_ffn_ep(p, x, n_experts=n_experts, top_k=top_k,
                                  capacity_factor=capacity_factor)
    return moe_ffn(p, x, n_experts=n_experts, top_k=top_k,
                   capacity_factor=capacity_factor)


def moe_aux_loss(router_logits: jax.Array, n_experts: int,
                 top_k: int) -> jax.Array:
    """Switch-style load-balancing loss over the whole batch."""
    probs = jax.nn.softmax(router_logits, axis=-1)            # (T, E)
    _, top = jax.lax.top_k(router_logits, top_k)
    onehot = jax.nn.one_hot(top, n_experts, dtype=jnp.float32).sum(1)
    frac_tokens = onehot.mean(0) / top_k
    frac_probs = probs.mean(0)
    return n_experts * jnp.sum(frac_tokens * frac_probs)
