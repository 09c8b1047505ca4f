"""Mixture-of-Experts FFN with sort-based grouped dispatch.

Token-choice top-k routing with per-group capacity (GShard-style dropping),
but **without** materialising GShard's dense dispatch/combine tensors — we
group tokens per batch row by a stable sort on expert id, scatter into
equal-capacity expert bins, run batched expert matmuls, and gather back.
Bin tensors are O(tokens · k · d), independent of E.

Parallelism (decided per-arch by the sharding rules, see DESIGN.md):
  * **EP**  — experts axis sharded over the model axis when divisible
    (qwen3-moe 128e, jamba 16e on a 16-way axis);
  * **TP-in-expert** — expert FF dim sharded instead when not divisible
    (mixtral 8e on a 16-way axis).
Both are expressed as sharding constraints on the bin/weight einsums; the
SPMD partitioner inserts the dispatch/combine collectives.  A shard_map
all-to-all variant lives in ``repro/distributed/ep_a2a.py`` (the §Perf
hillclimb for collective-bound MoE cells).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig

Array = jax.Array

# callers may install a sharding-constraint hook; identity by default
ConstraintFn = Callable[[Array, str], Array]
_identity: ConstraintFn = lambda x, kind: x


def init_moe_params(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    d = cfg.d_model
    e = cfg.num_experts
    ff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 4)
    scale_in = 1.0 / jnp.sqrt(d)
    scale_out = 1.0 / jnp.sqrt(ff)
    return {
        "router": L.dense_init(ks[0], d, e, jnp.float32),
        "w_gate": jax.random.normal(ks[1], (e, d, ff), dtype) * scale_in,
        "w_up": jax.random.normal(ks[2], (e, d, ff), dtype) * scale_in,
        "w_down": jax.random.normal(ks[3], (e, ff, d), dtype) * scale_out,
    }


def moe_apply(params: dict, x: Array, cfg: ModelConfig,
              constrain: ConstraintFn = _identity,
              capacity_factor: Optional[float] = None) -> Array:
    """x: (B, S, D) → (B, S, D).  Groups = batch rows (data-sharded).

    When the constrainer advertises an EP-capable mesh (experts divide the
    model axis), dispatch goes through the shard_map expert-parallel path —
    explicit local routing + one psum — instead of letting GSPMD re-shard
    the bin gather/scatter (which costs an all-gather of the full bin tensor
    per layer; the §Perf-A hillclimb measured a ~10× collective-term cut).
    """
    mesh = getattr(constrain, "mesh", None)
    if mesh is not None and getattr(constrain, "ep", False):
        return _moe_apply_shard_map(params, x, cfg, constrain,
                                    capacity_factor)
    return _moe_apply_pjit(params, x, cfg, constrain, capacity_factor)


def _moe_apply_pjit(params: dict, x: Array, cfg: ModelConfig,
                    constrain: ConstraintFn = _identity,
                    capacity_factor: Optional[float] = None) -> Array:
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity
    dtype = x.dtype

    logits = (x @ params["router"].astype(dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (B, S, E)
    topv, topi = jax.lax.top_k(probs, k)  # (B, S, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    cap = max(int(capacity_factor * s * k / e), 1)
    cap = min(cap, s)  # no point over-provisioning past the group size

    def group_one(xi, ti):
        """Per batch row: (S, D), (S, k) → bins (E, cap, D), slots (S*k,)."""
        flat_e = ti.reshape(-1)  # (S*k,)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        sorted_tok = order // k
        counts = jnp.bincount(flat_e, length=e)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(s * k) - starts[sorted_e]
        keep = rank < cap
        slot = jnp.where(keep, sorted_e * cap + rank, e * cap)  # overflow slot
        bins = jnp.zeros((e * cap + 1, d), dtype).at[slot].set(xi[sorted_tok])
        # invert: slot of each original (token, k) selection (for combine)
        inv = jnp.zeros((s * k,), jnp.int32).at[order].set(slot.astype(jnp.int32))
        return bins[: e * cap].reshape(e, cap, d), inv

    bins, inv = jax.vmap(group_one)(x, topi)  # (B, E, cap, D), (B, S*k)
    bins = constrain(bins, "moe_bins")

    w_gate = params["w_gate"].astype(dtype)
    w_up = params["w_up"].astype(dtype)
    w_down = params["w_down"].astype(dtype)
    h = L.ACTS[cfg.act](jnp.einsum("becd,edf->becf", bins, w_gate))
    h = h * jnp.einsum("becd,edf->becf", bins, w_up)
    out_bins = jnp.einsum("becf,efd->becd", h, w_down)
    out_bins = constrain(out_bins, "moe_bins")

    # combine: gather each token's k expert outputs back, weight, and sum
    flat = out_bins.reshape(b, e * cap, d)
    flat = jnp.concatenate([flat, jnp.zeros((b, 1, d), dtype)], axis=1)  # overflow→0
    gathered = jnp.take_along_axis(flat, inv[:, :, None], axis=1)  # (B, S*k, D)
    gathered = gathered.reshape(b, s, k, d)
    out = (gathered * topv[..., None].astype(dtype)).sum(axis=2)
    return constrain(out, "activation")


def _moe_apply_shard_map(params: dict, x: Array, cfg: ModelConfig,
                         constrain: ConstraintFn,
                         capacity_factor: Optional[float] = None) -> Array:
    """Expert-parallel MoE with explicit collectives (§Perf-A).

    Per (dp, tp) shard: activations are dp-sharded and tp-replicated
    (standard TP posture), expert weights are tp-sharded on the expert axis.
    Each shard routes its local tokens, builds bins **only for its local
    experts**, runs the expert FFNs, combines locally, and one ``psum`` over
    the model axis sums the per-expert-shard partial outputs.  Total
    collective volume per layer = one (B_loc, S, D) all-reduce — versus
    GSPMD's re-sharding of the (B, E, cap, D) bin tensor.
    """
    from jax.sharding import PartitionSpec as P

    mesh = constrain.mesh
    axes = constrain.axes
    dp_ax = axes.dp if len(axes.dp) > 1 else axes.dp[0]
    tp = axes.tp
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity
    cap = max(min(int(capacity_factor * s * k / e), s), 1)
    e_local = e // axes.tp_size(mesh)
    dtype = x.dtype
    b_spec = P(dp_ax, None, None) if b % axes.dp_size(mesh) == 0 else P()

    def local(x_l, router, w_gate, w_up, w_down):
        bl = x_l.shape[0]
        tp_idx = jax.lax.axis_index(tp)
        e0 = tp_idx * e_local
        logits = (x_l @ router.astype(dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, k)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

        def group_one(xi, ti):
            flat_e = ti.reshape(-1)
            order = jnp.argsort(flat_e, stable=True)
            sorted_e = flat_e[order]
            counts = jnp.bincount(flat_e, length=e)
            starts = jnp.cumsum(counts) - counts
            rank = jnp.arange(s * k) - starts[sorted_e]
            keep = rank < cap
            rel = sorted_e - e0
            local_ok = keep & (rel >= 0) & (rel < e_local)
            slot = jnp.where(local_ok, rel * cap + rank, e_local * cap)
            bins = jnp.zeros((e_local * cap + 1, x_l.shape[-1]), dtype
                             ).at[slot].set(xi[order // k])
            inv = jnp.zeros((s * k,), jnp.int32).at[order].set(
                slot.astype(jnp.int32))
            return bins[: e_local * cap].reshape(e_local, cap, -1), inv

        bins, inv = jax.vmap(group_one)(x_l, topi)
        h = L.ACTS[cfg.act](jnp.einsum("becd,edf->becf", bins,
                                       w_gate.astype(dtype)))
        h = h * jnp.einsum("becd,edf->becf", bins, w_up.astype(dtype))
        out_bins = jnp.einsum("becf,efd->becd", h, w_down.astype(dtype))
        flat = out_bins.reshape(bl, e_local * cap, -1)
        flat = jnp.concatenate(
            [flat, jnp.zeros((bl, 1, flat.shape[-1]), dtype)], axis=1)
        gathered = jnp.take_along_axis(flat, inv[:, :, None], axis=1)
        gathered = gathered.reshape(bl, s, k, -1)
        partial = (gathered * topv[..., None].astype(dtype)).sum(axis=2)
        return jax.lax.psum(partial, tp)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(b_spec, P(), P(tp, None, None), P(tp, None, None),
                  P(tp, None, None)),
        out_specs=b_spec,
        check_vma=False)
    return fn(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"])


def aux_load_balance_loss(logits: Array, topi: Array, num_experts: int) -> Array:
    """Switch-style auxiliary load-balancing loss (mean fraction · mean prob)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = probs.mean(axis=(0, 1))  # (E,)
    one_hot = jax.nn.one_hot(topi[..., 0], num_experts)
    ce = one_hot.mean(axis=(0, 1))
    return num_experts * jnp.sum(me * ce)
