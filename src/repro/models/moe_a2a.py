"""Explicit all-to-all MoE dispatch (shard_map) — §Perf iteration 4.

The pjit gather/scatter dispatch (models/moe.py) lets GSPMD lower the
cross-shard token gather as per-layer all-gathers of the full activation
tensor (~25 GB/chip/layer on deepseek train_4k).  This module routes tokens
explicitly instead — the *distributed* FliX pattern (core/distributed.py
``shard_apply_ops``'s a2a routing) applied to experts:

  * tokens are sharded over every mesh axis (data × model);
  * expert weights are EP-sharded over ``model`` and replicated over data,
    so a token on device (d, m) only ever needs devices (d, ·) — the
    all-to-all runs along the model axis within each data row;
  * each device sorts its local token-slots by expert (the sorted batch),
    slices per-destination ranges by searchsorted (the fence pull), and
    exchanges fixed-capacity buffers; experts compute locally; results
    return through the inverse all-to-all.

Per-chip bytes per layer ≈ 2 · T_loc · k · D (send + return) — independent
of the token-parallel width — vs the gather formulation's T · D all-gather.

Capacity contract: per-(src,dst) buffer is
``ceil(T_loc · k / n_exp_shards · factor)`` rounded to 8; overflow slots are
dropped (standard capacity-style MoE; the factor is config).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _local_capacity(t_loc: int, k: int, n_shards: int, factor: float) -> int:
    c = math.ceil(t_loc * k / n_shards * factor)
    return max(8, math.ceil(c / 8) * 8)


def moe_ffn_a2a(x: jax.Array, p: dict, cfg, mesh) -> jax.Array:
    """x: [T, D] (token-sharded over all mesh axes) → [T, D]."""
    E, k, split = cfg.num_experts, cfg.top_k, cfg.moe_split
    E_v, k_v = E * split, k * split
    ep_axis = "model"
    token_axes = tuple(a for a in mesh.axis_names)  # tokens over all axes
    n_ep = int(mesh.shape[ep_axis])
    e_loc = E_v // n_ep
    T, D = x.shape
    t_loc = T // int(mesh.devices.size)
    C_pair = _local_capacity(t_loc, k_v, n_ep, cfg.moe_capacity_factor)
    R = n_ep * C_pair  # received slots per device

    def body(x_loc, router, w_gate, w_up, w_down):
        tl = x_loc.shape[0]
        # --- route: top-k + virtual-expert expansion ----------------------
        logits = x_loc.astype(jnp.float32) @ router.astype(jnp.float32)
        weights, experts = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
        weights = weights / jnp.sum(weights, -1, keepdims=True)
        if split > 1:
            experts = (
                experts[..., None] * split
                + jnp.arange(split, dtype=experts.dtype)
            ).reshape(tl, k_v)
            weights = jnp.repeat(weights, split, axis=-1)

        # --- sort the batch by expert (the FliX sorted batch) --------------
        flat_e = experts.reshape(-1).astype(jnp.int32)          # [tl*k_v]
        order = jnp.argsort(flat_e, stable=True)
        e_sorted = flat_e[order]
        tok_sorted = order // k_v
        w_sorted = weights.reshape(-1)[order]

        # --- per-destination slices (fence searchsorted) -------------------
        # destination shard of expert e is e // e_loc
        shard_fences = (
            jnp.arange(1, n_ep + 1, dtype=jnp.int32) * e_loc
        )  # first expert NOT owned by shard s
        ends = jnp.searchsorted(e_sorted, shard_fences, side="left")
        starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])

        idx = starts[:, None] + jnp.arange(C_pair, dtype=jnp.int32)[None]
        valid = idx < ends[:, None]                             # [n_ep, C]
        idx_c = jnp.minimum(idx, tl * k_v - 1)
        send_x = jnp.where(
            valid[..., None], x_loc[tok_sorted[idx_c]], 0
        )                                                        # [n_ep, C, D]
        send_e = jnp.where(valid, e_sorted[idx_c], -1)           # local tag
        send_slot = jnp.where(valid, idx_c, -1)                  # for return

        # --- all-to-all along the EP axis ----------------------------------
        recv_x = jax.lax.all_to_all(send_x, ep_axis, 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, ep_axis, 0, 0, tiled=False)

        # --- local expert compute: sort received rows by local expert and
        #     pull per-expert capacity windows (FliX again, one level down) --
        my_first = jax.lax.axis_index(ep_axis) * e_loc
        rx = recv_x.reshape(R, D)
        re_raw = recv_e.reshape(R)
        valid_r = re_raw >= 0
        re = jnp.where(valid_r, re_raw - my_first, e_loc)        # pad → end
        order2 = jnp.argsort(re, stable=True)
        rx_s = rx[order2]
        offs = jnp.searchsorted(
            re[order2], jnp.arange(e_loc + 1, dtype=jnp.int32), side="left"
        )
        C_loc = min(R, _local_capacity(R, 1, e_loc, cfg.moe_capacity_factor))
        idx2 = offs[:-1, None] + jnp.arange(C_loc, dtype=jnp.int32)[None]
        valid2 = idx2 < offs[1:, None]                           # [e_loc,C_loc]
        idx2_c = jnp.minimum(idx2, R - 1)
        xe = jnp.where(valid2[..., None], rx_s[idx2_c], 0)       # [e_loc,C_loc,D]

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) * jnp.einsum(
            "ecd,edf->ecf", xe, w_up
        )
        ye = jnp.einsum("ecf,efd->ecd", h, w_down)               # [e_loc,C_loc,D]

        # scatter back to received-slot order (each row owned by one expert)
        dest = jnp.where(valid2, order2[idx2_c], R).reshape(-1)
        y = (
            jnp.zeros((R + 1, D), ye.dtype)
            .at[dest]
            .add(ye.reshape(e_loc * C_loc, D))[:R]
        )

        # --- return a2a + weighted combine ---------------------------------
        back = jax.lax.all_to_all(
            y.reshape(n_ep, C_pair, D), ep_axis, 0, 0, tiled=False
        )                                                         # [n_ep,C,D]
        contrib = back.reshape(n_ep * C_pair, D) * jnp.where(
            valid, w_sorted[idx_c], 0.0
        ).reshape(-1, 1).astype(back.dtype)
        tok = jnp.where(valid, tok_sorted[idx_c], tl).reshape(-1)
        out = jnp.zeros((tl + 1, D), contrib.dtype).at[tok].add(contrib)[:tl]
        return out.astype(x_loc.dtype)

    y = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(token_axes, None),
            P(),                           # router replicated
            P(ep_axis, None, None),        # EP expert weights
            P(ep_axis, None, None),
            P(ep_axis, None, None),
        ),
        out_specs=P(token_axes, None),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if cfg.num_shared_experts:  # dense, position-wise: no routing needed
        hs = jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])
        y = y + hs @ p["shared_down"]
    return y
