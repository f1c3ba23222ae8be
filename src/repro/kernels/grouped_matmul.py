"""Ragged grouped GEMM over expert slices (MegaBlocks-style, TPU form).

This is the FliX paradigm applied to MoE compute (DESIGN.md §4): tokens are
*sorted by expert* (the sorted batch), ``group_offsets`` are the per-expert
slice boundaries (the MKBA searchsorted), and each expert — a *bucket* —
pulls its contiguous token slice and runs a dense MXU matmul on it.

Grid = (token blocks, F blocks, expert span).  Scalar-prefetched per-block
expert ranges ``elo/ehi`` drive the weight BlockSpec: span steps beyond a
block's real range clamp to the same weight block (no DMA) and skip compute
— identical machinery to the flix_query bucket streaming.

Block shapes: x (BT, D) and w (1, D, BF) are full-depth; with BT=BF=128 and
D ≤ 8192 the VMEM working set is ≤ ~4 MiB in bf16.  MXU dims are 128-aligned
by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 128
DEFAULT_BLOCK_F = 128


def _gmm_kernel(
    offs_ref,   # scalar prefetch: [E+1] token offsets per expert
    elo_ref,    # scalar prefetch: [nT] first expert of token block
    ehi_ref,    # scalar prefetch: [nT] last expert of token block
    x_ref,      # [BT, D]
    w_ref,      # [1, D, BF]
    out_ref,    # [BT, BF] f32, revisited across the span dimension
    *,
    block_t: int,
    num_experts: int,
):
    t = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    e = elo_ref[t] + k
    active = e <= ehi_ref[t]

    @pl.when(active)
    def _accumulate():
        e_c = jnp.minimum(e, num_experts - 1)
        row0 = t * block_t
        lo = jnp.clip(offs_ref[e_c] - row0, 0, block_t)
        hi = jnp.clip(offs_ref[e_c + 1] - row0, 0, block_t)
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_t, 1), 0)
        mask = (rows >= lo) & (rows < hi)
        x = jnp.where(mask, x_ref[...], 0).astype(jnp.float32)
        w = w_ref[0].astype(jnp.float32)
        out_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("block_t", "block_f", "max_span", "interpret")
)
def grouped_matmul_pallas(
    x: jax.Array,              # [T, D] tokens sorted by group
    w: jax.Array,              # [E, D, F]
    group_offsets: jax.Array,  # [E+1] ascending, offsets[0]=0, offsets[E]=T
    *,
    block_t: int = DEFAULT_BLOCK_T,
    block_f: int = DEFAULT_BLOCK_F,
    max_span: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    T, D = x.shape
    E, _, F = w.shape
    assert T % block_t == 0 and F % block_f == 0, (T, F, block_t, block_f)
    offs = group_offsets.astype(jnp.int32)

    nT = T // block_t
    row0 = jnp.arange(nT, dtype=jnp.int32) * block_t
    # expert range per token block: offsets straddling [row0, row0+BT)
    elo = (jnp.searchsorted(offs, row0, side="right") - 1).astype(jnp.int32)
    ehi = (
        jnp.searchsorted(offs, row0 + block_t - 1, side="right") - 1
    ).astype(jnp.int32)
    elo = jnp.clip(elo, 0, E - 1)
    ehi = jnp.clip(ehi, 0, E - 1)
    span = E if max_span is None else max_span

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nT, F // block_f, span),
        in_specs=[
            pl.BlockSpec((block_t, D), lambda t, f, k, offs, lo, hi: (t, 0)),
            pl.BlockSpec(
                (1, D, block_f),
                lambda t, f, k, offs, lo, hi: (
                    jnp.clip(lo[t] + k, 0, w.shape[0] - 1),
                    0,
                    f,
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_t, block_f), lambda t, f, k, offs, lo, hi: (t, f)
        ),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, block_t=block_t, num_experts=E),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, F), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
    )(offs, elo, ehi, x, w)
