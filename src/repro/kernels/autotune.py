"""Deterministic tile autotuner for the fused apply kernel (DESIGN.md §16).

The fused kernel's two tile knobs trade off against each other:

  * ``block_q`` — ops per window.  Larger windows mean fewer grid passes
    over the bucket blocks, but each window revisits every bucket block its
    op span touches, so an oversized window drags cold stripes through VMEM
    for a handful of ops.
  * ``block_b`` — bucket stripes per block.  The kernel walks a block's
    buckets one at a time, so ``block_b`` only sizes the DMA'd blocks (two
    of each, double-buffered) and amortizes per-step grid overhead.

The right point depends on (build_size, batch_size), which is exactly the
:class:`~repro.core.config.TileTable` key.  This module sweeps the
candidate grid per size bucket and records one winner per bucket:

  * **model mode** (default): a closed-form cost model scores every
    candidate — VMEM feasibility, per-step merge cost, window revisit
    traffic, and fixed grid overhead.  Pure integer arithmetic on the
    requested sizes: the same sweep on any host picks the same tiles, which
    is what lets the committed bench artifact embed the table and the
    determinism test pin it.
  * **measure mode** (``measure=True``): wall-clock the fused kernel per
    feasible candidate on a synthetic build and take the best median.
    Opt-in, machine-dependent — for producing a table on real hardware, not
    for CI.

Either way the output is plain data: a ``TileTable`` (drops straight into
``ExecConfig(tile_table=...)``) plus a JSON-ready sweep record that
``benchmarks/run.py`` embeds in the bench artifact.
"""

from __future__ import annotations

import math

from repro.core.config import TileTable, _pow2_bucket
from repro.kernels.flix_apply import N_COLS, VMEM_LIMIT_BYTES, _chunk_rows

# candidate grid — DEFAULT_BLOCK_Q (flix_query) and DEFAULT_BLOCK_B
# (flix_apply) are both members, so the tuned table can only match or beat
# the static defaults under the model.  Every candidate compiles for a v5e at
# both served geometries (node_size × nodes_per_bucket = 16×8 and 32×16).
CANDIDATE_BLOCK_Q = (128, 256, 512)
CANDIDATE_BLOCK_B = (1, 2, 4, 8)

# the scoped-VMEM limit the kernel is compiled with — one number for both
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES
_I32 = 4  # bytes


def _tile(rows: int, lanes: int) -> int:
    """int32 elements a [rows, lanes] block occupies in (8, 128) VMEM tiles."""
    return -(-rows // 8) * 8 * (-(-lanes // 128) * 128)


def vmem_bytes(block_q: int, block_b: int, *, node_size: int, nodes_per_bucket: int,
               max_results: int = 128) -> int:
    """Model of the kernel's VMEM residency for one grid step.

    Every blocked operand is double-buffered by the pipeline (the explicit
    two-slot stripe scratch of the pipelined variant is the same size);
    the column scratch holds N_COLS [S, 1] vectors, each one lane of an
    (8, 128) tile; the loop bodies keep a few [CHUNK, max(S, QB, MR)] tiles
    live.
    """
    S = node_size * nodes_per_bucket
    npb = nodes_per_bucket
    stripes = 7 * _tile(block_b, S)          # keys/vals in + out, ik/iv/dk
    meta = 4 * _tile(block_b, npb) + 2 * _tile(block_b, 128)
    window = 4 * _tile(1, block_q)           # tags, keys, resv, resk
    rng = 3 * _tile(1, max_results)
    cols = N_COLS * _tile(S, 1)
    temps = 8 * _tile(_chunk_rows(S), max(S, block_q, max_results))
    return _I32 * (2 * (stripes + meta + window + rng) + cols + temps)


def model_cost(
    block_q: int,
    block_b: int,
    *,
    build_size: int,
    batch_size: int,
    node_size: int,
    nodes_per_bucket: int,
) -> float:
    """Deterministic cost score for one candidate (lower is better).

    Grid shape: ``n_windows × nb_blocks`` steps.  Window 0 sweeps every
    bucket block (the full update pass); each later window revisits the
    ≈ ``block_q / batch`` fraction of the key space its sorted ops span.
    Active steps pay the O(block_b · S²) merge plus per-op read compute;
    every step — active or not — pays a fixed dispatch overhead, which is
    what large tiles amortize.
    """
    S = node_size * nodes_per_bucket
    nb = max(1, math.ceil(build_size / S))
    nb_p = math.ceil(nb / block_b) * block_b
    nb_blocks = nb_p // block_b
    n = max(1, batch_size)
    n_windows = math.ceil(n / block_q)

    # sorted ops: one window's span of the bucket-block axis
    span = min(nb_blocks, math.ceil(nb_blocks * block_q / n) + 1)
    active = nb_blocks + (n_windows - 1) * span
    total = n_windows * nb_blocks

    merge = block_b * S * S          # phase-1/2 masks per active step
    reads = block_q * (block_b + nodes_per_bucket + node_size)
    step_overhead = 4096             # dispatch + pipeline bubble per step
    return float(active * (merge + reads) + total * step_overhead)


def sweep_bucket(
    build_size: int,
    batch_size: int,
    *,
    node_size: int = 16,
    nodes_per_bucket: int = 8,
    candidates_q=CANDIDATE_BLOCK_Q,
    candidates_b=CANDIDATE_BLOCK_B,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    measure: bool = False,
) -> dict:
    """Score every candidate for one (build, batch) bucket; pick the winner.

    Returns a JSON-ready record: the bucket, every candidate's score and
    feasibility, and the chosen ``(block_q, block_b)``.  Ties break on the
    sorted candidate order, so the sweep is a pure function of its inputs.
    """
    rows = []
    for bq in sorted(candidates_q):
        for bb in sorted(candidates_b):
            vb = vmem_bytes(
                bq, bb, node_size=node_size, nodes_per_bucket=nodes_per_bucket
            )
            feasible = vb <= vmem_budget
            cost = (
                model_cost(
                    bq,
                    bb,
                    build_size=build_size,
                    batch_size=batch_size,
                    node_size=node_size,
                    nodes_per_bucket=nodes_per_bucket,
                )
                if feasible
                else None
            )
            rows.append(
                {
                    "block_q": bq,
                    "block_b": bb,
                    "vmem_bytes": vb,
                    "feasible": feasible,
                    "model_cost": cost,
                }
            )
    feas = [r for r in rows if r["feasible"]]
    if not feas:  # pathological geometry: fall back to the smallest tiles
        feas = [rows[0]]
        feas[0]["model_cost"] = 0.0
    if measure:
        _measure_rows(
            feas,
            build_size=build_size,
            batch_size=batch_size,
            node_size=node_size,
            nodes_per_bucket=nodes_per_bucket,
        )
        key = lambda r: (r["wall_s"], r["block_q"], r["block_b"])
    else:
        key = lambda r: (r["model_cost"], r["block_q"], r["block_b"])
    best = min(feas, key=key)
    return {
        "build_bucket": _pow2_bucket(build_size),
        "batch_bucket": _pow2_bucket(batch_size),
        "block_q": best["block_q"],
        "block_b": best["block_b"],
        "measured": bool(measure),
        "candidates": rows,
    }


def _measure_rows(rows, *, build_size, batch_size, node_size, nodes_per_bucket):
    """Wall-clock each feasible candidate on a synthetic mixed batch
    (opt-in: timings are machine truth, not reproducible model truth)."""
    import time

    import jax
    import numpy as np

    from repro.core.build import build
    from repro.core.config import ExecConfig
    from repro.core.ops import OP_INSERT, OP_POINT, apply_ops, make_ops

    rng = np.random.default_rng(0)
    keys = rng.choice(build_size * 8, size=build_size, replace=False)
    state = build(
        keys, np.arange(build_size),
        node_size=node_size, nodes_per_bucket=nodes_per_bucket,
    )
    half = max(1, batch_size // 2)
    qk = rng.choice(keys, size=half)
    ik = rng.choice(build_size * 8, size=batch_size - half) | 1
    tags = np.concatenate([np.full(half, OP_POINT), np.full(batch_size - half, OP_INSERT)])
    ops, _ = make_ops(tags, np.concatenate([qk, ik]), np.concatenate([qk, ik]))
    for r in rows:
        cfg = ExecConfig(impl="fused", block_q=r["block_q"], block_b=r["block_b"])
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = apply_ops(state, ops, config=cfg)
            jax.block_until_ready(out[0].keys)
            times.append(time.perf_counter() - t0)
        r["wall_s"] = sorted(times)[1]


def autotune(
    build_sizes,
    batch_sizes,
    *,
    node_size: int = 16,
    nodes_per_bucket: int = 8,
    candidates_q=CANDIDATE_BLOCK_Q,
    candidates_b=CANDIDATE_BLOCK_B,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    measure: bool = False,
) -> tuple[TileTable, dict]:
    """Sweep the cross product of size buckets → (TileTable, sweep record).

    The table is ready to thread through ``ExecConfig(tile_table=...)``;
    the record is JSON-ready for the bench artifact and round-trips back
    via ``TileTable.from_json(record["table"])``.
    """
    sweeps = []
    entries = {}
    for build in sorted({_pow2_bucket(b) for b in build_sizes}):
        for batch in sorted({_pow2_bucket(q) for q in batch_sizes}):
            rec = sweep_bucket(
                build,
                batch,
                node_size=node_size,
                nodes_per_bucket=nodes_per_bucket,
                candidates_q=candidates_q,
                candidates_b=candidates_b,
                vmem_budget=vmem_budget,
                measure=measure,
            )
            sweeps.append(rec)
            entries[(build, batch)] = (rec["block_q"], rec["block_b"])
    table = TileTable(
        entries=tuple(
            (build, batch, bq, bb)
            for (build, batch), (bq, bb) in sorted(entries.items())
        )
    )
    record = {
        "node_size": node_size,
        "nodes_per_bucket": nodes_per_bucket,
        "vmem_budget_bytes": vmem_budget,
        "measured": bool(measure),
        "table": table.to_json(),
        "sweeps": sweeps,
    }
    return table, record
