"""ExecConfig: the one typed execution-configuration surface (DESIGN.md §16).

PRs 2–9 grew six engine entry points — ``apply_ops``, ``apply_ops_safe``,
``shard_apply_ops``, ``shard_apply_ops_safe``, ``TieredFliX.apply``,
``KVPageIndex`` — and each sprouted its own copy of the tuning knobs
(``impl``, ``donate``, ``block_q``/``block_b``, ``max_results``,
``capacity``, ``routing``, validate flags).  The autotuner
(``kernels.autotune``) needs a single place to write its answers into, and
callers need one object they can build once and thread everywhere.  That
object is :class:`ExecConfig`:

  * frozen + hashable — safe as a jit-static carrier and as a cache key;
  * every knob is *execution strategy*, never *semantics*: two runs of the
    same batch under different configs must be byte-identical (the
    differential suite pins this).  Time (``now``) and batch-composition
    hints (``has_updates``/``has_ranges``) are therefore **not** config —
    they stay per-call keywords.

Every entry point takes ``config: ExecConfig | None = None`` and nothing
else for strategy (``None`` means ``ExecConfig()``); the per-entry-point
keywords the config replaced are gone.

:class:`TileTable` carries the autotuner's chosen (block_q, block_b) tile
per (build_size, batch_size) bucket.  It is plain data — hashable tuples
in, JSON out — so it round-trips through the bench artifact
(``benchmarks/run.py`` embeds it) and back into an ``ExecConfig``.
"""

from __future__ import annotations

import dataclasses

DEFAULT_MAX_RESULTS = 128  # per-batch RANGE output budget (static)


def _pow2_bucket(n: int) -> int:
    """Smallest power of two ≥ n (≥ 1) — the TileTable's size-bucketing."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class TileTable:
    """Autotuned (block_q, block_b) per (build_size, batch_size) bucket.

    ``entries`` rows are ``(build_bucket, batch_bucket, block_q, block_b)``
    with power-of-two buckets; lookups round both sizes *up* to their
    bucket and fall back to the nearest recorded bucket (so a table swept
    at a few sizes still answers everywhere deterministically).
    """

    entries: tuple[tuple[int, int, int, int], ...] = ()

    def __post_init__(self):
        object.__setattr__(
            self, "entries", tuple(tuple(int(x) for x in row) for row in self.entries)
        )

    def lookup(self, build_size: int, batch_size: int) -> tuple[int, int] | None:
        """The tiles for the nearest recorded bucket (None on an empty table).

        Distance is measured in octaves (log2 space) on both axes, with a
        deterministic tie-break on the sorted entry order.
        """
        if not self.entries:
            return None
        want_b = _pow2_bucket(build_size).bit_length()
        want_q = _pow2_bucket(batch_size).bit_length()
        best = min(
            sorted(self.entries),
            key=lambda row: (
                abs(row[0].bit_length() - want_b) + abs(row[1].bit_length() - want_q),
                row,
            ),
        )
        return best[2], best[3]

    def to_json(self) -> list[list[int]]:
        return [list(row) for row in sorted(self.entries)]

    @classmethod
    def from_json(cls, rows) -> "TileTable":
        return cls(entries=tuple(tuple(int(x) for x in row) for row in rows or ()))


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Execution strategy for one engine call chain.  Frozen + hashable.

    ``impl``         — ``"auto" | "fused" | "reference"`` executor choice.
    ``pipeline``     — fused-kernel bucket-stripe staging: ``"auto"`` uses
                       the double-buffered DMA kernel on TPU and the
                       single-buffer path elsewhere; ``"on"`` forces the
                       double-buffered kernel (interpret mode included —
                       the differential tests do this); ``"off"`` forces
                       the single-buffer path.  Byte-identical either way.
    ``donate``       — donate input state buffers (fused only; unsafe when
                       a restructure retry may replay the batch).
    ``block_q``      — ops per fused-kernel window (None → default/tile
                       table).
    ``block_b``      — bucket stripes per fused-kernel block (None →
                       default/tile table).
    ``tile_table``   — autotuned tiles consulted when block_q/block_b are
                       None (explicit overrides always win).
    ``max_results``  — per-batch dense RANGE output budget (static).
    ``capacity``     — a2a per-(src, dst) routing capacity (None → policy:
                       ``shard_apply_ops`` uses the never-overflowing chunk
                       size, ``shard_apply_ops_safe`` the skew-derived
                       ``default_a2a_capacity``).
    ``routing``      — sharded routing: ``"replicated" | "a2a"``.
    ``validate``     — run ``check_invariants`` on results (safe drivers).
    ``validate_ranges`` — run ``check_range_results`` (safe drivers).
    """

    impl: str = "auto"
    pipeline: str = "auto"
    donate: bool = False
    block_q: int | None = None
    block_b: int | None = None
    tile_table: TileTable | None = None
    max_results: int = DEFAULT_MAX_RESULTS
    capacity: int | None = None
    routing: str = "replicated"
    validate: bool = False
    validate_ranges: bool = False

    def __post_init__(self):
        if self.impl not in ("auto", "fused", "reference"):
            raise ValueError(f"unknown impl: {self.impl!r}")
        if self.pipeline not in ("auto", "on", "off"):
            raise ValueError(f"unknown pipeline mode: {self.pipeline!r}")
        if self.routing not in ("replicated", "a2a"):
            raise ValueError(f"unknown routing: {self.routing!r}")

    def replace(self, **kw) -> "ExecConfig":
        return dataclasses.replace(self, **kw)

    def resolve_blocks(self, build_size: int, batch_size: int) -> tuple[int | None, int | None]:
        """The (block_q, block_b) to hand the fused kernel: explicit
        overrides win, then the tile table, then (None, None) → kernel
        defaults."""
        bq, bb = self.block_q, self.block_b
        if (bq is None or bb is None) and self.tile_table is not None:
            hit = self.tile_table.lookup(build_size, batch_size)
            if hit is not None:
                bq = bq if bq is not None else hit[0]
                bb = bb if bb is not None else hit[1]
        return bq, bb
