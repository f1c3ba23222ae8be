"""FliX-backed KV page index — the paper's CDS inside an LLM serving plane.

The serving control plane must map (sequence_id, page_no) → cache slot for
batched requests, under continuous allocation (prefill) and freeing
(sequence completion) — exactly the dynamic ordered-map workload FliX is
built for.  Keys are ``seq_id << PAGE_BITS | page_no``, so one successor /
range query enumerates a sequence's pages *in order* (hash tables can't),
and batched frees are physical deletions with immediate slot reclamation —
no tombstone accumulation across the serving day (the paper's §6.5 LSMu
collapse is precisely the failure mode this avoids).

Execution matches the paper's batch model exactly: each engine step submits
**one mixed sorted batch** of (allocate | lookup | enumerate | free)
operations through ``core.ops.apply_ops`` — one sort, one bucket routing,
one flipped pass — instead of sorting and routing per op type.  Sequence
page enumeration (``pages_of``) is the RANGE op: ``[seq << PAGE_BITS,
(seq+1) << PAGE_BITS)`` travels in the batch like any other operation, so
there is no engine bypass and an enumeration in an update step observes
that step's allocations and frees (update-then-read).  Batches are padded
to the next power of two so jit traces once per size class, not once per
step.

``shards=N`` range-partitions the index across the first N local devices
and routes every engine step through ``core.distributed.shard_apply_ops``
— same mixed batch, same contract, one ``shard_map`` step — so ``pages_of``
and friends are served across the mesh with no separate distributed code
path (DESIGN.md §11).

Two first-class time features ride the same batch model (DESIGN.md §14):

* **TTL** — ``step(now=...)`` threads the serving plane's virtual clock
  into the engine (rows whose deadline has passed are invisible and
  reclaimed lazily), and ``getsets`` submits get-or-set-with-TTL ops
  (``OP_EXPIRE``) in the same mixed batch as everything else;
* **snapshot reads** — with ``snapshot_window > 0`` every committed
  update step pins a version of the (immutable, functional) state;
  ``step(as_of=v)`` serves reads against that pinned version at its
  pinned clock, byte-identical no matter how many later batches commit,
  until the window slides past it (:class:`SnapshotGone`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    NO_EXPIRY,
    OP_DELETE,
    OP_EXPIRE,
    OP_INSERT,
    OP_POINT,
    OP_RANGE,
    apply_ops,
    apply_ops_safe,
    build,
    make_ops,
    unsort,
)
from repro.core.config import ExecConfig

PAGE_BITS = 12  # up to 4096 pages (≈ pages × page_size tokens) per sequence


@dataclasses.dataclass(frozen=True)
class StepResult:
    """One engine step's outcome (:meth:`KVPageIndex.step`).

    * ``slots``     — resolved cache slots aligned with the ``lookups``
      input order followed by the ``getsets`` input order (NOT_FOUND = -1).
    * ``range_out`` — None without ``ranges``, else the dense ``keys`` /
      ``vals`` arrays plus per-op ``start`` / ``count`` aligned with the
      ``ranges`` input order.
    * ``stats``     — the engine step's stats dict (empty for a no-op step).

    Deliberately NOT iterable: the pre-PR-10 positional
    ``(slots, range_out, stats)`` tuple is gone, and stale unpacking should
    fail loudly here rather than silently misbind fields.
    """

    slots: jax.Array
    range_out: dict | None
    stats: dict


class SnapshotGone(LookupError):
    """The requested pinned version slid out of the retention window (its
    buffers were released for reclamation) — the read must be re-issued
    against a live version.  Typed so the gateway can map it to a
    non-retryable ``SNAPSHOT_GONE`` rejection."""


def _key(seq_ids, page_nos):
    return (seq_ids.astype(jnp.int32) << PAGE_BITS) | page_nos.astype(jnp.int32)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class KVPageIndex:
    """Host-driven wrapper around a FliXState (functional underneath).

    ``config`` is the execution strategy for every engine step — one
    :class:`~repro.core.config.ExecConfig` whose ``impl`` picks the
    ``apply_ops`` executor (``"auto"`` = the fused compute-to-bucket kernel
    on TPU, the jnp reference engine elsewhere), whose ``routing`` picks
    the distributed batch mode when sharded, and whose pipeline/tile knobs
    thread to the fused kernel; ``None`` means ``ExecConfig()``.

    ``shards`` > 0 range-partitions the index over that many local devices
    and serves every step through ``shard_apply_ops`` (replicated routing
    is right for the control-plane batch sizes this index sees).  All
    public methods behave identically.

    ``durability_dir`` switches on the DESIGN.md §12 persistence layer:
    every update step is WAL-logged (fsynced) before execution and
    snapshotted every ``snapshot_every`` steps, and constructing against a
    directory that already holds a durable history *recovers* it (latest
    snapshot + replay) instead of starting empty.  Pure-read steps never
    touch the log.  ``wal_fsync=False`` removes the durability boundary —
    it exists for the negative crash tests, never for serving.

    ``snapshot_window`` > 0 retains that many recent committed versions
    for ``step(as_of=...)`` snapshot reads; it also disables buffer
    donation on update steps (pinned versions alias the pre-update
    buffers, which must stay intact).

    ``device_budget`` (bytes) switches the local engine to the tiered
    residency state (``core.residency.TieredFliX``, DESIGN.md §15): the
    index may grow far beyond the budget, with every step promoting the
    buckets its batch touches and demoting back under the budget after
    commit.  Results and durable bytes are identical to the unbounded
    engine; ``step`` stats additionally carry the residency counters
    (``resident_bytes`` / ``promoted`` / ``demoted`` / ``reclaimed_bytes``).
    Incompatible with ``shards`` (per-shard budgets are planned host-side
    via ``core.distributed.plan_shard_budget``) and with
    ``snapshot_window`` (pinned versions require immutable functional
    states; the tiered handle is mutating).
    """

    def __init__(
        self,
        *,
        node_size: int = 16,
        nodes_per_bucket: int = 8,
        config: ExecConfig | None = None,
        shards: int = 0,
        durability_dir=None,
        snapshot_every: int = 64,
        wal_fsync: bool = True,
        crash_hook=None,
        snapshot_window: int = 0,
        device_budget: int | None = None,
    ):
        # seed with one sentinel key (outside the (seq,page) space) so the
        # structure is never empty
        from repro.core import MAX_VALID

        self.config = config or ExecConfig()
        self.impl = self.config.impl
        self.routing = self.config.routing
        self._durable = None
        self._closed = False
        self.snapshot_window = int(snapshot_window)
        self.device_budget = device_budget
        self._version = 0
        self._pins: dict[int, tuple[object, int | None]] = {}
        if device_budget is not None:
            if shards:
                raise ValueError(
                    "device_budget is a single-device residency bound; "
                    "sharded indexes size each shard via plan_shard_budget"
                )
            if snapshot_window:
                raise ValueError(
                    "device_budget and snapshot_window are incompatible: "
                    "pinned versions need immutable functional states"
                )
        seed_keys = jnp.array([MAX_VALID], jnp.int32)
        seed_vals = jnp.array([0], jnp.int32)
        if shards:
            from repro.core.distributed import make_shard_mesh, shard_build

            self.mesh = make_shard_mesh(shards)
            self.sharded = shard_build(
                seed_keys,
                seed_vals,
                self.mesh,
                node_size=node_size,
                nodes_per_bucket=nodes_per_bucket,
            )
            self.state = None
        else:
            self.mesh = None
            self.sharded = None
            self.state = build(
                seed_keys,
                seed_vals,
                node_size=node_size,
                nodes_per_bucket=nodes_per_bucket,
            )
            if device_budget is not None:
                from repro.core.residency import TieredFliX

                self.state = TieredFliX.from_state(
                    self.state, budget_bytes=device_budget
                )
        if durability_dir is not None:
            from repro.checkpoint import (
                DurableFliX,
                LocalEngine,
                ShardEngine,
                TieredEngine,
            )

            if self.mesh is not None:
                engine = ShardEngine(
                    self.mesh,
                    config=self.config,
                    node_size=node_size,
                    nodes_per_bucket=nodes_per_bucket,
                )
            elif device_budget is not None:
                engine = TieredEngine(
                    budget_bytes=device_budget,
                    config=self.config,
                    node_size=node_size,
                    nodes_per_bucket=nodes_per_bucket,
                )
            else:
                engine = LocalEngine(
                    config=self.config,
                    node_size=node_size,
                    nodes_per_bucket=nodes_per_bucket,
                )
            if DurableFliX.exists(durability_dir):
                self._durable = DurableFliX.open(
                    durability_dir,
                    engine=engine,
                    snapshot_every=snapshot_every,
                    fsync=wal_fsync,
                    crash_hook=crash_hook,
                )
            else:
                handle = self.sharded if self.mesh is not None else self.state
                self._durable = DurableFliX.create(
                    durability_dir,
                    handle,
                    engine=engine,
                    snapshot_every=snapshot_every,
                    fsync=wal_fsync,
                    crash_hook=crash_hook,
                )
            self._commit(self._durable.handle)
        if self.snapshot_window:
            live = self.sharded if self.mesh is not None else self.state
            self._pins[0] = (live, None)

    # ---- the engine step: one mixed batch ------------------------------
    def step(
        self,
        *,
        allocs=None,
        lookups=None,
        getsets=None,
        free_seqs=None,
        ranges=None,
        max_pages: int = 256,
        range_budget: int = 256,
        meta=None,
        now: int | None = None,
        as_of: int | None = None,
    ):
        """Submit one engine step's mixed work as a single sorted batch.

        ``allocs``    — (seq_ids, page_nos, slots[, deadlines]): register
                        pages; the optional 4th tuple gives each page an
                        absolute expiry deadline (virtual time).
        ``lookups``   — (seq_ids, page_nos): resolve pages → slots.
        ``getsets``   — (seq_ids, page_nos, slots, deadlines): get-or-set
                        with TTL (``OP_EXPIRE``): a mapped page returns its
                        EXISTING slot and has its deadline refreshed; an
                        unmapped one is registered with the given slot and
                        deadline and returns NOT_FOUND.
        ``free_seqs`` — sequence ids whose pages are all physically freed.
        ``ranges``    — (lo_keys, hi_keys): half-open ``[lo, hi)`` RANGE ops
                        in raw key space, answered against this step's
                        post-update state under the batch's static
                        ``range_budget`` (see ``apply_ops``' truncation
                        contract).

        ``now`` is the step's virtual clock: rows whose deadline has
        passed (``exp <= now``) are reclaimed before the batch's updates
        and invisible to its reads.  On a read-only step the expiry view
        is computed on a throwaway functional copy — nothing is committed
        or logged (sound: expiry is monotone in ``now``).

        ``as_of`` pins the step to a retained committed version
        (``snapshot_window``): the batch must be read-only, runs against
        that version's state at its OWN pinned clock (``now`` must be
        None), and returns byte-identical results for as long as the
        version is retained; a reclaimed version raises
        :class:`SnapshotGone`.

        ``meta`` (JSON-serializable, e.g. the gateway's idempotency keys)
        is logged inside the update batch's WAL record when durability is
        on and ignored otherwise — pure-read steps never log, so meta on a
        read-only step is dropped.

        ``allocs``, ``getsets`` and ``free_seqs`` must not overlap in key
        space within one step: that would put two update ops on one key,
        violating ``apply_ops``' one-update-op-per-key precondition.
        Checked here because the ids are host values anyway.

        Returns a :class:`StepResult` (``slots`` / ``range_out`` /
        ``stats`` — see its docstring for the field contracts).
        """
        # empty op lists are the same as absent ones — callers naturally pass
        # this step's (often empty) completion list every step, and an empty
        # free list must not push a pure-lookup batch onto the update path
        if allocs is not None and len(np.asarray(allocs[0])) == 0:
            allocs = None
        if free_seqs is not None and len(np.asarray(free_seqs)) == 0:
            free_seqs = None
        if lookups is not None and len(np.asarray(lookups[0])) == 0:
            lookups = None
        if getsets is not None and len(np.asarray(getsets[0])) == 0:
            getsets = None
        if ranges is not None and len(np.asarray(ranges[0])) == 0:
            ranges = None
        if allocs is not None and free_seqs is not None:
            overlap = set(np.asarray(allocs[0]).tolist()) & set(
                np.asarray(free_seqs).tolist()
            )
            if overlap:
                raise ValueError(
                    f"sequences {sorted(overlap)} appear in both allocs and "
                    "free_seqs within one step; free them the step after "
                    "their last allocation"
                )
        if getsets is not None:
            gs_keys = {
                (int(s) << PAGE_BITS) | int(p)
                for s, p in zip(np.asarray(getsets[0]), np.asarray(getsets[1]))
            }
            if free_seqs is not None:
                overlap = set(np.asarray(getsets[0]).tolist()) & set(
                    np.asarray(free_seqs).tolist()
                )
                if overlap:
                    raise ValueError(
                        f"sequences {sorted(overlap)} appear in both getsets "
                        "and free_seqs within one step"
                    )
            if allocs is not None:
                al_keys = {
                    (int(s) << PAGE_BITS) | int(p)
                    for s, p in zip(np.asarray(allocs[0]), np.asarray(allocs[1]))
                }
                if al_keys & gs_keys:
                    raise ValueError(
                        "the same page appears in both allocs and getsets "
                        "within one step"
                    )

        pinned = None
        if as_of is not None:
            if allocs is not None or getsets is not None or free_seqs is not None:
                raise ValueError("as_of pins a read-only step; it cannot update")
            if now is not None:
                raise ValueError(
                    "as_of reads run at the pinned version's own clock; "
                    "pass now=None"
                )
            if self.snapshot_window <= 0:
                raise ValueError("snapshot reads require snapshot_window > 0")
            if not (0 <= as_of <= self._version):
                raise ValueError(
                    f"as_of={as_of} was never committed (version={self._version})"
                )
            if as_of not in self._pins:
                raise SnapshotGone(
                    f"version {as_of} left the {self.snapshot_window}-deep "
                    f"retention window (current version {self._version})"
                )
            pinned, now = self._pins[as_of]

        tags, keys, vals, exps = [], [], [], []
        has_ttl = getsets is not None or (allocs is not None and len(allocs) == 4)
        n_alloc = n_lookup = n_getset = 0
        if allocs is not None:
            seq, page, slot = allocs[:3]
            k = _key(jnp.asarray(seq), jnp.asarray(page))
            n_alloc = k.shape[0]
            tags.append(jnp.full((n_alloc,), OP_INSERT, jnp.int32))
            keys.append(k)
            vals.append(jnp.asarray(slot, jnp.int32))
            exps.append(
                jnp.asarray(allocs[3], jnp.int32)
                if len(allocs) == 4
                else jnp.full((n_alloc,), NO_EXPIRY, jnp.int32)
            )
        if lookups is not None:
            seq, page = lookups
            k = _key(jnp.asarray(seq), jnp.asarray(page))
            n_lookup = k.shape[0]
            tags.append(jnp.full((n_lookup,), OP_POINT, jnp.int32))
            keys.append(k)
            vals.append(jnp.zeros((n_lookup,), jnp.int32))
            exps.append(jnp.full((n_lookup,), NO_EXPIRY, jnp.int32))
        if getsets is not None:
            seq, page, slot, deadline = getsets
            k = _key(jnp.asarray(seq), jnp.asarray(page))
            n_getset = k.shape[0]
            tags.append(jnp.full((n_getset,), OP_EXPIRE, jnp.int32))
            keys.append(k)
            vals.append(jnp.asarray(slot, jnp.int32))
            exps.append(jnp.asarray(deadline, jnp.int32))
        if free_seqs is not None:
            seq = jnp.asarray(free_seqs, jnp.int32)
            k = (
                (seq[:, None] << PAGE_BITS)
                | jnp.arange(max_pages, dtype=jnp.int32)[None, :]
            ).reshape(-1)
            tags.append(jnp.full(k.shape, OP_DELETE, jnp.int32))
            keys.append(k)
            vals.append(jnp.zeros(k.shape, jnp.int32))
            exps.append(jnp.full(k.shape, NO_EXPIRY, jnp.int32))
        n_before_range = sum(int(k.shape[0]) for k in keys)
        n_range = 0
        if ranges is not None:
            lo, hi = ranges
            lo = jnp.asarray(lo, jnp.int32)
            n_range = lo.shape[0]
            tags.append(jnp.full((n_range,), OP_RANGE, jnp.int32))
            keys.append(lo)
            vals.append(jnp.asarray(hi, jnp.int32))
            exps.append(jnp.full((n_range,), NO_EXPIRY, jnp.int32))
        if not keys:
            return StepResult(slots=jnp.zeros((0,), jnp.int32), range_out=None, stats={})

        tag = jnp.concatenate(tags)
        key = jnp.concatenate(keys)
        val = jnp.concatenate(vals)
        pad_to = _next_pow2(key.shape[0])
        if self.mesh is not None:
            # a2a routing position-shards the batch: round the padded size
            # up to a shard-count multiple so every chunk is equal
            n_shards = int(self.mesh.shape["shards"])
            pad_to = -(-pad_to // n_shards) * n_shards
        ops, perm = make_ops(
            tag,
            key,
            val,
            exps=jnp.concatenate(exps) if has_ttl else None,
            pad_to=pad_to,
        )
        read_only = n_alloc == 0 and n_getset == 0 and free_seqs is None
        has_ranges = n_range > 0
        if read_only:
            # pure-read step (lookups and/or ranges): the state is
            # untouched, so keep the pre-batch state/index instead of
            # swapping in the engine's pass-through copy.  Always the
            # reference engine here — the fused kernel's update sweep
            # rewrites the whole state, pure waste for an update-free batch
            # (DESIGN.md §9/§10), while the reference lax.cond phases skip
            # it.
            cfg = self.config.replace(
                impl="reference", max_results=range_budget, donate=False
            )
            _, results, stats = self._apply(
                ops, config=cfg, has_ranges=has_ranges, now=now, handle=pinned
            )
        elif n_alloc == 0 and n_getset == 0:
            # only inserts can overflow — free steps skip the restructure-
            # and-retry wrapper (and its host sync), and since no retry can
            # replay the batch, the old state's buffers are donated to the
            # step (fused path; a no-op on CPU) — unless pinned snapshot
            # versions alias them (snapshot_window > 0)
            cfg = self.config.replace(
                max_results=range_budget, donate=self.snapshot_window == 0
            )
            new, results, stats = self._apply(
                ops,
                config=cfg,
                has_updates=True,
                has_ranges=has_ranges,
                meta=meta,
                now=now,
            )
            self._commit(new, bump=True, now=now)
        else:
            # allocation steps go through the safe driver; its retry path
            # regrows (sharded: rebalances fences via shard_restructure —
            # the cluster analogue of §3.5 relaunch) and replays the batch
            cfg = self.config.replace(max_results=range_budget, donate=False)
            new, results, stats = self._apply(
                ops,
                config=cfg,
                safe=True,
                has_updates=True,
                has_ranges=has_ranges,
                meta=meta,
                now=now,
            )
            self._commit(new, bump=True, now=now)
        values = unsort(results["value"], perm[: key.shape[0]])
        range_out = None
        if n_range:
            sub = perm[n_before_range : n_before_range + n_range]
            range_out = {
                "keys": results["range_key"],
                "vals": results["range_val"],
                "start": unsort(results["range_start"], sub),
                "count": unsort(results["range_count"], sub),
            }
        return StepResult(
            slots=values[n_alloc : n_alloc + n_lookup + n_getset],
            range_out=range_out,
            stats=stats,
        )

    def _apply(
        self,
        ops,
        *,
        config: ExecConfig,
        safe=False,
        has_updates=None,
        has_ranges=False,
        meta=None,
        now=None,
        handle=None,
    ):
        """Dispatch one engine batch to the local or sharded executor.

        Same step policy either way (one copy of it, in :meth:`step`):
        ``config`` carries the whole execution strategy for the batch,
        already specialized per step kind (reference-engine reads, donated
        frees, safe allocations).  ``has_updates`` / ``has_ranges`` are the
        host-known batch-composition hints.

        ``handle`` overrides the state the batch runs against (pinned
        snapshot reads — read-only by construction, never committed).

        With durability on, every update batch commits through
        ``DurableFliX.apply`` — WAL-ahead, restructure-and-retry inside —
        so it forfeits donation; pure reads bypass the log entirely.
        """
        if self._durable is not None and (safe or has_updates):
            results, stats = self._durable.apply(
                ops, config=config.replace(donate=False), meta=meta, now=now
            )
            return self._durable.handle, results, stats
        if self.mesh is not None:
            from repro.core.distributed import shard_apply_ops, shard_apply_ops_safe

            sharded = self.sharded if handle is None else handle
            if safe:
                return shard_apply_ops_safe(
                    sharded,
                    ops,
                    self.mesh,
                    config=config.replace(donate=False),
                    has_updates=has_updates,
                    has_ranges=has_ranges,
                    now=now,
                )
            return shard_apply_ops(
                sharded,
                ops,
                self.mesh,
                config=config,
                has_updates=has_updates,
                has_ranges=has_ranges,
                now=now,
            )
        state = self.state if handle is None else handle
        from repro.core.residency import TieredFliX

        if isinstance(state, TieredFliX):
            # the tiered handle mutates in place and carries its own
            # restructure-and-retry; commit=False keeps read-only steps
            # (incl. throwaway expiry views) from changing logical content
            results, stats, _ = state.apply(
                ops,
                config=config,
                now=now,
                commit=bool(safe or has_updates),
            )
            return state, results, stats
        if safe:
            return apply_ops_safe(
                state, ops, config=config.replace(donate=False), now=now,
                has_updates=has_updates,
            )
        return apply_ops(state, ops, config=config, has_updates=has_updates, now=now)

    def _commit(self, new, *, bump: bool = False, now: int | None = None):
        """Install an update step's result (local state or sharded index);
        ``bump`` advances the version counter and, with a retention
        window, pins the committed version (plus its clock) for
        ``step(as_of=...)`` until the window slides past it."""
        if self.mesh is not None:
            self.sharded = new
        else:
            self.state = new
        if bump:
            self._version += 1
            if self.snapshot_window:
                self._pins[self._version] = (new, now)
                low = self._version - self.snapshot_window
                for v in [v for v in self._pins if v <= low]:
                    del self._pins[v]

    # ---- per-type conveniences (each is still one engine step) ---------
    def allocate(self, seq_ids, page_nos, slots):
        """Batch-register pages → slots (an engine allocation step)."""
        return self.step(allocs=(seq_ids, page_nos, slots)).stats

    def lookup(self, seq_ids, page_nos):
        """Batch lookup → cache slots (NOT_FOUND = -1 for unmapped pages)."""
        return self.step(lookups=(seq_ids, page_nos)).slots

    def free_sequences(self, seq_ids, *, max_pages: int = 256):
        """Batch-free every page of the given sequences (physical removal)."""
        return self.step(free_seqs=seq_ids, max_pages=max_pages).stats

    def pages_of(self, seq_id: int, *, max_pages: int = 256):
        """All (page_no, slot) of a sequence, in order (a RANGE engine step).

        Routed through ``apply_ops`` like every other operation — no
        standalone ``range_query`` bypass, so enumeration always reads the
        engine's own state (a cache-carrying read state included) and can
        legally share a batch with updates via :meth:`step`.
        """
        lo = seq_id << PAGE_BITS
        hi = (seq_id + 1) << PAGE_BITS
        rng_out = self.step(ranges=([lo], [hi]), range_budget=max_pages).range_out
        return (
            rng_out["keys"] & ((1 << PAGE_BITS) - 1),
            rng_out["vals"],
            rng_out["count"][0],
        )

    def live_pages(self) -> int:
        state = self.sharded.state if self.mesh is not None else self.state
        return int(state.live_keys()) - 1  # minus the seed key

    def getset(self, seq_ids, page_nos, slots, deadlines, *, now=None):
        """Batch get-or-set with TTL (one ``OP_EXPIRE`` engine step):
        returns the existing slot (deadline refreshed) for mapped pages,
        NOT_FOUND for pages registered by this call."""
        return self.step(getsets=(seq_ids, page_nos, slots, deadlines), now=now).slots

    # ---- snapshot versions ----------------------------------------------
    @property
    def version(self) -> int:
        """Count of committed update steps — the newest ``as_of`` value."""
        return self._version

    @property
    def retained_versions(self) -> list[int]:
        """Versions currently answerable via ``step(as_of=...)``."""
        return sorted(self._pins)

    # ---- residency -------------------------------------------------------
    @property
    def resident_bytes(self) -> int | None:
        """Device-tier footprint of a tiered index (None when single-tier:
        the whole index is device-resident by construction)."""
        from repro.core.residency import TieredFliX

        state = self._durable.handle if self._durable is not None else self.state
        if isinstance(state, TieredFliX):
            return state.memory_bytes_resident()
        return None

    # ---- durability / health -------------------------------------------
    @property
    def durable_seq(self) -> int | None:
        """Last durably committed batch seq (None with durability off)."""
        return self._durable.seq if self._durable is not None else None

    @property
    def healthy(self) -> bool:
        """True while the UPDATE path is trustworthy.

        Goes False when the durable layer is poisoned (live and durable
        state diverged after a failed WAL rollback) or the index is
        closed.  Reads against the live state remain valid either way —
        the serving gateway uses exactly this split for degraded
        read-only routing (DESIGN.md §13).
        """
        if self._closed:
            return False
        return self._durable is None or self._durable.healthy

    def dedup_seed(self) -> list[tuple[int, object]]:
        """The durable ``(seq, meta)`` trail of recent update commits
        (empty with durability off) — what the gateway reseeds its
        idempotency dedup window from after crash recovery."""
        return self._durable.meta_trail() if self._durable is not None else []

    def snapshot(self):
        """Force a snapshot now (durability on); returns its directory.

        Idempotent — a snapshot at the current seq already on disk is
        revalidated, not rewritten — and safe on an unhealthy instance:
        a poisoned durable layer has nothing trustworthy to persist
        beyond the WAL it already holds, so this returns None instead of
        raising from a teardown path (reopening resynchronizes).
        """
        if self._durable is None:
            raise RuntimeError("durability is off (no durability_dir)")
        if not self._durable.healthy:
            return None
        return self._durable.snapshot()

    def close(self):
        """Flush and close the WAL (no-op with durability off).

        Idempotent and safe on a poisoned durable layer: teardown never
        raises on top of the failure that poisoned the instance.
        """
        if self._closed:
            return
        self._closed = True
        if self._durable is not None:
            self._durable.close()
