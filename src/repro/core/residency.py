"""Tiered residency: beyond-HBM FliX state with real page reclamation.

DESIGN.md §15.  The single-tier engine holds every bucket in one device
pytree, so the index must fit in accelerator memory.  ``TieredFliX`` splits
the same logical state across two tiers:

  * **host tier** — a numpy mirror of every bucket's rows, keyed by bucket
    id (the authoritative copy for non-resident buckets);
  * **device tier** — a *packed* ``FliXState`` holding only the resident
    buckets, in fence order, with the packed ``mkba[-1]`` forced to
    ``MAX_VALID`` so the packed state satisfies I5 on its own.

Residency is *physical placement only*: logical content (canonical triple
bytes, query results, stats) is byte-identical to an unconstrained
single-tier oracle — enforced by ``tests/test_tiered.py`` and invariant I7
(``core.invariants.check_tiered_invariants``).

Every ``apply`` runs a host-side **prefetch pre-pass**
(``core.ops.touched_buckets``) that reuses the engine's own fence routing to
compute which buckets the batch can read or write, promotes exactly those
(page-in), runs the *unchanged* executors (``apply_ops``) against the packed
working set, and demotes down to the device budget after commit (LRU
page-out).  Correctness of running the full-state executors on a packed
subset rests on fence disjointness: a bucket's bytes can only influence ops
routed to it (point/insert/delete/expire), rank arithmetic over an
*interval* of buckets (range — the whole interval is promoted), or the
first-non-empty-bucket fallback (successor — the forward walk up to a
guaranteed-surviving bucket is promoted).  Buckets outside the touched set
pass through ``apply_ops`` untouched up to insert-phase padding-value
scrubbing, which the masked comparison contract ignores (padding values are
unreachable through every read path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.build import plan_geometry
from repro.core.config import ExecConfig
from repro.core.expiry import NO_EXPIRY
from repro.core.ops import OpBatch, apply_ops, touched_buckets
from repro.core.restructure import (
    replay_on_overflow,
    restructure_grow,
    restructure_shrink,
)
from repro.core.state import EMPTY, MAX_VALID, FliXState


def bucket_device_bytes(nodes_per_bucket: int, node_size: int, has_exps: bool) -> int:
    """Device bytes one bucket occupies across every per-bucket array."""
    cells = nodes_per_bucket * node_size
    per = cells * 4 * (3 if has_exps else 2)     # keys + vals (+ exps)
    per += nodes_per_bucket * 4 * 2              # node_count + node_max
    per += 4 + 4                                 # num_nodes + mkba
    return per


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@jax.jit
def _bucket_meta(state: FliXState):
    """Per-bucket (live row count, min live expiry deadline) for the packed
    working set — the host metadata refresh after a commit."""
    from repro.core.expiry import bucket_min_exp

    live = jnp.sum(state.node_count, axis=1).astype(jnp.int32)
    return live, bucket_min_exp(state)


@jax.jit
def _take_buckets(state: FliXState, idx: jax.Array) -> FliXState:
    """Packed sub-state holding rows ``idx`` (sorted positions), with the
    packed fence array re-closed at ``MAX_VALID`` (I5)."""
    return FliXState(
        keys=state.keys[idx],
        vals=state.vals[idx],
        node_count=state.node_count[idx],
        node_max=state.node_max[idx],
        num_nodes=state.num_nodes[idx],
        mkba=state.mkba[idx].at[-1].set(MAX_VALID),
        needs_restructure=state.needs_restructure,
        exps=None if state.exps is None else state.exps[idx],
    )


def _host_build(keys, vals, exps=None, *, node_size=32, nodes_per_bucket=16, fill=0.5):
    """Numpy mirror of ``checkpoint.serialize.state_from_pairs`` — the same
    deterministic half-full layout, built entirely on the host.

    This is what lets recovery of a tiered index avoid materializing the
    full structure on device: the snapshot's sorted live triples become the
    host-tier mirror directly.  Byte-exact with the device build because
    canonical triples are clean (no padding garbage to propagate): every
    padding cell is EMPTY/0/NO_EXPIRY in both.
    """
    keys = np.asarray(keys, np.int32)
    vals = np.asarray(vals, np.int32)
    if exps is not None:
        exps = np.asarray(exps, np.int32)
        if not (exps != int(NO_EXPIRY)).any():
            exps = None
    nb, npb, ns = plan_geometry(
        len(keys), node_size=node_size, nodes_per_bucket=nodes_per_bucket, fill=fill
    )
    nb = -(-nb // 8) * 8  # same jit-cache quantization as state_from_pairs
    p = max(1, int(ns * fill))

    def one_plane(col, background):
        flat = np.full((nb * p,), background, np.int32)
        take = min(len(col), nb * p)
        flat[:take] = col[:take]
        plane = np.full((nb, npb, ns), background, np.int32)
        plane[:, 0, :p] = flat.reshape(nb, p)
        return plane

    k3 = one_plane(keys, int(EMPTY))
    v3 = one_plane(vals, 0)
    bkeys = k3[:, 0, :p]
    counts0 = (bkeys != int(EMPTY)).sum(axis=1).astype(np.int32)
    node_count = np.zeros((nb, npb), np.int32)
    node_count[:, 0] = counts0
    nmax0 = np.where(
        counts0 > 0, bkeys[np.arange(nb), np.maximum(counts0 - 1, 0)], int(EMPTY)
    ).astype(np.int32)
    node_max = np.full((nb, npb), int(EMPTY), np.int32)
    node_max[:, 0] = nmax0
    num_nodes = (counts0 > 0).astype(np.int32)
    mkba = np.where(counts0 > 0, nmax0, int(MAX_VALID)).astype(np.int32)
    mkba[-1] = int(MAX_VALID)
    mkba = np.maximum.accumulate(mkba)

    e3 = None
    if exps is not None:
        e3 = one_plane(exps, int(NO_EXPIRY))
        e3 = np.where(k3 == int(EMPTY), int(NO_EXPIRY), e3).astype(np.int32)
    return k3, v3, node_count, node_max, num_nodes, mkba, e3


class _HostView:
    """Duck-typed read-only state over host numpy arrays.

    ``checkpoint.serialize.bucket_segments`` and
    ``core.invariants.check_invariants`` only access array attributes (and
    ``jax.device_get``/``np.asarray`` are identity on numpy), so this stands
    in for a ``FliXState`` without a device round-trip.
    """

    def __init__(self, keys, vals, node_count, node_max, num_nodes, mkba, exps):
        self.keys = keys
        self.vals = vals
        self.node_count = node_count
        self.node_max = node_max
        self.num_nodes = num_nodes
        self.mkba = mkba
        self.exps = exps
        self.needs_restructure = np.asarray(False)


class TieredFliX:
    """Host-driven tiered engine: a FliX index whose device footprint is
    bounded by ``budget_bytes`` while the full index lives in host memory.

    Mutating companion class in the style of ``checkpoint.durable
    .DurableFliX`` (NOT a pytree): methods mutate ``self`` and return
    results.  The authority split is the core invariant (I7):

      * buckets in ``resident_ids`` are authoritative **on device** (the
        mirror rows for them may be stale until ``sync()``);
      * every other bucket is authoritative **in the mirror**;
      * per-bucket metadata (``h_live``, ``h_min_exp``) is fresh for ALL
        buckets at all times (refreshed from the packed state post-commit).

    ``budget_bytes=None`` means unbounded (everything may become resident —
    still packed/demand-paged, but never evicted).
    """

    def __init__(
        self,
        keys,
        vals,
        node_count,
        node_max,
        num_nodes,
        mkba,
        exps=None,
        *,
        budget_bytes: int | None = None,
        needs_restructure: bool = False,
    ):
        # owned, writable copies: device_get may hand back read-only views
        self.h_keys = np.array(keys, dtype=np.int32, order="C", copy=True)
        self.h_vals = np.array(vals, dtype=np.int32, order="C", copy=True)
        self.h_node_count = np.array(node_count, dtype=np.int32, order="C", copy=True)
        self.h_node_max = np.array(node_max, dtype=np.int32, order="C", copy=True)
        self.h_num_nodes = np.array(num_nodes, dtype=np.int32, order="C", copy=True)
        self.h_mkba = np.array(mkba, dtype=np.int32, order="C", copy=True)
        self.h_exps = (
            None if exps is None else np.array(exps, dtype=np.int32, order="C", copy=True)
        )
        self.needs_restructure = bool(needs_restructure)
        self.budget_bytes = budget_bytes

        nb = self.h_keys.shape[0]
        self.resident_ids = np.zeros((0,), np.int32)
        self._packed: FliXState | None = None
        self.last_used = np.zeros((nb,), np.int64)
        self._step = 0
        self.promoted_total = 0
        self.demoted_total = 0
        self.reclaimed_total = 0
        self._recompute_meta()

    # ---- constructors ----------------------------------------------------
    @classmethod
    def from_state(cls, state: FliXState, *, budget_bytes: int | None = None):
        """Adopt an existing single-tier device state (one full page-out)."""
        st = state.drop_volatile()
        host = jax.device_get(
            (st.keys, st.vals, st.node_count, st.node_max, st.num_nodes, st.mkba)
        )
        exps = None if st.exps is None else np.asarray(jax.device_get(st.exps))
        return cls(
            *host,
            exps,
            budget_bytes=budget_bytes,
            needs_restructure=bool(st.needs_restructure),
        )

    @classmethod
    def from_pairs(
        cls,
        keys,
        vals,
        exps=None,
        *,
        node_size: int = 32,
        nodes_per_bucket: int = 16,
        fill: float = 0.5,
        budget_bytes: int | None = None,
    ):
        """Rebuild from sorted live triples without ever materializing the
        full index on device (host-tier recovery path; byte-identical to
        ``state_from_pairs``)."""
        k3, v3, nc, nm, nn, mk, e3 = _host_build(
            keys,
            vals,
            exps,
            node_size=node_size,
            nodes_per_bucket=nodes_per_bucket,
            fill=fill,
        )
        return cls(k3, v3, nc, nm, nn, mk, e3, budget_bytes=budget_bytes)

    # ---- geometry / accounting -------------------------------------------
    @property
    def num_buckets(self) -> int:
        return self.h_keys.shape[0]

    @property
    def geometry(self) -> tuple[int, int, int]:
        return self.h_keys.shape

    @property
    def node_size(self) -> int:
        return self.h_keys.shape[2]

    @property
    def nodes_per_bucket(self) -> int:
        return self.h_keys.shape[1]

    @property
    def bucket_bytes(self) -> int:
        return bucket_device_bytes(
            self.nodes_per_bucket, self.node_size, self.h_exps is not None
        )

    @property
    def budget_buckets(self) -> int:
        """Resident-set cap in buckets (≥ 1: one bucket must always fit)."""
        nb = self.num_buckets
        if self.budget_bytes is None:
            return nb
        return min(nb, max(1, int(self.budget_bytes) // self.bucket_bytes))

    def memory_bytes_resident(self) -> int:
        """Device-tier footprint (the budget-governed quantity of I7)."""
        return len(self.resident_ids) * self.bucket_bytes

    def live_keys(self) -> int:
        return int(self.h_live.sum())

    # ---- metadata --------------------------------------------------------
    def _recompute_meta(self):
        """Full metadata recompute from the mirror (mirror must be synced)."""
        self.h_live = self.h_node_count.sum(axis=1).astype(np.int32)
        if self.h_exps is None:
            self.h_min_exp = np.full((self.num_buckets,), int(NO_EXPIRY), np.int32)
        else:
            self.h_min_exp = np.where(
                self.h_keys != int(EMPTY), self.h_exps, int(NO_EXPIRY)
            ).min(axis=(1, 2)).astype(np.int32)

    def _refresh_meta(self, ids: np.ndarray):
        """Refresh metadata for the packed working set from device."""
        if self._packed is None or len(ids) == 0:
            return
        live, min_exp = jax.device_get(_bucket_meta(self._packed))
        self.h_live[ids] = live
        self.h_min_exp[ids] = min_exp

    # ---- residency plumbing ----------------------------------------------
    def sync(self):
        """Page resident bucket rows back into the mirror (keeps residency).

        After this the mirror is authoritative for every bucket — the basis
        for host-side serialization, invariant checking, and restructure.
        """
        if self._packed is None or len(self.resident_ids) == 0:
            return
        st = self._packed
        ids = self.resident_ids
        k, v, nc, nm, nn = jax.device_get(
            (st.keys, st.vals, st.node_count, st.node_max, st.num_nodes)
        )
        self.h_keys[ids] = k
        self.h_vals[ids] = v
        self.h_node_count[ids] = nc
        self.h_node_max[ids] = nm
        self.h_num_nodes[ids] = nn
        if st.exps is not None:
            if self.h_exps is None:
                self.h_exps = np.full(self.h_keys.shape, int(NO_EXPIRY), np.int32)
            self.h_exps[ids] = np.asarray(jax.device_get(st.exps))
        # NEVER the packed mkba: its last entry is forced to MAX_VALID.

    def _gather(self, ids: np.ndarray) -> FliXState:
        """Upload mirror rows ``ids`` (sorted) as a packed device state."""
        mk = self.h_mkba[ids].copy()
        mk[-1] = int(MAX_VALID)
        return FliXState(
            keys=jnp.asarray(self.h_keys[ids]),
            vals=jnp.asarray(self.h_vals[ids]),
            node_count=jnp.asarray(self.h_node_count[ids]),
            node_max=jnp.asarray(self.h_node_max[ids]),
            num_nodes=jnp.asarray(self.h_num_nodes[ids]),
            mkba=jnp.asarray(mk),
            needs_restructure=jnp.asarray(self.needs_restructure),
            exps=None if self.h_exps is None else jnp.asarray(self.h_exps[ids]),
        )

    def _pad_working_set(self, ids: np.ndarray) -> np.ndarray:
        """Quantize the working set to min(nb, pow2) distinct buckets so the
        executors trace a bounded number of packed shapes."""
        nb = self.num_buckets
        target = min(nb, _pow2_ceil(max(len(ids), 1)))
        if target <= len(ids):
            return ids
        cold = np.setdiff1d(np.arange(nb, dtype=np.int32), ids, assume_unique=True)
        return np.sort(np.concatenate([ids, cold[: target - len(ids)]]))

    def _evict_to_budget(self) -> int:
        """LRU page-out down to the device budget (I7, post-commit)."""
        r = self.budget_buckets
        ids = self.resident_ids
        if len(ids) <= r or self._packed is None:
            return 0
        # keep the R most recently used (ties → lower bucket id)
        order = np.lexsort((ids, -self.last_used[ids]))
        kept = np.sort(ids[order[:r]])
        evicted = np.sort(ids[order[r:]])
        st = self._packed
        evict_pos = np.searchsorted(ids, evicted).astype(np.int32)
        k, v, nc, nm, nn = jax.device_get(
            (
                st.keys[evict_pos],
                st.vals[evict_pos],
                st.node_count[evict_pos],
                st.node_max[evict_pos],
                st.num_nodes[evict_pos],
            )
        )
        self.h_keys[evicted] = k
        self.h_vals[evicted] = v
        self.h_node_count[evicted] = nc
        self.h_node_max[evicted] = nm
        self.h_num_nodes[evicted] = nn
        if st.exps is not None:
            if self.h_exps is None:
                self.h_exps = np.full(self.h_keys.shape, int(NO_EXPIRY), np.int32)
            self.h_exps[evicted] = np.asarray(jax.device_get(st.exps[evict_pos]))
        kept_pos = jnp.asarray(np.searchsorted(ids, kept).astype(np.int32))
        self._packed = _take_buckets(st, kept_pos)
        self.resident_ids = kept
        self.demoted_total += len(evicted)
        return len(evicted)

    # ---- the engine ------------------------------------------------------
    def apply(
        self,
        ops: OpBatch,
        *,
        config: "ExecConfig | None" = None,
        now: int | None = None,
        commit: bool = True,
    ):
        """Prefetch → promote → run the unchanged executors → demote.

        Returns ``(results, stats, restructured)``; mutates ``self``.
        Execution strategy comes in as one ``config=ExecConfig(...)``
        forwarded to the inner ``apply_ops``.  A committed batch runs under
        ``core.restructure.replay_on_overflow``, as every engine does.
        ``commit=False`` runs a read-only batch: promotion/demotion still
        happen (residency is physical placement, not logical content) but
        the post-apply packed bytes are discarded — required for expiring
        reads that must not physically reclaim rows.
        """
        cfg = config or ExecConfig()
        # this engine replays batches on overflow and keeps the packed bytes
        # as its own working set: never donate
        cfg = cfg.replace(donate=False)
        tag, key, val, _ = ops.to_host()
        touched = touched_buckets(
            self.h_mkba,
            tag,
            key,
            val,
            live=self.h_live,
            min_exp=self.h_min_exp,
            now=now,
        )
        t_ids = np.nonzero(touched)[0].astype(np.int32)
        self._step += 1
        self.last_used[t_ids] = self._step

        promoted = 0
        s_ids = self.resident_ids
        if self._packed is not None and np.isin(
            t_ids, s_ids, assume_unique=True
        ).all():
            w_ids = s_ids  # fast path: zero transfers
            packed = self._packed
        else:
            self.sync()
            w_ids = np.union1d(s_ids, t_ids).astype(np.int32)
            w_ids = self._pad_working_set(w_ids)
            promoted = int(len(w_ids) - len(s_ids))
            packed = self._gather(w_ids)
        self.promoted_total += promoted

        before = 0

        def grow(handle, *, extra_keys):
            # bucket overflow: regrow the PRE-batch state from a full
            # materialization.  This is the one tiered operation that
            # transiently needs the whole index on device (same cost class
            # as the paper's restructure relaunch).
            nonlocal before
            self.resident_ids = w_ids
            self._packed = handle
            full = self.materialize()
            before = full.memory_bytes()
            return restructure_grow(full, extra_keys=extra_keys)

        def run(state):
            return apply_ops(state, ops, config=cfg, now=now)

        if commit:
            new, results, stats = replay_on_overflow(run, grow, packed, ops)
        else:
            new, results, stats = run(packed)
            stats = dict(stats, restructure_retries=0)
        restructured = stats["restructure_retries"] == 1
        reclaimed = 0

        if restructured:
            self._install_full(new)
            reclaimed = max(0, before - new.memory_bytes())
            self.reclaimed_total += reclaimed
        elif commit:
            self._packed = new
            self.resident_ids = w_ids
            self.needs_restructure = bool(new.needs_restructure)
            if self.h_exps is None and new.exps is not None:
                # TTL plane materialized mid-stream (first batch with exps)
                self.h_exps = np.full(self.h_keys.shape, int(NO_EXPIRY), np.int32)
            self._refresh_meta(w_ids)
        else:
            # read-only: retain the pre-apply packed bytes
            self._packed = packed
            self.resident_ids = w_ids

        demoted = self._evict_to_budget()
        stats["promoted"] = promoted
        stats["demoted"] = demoted
        stats["resident_bytes"] = self.memory_bytes_resident()
        stats["reclaimed_bytes"] = reclaimed
        return results, stats, restructured

    # ---- full-state transitions ------------------------------------------
    def materialize(self) -> FliXState:
        """The full single-tier device state (restructure/tests only — this
        is exactly the allocation the tiered engine otherwise avoids)."""
        self.sync()
        return FliXState(
            keys=jnp.asarray(self.h_keys),
            vals=jnp.asarray(self.h_vals),
            node_count=jnp.asarray(self.h_node_count),
            node_max=jnp.asarray(self.h_node_max),
            num_nodes=jnp.asarray(self.h_num_nodes),
            mkba=jnp.asarray(self.h_mkba),
            needs_restructure=jnp.asarray(self.needs_restructure),
            exps=None if self.h_exps is None else jnp.asarray(self.h_exps),
        )

    def _install_full(self, state: FliXState):
        """Replace the whole logical state (post-restructure): page
        everything out to the mirror and reset residency."""
        st = state.drop_volatile()
        k, v, nc, nm, nn, mk = jax.device_get(
            (st.keys, st.vals, st.node_count, st.node_max, st.num_nodes, st.mkba)
        )
        self.h_keys = np.array(k, np.int32, copy=True)
        self.h_vals = np.array(v, np.int32, copy=True)
        self.h_node_count = np.array(nc, np.int32, copy=True)
        self.h_node_max = np.array(nm, np.int32, copy=True)
        self.h_num_nodes = np.array(nn, np.int32, copy=True)
        self.h_mkba = np.array(mk, np.int32, copy=True)
        self.h_exps = (
            None
            if st.exps is None
            else np.array(jax.device_get(st.exps), np.int32, copy=True)
        )
        self.needs_restructure = bool(st.needs_restructure)
        self.resident_ids = np.zeros((0,), np.int32)
        self._packed = None
        self.last_used = np.zeros((self.num_buckets,), np.int64)
        self._recompute_meta()

    def compact(self, *, fill: float = 0.5) -> int:
        """Shrink to the smallest geometry for the live set and reclaim the
        freed pages.  Returns reclaimed bytes."""
        full = self.materialize()
        new, reclaimed = restructure_shrink(full, fill=fill)
        self._install_full(new)
        self.reclaimed_total += reclaimed
        return reclaimed

    # ---- durability / inspection hooks -----------------------------------
    def host_view(self) -> _HostView:
        """Synced read-only numpy view (serialization & invariants)."""
        self.sync()
        return _HostView(
            self.h_keys,
            self.h_vals,
            self.h_node_count,
            self.h_node_max,
            self.h_num_nodes,
            self.h_mkba,
            self.h_exps,
        )

    def expired_buckets(self, now: int) -> np.ndarray:
        """Bucket ids holding at least one live row with deadline ≤ now
        (metadata-only: no device scan, no transfer)."""
        return np.nonzero(self.h_min_exp <= np.int32(now))[0]
