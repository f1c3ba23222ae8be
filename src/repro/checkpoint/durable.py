"""Engine-aware durable persistence for a FliX index (DESIGN.md §12).

Commit protocol, per engine batch (WAL-ahead):

  1. frame + append the sorted ``OpBatch`` (with its ``max_results``) to
     the write-ahead log and fsync — the batch is durable *before* the
     engine runs it;
  2. execute it (``apply_ops_safe`` / ``shard_apply_ops_safe`` /
     ``TieredFliX.apply`` behind an engine adapter, restructure-and-retry
     included);
  3. fold the batch's update keys into the dirty-bucket set (fences are
     fixed between restructures, so host-side ``searchsorted`` routing is
     exact); a restructure bumps the *fence epoch* and dirties everything;
  4. every ``snapshot_every`` batches, write a snapshot — a dirty-bucket
     delta within an epoch, a full canonical payload after an epoch bump
     or every ``full_every``-th snapshot.

Snapshots are atomic (unique tmp sibling dir, fsync, rename, dir fsync)
and *canonical* (``checkpoint.serialize``): the same logical index always
produces the same payload bytes, so restructures and shard rebalances are
logical no-ops that never need WAL entries of their own.

Recovery (resumable, idempotent):

  1. load the newest crc-verified snapshot chain (full + deltas);
  2. truncate the WAL's torn tail (a crash mid-append);
  3. replay every logged batch after the snapshot through the engine;
  4. reopen the WAL for append — the instance continues exactly where the
     durable history ends.

Crashing *during* recovery is safe: its only write is the idempotent
tail truncation.  ``crash_hook`` is the fault-injection seam — the named
events in ``WriteAheadLog.append`` / ``DurableFliX.snapshot`` are where
``tests/fault_injection.py`` kills the process.
"""

from __future__ import annotations

import json
import os
import shutil
import zlib
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from repro.checkpoint import wal as wal_mod
from repro.checkpoint.manager import tmp_sibling
from repro.checkpoint.serialize import (
    bucket_segments,
    pack_delta,
    pairs_to_bytes,
    parse_canonical,
    parse_delta,
    segment_crcs,
    state_from_pairs,
)
from repro.checkpoint.wal import WriteAheadLog, decode_ops, encode_ops
from repro.core.config import ExecConfig
from repro.core.expiry import NO_EXPIRY
from repro.core.ops import OP_DELETE, OP_EXPIRE, OP_INSERT, OpBatch, apply_ops_safe
from repro.core.state import EMPTY

SNAP_FORMAT = "flix-durable-v1"
_SNAP_PREFIX = "snap_"


class SnapshotCorruptionError(RuntimeError):
    """A snapshot failed structural or checksum validation at load."""


def _noop_hook(event: str) -> None:
    return None


# ---------------------------------------------------------------------------
# engine adapters: one batch in, (new handle, results, stats, restructured)
# ---------------------------------------------------------------------------


class EngineBase:
    """Shared engine surface the durability layer talks to.

    Beyond ``rebuild``/``flix``/``apply``, the durable wrapper needs four
    read-only views of the handle.  The defaults go through ``flix()`` (a
    full device state) — correct for the single-device and sharded engines,
    whose handle IS device-resident.  The tiered engine overrides every one
    with host-tier implementations so that durability never forces the full
    index onto the device (DESIGN.md §15: snapshots and recovery are
    tier-oblivious).
    """

    def mkba_host(self, handle) -> np.ndarray:
        """The fence array as host numpy (dirty-bucket routing)."""
        return np.asarray(self.flix(handle).mkba)

    def geometry(self, handle) -> tuple[int, int, int]:
        """(num_buckets, nodes_per_bucket, node_size) of the handle."""
        return self.flix(handle).geometry

    def segments(self, handle, buckets=None):
        """Canonical per-bucket segments (``serialize.bucket_segments``)."""
        return bucket_segments(self.flix(handle), buckets)

    def expired_buckets(self, handle, now) -> np.ndarray | None:
        """Bucket ids holding live rows with deadline ≤ now, or None when
        the state carries no expiry column (pre-apply dirty marking)."""
        pre = self.flix(handle)
        if now is None or pre.exps is None:
            return None
        hit = jnp.any((pre.exps <= jnp.int32(now)) & (pre.keys != EMPTY), axis=(1, 2))
        return np.nonzero(np.asarray(hit))[0]


class LocalEngine(EngineBase):
    """Single-device executor behind the durability layer.

    ``config`` carries the execution strategy (executor, kernel pipeline,
    tiles, …) threaded to every ``apply_ops_safe``.  The per-batch
    ``max_results`` is NOT part of this config — it is logged per WAL
    record so replay re-runs each batch under its own budget.
    """

    kind = "local"

    def __init__(
        self,
        *,
        config: ExecConfig | None = None,
        node_size: int = 32,
        nodes_per_bucket: int = 16,
        fill: float = 0.5,
    ):
        self.config = config or ExecConfig()
        self.node_size = node_size
        self.nodes_per_bucket = nodes_per_bucket
        self.fill = fill

    def rebuild(self, keys, vals, exps=None, geometry: dict | None = None):
        g = geometry or {}
        return state_from_pairs(
            keys,
            vals,
            exps,
            node_size=g.get("node_size", self.node_size),
            nodes_per_bucket=g.get("nodes_per_bucket", self.nodes_per_bucket),
            fill=g.get("fill", self.fill),
        )

    def flix(self, handle):
        return handle

    def apply(self, handle, ops: OpBatch, *, max_results: int, now=None):
        """``apply_ops_safe``.  A restructure replay moves the fences, and
        the durability layer learns of that fence-epoch bump from
        ``stats["restructure_retries"]``."""
        cfg = self.config.replace(max_results=max_results)
        new, results, stats = apply_ops_safe(handle, ops, config=cfg, now=now)
        return new, results, stats, bool(stats["restructure_retries"])


class ShardEngine(EngineBase):
    """Sharded executor (``core.distributed``) behind the durability layer.

    The handle is a ``ShardedFliX``; rebuilds go through ``shard_build``
    (so recovery re-partitions fences from the recovered contents — the
    durable analogue of ``shard_restructure``), and batches run through
    ``shard_apply_ops_safe``, whose ``restructure_retries`` reports the
    epoch bump.
    """

    kind = "sharded"

    def __init__(
        self,
        mesh,
        *,
        config: ExecConfig | None = None,
        node_size: int = 32,
        nodes_per_bucket: int = 16,
        fill: float = 0.5,
    ):
        self.mesh = mesh
        self.config = config or ExecConfig()
        self.node_size = node_size
        self.nodes_per_bucket = nodes_per_bucket
        self.fill = fill

    def rebuild(self, keys, vals, exps=None, geometry: dict | None = None):
        from repro.core.distributed import shard_build

        g = geometry or {}
        if exps is not None:
            exps = np.asarray(exps, np.int32)
            if not (exps != int(NO_EXPIRY)).any():
                exps = None  # all-sentinel column ⇒ TTL-free rebuild
        return shard_build(
            jnp.asarray(np.asarray(keys, np.int32)),
            jnp.asarray(np.asarray(vals, np.int32)),
            self.mesh,
            node_size=g.get("node_size", self.node_size),
            nodes_per_bucket=g.get("nodes_per_bucket", self.nodes_per_bucket),
            fill=g.get("fill", self.fill),
            sorted_exps=None if exps is None else jnp.asarray(exps),
        )

    def flix(self, handle):
        return handle.state

    def apply(self, handle, ops: OpBatch, *, max_results: int, now=None):
        """``shard_apply_ops_safe``: same fence-epoch signal as
        :meth:`LocalEngine.apply`."""
        from repro.core.distributed import shard_apply_ops_safe

        cfg = self.config.replace(max_results=max_results)
        new, results, stats = shard_apply_ops_safe(
            handle, ops, self.mesh, config=cfg, now=now
        )
        return new, results, stats, bool(stats["restructure_retries"])


class TieredEngine(EngineBase):
    """Budget-bounded tiered executor (``core.residency``) behind the
    durability layer.

    The handle is a ``TieredFliX``.  Every hook runs against the host tier:
    recovery rebuilds the mirror with the numpy twin of
    ``state_from_pairs`` (byte-identical layout, zero device allocation),
    snapshots canonicalize the synced mirror, and the pre-apply expired-
    bucket scan reads the residency plane's per-bucket min-deadline
    metadata — so a durable tiered index never needs the full structure to
    fit on device (the restructure relaunch inside ``TieredFliX.apply`` is
    the sole transient exception).
    """

    kind = "tiered"

    def __init__(
        self,
        *,
        budget_bytes: int | None = None,
        config: ExecConfig | None = None,
        node_size: int = 32,
        nodes_per_bucket: int = 16,
        fill: float = 0.5,
    ):
        self.budget_bytes = budget_bytes
        self.config = config or ExecConfig()
        self.node_size = node_size
        self.nodes_per_bucket = nodes_per_bucket
        self.fill = fill

    def rebuild(self, keys, vals, exps=None, geometry: dict | None = None):
        from repro.core.residency import TieredFliX

        g = geometry or {}
        return TieredFliX.from_pairs(
            keys,
            vals,
            exps,
            node_size=g.get("node_size", self.node_size),
            nodes_per_bucket=g.get("nodes_per_bucket", self.nodes_per_bucket),
            fill=g.get("fill", self.fill),
            budget_bytes=self.budget_bytes,
        )

    def flix(self, handle):
        # tests / inspection only: this materializes the full device state,
        # exactly what the overridden hooks below exist to avoid
        return handle.materialize()

    def apply(self, handle, ops: OpBatch, *, max_results: int, now=None):
        results, stats, restructured = handle.apply(
            ops, config=self.config.replace(max_results=max_results), now=now
        )
        return handle, results, stats, restructured

    def mkba_host(self, handle) -> np.ndarray:
        return handle.h_mkba

    def geometry(self, handle) -> tuple[int, int, int]:
        return handle.geometry

    def segments(self, handle, buckets=None):
        return bucket_segments(handle.host_view(), buckets)

    def expired_buckets(self, handle, now) -> np.ndarray | None:
        if now is None or handle.h_exps is None:
            return None
        return handle.expired_buckets(now)


# ---------------------------------------------------------------------------
# snapshot store helpers
# ---------------------------------------------------------------------------


def _snap_name(seq: int) -> str:
    return f"{_SNAP_PREFIX}{seq:012d}"


def _snapshot_dirs(directory: Path) -> list[tuple[int, Path]]:
    """(seq, path) for committed snapshots, ascending; scratch dirs with
    ``.tmp`` in the name are crash leftovers and never listed."""
    out = []
    for p in Path(directory).glob(f"{_SNAP_PREFIX}*"):
        if not p.is_dir() or ".tmp" in p.name:
            continue
        try:
            seq = int(p.name[len(_SNAP_PREFIX) :])
        except ValueError:
            continue
        out.append((seq, p))
    return sorted(out)


def _read_manifest(path: Path) -> dict:
    try:
        with open(path / "manifest.json") as f:
            m = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SnapshotCorruptionError(f"{path.name}: unreadable manifest: {e}") from e
    if m.get("format") != SNAP_FORMAT:
        raise SnapshotCorruptionError(
            f"{path.name}: format {m.get('format')!r} != {SNAP_FORMAT!r}"
        )
    return m


def _read_payload(path: Path, manifest: dict) -> bytes:
    try:
        data = (path / "payload.bin").read_bytes()
    except OSError as e:
        raise SnapshotCorruptionError(f"{path.name}: unreadable payload: {e}") from e
    if zlib.crc32(data) != manifest["payload_crc"]:
        raise SnapshotCorruptionError(f"{path.name}: payload checksum mismatch")
    return data


def load_snapshot_chain(directory: Path, seq: int):
    """Reconstruct the canonical pairs at snapshot ``seq``: follow the
    delta chain back to its base full snapshot, then replay the diffs
    forward, verifying every checksum on the way.  Returns
    ``(keys, vals, exps, manifest)`` for the requested snapshot."""
    directory = Path(directory)
    chain: list[tuple[Path, dict]] = []
    name = _snap_name(seq)
    while True:
        path = directory / name
        m = _read_manifest(path)
        chain.append((path, m))
        if m["kind"] == "full":
            break
        if m["kind"] != "delta" or not m.get("base"):
            raise SnapshotCorruptionError(f"{path.name}: malformed chain entry")
        name = m["base"]
        if len(chain) > 10_000:
            raise SnapshotCorruptionError("delta chain does not terminate")
    chain.reverse()  # base full first

    base_path, base_m = chain[0]
    epoch = base_m["epoch"]
    keys, vals, exps = parse_canonical(_read_payload(base_path, base_m))
    lens = np.asarray(base_m["seg_lens"], np.int64)
    if int(lens.sum()) != keys.size:
        raise SnapshotCorruptionError(f"{base_path.name}: seg_lens/payload mismatch")
    bounds = np.concatenate([[0], np.cumsum(lens)])
    seg_k = [keys[bounds[b] : bounds[b + 1]] for b in range(len(lens))]
    seg_v = [vals[bounds[b] : bounds[b + 1]] for b in range(len(lens))]
    seg_e = [exps[bounds[b] : bounds[b + 1]] for b in range(len(lens))]

    for path, m in chain[1:]:
        if m["epoch"] != epoch:
            raise SnapshotCorruptionError(
                f"{path.name}: epoch {m['epoch']} != chain epoch {epoch}"
            )
        bi, ln, ks, vs, es = parse_delta(_read_payload(path, m))
        off = 0
        for b, n in zip(bi, ln):
            if not 0 <= b < len(seg_k):
                raise SnapshotCorruptionError(f"{path.name}: bucket {b} out of range")
            seg_k[b] = ks[off : off + n]
            seg_v[b] = vs[off : off + n]
            seg_e[b] = es[off : off + n]
            off += int(n)

    final_m = chain[-1][1]
    want_lens = np.asarray(final_m["seg_lens"], np.int64)
    got_lens = np.array([len(s) for s in seg_k], np.int64)
    if len(want_lens) != len(got_lens) or (want_lens != got_lens).any():
        raise SnapshotCorruptionError(f"{_snap_name(seq)}: reconstructed lens differ")
    flat_k = np.concatenate(seg_k) if seg_k else np.zeros(0, np.int32)
    flat_v = np.concatenate(seg_v) if seg_v else np.zeros(0, np.int32)
    flat_e = np.concatenate(seg_e) if seg_e else np.zeros(0, np.int32)
    crcs = segment_crcs(
        got_lens, flat_k.astype("<i4"), flat_v.astype("<i4"), flat_e.astype("<i4")
    )
    if crcs != list(final_m["bucket_crcs"]):
        raise SnapshotCorruptionError(f"{_snap_name(seq)}: bucket checksum mismatch")
    return (
        flat_k.astype(np.int32),
        flat_v.astype(np.int32),
        flat_e.astype(np.int32),
        final_m,
    )


# ---------------------------------------------------------------------------
# the durable index
# ---------------------------------------------------------------------------


class DurableFliX:
    """WAL-ahead durable wrapper around a FliX engine (DESIGN.md §12).

    Use :meth:`create` for a fresh directory and :meth:`open` to recover;
    ``apply`` is the only mutation path.  ``seq`` counts applied batches
    (0 = the initial snapshot), and every batch whose ``apply`` returned
    is durable: it was fsynced into the WAL before execution.
    """

    def __init__(
        self,
        directory,
        engine,
        handle,
        *,
        seq: int,
        epoch: int,
        snapshot_every: int = 64,
        full_every: int = 8,
        keep_full: int = 2,
        fsync: bool = True,
        crash_hook=None,
        meta_window: int = 256,
    ):
        self.dir = Path(directory)
        self.engine = engine
        self.handle = handle
        self.snapshot_every = snapshot_every
        self.full_every = max(1, full_every)
        self.keep_full = max(1, keep_full)
        self.meta_window = max(0, meta_window)
        self._seq = seq
        self._epoch = epoch
        self._hook = crash_hook or _noop_hook
        self._wal = WriteAheadLog(self.dir, fsync=fsync, crash_hook=self._hook)
        self._dirty: set[int] = set()
        self._all_dirty = True
        self._mkba_host = np.asarray(self.engine.mkba_host(self.handle))
        self._bucket_lens: np.ndarray | None = None
        self._bucket_crcs: list[int] | None = None
        self._snaps_since_full = 0
        self._poisoned: str | None = None
        self._closed = False
        # bounded (seq, meta) trail of recent commits: logged in each WAL
        # record, carried across snapshots via the manifest, rebuilt on
        # open() — the gateway's durable dedup window (DESIGN.md §13)
        self._meta: list[tuple[int, object]] = []

    # -- constructors -----------------------------------------------------
    @staticmethod
    def exists(directory) -> bool:
        d = Path(directory)
        return d.is_dir() and (
            bool(_snapshot_dirs(d)) or bool(wal_mod.segment_files(d))
        )

    @classmethod
    def create(
        cls,
        directory,
        handle,
        *,
        engine=None,
        snapshot_every: int = 64,
        full_every: int = 8,
        keep_full: int = 2,
        fsync: bool = True,
        crash_hook=None,
        meta_window: int = 256,
    ) -> "DurableFliX":
        """Start a durable history at ``seq=0`` from an existing state:
        writes the initial full snapshot and opens the first WAL segment."""
        directory = Path(directory)
        directory.mkdir(parents=True, exist_ok=True)
        if cls.exists(directory):
            raise FileExistsError(
                f"{directory} already holds a durable index — use open()"
            )
        self = cls(
            directory,
            engine or LocalEngine(),
            handle,
            seq=0,
            epoch=0,
            snapshot_every=snapshot_every,
            full_every=full_every,
            keep_full=keep_full,
            fsync=fsync,
            crash_hook=crash_hook,
            meta_window=meta_window,
        )
        self.snapshot(full=True)  # also opens WAL segment seq+1
        return self

    @classmethod
    def open(
        cls,
        directory,
        *,
        engine=None,
        snapshot_every: int = 64,
        full_every: int = 8,
        keep_full: int = 2,
        fsync: bool = True,
        crash_hook=None,
        truncate_torn: bool = True,
        meta_window: int = 256,
    ) -> "DurableFliX":
        """Crash recovery: newest valid snapshot chain + WAL replay.

        Every batch whose append was acknowledged is recovered; a torn
        tail (crash mid-append) is truncated — or, with
        ``truncate_torn=False``, surfaces as ``WALCorruptionError``.
        Recovery itself is crash-safe and idempotent, and rebuilding from
        canonical pairs is an epoch bump (fresh fences), so the first
        snapshot afterwards is automatically full.
        """
        directory = Path(directory)
        engine = engine or LocalEngine()
        snaps = _snapshot_dirs(directory)
        if not snaps:
            raise FileNotFoundError(f"no snapshots under {directory}")
        keys = vals = exps = manifest = None
        errors = []
        for seq, _path in reversed(snaps):
            try:
                keys, vals, exps, manifest = load_snapshot_chain(directory, seq)
                break
            except SnapshotCorruptionError as e:  # fall back to an older one
                errors.append(str(e))
        if manifest is None:
            raise SnapshotCorruptionError(
                f"no loadable snapshot under {directory}: {errors}"
            )

        handle = engine.rebuild(keys, vals, exps, manifest.get("geometry"))
        self = cls(
            directory,
            engine,
            handle,
            seq=manifest["seq"],
            epoch=manifest["epoch"] + 1,  # rebuilt fences = new epoch
            snapshot_every=snapshot_every,
            full_every=full_every,
            keep_full=keep_full,
            fsync=fsync,
            crash_hook=crash_hook,
            meta_window=meta_window,
        )
        # the dedup/meta trail up to the snapshot rides in its manifest;
        # the replayed tail below extends it exactly as live applies did
        for mseq, mobj in manifest.get("meta_window") or []:
            self._record_meta(int(mseq), mobj)
        records = wal_mod.replay(
            directory, after_seq=manifest["seq"], truncate_torn=truncate_torn
        )
        for seq, payload in records:
            tag, key, val, max_results, meta_bytes, exp, wnow = decode_ops(payload)
            ops = OpBatch.from_host(tag, key, val, exp)
            # replay at the LOGGED virtual clock — time-deterministic: the
            # recovered expiry state is what the live engine computed, no
            # matter when (in wall time) recovery runs
            new, _results, _stats, restructured = engine.apply(
                self.handle, ops, max_results=max_results, now=wnow
            )
            self.handle = new
            if restructured:
                # full _bump_epoch, not a bare counter: a replayed
                # restructure moves the fences, and apply()'s dirty-bucket
                # routing reads the refreshed _mkba_host ever after
                self._bump_epoch()
            self._seq = seq
            if meta_bytes:
                self._record_meta(seq, json.loads(meta_bytes.decode()))
        self.replayed = len(records)

        # resume appending where the durable history ends: the newest
        # segment (tail-truncated above) stays the active one
        segs = wal_mod.segment_files(directory)
        if segs:
            self._wal.open_segment(segs[-1][0], path=segs[-1][1])
        else:
            self._wal.open_segment(self._seq + 1)
        if self.snapshot_every and self.replayed >= self.snapshot_every:
            self.snapshot()  # bound the next recovery's replay cost
        return self

    # -- accessors --------------------------------------------------------
    @property
    def seq(self) -> int:
        return self._seq

    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def state(self):
        """The engine's current FliXState view (single-device: the handle
        itself; sharded: the global-view state)."""
        return self._flix_state()

    @property
    def healthy(self) -> bool:
        """False once live and durable state have diverged (failed WAL
        rollback) — ``apply``/``snapshot`` are refused; reads of the live
        handle remain valid, and reopening from disk resynchronizes."""
        return self._poisoned is None and not self._closed

    @property
    def poisoned_reason(self) -> str | None:
        return self._poisoned

    def meta_trail(self) -> list[tuple[int, object]]:
        """The bounded ``(seq, meta)`` trail of recent durable commits,
        ascending — everything the last ``meta_window`` metadata-carrying
        batches logged, surviving snapshots and crash recovery."""
        return list(self._meta)

    def _record_meta(self, seq: int, meta: object) -> None:
        if meta is None or self.meta_window == 0:
            return
        self._meta.append((seq, meta))
        if len(self._meta) > self.meta_window:
            del self._meta[: len(self._meta) - self.meta_window]

    def _flix_state(self):
        return self.engine.flix(self.handle)

    # -- the commit path --------------------------------------------------
    def apply(
        self,
        ops: OpBatch,
        *,
        config: ExecConfig | None = None,
        meta=None,
        now: int | None = None,
    ):
        """Durably execute one sorted batch; returns ``(results, stats)``.

        Execution strategy rides on ``config=ExecConfig(...)``.  Only its
        ``max_results`` is durable — it is logged per WAL record so replay
        re-runs each batch under its own budget; the rest of the strategy
        (impl, pipeline, tiles) belongs to the live engine and may differ
        at recovery time without changing the recovered state.

        ``now`` is the batch's virtual clock (DESIGN.md §14): it is logged
        in the WAL record alongside any per-op expiry column, so replay
        re-runs the batch at the identical time and recovers the identical
        expiry state — durability never consults the wall clock.

        ``meta`` (any JSON-serializable object, e.g. the gateway's
        idempotency keys) is logged inside the batch's WAL record and kept
        in the bounded :meth:`meta_trail` — it becomes durable in the SAME
        fsync as the ops, so a recovered history always agrees with itself
        about which annotated batches it contains.

        The WAL append (fsynced) precedes execution, so a crash at ANY
        later point replays this batch to the identical logical state —
        the engine never sees an op the log does not already hold.

        If the ENGINE fails (overflow assertion, OOM) the handle is
        unchanged — the engine is functional — so the just-appended record
        is rolled back before re-raising: the durable history must hold
        exactly the batches the live instance executed.  Should that
        rollback itself fail, the instance is poisoned (further apply /
        snapshot refused) because live and durable state have diverged —
        reopening from disk is the only consistent continuation.
        """
        mr = (config or ExecConfig()).max_results
        self._check_poisoned()
        tag, key, val, exp = ops.to_host()
        if exp is None and now is not None:
            # the record form needs an expiry column to carry the clock;
            # an all-sentinel one is logically "no per-op deadlines"
            exp = np.full(tag.shape, int(NO_EXPIRY), np.int32)
        seq = self._seq + 1
        meta_bytes = b"" if meta is None else json.dumps(meta).encode()
        wal_pos = self._wal.tell()
        self._wal.append(
            seq, encode_ops(tag, key, val, mr, meta_bytes, exp=exp, now=now)
        )
        self._seq = seq

        # buckets holding rows the expire pass is about to reclaim change
        # WITHOUT appearing among the batch's update keys — mark them dirty
        # from the pre-apply state so delta snapshots cover the reclamation
        expired_buckets = self.engine.expired_buckets(self.handle, now)

        try:
            new, results, stats, restructured = self.engine.apply(
                self.handle, ops, max_results=mr, now=now
            )
        except BaseException:
            self._seq = seq - 1
            try:
                self._wal.truncate_to(wal_pos)
            except BaseException:
                self._poisoned = (
                    f"batch seq={seq} was logged but neither executed nor "
                    "rolled back; reopen from disk to resynchronize"
                )
            raise
        self.handle = new
        if restructured:
            self._bump_epoch()
        else:
            upd = (tag == OP_INSERT) | (tag == OP_DELETE) | (tag == OP_EXPIRE)
            if upd.any():
                buckets = np.searchsorted(self._mkba_host, key[upd], side="left")
                self._dirty.update(int(b) for b in np.unique(buckets))
            if expired_buckets is not None:
                self._dirty.update(int(b) for b in expired_buckets)
        self._record_meta(seq, meta)
        self._hook("apply.done")

        if self.snapshot_every and seq % self.snapshot_every == 0:
            self.snapshot()
        return results, stats

    def _bump_epoch(self) -> None:
        self._epoch += 1
        self._all_dirty = True
        self._dirty.clear()
        self._mkba_host = np.asarray(self.engine.mkba_host(self.handle))

    def _check_poisoned(self) -> None:
        if self._poisoned:
            raise RuntimeError(
                f"durable history diverged from live state: {self._poisoned}"
            )
        if self._closed:
            raise RuntimeError("durable index is closed")

    # -- snapshots --------------------------------------------------------
    def snapshot(self, *, full: bool | None = None) -> Path:
        """Write one snapshot at the current seq (atomic commit).

        ``full=None`` picks automatically: full on the first snapshot,
        after an epoch bump (fences moved — the delta partition is void),
        and every ``full_every``-th snapshot; otherwise a dirty-bucket
        delta whose write cost is proportional to churn.
        """
        self._check_poisoned()
        name = _snap_name(self._seq)
        if (self.dir / name).is_dir():
            # a snapshot at this seq is already committed, and seq determines
            # the logical content — forcing another is an idempotent no-op
            # (e.g. close-time snapshot right after an auto-snapshot).  But
            # only after it validates: open() may have fallen back PAST a
            # corrupt snapshot at exactly this seq, and trusting it would
            # leave every future recovery replaying the whole WAL tail.
            try:
                load_snapshot_chain(self.dir, self._seq)
                return self.dir / name
            except SnapshotCorruptionError:
                shutil.rmtree(self.dir / name, ignore_errors=True)
        if full is None:
            full = (
                self._all_dirty
                or self._bucket_lens is None
                or self._snaps_since_full >= self.full_every - 1
            )
        prev_full_name = None
        if not full:
            prev_full_name = self._latest_snap_name()

        if full:
            lens, seg_k, seg_v, seg_e = self.engine.segments(self.handle)
            payload = pairs_to_bytes(seg_k, seg_v, seg_e)
            all_lens = lens
            all_crcs = segment_crcs(lens, seg_k, seg_v, seg_e)
            kind = "full"
        else:
            dirty = sorted(self._dirty)
            lens, seg_k, seg_v, seg_e = self.engine.segments(self.handle, dirty)
            payload = pack_delta(dirty, lens, seg_k, seg_v, seg_e)
            all_lens = np.array(self._bucket_lens, np.int64)
            all_crcs = list(self._bucket_crcs)
            new_crcs = segment_crcs(lens, seg_k, seg_v, seg_e)
            for i, b in enumerate(dirty):
                all_lens[b] = lens[i]
                all_crcs[b] = new_crcs[i]
            kind = "delta"

        nb, npb, ns = self.engine.geometry(self.handle)
        manifest = {
            "format": SNAP_FORMAT,
            "kind": kind,
            "seq": self._seq,
            "epoch": self._epoch,
            "base": prev_full_name,
            "engine": self.engine.kind,
            "geometry": {
                "num_buckets": nb,
                "nodes_per_bucket": npb,
                "node_size": ns,
                "fill": getattr(self.engine, "fill", 0.5),
            },
            "n_live": int(np.asarray(all_lens, np.int64).sum()),
            "seg_lens": [int(x) for x in all_lens],
            "bucket_crcs": [int(c) for c in all_crcs],
            "payload_crc": zlib.crc32(payload),
            # carry the dedup/meta trail across the WAL segments this
            # snapshot retires — open() reseeds from here, then extends
            # with the replayed tail (DESIGN.md §13)
            "meta_window": [[s, m] for s, m in self._meta],
        }

        tmp = tmp_sibling(self.dir / name)
        tmp.mkdir(parents=True)
        try:
            self._write_file(tmp / "payload.bin", payload, split=True)
            self._hook("snap.payload.written")
            self._write_file(
                tmp / "manifest.json",
                json.dumps(manifest, sort_keys=True).encode(),
            )
            self._hook("snap.manifest.written")
            self._hook("snap.before_rename")
            os.rename(tmp, self.dir / name)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self._fsync_dir()
        self._hook("snap.committed")

        self._bucket_lens = np.asarray(all_lens, np.int64)
        self._bucket_crcs = list(all_crcs)
        self._dirty.clear()
        self._all_dirty = False
        self._snaps_since_full = 0 if full else self._snaps_since_full + 1
        self._wal.rotate(self._seq + 1)
        self._gc()
        self._hook("snap.gc")
        return self.dir / name

    def _latest_snap_name(self) -> str:
        snaps = _snapshot_dirs(self.dir)
        if not snaps:
            raise RuntimeError("delta snapshot requires an existing base")
        return snaps[-1][1].name

    def _write_file(self, path: Path, data: bytes, *, split: bool = False) -> None:
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o644)
        try:
            if split and len(data) > 1:
                # two writes so the crash hook can land mid-payload
                wal_mod.write_all(fd, data[: len(data) // 2])
                self._hook("snap.payload.partial")
                wal_mod.write_all(fd, data[len(data) // 2 :])
            else:
                wal_mod.write_all(fd, data)
            os.fsync(fd)
        finally:
            os.close(fd)

    def _fsync_dir(self) -> None:
        dfd = os.open(self.dir, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    def _gc(self) -> None:
        """Retention: keep the ``keep_full`` newest full snapshots, every
        delta above the oldest kept full, and the WAL segments needed to
        replay past the oldest kept snapshot.  Deltas below the cutoff are
        unreachable (their chains end in deleted fulls) and fulls below it
        are redundant history."""
        snaps = [
            (seq, p, _read_manifest(p)["kind"]) for seq, p in _snapshot_dirs(self.dir)
        ]
        fulls = [seq for seq, _p, kind in snaps if kind == "full"]
        if len(fulls) <= self.keep_full:
            return
        cutoff = sorted(fulls)[-self.keep_full]
        for seq, p, _kind in snaps:
            if seq < cutoff:
                shutil.rmtree(p, ignore_errors=True)
        segs = wal_mod.segment_files(self.dir)
        for (start, path), nxt in zip(segs, segs[1:]):
            # a segment holds records [start, next_start); all ≤ cutoff are
            # covered by the oldest kept snapshot
            if nxt[0] <= cutoff + 1:
                path.unlink(missing_ok=True)

    def close(self) -> None:
        """Flush and close the WAL.  Idempotent, and safe on a poisoned
        instance: teardown of a diverged index must not raise on top of
        the failure that poisoned it — the durable history on disk is
        already self-consistent, and reopening resynchronizes."""
        if self._closed:
            return
        self._closed = True
        try:
            self._wal.close()
        except OSError:
            if self._poisoned is None:
                raise
