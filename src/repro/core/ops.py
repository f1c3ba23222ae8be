"""Mixed-operation batch engine (the paper's batch execution model, §4.1).

The paper's execution unit is *one sorted batch per step*: the engine sorts
whatever operations arrived — inserts, deletes, point lookups, successor
probes — into a single key-ordered batch, and each bucket pulls *all* of its
work with one binary search.  This module is that engine:

  * ``OpBatch`` — a tagged operation batch (tag, key, val per slot).
  * ``make_ops`` — the one global sort (the only O(N log N) step).
  * ``apply_ops`` — the executor: one ``bucket_slices`` routing of the whole
    mixed batch.  Per-type views are *derived* from it with no second sort:
    order-preserving prefix-count scatters compact the insert/delete keys,
    and the insert phase's slice boundaries come from the single routing via
    prefix counts (``starts_ins = C_ins[starts]``) rather than a second
    fence routing.  The delete phase then uses deletion's flipped
    *whole-batch* membership search (data looks up the batch — no fence
    routing at all), and reads are answered from the updated state by the
    flipped compare-count forms (which binary-search the fences per query,
    as every FliX read does).

Within a batch the semantics are update-then-read:

  1. INSERT ops merge in first (upsert — incoming value wins),
  2. DELETE ops remove physically (present-key hits only),
  3. POINT, SUCCESSOR, and RANGE ops observe the post-update state.

RANGE is the ordered-CDS capability hash tables lack (the paper's central
functionality claim): an op reuses the key column for ``lo`` and the val
column for ``hi`` and answers the half-open ``[lo, hi)``.  Each batch
carries one static ``max_results`` output budget; results are packed
densely at exclusive-scan offsets (earlier sorted ops win the budget, each
op emits a prefix of its smallest in-range keys — deterministic, and
truncation is flagged in ``stats``).  See DESIGN.md §10.

``apply_ops`` has two executors behind one contract (``impl=``): the jnp
*reference* engine — four device passes whose insert path literally shares
``insert_with_slices`` with ``core.insert``, delete path shares
``core.delete``, read paths share ``core.query`` — and the *fused*
compute-to-bucket Pallas kernel (``kernels/flix_apply``, DESIGN.md §9) that
executes the whole update-then-read sequence in one VMEM-resident pass per
bucket.  Both are byte-identical to sequential per-type application
(``insert`` → ``delete`` → ``point_query`` → ``successor_query`` on the
sorted per-type sub-batches); ``tests/test_differential.py`` pins this down.

Precondition: at most one *update* op (INSERT or DELETE) per key per batch
(reads may repeat keys freely) — the same uniqueness contract ``insert``
already imposes.  ``OP_NOP`` slots (key must be ``EMPTY``) let callers pad
batches to a fixed size so jit traces once per geometry.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.batch import bucket_slices
from repro.core.config import (
    _UNSET,
    DEFAULT_MAX_RESULTS as DEFAULT_MAX_RESULTS,  # canonical home: core.config
    ExecConfig as ExecConfig,
    resolve_config,
)
from repro.core.state import EMPTY, KEY_DTYPE, NOT_FOUND, VAL_DTYPE, FliXState
from repro.core.trace import span

OP_INSERT = 0
OP_DELETE = 1
OP_POINT = 2
OP_SUCCESSOR = 3
OP_NOP = 4  # padding slot; key must be EMPTY so it routes past every bucket
OP_RANGE = 5  # key column = lo, val column = hi; answers [lo, hi)
OP_EXPIRE = 6  # get-or-set with TTL: exp column = absolute deadline; returns
#                the stored value (refreshing its TTL to the op's deadline)
#                when the key is live, else inserts (key, val, exp) and
#                returns NOT_FOUND.  Counts as an update op.  Requires the
#                batch to carry an exp column (DESIGN.md §14).

OP_DTYPE = jnp.int32


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OpBatch:
    """A key-sorted batch of tagged operations (a pytree of device arrays)."""

    tag: jax.Array  # [N] OP_DTYPE
    key: jax.Array  # [N] KEY_DTYPE, ascending (EMPTY = NOP padding, at end;
    #                 RANGE ops sort by their lo, which lives here)
    val: jax.Array  # [N] VAL_DTYPE (INSERT: value; RANGE: exclusive hi)
    # Optional per-op expiry column (KEY_DTYPE absolute deadlines;
    # NO_EXPIRY for ops without one).  INSERT ops take it as the new key's
    # TTL; EXPIRE ops require it.  ``None`` = legacy TTL-free batch.
    exp: jax.Array | None = None

    @property
    def size(self) -> int:
        return self.key.shape[0]

    def to_host(self):
        """The batch as host numpy arrays ``(tag, key, val, exp)`` — the form
        the write-ahead log frames (``checkpoint.wal``) and the dirty-bucket
        tracker consume (one device transfer, shared by both).  ``exp`` is
        ``None`` for TTL-free batches."""
        import numpy as np

        return (
            np.asarray(jax.device_get(self.tag)),
            np.asarray(jax.device_get(self.key)),
            np.asarray(jax.device_get(self.val)),
            None if self.exp is None else np.asarray(jax.device_get(self.exp)),
        )

    @classmethod
    def from_host(cls, tag, key, val, exp=None) -> "OpBatch":
        """Rehydrate a batch from host arrays *without re-sorting*: WAL
        records store already-sorted batches, and replay must apply exactly
        the bytes that were logged."""
        return cls(
            tag=jnp.asarray(tag, OP_DTYPE),
            key=jnp.asarray(key, KEY_DTYPE),
            val=jnp.asarray(val, VAL_DTYPE),
            exp=None if exp is None else jnp.asarray(exp, KEY_DTYPE),
        )


def make_ops(tags, keys, vals=None, *, exps=None, pad_to: int | None = None):
    """Sort a raw operation list by key into an :class:`OpBatch`.

    This is the engine's one global sort.  Returns ``(ops, perm)`` where
    ``perm[j]`` is the sorted position input op ``j`` landed at, so
    ``sorted_result[perm]`` (= :func:`unsort`) maps per-op results back to
    submission order.

    ``exps`` attaches a per-op expiry-deadline column (sorted and padded
    with ``NO_EXPIRY`` alongside the keys); required for batches containing
    ``OP_EXPIRE`` or TTL'd inserts.

    ``pad_to`` appends ``OP_NOP`` slots up to a fixed size so callers with
    variable-length steps trace one jit program per geometry.
    """
    from repro.core.expiry import NO_EXPIRY

    with span("make_ops"):
        tags = jnp.asarray(tags, OP_DTYPE)
        keys = jnp.asarray(keys, KEY_DTYPE)
        if vals is None:
            vals = jnp.zeros(keys.shape, VAL_DTYPE)
        vals = jnp.asarray(vals, VAL_DTYPE)
        if exps is not None:
            exps = jnp.asarray(exps, KEY_DTYPE)
        if pad_to is not None and pad_to > keys.shape[0]:
            extra = pad_to - keys.shape[0]
            tags = jnp.concatenate([tags, jnp.full((extra,), OP_NOP, OP_DTYPE)])
            keys = jnp.concatenate([keys, jnp.full((extra,), EMPTY, KEY_DTYPE)])
            vals = jnp.concatenate([vals, jnp.zeros((extra,), VAL_DTYPE)])
            if exps is not None:
                exps = jnp.concatenate([exps, jnp.full((extra,), NO_EXPIRY, KEY_DTYPE)])
        order = jnp.argsort(keys, stable=True)
        # inverse permutation (input position -> sorted position) by O(N) scatter
        perm = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
        return (
            OpBatch(
                tag=tags[order],
                key=keys[order],
                val=vals[order],
                exp=None if exps is None else exps[order],
            ),
            perm,
        )


def unsort(sorted_result: jax.Array, perm: jax.Array) -> jax.Array:
    """Map a sorted-order result array back to submission order."""
    return sorted_result[perm]


def touched_buckets(mkba_host, tag, key, val, *, live=None, min_exp=None, now=None):
    """Host-side prefetch pre-pass: which buckets a sorted batch can touch.

    The tiered engine (``core.residency``, DESIGN.md §15) promotes exactly
    the buckets whose bytes the executors may consult, so that running the
    *unchanged* executors against the packed resident subset is
    bucket-for-bucket identical to running them against the full state.
    The routing is the same one binary search per key the engine itself
    performs (``bucket_slices`` transposed to the classical direction, with
    the same ``min(b, nb-1)`` clamp the read paths apply).

    Per op type:
      * INSERT / DELETE / POINT / EXPIRE — the op's fence bucket.
      * RANGE — every bucket from ``b(lo)`` through ``b(hi)`` *inclusive*:
        the dense scan's rank arithmetic cancels the live counts of buckets
        entirely outside ``[b(lo), b(hi)]`` but consults every bucket
        inside it.
      * SUCCESSOR — ``b(q)`` plus the forward fence walk up to (and
        including) the first bucket *guaranteed* non-empty after the
        batch's own updates and expiry pass (an insert routed to it, or
        surviving pre-batch rows).  The out-of-bucket fallback reads the
        first non-empty bucket after ``b(q)``; promoting the whole walk
        makes the packed suffix-min agree with the full one.
      * additionally, when ``now`` is given — every bucket whose minimum
        live expiry deadline is ≤ ``now``: the expiry pre-pass physically
        reclaims those rows, so the buckets must be resident to change.

    ``live`` / ``min_exp`` are per-bucket host metadata ([nb] arrays: live
    row count; minimum live expiry deadline, ``NO_EXPIRY`` without TTLs).
    Both are optional, degrading conservatively: without ``live`` only
    inserts can guarantee non-emptiness (longer successor walks); a TTL'd
    caller must supply ``min_exp`` whenever it passes ``now``.

    All inputs are host numpy arrays; returns an [nb] bool mask.
    """
    import numpy as np

    mkba = np.asarray(mkba_host)
    nb = mkba.shape[0]
    tag = np.asarray(tag)
    key = np.asarray(key)
    val = np.asarray(val)
    touched = np.zeros(nb, dtype=bool)

    def b_of(q):
        return np.minimum(np.searchsorted(mkba, q, side="left"), nb - 1)

    simple = (
        (tag == OP_INSERT) | (tag == OP_DELETE) | (tag == OP_POINT) | (tag == OP_EXPIRE)
    )
    if simple.any():
        touched[b_of(key[simple])] = True

    is_range = tag == OP_RANGE
    if is_range.any():
        lo_b = b_of(key[is_range])
        hi_b = b_of(val[is_range])
        touched[lo_b] = True
        touched[hi_b] = True
        ok = lo_b <= hi_b
        if ok.any():
            d = np.zeros(nb + 1, np.int64)
            np.add.at(d, lo_b[ok], 1)
            np.add.at(d, hi_b[ok] + 1, -1)
            touched |= np.cumsum(d[:nb]) > 0

    is_succ = tag == OP_SUCCESSOR
    if is_succ.any():
        n_ins = np.zeros(nb, np.int64)
        upd_ins = ((tag == OP_INSERT) | (tag == OP_EXPIRE)) & (key != EMPTY)
        if upd_ins.any():
            np.add.at(n_ins, b_of(key[upd_ins]), 1)
        guaranteed = n_ins > 0
        if live is not None:
            n_del = np.zeros(nb, np.int64)
            upd_del = (tag == OP_DELETE) & (key != EMPTY)
            if upd_del.any():
                np.add.at(n_del, b_of(key[upd_del]), 1)
            survives = np.asarray(live).astype(np.int64) - n_del > 0
            if now is not None:
                if min_exp is None:
                    survives &= False  # no deadline metadata: nothing is safe
                else:
                    survives &= np.asarray(min_exp).astype(np.int64) > int(now)
            guaranteed |= survives
        b = b_of(key[is_succ])
        touched[b] = True
        # next_g[j] = first guaranteed bucket index ≥ j (nb if none)
        gidx = np.where(guaranteed, np.arange(nb, dtype=np.int64), nb)
        next_g = np.minimum.accumulate(gidx[::-1])[::-1]
        next_g = np.append(next_g, nb)
        starts = b + 1
        inb = starts < nb
        if inb.any():
            s = starts[inb]
            t = next_g[s]
            e = np.where(t < nb, t, nb - 1)  # walk to the end if none
            d = np.zeros(nb + 1, np.int64)
            np.add.at(d, s, 1)
            np.add.at(d, e + 1, -1)
            touched |= np.cumsum(d[:nb]) > 0

    if now is not None and min_exp is not None:
        touched |= np.asarray(min_exp).astype(np.int64) <= int(now)
    return touched


def _compact_by_mask(keys: jax.Array, mask: jax.Array, vals: jax.Array | None = None):
    """Front-pack ``keys[mask]`` preserving order; EMPTY tail.  No sort:
    destinations are a prefix count, so ascending order is preserved."""
    n = keys.shape[0]
    dest = jnp.where(mask, jnp.cumsum(mask) - 1, n)  # n = discard slot
    out_k = jnp.full((n + 1,), EMPTY, KEY_DTYPE).at[dest].set(keys)[:n]
    if vals is None:
        return out_k
    out_v = jnp.zeros((n + 1,), VAL_DTYPE).at[dest].set(vals)[:n]
    return out_k, out_v


def derive_type_views(state: FliXState, tag: jax.Array, key: jax.Array, val: jax.Array):
    """The engine's single routing plus the per-type views derived from it.

    Shared by both executors (``_apply_ops_reference`` and
    ``kernels.flix_apply``) so the routing contract cannot diverge between
    them.  Returns ``(is_ins, is_del, ins_keys, ins_vals, del_keys,
    ins_starts, ins_ends)``: the mixed-batch slice boundaries are mapped to
    insert-slice boundaries by prefix counts — no second sort, no second
    fence routing.
    """
    starts, ends = bucket_slices(state, key)
    is_ins = tag == OP_INSERT
    is_del = tag == OP_DELETE
    ins_keys, ins_vals = _compact_by_mask(key, is_ins, val)
    del_keys = _compact_by_mask(key, is_del)
    c_ins = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(is_ins).astype(jnp.int32)]
    )
    return is_ins, is_del, ins_keys, ins_vals, del_keys, c_ins[starts], c_ins[ends]


@functools.partial(jax.jit, static_argnames=("max_results",))
def _apply_ops_reference(
    state: FliXState, ops: OpBatch, *, max_results: int = DEFAULT_MAX_RESULTS
):
    """Reference engine: five jnp phases (the oracle for the fused kernel)."""
    from repro.core.delete import delete
    from repro.core.insert import insert_with_slices
    from repro.core.query import dense_range_scan, point_query, successor_query

    # drop any successor cache up front: the update phases construct cache-
    # free states, and lax.cond branches must agree on the pytree structure
    state = state.drop_volatile()

    tag, key, val = ops.tag, ops.key, ops.val
    n = key.shape[0]

    # --- the single routing + derived per-type views (no second sort) -----
    with jax.named_scope("flix.reference.route"):
        (
            is_ins,
            is_del,
            ins_keys,
            ins_vals,
            del_keys,
            ins_starts,
            ins_ends,
        ) = derive_type_views(state, tag, key, val)

    # --- update phase: merge inserts, then physical deletes ---------------
    # an absent op class skips its phase entirely (lax.cond executes one
    # branch), so read-heavy batches don't pay the merge machinery; the
    # differential contract is correspondingly "apply the present types".
    with jax.named_scope("flix.reference.insert"):
        s1, ins_stats = jax.lax.cond(
            jnp.any(is_ins),
            lambda: insert_with_slices(state, ins_keys, ins_vals, ins_starts, ins_ends),
            lambda: (
                state,
                {
                    "inserted": jnp.int32(0),
                    "nodes_after": jnp.sum(state.num_nodes),
                    "splits": jnp.int32(0),
                    "overflowed_buckets": jnp.int32(0),
                },
            ),
        )
    with jax.named_scope("flix.reference.delete"):
        s2, del_stats = jax.lax.cond(
            jnp.any(is_del),
            lambda: delete(s1, del_keys),
            lambda: (s1, {"deleted": jnp.int32(0), "nodes_freed": jnp.int32(0)}),
        )

    # --- read phase: flipped compare-count against the updated state ------
    is_point = tag == OP_POINT
    is_succ = tag == OP_SUCCESSOR
    with jax.named_scope("flix.reference.point"):
        pv = jax.lax.cond(
            jnp.any(is_point),
            lambda: point_query(s2, key),
            lambda: jnp.full((n,), NOT_FOUND, VAL_DTYPE),
        )
    with jax.named_scope("flix.reference.successor"):
        sk, sv = jax.lax.cond(
            jnp.any(is_succ),
            lambda: successor_query(s2, key),
            lambda: (
                jnp.full((n,), EMPTY, KEY_DTYPE),
                jnp.full((n,), NOT_FOUND, VAL_DTYPE),
            ),
        )
    # --- range phase: dense [lo, hi) scans against the updated state ------
    is_range = tag == OP_RANGE
    with jax.named_scope("flix.reference.range"):
        rk, rv, rstart, rcnt, rtrunc = jax.lax.cond(
            jnp.any(is_range),
            lambda: dense_range_scan(
                s2, is_range, key, val.astype(KEY_DTYPE), max_results=max_results
            ),
            lambda: (
                jnp.full((max_results,), EMPTY, KEY_DTYPE),
                jnp.full((max_results,), NOT_FOUND, VAL_DTYPE),
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((n,), jnp.int32),
                jnp.int32(0),
            ),
        )

    results = {
        "value": jnp.where(is_point, pv, jnp.where(is_succ, sv, NOT_FOUND)),
        "succ_key": jnp.where(is_succ, sk, EMPTY),
        "range_key": rk,
        "range_val": rv,
        "range_start": rstart,
        "range_count": rcnt,
    }
    stats = {
        "inserted": ins_stats["inserted"],
        "deleted": del_stats["deleted"],
        "overflowed_buckets": ins_stats["overflowed_buckets"],
        "range_truncated": rtrunc,
    }
    return s2, results, stats


def resolve_impl(impl: str, ops: OpBatch, has_updates: bool | None = None) -> str:
    """The executor ``impl="auto"`` stands for: ``"fused"`` on TPU for a
    batch with updates, ``"reference"`` otherwise (see :func:`apply_ops`).
    ``has_updates`` answers the batch-composition check without a device
    sync when the caller already knows it."""
    if impl != "auto":
        return impl
    if jax.default_backend() != "tpu":
        return "reference"
    if has_updates is None:
        with span("sync.has_updates"):
            is_update = (
                (ops.tag == OP_INSERT) | (ops.tag == OP_DELETE) | (ops.tag == OP_EXPIRE)
            )
            has_updates = bool(jnp.any(is_update))
    return "fused" if has_updates else "reference"


def plain_executor(state: FliXState, ops: OpBatch, *, impl: str, cfg: ExecConfig):
    """The jitted executor for one TTL-free batch (``impl`` resolved) and
    its call: ``(fn, args, kwargs)``.  ``fn.lower(*args, **kwargs)``
    compiles exactly what :func:`apply_ops` runs."""
    if impl == "reference":
        return _apply_ops_reference, (state, ops), {"max_results": cfg.max_results}
    if impl != "fused":
        raise ValueError(f"unknown apply_ops impl: {impl!r}")

    from repro.kernels.flix_apply import (
        DEFAULT_BLOCK_B,
        flix_apply_pallas,
        flix_apply_pallas_donated,
    )
    from repro.kernels.flix_query import DEFAULT_BLOCK_Q

    backend = jax.default_backend()
    fn = (
        flix_apply_pallas_donated
        if cfg.donate and backend != "cpu"
        else flix_apply_pallas
    )
    build_size = state.num_buckets * state.nodes_per_bucket * state.node_size
    block_q, block_b = cfg.resolve_blocks(build_size, ops.size)
    # "auto" pipelining is a backend property: the double-buffered DMA path
    # exists to overlap real HBM→VMEM copies with compute, so it engages on
    # TPU and falls back to the single-buffer kernel elsewhere.  "on"
    # forces it anywhere (interpret mode included — how the differential
    # suite proves byte-identity on CPU); "off" forces the fallback.
    pipeline = (backend == "tpu") if cfg.pipeline == "auto" else (cfg.pipeline == "on")
    return fn, (state, ops.tag, ops.key, ops.val), {
        "block_q": block_q or DEFAULT_BLOCK_Q,
        "block_b": block_b or DEFAULT_BLOCK_B,
        "max_results": cfg.max_results,
        "interpret": backend != "tpu",
        "pipeline": pipeline,
    }


def _apply_ops_plain(state: FliXState, ops: OpBatch, *, impl: str, cfg: ExecConfig):
    """Run one TTL-free batch on the chosen executor (impl resolved)."""
    fn, args, kwargs = plain_executor(state, ops, impl=impl, cfg=cfg)
    with span("dispatch.fused" if impl == "fused" else "dispatch.reference"):
        return fn(*args, **kwargs)


def _apply_ops_ttl(
    state: FliXState,
    ops: OpBatch,
    *,
    impl: str,
    cfg: ExecConfig,
    now=None,
):
    """TTL-aware batch execution (DESIGN.md §14) over any plain executor.

    Three steps, none of which the executors can see:

      1. *Expire pass* — ``expire_state(state, now)`` physically reclaims
         every row with ``exp <= now`` (skipped when ``now is None``).
      2. *EXPIRE lowering* — OP_EXPIRE ops probe the post-expire pre-update
         state (one ``successor_query``: present ⟺ successor key == op key,
         which unlike POINT distinguishes a stored NOT_FOUND-valued key from
         a miss) and are rewritten to OP_INSERT: on a hit the insert re-puts
         the *stored* value (so the value is unchanged) while the expiry
         plane takes the op's new deadline (TTL refresh); on a miss it
         inserts the op's (val, exp).  Sound because update ops are unique
         per key within a batch, so the probe state is the state the op
         observes.
      3. *Two-plane execution* — the chosen executor runs twice: once on the
         value plane and once on a state whose ``vals`` column holds the
         expiry deadlines.  Every layout decision (insert merge positions,
         delete/expiry compaction orders, restructure flags) is a function
         of keys and tags only, so both planes land byte-identical key
         layouts and the expiry plane's ``vals`` *is* the new expiry column.

    The expiry plane runs first and is never donated; the value plane gets
    the caller's ``donate`` flag (its buffers are shared with the expiry
    plane's inputs, which are dead by then).
    """
    from repro.core.expiry import NO_EXPIRY, attach_expiry, expire_state
    from repro.core.query import successor_query

    state = attach_expiry(state.drop_volatile())
    tag, key, val = ops.tag, ops.key, ops.val
    exp = (
        ops.exp
        if ops.exp is not None
        else jnp.full(key.shape, NO_EXPIRY, KEY_DTYPE)
    )

    if now is not None:
        state, n_expired = expire_state(state, now)
    else:
        n_expired = jnp.int32(0)

    is_exp = tag == OP_EXPIRE
    value_state = dataclasses.replace(state, exps=None)
    exp_state = dataclasses.replace(state, vals=state.exps, exps=None)

    def _probe():
        sk, sv = successor_query(value_state, key)
        return is_exp & (sk == key), sv

    present, stored = jax.lax.cond(
        jnp.any(is_exp),
        _probe,
        lambda: (
            jnp.zeros(key.shape, bool),
            jnp.full(key.shape, NOT_FOUND, VAL_DTYPE),
        ),
    )

    tag2 = jnp.where(is_exp, OP_INSERT, tag)
    val2 = jnp.where(is_exp & present, stored, val)
    is_ins = tag2 == OP_INSERT
    val_e = jnp.where(is_ins, exp, val)  # RANGE hi rides val in both planes

    s2e, _, _ = _apply_ops_plain(
        exp_state,
        OpBatch(tag=tag2, key=key, val=val_e),
        impl=impl,
        cfg=cfg.replace(donate=False),
    )
    s2v, results, stats = _apply_ops_plain(
        value_state, OpBatch(tag=tag2, key=key, val=val2), impl=impl, cfg=cfg
    )

    new_exps = jnp.where(s2v.keys == EMPTY, NO_EXPIRY, s2e.vals)
    new_state = dataclasses.replace(s2v, exps=new_exps)

    results = dict(results)
    results["value"] = jnp.where(
        is_exp, jnp.where(present, stored, NOT_FOUND), results["value"]
    )
    stats = dict(stats)
    stats["expired"] = n_expired
    return new_state, results, stats


def apply_ops(
    state: FliXState,
    ops: OpBatch,
    *,
    config: ExecConfig | None = None,
    has_updates: bool | None = None,
    now=None,
    impl=_UNSET,
    donate=_UNSET,
    block_q=_UNSET,
    block_b=_UNSET,
    max_results=_UNSET,
):
    """Execute one mixed sorted batch.  Returns ``(state', results, stats)``.

    ``results`` is aligned with the sorted batch:
      * ``value``    — POINT: stored value or NOT_FOUND; SUCCESSOR: successor
                       value or NOT_FOUND; other tags: NOT_FOUND.
      * ``succ_key`` — SUCCESSOR: smallest stored key ≥ op key (post-update)
                       or EMPTY; other tags: EMPTY.
      * ``range_key`` / ``range_val`` — the dense ``[max_results]`` RANGE
        output: all range ops' results packed consecutively (post-update,
        key-ordered within each op's segment); EMPTY / NOT_FOUND beyond the
        emitted total.
      * ``range_start`` / ``range_count`` — per-op offset and length of its
        segment in the dense arrays (0 / 0 for non-RANGE ops).  Truncation
        under the budget is deterministic — earlier sorted ops win, each op
        keeps a prefix of its smallest keys — and flagged via
        ``stats["range_truncated"]``.

    ``config`` is the single execution-strategy surface
    (:class:`repro.core.config.ExecConfig`, DESIGN.md §16) — executor
    choice, pipelining, donation, tile sizes, the RANGE budget.  The bare
    keywords below (``impl``, ``donate``, ``block_q``, ``block_b``,
    ``max_results``) are deprecation shims that build one and warn once;
    they drop next release.  ``has_updates`` and ``now`` are *per-call*
    facts about the batch, not strategy, so they stay keywords.

    ``config.impl`` selects the executor:
      * ``"reference"`` — the five jnp phases above (insert merge, delete,
        point, successor, range: ≥ 4 full state sweeps).  The differential
        oracle.
      * ``"fused"``     — the compute-to-bucket Pallas kernel
        (``kernels.flix_apply``): one VMEM-resident pass per bucket does the
        whole update-then-read sequence.  Runs compiled on TPU, in interpret
        mode elsewhere.
      * ``"auto"``      — ``"fused"`` on TPU for batches that contain
        updates, ``"reference"`` otherwise: off-TPU interpret-mode Pallas is
        a correctness tool, not a fast path, and an update-free batch (pure
        point/successor/range reads — e.g. a range-heavy query stream) would
        pay the fused kernel's full state rewrite for nothing (DESIGN.md
        §10).  ``has_updates`` lets drivers that already know the batch
        composition host-side (``serve/kv_index.py`` does) answer that
        check without a device sync; leave it ``None`` to inspect the tags.

    ``config.donate=True`` (fused only) donates the input state's buffers to the
    step so step N+1 reuses step N's allocation instead of copying — the
    caller must not touch ``state`` afterwards, so it is unsuitable when a
    restructure-and-retry may replay the batch (``apply_ops_safe`` never
    donates).  Ignored on CPU, where XLA does not implement donation.

    ``now`` is the engine's only notion of time (DESIGN.md §14): when the
    state or batch carries an expiry column, rows with ``exp <= now`` are
    physically reclaimed before the update phase and OP_EXPIRE ops execute
    get-or-set-with-TTL against the expired state.  ``now=None`` skips the
    expire pass (expiry columns are still maintained).  The engine never
    reads the wall clock — replay with the logged ``now`` is deterministic.

    On bucket overflow the returned state carries ``needs_restructure`` and
    the overflowing buckets are untrustworthy — same contract as ``insert``;
    hosts use :func:`apply_ops_safe`.
    """
    cfg = resolve_config(
        "apply_ops",
        config,
        impl=impl,
        donate=donate,
        block_q=block_q,
        block_b=block_b,
        max_results=max_results,
    )
    impl_r = resolve_impl(cfg.impl, ops, has_updates)
    # TTL activation is structural (does an expiry column exist on the state
    # or the batch?), so it is host-decidable even inside shard_map traces.
    if state.exps is not None or ops.exp is not None:
        return _apply_ops_ttl(state, ops, impl=impl_r, cfg=cfg, now=now)
    return _apply_ops_plain(state, ops, impl=impl_r, cfg=cfg)


def apply_ops_safe(
    state: FliXState,
    ops: OpBatch,
    *,
    config: ExecConfig | None = None,
    has_updates: bool | None = None,
    now=None,
    impl=_UNSET,
    max_results=_UNSET,
    validate_ranges=_UNSET,
    validate=_UNSET,
):
    """Host-level driver: apply, restructure-and-retry on overflow.

    Mirrors ``insert_safe`` — restructuring is host-driven because the new
    geometry changes static shapes.  The retry replays the *whole* batch on
    the regrown pre-batch state, which is safe because ``apply_ops`` never
    mutates its input (which is also why this driver never donates).

    ``config.validate_ranges=True`` additionally runs the structural RANGE-result
    checker (``core.invariants.check_range_results``: segments sorted,
    in-bounds, duplicate-free, consecutively packed) on the final results —
    a host-side debugging/testing aid, off on the hot path.
    ``config.validate=True`` runs the full structural invariant checker
    (``check_invariants``, incl. the I6 expiry-liveness check against the
    threaded ``now``) on the result state — same caveat.

    The returned ``stats`` gains ``restructure_retries`` (host int): how
    many times the batch was replayed on a regrown state.  It reflects the
    whole driver run, not just the final attempt — callers that account
    for retry cost (the serving gateway does) read it after the fact.
    """
    from repro.core.restructure import restructure_grow

    with span("apply_ops_safe"):
        cfg = resolve_config(
            "apply_ops_safe",
            config,
            impl=impl,
            max_results=max_results,
            validate_ranges=validate_ranges,
            validate=validate,
        )
        # a retry replays the batch on the pre-batch state — never donate here
        run_cfg = cfg.replace(donate=False, validate=False, validate_ranges=False)
        restructure_retries = 0
        new_state, results, stats = apply_ops(
            state, ops, config=run_cfg, has_updates=has_updates, now=now
        )
        # waits for the executor: the device's tail of the batch ends here
        with span("sync.needs_restructure"):
            overflowed = bool(new_state.needs_restructure) and not bool(
                state.needs_restructure
            )
        if overflowed:
            with span("restructure"):
                n_ins = int(jnp.sum((ops.tag == OP_INSERT) | (ops.tag == OP_EXPIRE)))
                grown = restructure_grow(state, extra_keys=max(n_ins, 1))
                new_state, results, stats = apply_ops(
                    grown, ops, config=run_cfg, has_updates=has_updates, now=now
                )
                assert not bool(new_state.needs_restructure), (
                    "post-restructure overflow"
                )
            restructure_retries = 1
        stats = dict(stats)
        stats["restructure_retries"] = restructure_retries
        if cfg.validate_ranges:
            from repro.core.invariants import check_range_results

            check_range_results(ops, results, max_results=cfg.max_results)
        if cfg.validate:
            from repro.core.invariants import check_invariants

            check_now = now
            if now is not None and ops.exp is not None:
                # the §14 same-batch edge: a row THIS batch wrote with
                # ``exp <= now`` is legitimately live until the next batch's
                # expiry pre-pass, so liveness-at-now cannot be asserted on
                # the post-state of a batch carrying dead-on-arrival writes
                wrote = (ops.tag == OP_INSERT) | (ops.tag == OP_EXPIRE)
                if bool(jnp.any(wrote & (ops.exp <= jnp.asarray(now, KEY_DTYPE)))):
                    check_now = None
            check_invariants(new_state, now=check_now)
        return new_state, results, stats
