"""Bucket-sharded FliX across a device mesh (the distributed index service).

Buckets are *range-partitioned* across shards (contiguous MKBA ranges per
device), so the flipped paradigm lifts directly to the cluster level: a
shard is just a super-bucket, and a sorted operation batch is routed by the
same fence-searchsorted primitive — each shard pulls its slice.

Since PR 5 the unit of distributed execution is the **mixed batch**:
:func:`shard_apply_ops` runs one whole ``OpBatch`` (POINT / SUCCESSOR /
INSERT / DELETE / RANGE) under a single ``shard_map`` step, with per-shard
compute delegated to ``core.ops.apply_ops`` *unchanged* — including the
``impl="fused"`` compute-to-bucket kernel and buffer donation — so the
hierarchy composes: bucket ⊂ shard ⊂ cluster.  The legacy per-op-type
entry points (``insert``/``delete``/``point_query``/``successor_query``)
are gone.

Two routing modes (DESIGN.md §11):

* ``replicated`` — the sorted batch is broadcast; each shard masks the
  *update* ops to its fence range (reads run everywhere — a successor or
  range answer may live outside the op key's owner shard) and recombines
  with one collective round.  Right for query-dominant workloads where the
  batch is small relative to the structure (the paper's regime).
* ``a2a`` — each shard holds a batch shard; op rows are routed to their
  owner shard by one partition-fence searchsorted driving a padded
  ``all_to_all``, results travel back over the inverse ``all_to_all``.
  Right at ingest scale where batches arrive sharded.  Fixed per-pair
  ``capacity`` keeps shapes static; overflow is counted and surfaced in
  ``stats["a2a_overflow"]`` (the caller re-routes with a bigger capacity —
  ``shard_apply_ops`` never mutates its input, so the retry replays the
  same batch on the same pre-batch index).

RANGE results are recombined into the dense exclusive-scan contract of
DESIGN.md §10 with *global* offsets: per-op local in-range counts are
``all_gather``-ed, an exclusive scan over shards gives each shard its slot
window inside every op's segment, and truncation is applied against the
single global ``max_results`` budget — byte-identical to the single-device
``apply_ops`` output.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.batch import bucket_slices, gather_sublists
from repro.core.build import build_from_sorted
from repro.core.config import _UNSET, ExecConfig, resolve_config
from repro.core.expiry import NO_EXPIRY
from repro.core.ops import (
    OP_DELETE,
    OP_EXPIRE,
    OP_INSERT,
    OP_NOP,
    OP_POINT,
    OP_RANGE,
    OP_SUCCESSOR,
    OpBatch,
    _compact_by_mask,
    apply_ops,
    resolve_impl,
)
from repro.core.query import _suffix_min_with_index, flat_rank, range_offsets
from repro.core.state import (
    EMPTY,
    KEY_DTYPE,
    MAX_VALID,
    MIN_KEY,
    NOT_FOUND,
    VAL_DTYPE,
    FliXState,
    flatten_bucket_sorted,
)
from repro.core.trace import span

# max_results handed to the *inner* apply_ops when the cross-shard range
# phase answers the batch's RANGE ops (the inner dense arrays are ignored)
_INNER_MR = 8


class ShardedFliX(NamedTuple):
    state: FliXState          # bucket dim sharded over ``axis``
    lower_fence: jax.Array    # [n_shards] fence below each shard's range
    part_fences: jax.Array    # [n_shards] upper fence per shard (replicated)
    axis: str


def plan_shard_budget(total_budget: int | None, n_shards: int) -> int | None:
    """Split a global device-memory budget across shards (DESIGN.md §15).

    Buckets are range-partitioned evenly, so the per-shard residency bound
    is simply an even split — each shard's residency plane enforces its
    slice independently and I7 holds globally because shard bucket sets are
    disjoint.  Returns a per-shard byte budget (``None`` = unbounded).
    """
    if total_budget is None:
        return None
    return max(1, int(total_budget) // max(1, n_shards))


def shard_memory_bytes(idx: ShardedFliX) -> int:
    """Total allocated footprint of a sharded index across the mesh —
    the per-shard ``memory_bytes`` summed (every shard holds the same
    static geometry, so this is shards × the per-shard footprint)."""
    return idx.state.memory_bytes() + idx.lower_fence.size * 4 + idx.part_fences.size * 4


def make_shard_mesh(n_shards: int, *, axis: str = "shards") -> jax.sharding.Mesh:
    """A 1-D mesh over the first ``n_shards`` local devices."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"need {n_shards} devices for {n_shards} shards, have {len(devs)} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU)"
        )
    return jax.sharding.Mesh(np.array(devs[:n_shards]), (axis,))


@functools.partial(
    jax.jit, static_argnames=("num_buckets", "nodes_per_bucket", "node_size", "fill")
)
def _build_shard(
    keys, vals, exps, last, *, num_buckets, nodes_per_bucket, node_size, fill
):
    """One shard's slice of the table, built on the device its inputs are on.

    The slice build closes the key space at its own last bucket
    (``MAX_VALID``); unless ``last``, that fence is set back to the bucket's
    largest key, as the whole-table build has it."""
    kw = dict(
        num_buckets=num_buckets,
        nodes_per_bucket=nodes_per_bucket,
        node_size=node_size,
        fill=fill,
    )
    state = build_from_sorted(keys, vals, **kw)
    if exps is not None:
        # expiry plane of the same build: identical layout, exps in vals
        built_e = build_from_sorted(keys, exps, **kw)
        state = dataclasses.replace(
            state, exps=jnp.where(state.keys == EMPTY, NO_EXPIRY, built_e.vals)
        )
    top = jnp.where(state.num_nodes[-1] > 0, state.node_max[-1, 0], MAX_VALID)
    return dataclasses.replace(
        state, mkba=state.mkba.at[-1].set(jnp.where(last, MAX_VALID, top))
    )


def shard_build(
    sorted_keys,
    sorted_vals,
    mesh,
    *,
    axis: str = "shards",
    node_size: int = 32,
    nodes_per_bucket: int = 16,
    fill: float = 0.5,
    extra_keys: int = 0,
    sorted_exps=None,
) -> ShardedFliX:
    """Build a range-partitioned table across ``mesh``'s ``axis``, each
    shard on its own device.

    Bucket ``i`` takes sorted keys ``[i * p, (i + 1) * p)``, so shard ``s``
    builds its buckets from one contiguous slice of the sorted keys; no
    device holds more than its own shard and that shard's build
    temporaries.  The result equals the whole-table ``build_from_sorted``
    placed over the mesh, array by array.

    ``extra_keys`` over-provisions the bucket count (the distributed
    analogue of ``restructure_grow``'s headroom argument) so a subsequent
    batch of that many inserts cannot overflow a fresh structure.
    ``sorted_exps`` carries the per-key expiry column (sorted alongside the
    keys); the built state then serves the TTL path (DESIGN.md §14).
    """
    n_shards = int(mesh.shape[axis])
    p = max(1, int(node_size * fill))
    keys = np.asarray(sorted_keys).astype(np.int32)
    vals = np.asarray(sorted_vals).astype(np.int32)
    exps = None if sorted_exps is None else np.asarray(sorted_exps).astype(np.int32)
    n = int(np.sum(keys != EMPTY)) + extra_keys
    per_shard_buckets = max(1, math.ceil(math.ceil(n / p) / n_shards))
    width = per_shard_buckets * p

    def piece(a, s, pad):
        out = np.full((width,), pad, np.int32)
        part = a[s * width : (s + 1) * width]
        out[: part.size] = part
        return out

    shard3 = NamedSharding(mesh, P(axis, None, None))
    shard2 = NamedSharding(mesh, P(axis, None))
    shard1 = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    shape = (per_shard_buckets * n_shards, nodes_per_bucket, node_size)
    built, tops = [], [None] * n_shards
    for dev, index in shard3.addressable_devices_indices_map(shape).items():
        s = (index[0].start or 0) // per_shard_buckets
        state = _build_shard(
            jax.device_put(piece(keys, s, EMPTY), dev),
            jax.device_put(piece(vals, s, 0), dev),
            None if exps is None else jax.device_put(piece(exps, s, 0), dev),
            jax.device_put(np.bool_(s == n_shards - 1), dev),
            num_buckets=per_shard_buckets,
            nodes_per_bucket=nodes_per_bucket,
            node_size=node_size,
            fill=fill,
        )
        built.append(state)
        tops[s] = state.mkba[-1]
    part_fences = np.asarray(jax.device_get(tops), np.int32)
    lower_fence = np.concatenate([np.array([MIN_KEY], np.int32), part_fences[:-1]])

    def assemble(field, sharding):
        parts = [getattr(st, field) for st in built]
        return jax.make_array_from_single_device_arrays(
            (shape[0], *parts[0].shape[1:]), sharding, parts
        )

    state = FliXState(
        keys=assemble("keys", shard3),
        vals=assemble("vals", shard3),
        node_count=assemble("node_count", shard2),
        node_max=assemble("node_max", shard2),
        num_nodes=assemble("num_nodes", shard1),
        mkba=assemble("mkba", shard1),
        needs_restructure=jax.device_put(np.bool_(False), rep),
        exps=None if exps is None else assemble("exps", shard3),
    )
    return ShardedFliX(
        state=state,
        lower_fence=jax.device_put(lower_fence, shard1),
        part_fences=jax.device_put(part_fences, rep),
        axis=axis,
    )


def shard_restructure(
    idx: ShardedFliX,
    mesh,
    *,
    extra_keys: int = 0,
    fill: float = 0.5,
) -> ShardedFliX:
    """Rebalance partition fences from the live-key distribution.

    The cluster analogue of the paper's §3.5 relaunch: the host pulls the
    live contents, re-plans a uniform geometry for ``live + extra_keys``
    keys, and re-partitions so every shard owns an equal bucket count of an
    evenly-filled structure — skew accumulated since the last build (every
    new tenant hashing into one shard's fence range, say) is erased.

    Host-driven by design, exactly like single-device ``restructure``: the
    new static geometry (bucket count, possibly a widened chain) cannot be
    chosen on device.  Functional — the input index is untouched.
    """
    state = idx.state
    flat_k = np.asarray(jax.device_get(state.keys)).reshape(-1)
    flat_v = np.asarray(jax.device_get(state.vals)).reshape(-1)
    order = np.argsort(flat_k, kind="stable")  # EMPTY sentinels sort last
    sorted_k, sorted_v = flat_k[order], flat_v[order]
    sorted_e = None
    if state.exps is not None:
        sorted_e = np.asarray(jax.device_get(state.exps)).reshape(-1)[order]

    live = int((flat_k != EMPTY).sum())
    p = max(1, int(state.node_size * fill))
    cap = state.nodes_per_bucket * state.node_size
    if p + extra_keys > cap:
        # pathological skew: widen the chain so one bucket can absorb the
        # whole pending batch (mirrors restructure_grow)
        npb = math.ceil((p + extra_keys) / state.node_size)
    else:
        npb = state.nodes_per_bucket
    return shard_build(
        sorted_k,
        sorted_v,
        mesh,
        axis=idx.axis,
        node_size=state.node_size,
        nodes_per_bucket=npb,
        fill=fill,
        extra_keys=extra_keys,
        sorted_exps=sorted_e,
    )


def shard_live_counts(idx: ShardedFliX, mesh) -> jax.Array:
    """Per-shard live-key counts ``[n_shards]`` (balance diagnostics)."""
    axis = idx.axis

    def body(node_count):
        return jax.lax.all_gather(jnp.sum(node_count).reshape(1), axis).reshape(-1)

    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis, None),),
            out_specs=P(),
            check_vma=False,
        )
    )(idx.state.node_count)


def _state_specs(axis: str, has_ttl: bool = False) -> FliXState:
    return FliXState(
        keys=P(axis, None, None),
        vals=P(axis, None, None),
        node_count=P(axis, None),
        node_max=P(axis, None),
        num_nodes=P(axis),
        mkba=P(axis),
        needs_restructure=P(),
        exps=P(axis, None, None) if has_ttl else None,
    )


def replicate_batch(ops: OpBatch, mesh) -> OpBatch:
    """Place an :class:`OpBatch` fully replicated on ``mesh``."""
    rep = NamedSharding(mesh, P())
    return OpBatch(
        tag=jax.device_put(ops.tag, rep),
        key=jax.device_put(ops.key, rep),
        val=jax.device_put(ops.val, rep),
        exp=None if ops.exp is None else jax.device_put(ops.exp, rep),
    )


def shard_batch(ops: OpBatch, mesh, *, axis: str = "shards") -> OpBatch:
    """Position-shard an :class:`OpBatch` over ``axis`` (a2a-mode input).

    Each shard's chunk must be key-sorted locally (a globally sorted batch
    split into contiguous chunks qualifies); chunks from different shards
    need no mutual order.
    """
    sh = NamedSharding(mesh, P(axis))
    return OpBatch(
        tag=jax.device_put(ops.tag, sh),
        key=jax.device_put(ops.key, sh),
        val=jax.device_put(ops.val, sh),
        exp=None if ops.exp is None else jax.device_put(ops.exp, sh),
    )


def _inverse_permutation(order: jax.Array) -> jax.Array:
    return jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype)
    )


def _post_update_shard_min(state: FliXState):
    """Smallest present key in this shard (EMPTY if none) and its value."""
    bucket_min = jnp.where(state.num_nodes > 0, state.keys[:, 0, 0], EMPTY)
    b = jnp.argmin(bucket_min).astype(jnp.int32)
    m = bucket_min[b]
    v = jnp.where(m != EMPTY, state.vals[b, 0, 0], NOT_FOUND)
    return m, v


def _predict_post_keys(state: FliXState, ins_keys: jax.Array, del_keys: jax.Array):
    """Post-update per-bucket sorted key rows + rank fences, *pre-apply*.

    The fused kernel's predict-without-running-the-update argument
    (``kernels/flix_apply._range_plumbing``) lifted to the shard level: a
    shard's post-update bucket multiset is (surviving stripe keys minus
    upsert duplicates) ∪ (this shard's masked insert keys) — exact because
    one batch never inserts and deletes the same key, and EXPIRE keys count
    as inserts (get-or-set leaves the key present either way).  This is
    what lets the cross-shard RANGE counts collective launch *before* the
    per-shard update pass (DESIGN.md §16): the two touch no shared data
    until the final dense extract.  NOT valid under an expiry pass at
    ``now`` — the caller gates on ``has_now`` and falls back to the
    sequential post-apply phase.

    ``ins_keys``/``del_keys`` are the shard's masked update keys, sorted,
    EMPTY-padded.  Returns ``(post_keys [nb, S+cap], pref [nb+1])``.
    """
    flat_k, _ = flatten_bucket_sorted(state)
    nb, S = flat_k.shape
    cap = state.bucket_capacity
    mflat = flat_k.reshape(-1)
    nk = max(del_keys.shape[0] - 1, 0)
    dpos = jnp.minimum(jnp.searchsorted(del_keys, mflat, side="left"), nk)
    dhit = (del_keys[dpos] == mflat) & (mflat != EMPTY)
    masked = jnp.where(dhit.reshape(nb, S), EMPTY, flat_k)

    ni = max(ins_keys.shape[0] - 1, 0)
    ipos = jnp.minimum(jnp.searchsorted(ins_keys, masked.reshape(-1), side="left"), ni)
    upserted = (ins_keys[ipos] == masked.reshape(-1)) & (masked.reshape(-1) != EMPTY)

    istarts, iends = bucket_slices(state, ins_keys)
    ik, _, _ = gather_sublists(ins_keys, istarts, iends, cap)
    post_rows = jnp.concatenate(
        [jnp.where(upserted.reshape(nb, S), EMPTY, masked), ik], axis=1
    )
    post_keys = jnp.sort(post_rows, axis=1)
    live = jnp.sum(post_keys != EMPTY, axis=1).astype(jnp.int32)
    pref = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(live).astype(jnp.int32)]
    )
    return post_keys, pref


def _range_counts_phase(
    post_keys: jax.Array,
    pref: jax.Array,
    mkba: jax.Array,
    is_range: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    axis: str,
    max_results: int,
):
    """The collective half of cross-shard RANGE: ranks → gathered counts →
    global offsets → per-slot (bucket, rank) sources for this shard.

    The §10 dense exclusive-scan contract with *global* offsets: local
    in-range counts are gathered across shards, an exclusive scan over the
    shard axis gives this shard its slot window inside every op's segment,
    and each emitted slot is filled by exactly one shard.  ``post_keys`` /
    ``pref`` describe the shard's post-update key layout — either read from
    the updated state (sequential path) or predicted pre-apply
    (:func:`_predict_post_keys`, the overlapped path).  ``is_range`` /
    ``lo`` / ``hi`` must be replicated and in global sorted-batch order.
    """
    n = lo.shape[0]
    rank_lo = flat_rank(post_keys, pref, mkba, lo)
    rank_hi = flat_rank(post_keys, pref, mkba, hi)
    local_full = jnp.maximum(rank_hi - rank_lo, 0)
    local_full = jnp.where(is_range, local_full, 0).astype(jnp.int32)

    counts_all = jax.lax.all_gather(local_full, axis)          # [S, N]
    me = jax.lax.axis_index(axis)
    global_full = jnp.sum(counts_all, axis=0)
    prefix_lt = (jnp.cumsum(counts_all, axis=0) - counts_all)[me]

    start, emit, total_emit, truncated = range_offsets(
        global_full, is_range, max_results
    )

    # slot ownership: the shared §10 owner rule, then "is slot p's in-op
    # offset inside MY shard's window [prefix_lt, prefix_lt + local_full)?"
    p = jnp.arange(max_results, dtype=jnp.int32)
    owner = jnp.clip(
        jnp.searchsorted(start, p, side="right").astype(jnp.int32) - 1, 0, n - 1
    )
    j = p - start[owner]
    valid = p < total_emit
    mine = valid & (j >= prefix_lt[owner]) & (j < prefix_lt[owner] + local_full[owner])
    g = rank_lo[owner] + (j - prefix_lt[owner])                # local key rank
    g_c = jnp.where(mine, g, 0)
    nb = post_keys.shape[0]
    src_b = jnp.clip(
        jnp.searchsorted(pref, g_c, side="right").astype(jnp.int32) - 1, 0, nb - 1
    )
    src_p = g_c - pref[src_b]
    return (
        src_b,
        src_p,
        mine,
        valid,
        jnp.where(is_range, start, 0),
        jnp.where(is_range, emit, 0),
        truncated,
    )


def _range_extract_contrib(state: FliXState, src_b, src_p, mine):
    """This shard's additive contribution to the dense RANGE arrays: actual
    post-update bytes at the (bucket, in-bucket rank) sources the counts
    phase resolved.  Exactly one shard owns each emitted slot, so a psum
    recombines (the caller folds it into the single combine psum)."""
    flat_k, flat_v = flatten_bucket_sorted(state)
    src_p = jnp.minimum(src_p, flat_k.shape[1] - 1)  # overflowed buckets are
    #                            untrustworthy anyway (needs_restructure set)
    rk = jnp.where(mine, flat_k[src_b, src_p], 0)
    rv = jnp.where(mine, flat_v[src_b, src_p], 0)
    return rk, rv


def _empty_range_outputs(n: int, max_results: int):
    return (
        jnp.full((max_results,), EMPTY, KEY_DTYPE),
        jnp.full((max_results,), NOT_FOUND, VAL_DTYPE),
        jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), jnp.int32),
        jnp.int32(0),
    )


@functools.lru_cache(maxsize=64)
def _build_replicated(
    mesh, axis, inner_cfg, max_results, has_ranges, donate, has_ttl=False, has_now=False
):
    """jit(shard_map)-compiled replicated-routing executor (memoized).

    PR 10 overlap structure (DESIGN.md §16): when the batch has RANGE ops
    and no expiry pass, the cross-shard recombination's *counts* collective
    is issued against the predicted post-update layout BEFORE the per-shard
    update pass — the two touch no shared data until the dense extract — so
    the scheduler is free to run the ``all_gather`` concurrently with the
    update compute.  All POINT/SUCCESSOR/RANGE/stats recombination then
    collapses into a single fused ``psum`` over one contribution pytree
    (plus the one unavoidable ``pmin`` for the successor winner).
    """

    n_shards = int(mesh.shape[axis])

    def body(state, lf, tag, key, val, *extra):
        # extra = (exp,) / (exp, now) when the TTL lanes are enabled
        exp = extra[0] if has_ttl else None
        now = extra[1] if has_now else None
        with jax.named_scope("flix.shard.mask"):
            lf = lf[0]
            upper = state.mkba[-1]
            is_upd = (tag == OP_INSERT) | (tag == OP_DELETE) | (tag == OP_EXPIRE)
            is_rng = tag == OP_RANGE
            # updates run on their owner shard only; POINT/SUCCESSOR run
            # everywhere (a successor answer may live past the owner's fence);
            # RANGE is lifted out entirely for the cross-shard phase
            keep = (~is_upd | ((key > lf) & (key <= upper))) & ~is_rng
            mtag = jnp.where(keep, tag, OP_NOP)
            mkey = jnp.where(keep, key, EMPTY)
            mval = jnp.where(keep, val, 0)
            order = jnp.argsort(mkey, stable=True)
            inv = _inverse_permutation(order)
            stag, skey = mtag[order], mkey[order]

        # overlapped RANGE counts phase: issued pre-apply from the predicted
        # post-update layout (invalid under an expiry pass at ``now`` — the
        # prediction cannot see which keys the clock removes)
        overlap = has_ranges and not has_now
        if overlap:
            with jax.named_scope("flix.shard.range_counts"):
                ins_keys = _compact_by_mask(
                    skey, (stag == OP_INSERT) | (stag == OP_EXPIRE)
                )
                del_keys = _compact_by_mask(skey, stag == OP_DELETE)
                post_keys, pref = _predict_post_keys(state, ins_keys, del_keys)
                src_b, src_p, mine, rvalid, rstart, rcnt, rtrunc = _range_counts_phase(
                    post_keys,
                    pref,
                    state.mkba,
                    is_rng,
                    key,
                    val.astype(KEY_DTYPE),
                    axis,
                    max_results,
                )

        with jax.named_scope("flix.shard.apply"):
            new_state, res, st = apply_ops(
                state,
                OpBatch(
                    tag=stag,
                    key=skey,
                    val=mval[order],
                    exp=None
                    if exp is None
                    else jnp.where(keep, exp, NO_EXPIRY)[order],
                ),
                config=inner_cfg,
                now=now,
            )

        if has_ranges and not overlap:
            # sequential fallback (TTL with ``now``): counts phase against
            # the actually-updated state
            with jax.named_scope("flix.shard.range_counts"):
                flat_k, _ = flatten_bucket_sorted(new_state)
                live = jnp.sum(flat_k != EMPTY, axis=1).astype(jnp.int32)
                pref = jnp.concatenate(
                    [jnp.zeros((1,), jnp.int32), jnp.cumsum(live).astype(jnp.int32)]
                )
                src_b, src_p, mine, rvalid, rstart, rcnt, rtrunc = _range_counts_phase(
                    flat_k,
                    pref,
                    new_state.mkba,
                    is_rng,
                    key,
                    val.astype(KEY_DTYPE),
                    axis,
                    max_results,
                )

        with jax.named_scope("flix.shard.combine"):
            value = res["value"][inv]
            succ_key = res["succ_key"][inv]

            # POINT: at most one shard holds the key, the rest answer NOT_FOUND.
            # EXPIRE recombines the same way: it is masked to its owner shard,
            # whose get-or-set answer comes back through the value lane
            is_point = (tag == OP_POINT) | (tag == OP_EXPIRE)
            hit = is_point & (value != NOT_FOUND)

            # SUCCESSOR: shard-local candidates, global min; shard key ranges
            # are disjoint so the min is attained by exactly one shard
            is_succ = tag == OP_SUCCESSOR
            cand = jnp.where(is_succ, succ_key, EMPTY)
            kmin = jax.lax.pmin(cand, axis)
            winner = is_succ & (cand == kmin) & (cand != EMPTY)

            # ONE fused combine psum over the whole contribution pytree (the
            # fused executor's ``updated_buckets`` and ``delete_min_fixups``
            # are left out: the engine's stats are the same whichever
            # executor the shards run)
            contrib = {
                "pv": jnp.where(hit, value, 0),
                "n_hit": hit.astype(jnp.int32),
                "sv": jnp.where(winner, value, 0),
                "inserted": st["inserted"],
                "deleted": st["deleted"],
                "overflowed_buckets": st["overflowed_buckets"],
                "restructure": new_state.needs_restructure.astype(jnp.int32),
                # this shard's entry: the batch's updates it owns
                "shard_updates": jnp.zeros((n_shards,), jnp.int32)
                .at[jax.lax.axis_index(axis)]
                .set(jnp.sum(keep & is_upd).astype(jnp.int32)),
            }
            if has_ttl:
                contrib["expired"] = st["expired"]
            if has_ranges:
                rk_c, rv_c = _range_extract_contrib(new_state, src_b, src_p, mine)
                contrib["rk"] = rk_c
                contrib["rv"] = rv_c
            summed = jax.lax.psum(contrib, axis)

            point_val = jnp.where(summed["n_hit"] > 0, summed["pv"], NOT_FOUND)
            succ_val = jnp.where(kmin != EMPTY, summed["sv"], NOT_FOUND)
            if has_ranges:
                rk = jnp.where(rvalid, summed["rk"], EMPTY)
                rv = jnp.where(rvalid, summed["rv"], NOT_FOUND)
            else:
                rk, rv, rstart, rcnt, rtrunc = _empty_range_outputs(
                    key.shape[0], max_results
                )

            results = {
                "value": jnp.where(
                    is_point, point_val, jnp.where(is_succ, succ_val, NOT_FOUND)
                ),
                "succ_key": jnp.where(is_succ, kmin, EMPTY),
                "range_key": rk,
                "range_val": rv,
                "range_start": rstart,
                "range_count": rcnt,
            }
            stats = {
                "inserted": summed["inserted"],
                "deleted": summed["deleted"],
                "overflowed_buckets": summed["overflowed_buckets"],
                "range_truncated": rtrunc,
                "a2a_overflow": jnp.int32(0),
                "shard_updates": summed["shard_updates"],
            }
            if has_ttl:
                stats["expired"] = summed["expired"]
            new_state = dataclasses.replace(
                new_state,
                needs_restructure=(summed["restructure"] > 0),
            )
            return new_state, results, stats

    specs = _state_specs(axis, has_ttl)
    rep_results = {
        "value": P(),
        "succ_key": P(),
        "range_key": P(),
        "range_val": P(),
        "range_start": P(),
        "range_count": P(),
    }
    rep_stats = {
        "inserted": P(),
        "deleted": P(),
        "overflowed_buckets": P(),
        "range_truncated": P(),
        "a2a_overflow": P(),
        "shard_updates": P(),
    }
    if has_ttl:
        rep_stats["expired"] = P()
    in_specs = (specs, P(axis), P(), P(), P())
    if has_ttl:
        in_specs += (P(),)
    if has_now:
        in_specs += (P(),)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(specs, rep_results, rep_stats),
        check_vma=False,
    )

    def flix_shard_replicated(*args):
        # the program's name on a device trace: ``jit_flix_shard_replicated``
        return sharded(*args)

    donate_argnums = (0,) if donate else ()
    return jax.jit(flix_shard_replicated, donate_argnums=donate_argnums)


@functools.lru_cache(maxsize=64)
def _build_a2a(
    mesh,
    axis,
    inner_cfg,
    max_results,
    has_ranges,
    capacity,
    donate,
    has_ttl=False,
    has_now=False,
):
    """jit(shard_map)-compiled a2a-routing executor (memoized).

    Same PR 10 overlap structure as the replicated builder: the RANGE-side
    batch ``all_gather`` depends only on the raw inputs and is hoisted
    before routing; the counts collective runs pre-apply against the
    predicted post-update layout of the *received* rows (gated off under an
    expiry pass at ``now``); recombination is one fused ``psum`` pytree.
    """
    n_shards = int(mesh.shape[axis])

    def body(state, part_fences, tag, key, val, *extra):
        # extra = (exp,) / (exp, now) when the TTL lanes are enabled
        exp = extra[0] if has_ttl else None
        now = extra[1] if has_now else None
        n_local = key.shape[0]
        me = jax.lax.axis_index(axis)
        is_rng = tag == OP_RANGE

        overlap = has_ranges and not has_now
        if has_ranges:
            # gather every shard's RANGE rows up front — depends only on the
            # batch inputs, so it overlaps the routing + update below
            with jax.named_scope("flix.shard.range_counts"):
                g_tag = jax.lax.all_gather(tag, axis).reshape(-1)
                g_lo = jax.lax.all_gather(key, axis).reshape(-1)
                g_hi = jax.lax.all_gather(val, axis).reshape(-1)
                g_isr = g_tag == OP_RANGE
                gorder = jnp.argsort(jnp.where(g_isr, g_lo, EMPTY), stable=True)
                isr_s = g_isr[gorder]
                q_lo = g_lo[gorder]
                q_hi = g_hi[gorder].astype(KEY_DTYPE)

        with jax.named_scope("flix.shard.mask"):
            # RANGE rows never ride the a2a (the cross-shard phase answers
            # them from the gathered batch); masking them to the EMPTY tail
            # keeps the local sort a valid routing order
            rkey = jnp.where(is_rng, EMPTY, key)
            order = jnp.argsort(rkey, stable=True)
            inv = _inverse_permutation(order)
            s_tag, s_key, s_val = tag[order], rkey[order], val[order]
            s_exp = None if exp is None else exp[order]

            # per-destination slices by one partition-fence searchsorted
            ends = jnp.searchsorted(s_key, part_fences, side="right").astype(jnp.int32)
            starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
            counts = ends - starts
            overflow = jnp.sum(jnp.maximum(counts - capacity, 0))

            idx = starts[:, None] + jnp.arange(capacity, dtype=jnp.int32)[None]
            valid = idx < ends[:, None]
            idx_c = jnp.minimum(idx, n_local - 1)
            send_t = jnp.where(valid, s_tag[idx_c], OP_NOP)
            send_k = jnp.where(valid, s_key[idx_c], EMPTY)
            send_v = jnp.where(valid, s_val[idx_c], 0)

        with jax.named_scope("flix.shard.exchange"):
            recv_t = jax.lax.all_to_all(send_t, axis, 0, 0).reshape(-1)
            recv_k = jax.lax.all_to_all(send_k, axis, 0, 0).reshape(-1)
            recv_v = jax.lax.all_to_all(send_v, axis, 0, 0).reshape(-1)
            recv_e = None
            if s_exp is not None:
                # the expiry deadline rides as a fourth send lane; EXPIRE rows
                # route to their owner by key exactly like other update ops
                send_e = jnp.where(valid, s_exp[idx_c], NO_EXPIRY)
                recv_e = jax.lax.all_to_all(send_e, axis, 0, 0).reshape(-1)

        with jax.named_scope("flix.shard.mask"):
            rord = jnp.argsort(recv_k, stable=True)
            rinv = _inverse_permutation(rord)
            r_tag, r_key = recv_t[rord], recv_k[rord]

        if overlap:
            # counts collective pre-apply: the received rows ARE this
            # shard's update batch, so the prediction sees exactly what the
            # update pass will apply
            with jax.named_scope("flix.shard.range_counts"):
                ins_keys = _compact_by_mask(
                    r_key, (r_tag == OP_INSERT) | (r_tag == OP_EXPIRE)
                )
                del_keys = _compact_by_mask(r_key, r_tag == OP_DELETE)
                post_keys, pref = _predict_post_keys(state, ins_keys, del_keys)
                src_b, src_p, mine, rvalid, start_s, emit_s, rtrunc = (
                    _range_counts_phase(
                        post_keys,
                        pref,
                        state.mkba,
                        isr_s,
                        q_lo,
                        q_hi,
                        axis,
                        max_results,
                    )
                )

        with jax.named_scope("flix.shard.apply"):
            new_state, res, st = apply_ops(
                state,
                OpBatch(
                    tag=r_tag,
                    key=r_key,
                    val=recv_v[rord],
                    exp=None if recv_e is None else recv_e[rord],
                ),
                config=inner_cfg,
                now=now,
            )

        if has_ranges and not overlap:
            # sequential fallback (TTL with ``now``): counts phase against
            # the actually-updated state
            with jax.named_scope("flix.shard.range_counts"):
                flat_k, _ = flatten_bucket_sorted(new_state)
                live = jnp.sum(flat_k != EMPTY, axis=1).astype(jnp.int32)
                pref = jnp.concatenate(
                    [jnp.zeros((1,), jnp.int32), jnp.cumsum(live).astype(jnp.int32)]
                )
                src_b, src_p, mine, rvalid, start_s, emit_s, rtrunc = (
                    _range_counts_phase(
                        flat_k,
                        pref,
                        new_state.mkba,
                        isr_s,
                        q_lo,
                        q_hi,
                        axis,
                        max_results,
                    )
                )

        with jax.named_scope("flix.shard.combine"):
            value_r = res["value"][rinv]
            skey_r = res["succ_key"][rinv]

            # successor fallback across shards: an owner whose local state has
            # no key ≥ q answers with the first non-empty *later* shard's
            # minimum — the §8 fence-row trick one level up the hierarchy
            m, mv = _post_update_shard_min(new_state)
            mins = jax.lax.all_gather(m.reshape(1), axis).reshape(-1)      # [S]
            mvals = jax.lax.all_gather(mv.reshape(1), axis).reshape(-1)
            sufk, sufi = _suffix_min_with_index(mins)
            sufk_pad = jnp.concatenate([sufk, jnp.array([EMPTY], KEY_DTYPE)])
            sufi_pad = jnp.concatenate([sufi, jnp.array([0], jnp.int32)])
            fb_key = sufk_pad[me + 1]
            fb_val = jnp.where(fb_key != EMPTY, mvals[sufi_pad[me + 1]], NOT_FOUND)
            needs_fb = (recv_t == OP_SUCCESSOR) & (skey_r == EMPTY)
            skey_r = jnp.where(needs_fb, fb_key, skey_r)
            value_r = jnp.where(needs_fb, fb_val, value_r)

        with jax.named_scope("flix.shard.exchange"):
            # inverse a2a: owner d's row s carries results for the rows source
            # s sent to d, in their original slots
            back_v = jax.lax.all_to_all(value_r.reshape(n_shards, capacity), axis, 0, 0)
            back_sk = jax.lax.all_to_all(skey_r.reshape(n_shards, capacity), axis, 0, 0)

        with jax.named_scope("flix.shard.combine"):
            dest = jnp.where(valid, idx_c, n_local).reshape(-1)
            out_v = (
                jnp.full((n_local + 1,), NOT_FOUND, VAL_DTYPE)
                .at[dest]
                .set(back_v.reshape(-1))[:n_local][inv]
            )
            out_sk = (
                jnp.full((n_local + 1,), EMPTY, KEY_DTYPE)
                .at[dest]
                .set(back_sk.reshape(-1))[:n_local][inv]
            )

            # ONE fused combine psum over the whole contribution pytree (the
            # fused executor's ``updated_buckets`` and ``delete_min_fixups``
            # are left out: the engine's stats are the same whichever
            # executor the shards run)
            received = (
                (r_tag == OP_INSERT) | (r_tag == OP_DELETE) | (r_tag == OP_EXPIRE)
            )
            contrib = {
                "inserted": st["inserted"],
                "deleted": st["deleted"],
                "overflowed_buckets": st["overflowed_buckets"],
                "a2a_overflow": overflow.astype(jnp.int32),
                "restructure": new_state.needs_restructure.astype(jnp.int32),
                # this shard's entry: the updates it received
                "shard_updates": jnp.zeros((n_shards,), jnp.int32)
                .at[me]
                .set(jnp.sum(received).astype(jnp.int32)),
            }
            if has_ttl:
                contrib["expired"] = st["expired"]
            if has_ranges:
                rk_c, rv_c = _range_extract_contrib(new_state, src_b, src_p, mine)
                contrib["rk"] = rk_c
                contrib["rv"] = rv_c
            summed = jax.lax.psum(contrib, axis)

            if has_ranges:
                rk = jnp.where(rvalid, summed["rk"], EMPTY)
                rv = jnp.where(rvalid, summed["rv"], NOT_FOUND)
                # scatter per-op offsets back to this shard's input rows
                gid = gorder
                op_mine = isr_s & (gid // n_local == me)
                back = jnp.where(op_mine, gid - me * n_local, n_local)
                zeros = jnp.zeros((n_local + 1,), jnp.int32)
                rstart = zeros.at[back].set(jnp.where(isr_s, start_s, 0))[:n_local]
                rcnt = zeros.at[back].set(jnp.where(isr_s, emit_s, 0))[:n_local]
            else:
                rk, rv, _, _, rtrunc = _empty_range_outputs(n_local, max_results)
                rstart = jnp.zeros((n_local,), jnp.int32)
                rcnt = jnp.zeros((n_local,), jnp.int32)

            results = {
                "value": out_v,
                "succ_key": out_sk,
                "range_key": rk,
                "range_val": rv,
                "range_start": rstart,
                "range_count": rcnt,
            }
            stats = {
                "inserted": summed["inserted"],
                "deleted": summed["deleted"],
                "overflowed_buckets": summed["overflowed_buckets"],
                "range_truncated": rtrunc,
                "a2a_overflow": summed["a2a_overflow"],
                "shard_updates": summed["shard_updates"],
            }
            if has_ttl:
                stats["expired"] = summed["expired"]
            new_state = dataclasses.replace(
                new_state,
                needs_restructure=(summed["restructure"] > 0),
            )
            return new_state, results, stats

    specs = _state_specs(axis, has_ttl)
    out_results = {
        "value": P(axis),
        "succ_key": P(axis),
        "range_key": P(),
        "range_val": P(),
        "range_start": P(axis),
        "range_count": P(axis),
    }
    rep_stats = {
        "inserted": P(),
        "deleted": P(),
        "overflowed_buckets": P(),
        "range_truncated": P(),
        "a2a_overflow": P(),
        "shard_updates": P(),
    }
    if has_ttl:
        rep_stats["expired"] = P()
    in_specs = (specs, P(), P(axis), P(axis), P(axis))
    if has_ttl:
        in_specs += (P(axis),)
    if has_now:
        in_specs += (P(),)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(specs, out_results, rep_stats),
        check_vma=False,
    )

    def flix_shard_a2a(*args):
        # the program's name on a device trace: ``jit_flix_shard_a2a``
        return sharded(*args)

    donate_argnums = (0,) if donate else ()
    return jax.jit(flix_shard_a2a, donate_argnums=donate_argnums)


# a2a capacity headroom over the uniform per-destination share.  The value
# comes from benchmarks/sharded_mix.py's routing-skew measurement: uniform
# random batches land within ~1.5x of the even share at the sizes the bench
# sweeps, so 2x absorbs the observed skew while sending ~2/S of the
# never-overflowing chunk capacity (the safe driver's doubling retry
# absorbs the pathological remainder).
A2A_CAPACITY_HEADROOM = 2.0


def default_a2a_capacity(
    chunk: int, n_shards: int, *, headroom: float = A2A_CAPACITY_HEADROOM
) -> int:
    """Skew-derived per-(src, dst) a2a capacity for a per-shard batch chunk
    of ``chunk`` rows: the uniform share ``ceil(chunk / n_shards)`` times
    :data:`A2A_CAPACITY_HEADROOM`, clamped to ``chunk`` (which can never
    overflow).  Used by :func:`shard_apply_ops_safe` when the config leaves
    ``capacity`` unset — its doubling retry makes an underestimate cost one
    replay, never correctness."""
    chunk = max(1, int(chunk))
    if n_shards <= 1:
        return chunk
    share = math.ceil(chunk / n_shards)
    return max(1, min(chunk, math.ceil(share * headroom)))


def _inner_config(cfg: ExecConfig, impl: str) -> ExecConfig:
    """The ExecConfig handed to the per-shard inner ``apply_ops``: resolved
    impl, the kernel-tuning knobs threaded through, and the tiny
    ``_INNER_MR`` range budget (the inner dense arrays are ignored — the
    cross-shard phase answers RANGE).  Normalized so the lru-cached builders
    key on exactly the fields that matter."""
    return ExecConfig(
        impl=impl,
        pipeline=cfg.pipeline,
        block_q=cfg.block_q,
        block_b=cfg.block_b,
        tile_table=cfg.tile_table,
        max_results=_INNER_MR,
    )


def shard_apply_ops(
    idx: ShardedFliX,
    ops: OpBatch,
    mesh,
    *,
    config: ExecConfig | None = None,
    has_updates: bool | None = None,
    has_ranges: bool | None = None,
    now=None,
    routing=_UNSET,
    impl=_UNSET,
    max_results=_UNSET,
    donate=_UNSET,
    capacity=_UNSET,
):
    """Execute one mixed sorted batch across the mesh.

    Execution strategy comes in as one ``config=ExecConfig(...)``
    (``routing`` / ``impl`` / ``max_results`` / ``donate`` / ``capacity``
    plus the fused-kernel pipeline and tile knobs threaded to the per-shard
    ``apply_ops``); the trailing keywords are deprecated warn-once shims.
    Per-call facts (``has_updates`` / ``has_ranges`` hints, the TTL clock
    ``now``) stay keywords — they describe the batch, not the strategy.

    Returns ``(idx', results, stats)`` with the single-device ``apply_ops``
    contract (DESIGN.md §11):

    * ``routing="replicated"`` — ``ops`` is one global sorted batch (any
      placement; it is broadcast).  ``results`` is replicated and aligned
      with the sorted batch, byte-identical to ``apply_ops`` on the
      union state.
    * ``routing="a2a"`` — ``ops`` is position-sharded over the mesh axis
      (:func:`shard_batch`), each shard's chunk key-sorted.  ``value`` /
      ``succ_key`` / ``range_start`` / ``range_count`` come back sharded,
      aligned with each shard's input rows; the dense ``range_key`` /
      ``range_val`` arrays and ``stats`` are replicated.  ``capacity``
      bounds rows per (source, destination) pair (default: chunk size,
      which can never overflow); exceeding it is *not* an error — dropped
      rows are counted in ``stats["a2a_overflow"]`` and the caller replays
      the batch on the same (unmutated) ``idx`` with a larger capacity.

    On bucket overflow the returned state carries ``needs_restructure`` —
    hosts use :func:`shard_apply_ops_safe`, whose retry path regrows via
    :func:`shard_restructure`.
    """
    cfg = resolve_config(
        "shard_apply_ops",
        config,
        routing=routing,
        impl=impl,
        max_results=max_results,
        donate=donate,
        capacity=capacity,
    )
    fn, args, kwargs = shard_executor(
        idx, ops, mesh, cfg=cfg, has_updates=has_updates, has_ranges=has_ranges, now=now
    )
    with span("dispatch.sharded"):
        new_state, results, stats = fn(*args, **kwargs)
    return idx._replace(state=new_state), results, stats


def shard_executor(
    idx: ShardedFliX,
    ops: OpBatch,
    mesh,
    *,
    cfg: ExecConfig,
    has_updates: bool | None = None,
    has_ranges: bool | None = None,
    now=None,
):
    """The jitted sharded executor for one batch and its call:
    ``(fn, args, kwargs)``, the sharded analogue of
    ``core.ops.plain_executor``.  ``fn.lower(*args, **kwargs)`` compiles
    exactly what :func:`shard_apply_ops` runs, which calls this.  With
    ``cfg.impl`` not ``"auto"`` and ``has_ranges`` given, it reads nothing
    of the batch, so ``idx`` and ``ops`` may be ``jax.ShapeDtypeStruct``s."""
    routing = cfg.routing
    impl = resolve_impl(cfg.impl, ops, has_updates)
    max_results = cfg.max_results
    capacity = cfg.capacity
    if has_ranges is None:
        with span("sync.has_ranges"):
            has_ranges = bool(jnp.any(ops.tag == OP_RANGE))
    donate_r = cfg.donate and jax.default_backend() != "cpu"
    inner_cfg = _inner_config(cfg, impl)

    # TTL activation is structural, exactly as in single-device apply_ops: a
    # batch-side expiry column promotes the state (attaching an all-NO_EXPIRY
    # sharded column) so the shard_map pytree matches the TTL specs
    state = idx.state
    has_ttl = state.exps is not None or ops.exp is not None
    if has_ttl and state.exps is None:
        shard3 = NamedSharding(mesh, P(idx.axis, None, None))
        exps = jax.device_put(jnp.full(state.keys.shape, NO_EXPIRY, KEY_DTYPE), shard3)
        state = dataclasses.replace(state, exps=exps)
    has_now = has_ttl and now is not None
    extra = ()
    if has_ttl:
        exp_col = (
            ops.exp
            if ops.exp is not None
            else jnp.full((ops.size,), NO_EXPIRY, KEY_DTYPE)
        )
        extra = (exp_col,)
        if has_now:
            extra += (jnp.asarray(now, KEY_DTYPE),)

    if routing == "replicated":
        fn = _build_replicated(
            mesh, idx.axis, inner_cfg, max_results, has_ranges, donate_r, has_ttl, has_now
        )
        return fn, (state, idx.lower_fence, ops.tag, ops.key, ops.val, *extra), {}
    n_shards = int(mesh.shape[idx.axis])
    size = ops.tag.shape[0]
    if size % n_shards:
        raise ValueError(f"a2a batch size {size} not divisible by {n_shards} shards")
    if capacity is None:
        capacity = size // n_shards
    fn = _build_a2a(
        mesh,
        idx.axis,
        inner_cfg,
        max_results,
        has_ranges,
        capacity,
        donate_r,
        has_ttl,
        has_now,
    )
    return fn, (state, idx.part_fences, ops.tag, ops.key, ops.val, *extra), {}


def shard_apply_ops_safe(
    idx: ShardedFliX,
    ops: OpBatch,
    mesh,
    *,
    config: ExecConfig | None = None,
    has_updates: bool | None = None,
    has_ranges: bool | None = None,
    now=None,
    routing=_UNSET,
    impl=_UNSET,
    max_results=_UNSET,
    capacity=_UNSET,
):
    """Host-level driver: apply, restructure-and-retry on bucket overflow.

    Mirrors ``apply_ops_safe`` one level up: the retry replays the *whole*
    batch on a rebalanced (``shard_restructure``-grown) pre-batch index,
    which is safe because :func:`shard_apply_ops` never mutates its input
    (and is also why this driver never donates).  ``has_updates`` /
    ``has_ranges`` let drivers that already know the batch composition
    host-side skip the device syncs (``serve/kv_index.py`` does).
    Execution strategy comes in as one ``config=ExecConfig(...)``; the
    trailing keywords are deprecated warn-once shims.

    Under ``routing="a2a"``, per-pair overflow
    (``stats["a2a_overflow"] > 0``) is ALSO retried here — the documented
    re-route-with-larger-capacity replay, safe for the same
    no-input-mutation reason — doubling the capacity each round up to the
    chunk size, which can never overflow.  When the config leaves
    ``capacity`` unset, the starting point is the skew-derived
    :func:`default_a2a_capacity` rather than the worst-case chunk: ~n_shards
    times less a2a traffic on typical batches, with at most a couple of
    doubling replays on pathological skew.

    The returned ``stats`` surfaces the whole driver run (host ints, so
    the gateway and bench artifact can report them without device syncs):

    * ``restructure_retries``   — bucket-overflow replays on a regrown index;
    * ``a2a_retries``           — capacity re-route replays;
    * ``a2a_overflow_dropped``  — total rows dropped across the retried
      attempts (the final attempt's own ``a2a_overflow`` stays 0 on
      success — this counter is how the retries remain visible).
    """
    with span("shard_apply_ops_safe"):
        cfg = resolve_config(
            "shard_apply_ops_safe",
            config,
            routing=routing,
            impl=impl,
            max_results=max_results,
            capacity=capacity,
        )
        cap = cfg.capacity
        if cfg.routing == "a2a" and cap is None:
            cap = default_a2a_capacity(
                ops.size // int(mesh.shape[idx.axis]), int(mesh.shape[idx.axis])
            )
        # replays need the pre-batch buffers: never donate
        run_cfg = cfg.replace(donate=False, capacity=cap)
        a2a_retries = 0
        a2a_dropped = 0
        while True:
            new_idx, results, stats = shard_apply_ops(
                idx,
                ops,
                mesh,
                config=run_cfg,
                has_updates=has_updates,
                has_ranges=has_ranges,
                now=now,
            )
            if cfg.routing != "a2a":
                break
            chunk = ops.size // int(mesh.shape[idx.axis])
            overflow = int(stats["a2a_overflow"])
            if overflow == 0 or run_cfg.capacity >= chunk:
                break
            a2a_retries += 1
            a2a_dropped += overflow
            run_cfg = run_cfg.replace(capacity=min(chunk, run_cfg.capacity * 2))
        # waits for the executor: the device's tail of the batch ends here
        with span("sync.needs_restructure"):
            overflowed = bool(new_idx.state.needs_restructure) and not bool(
                idx.state.needs_restructure
            )
        if overflowed:
            with span("restructure"):
                n_ins = int(jnp.sum((ops.tag == OP_INSERT) | (ops.tag == OP_EXPIRE)))
                grown = shard_restructure(idx, mesh, extra_keys=max(n_ins, 1))
                new_idx, results, stats = shard_apply_ops(
                    grown,
                    ops,
                    mesh,
                    config=run_cfg,
                    has_updates=has_updates,
                    has_ranges=has_ranges,
                    now=now,
                )
                assert not bool(new_idx.state.needs_restructure), (
                    "post-restructure overflow"
                )
        stats = dict(stats)
        stats["restructure_retries"] = int(overflowed)
        stats["a2a_retries"] = a2a_retries
        stats["a2a_overflow_dropped"] = a2a_dropped
        return new_idx, results, stats
