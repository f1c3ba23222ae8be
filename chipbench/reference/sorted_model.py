"""The store's semantics on two sorted numpy arrays — the plain reference
of the ``paper-store-*`` configurations.

It imports nothing of the program.  One batch is update-then-read, exactly
as the configuration's guarantees state: INSERT (upsert, the batch's value
wins) and DELETE apply first, then POINT, SUCCESSOR and RANGE read the
result.  Op tags and sentinels are the public wire values of the index.
"""

from __future__ import annotations

import numpy as np

EMPTY = np.int32(np.iinfo(np.int32).max)   # successor miss / empty range slot
MISS = np.int32(-1)                         # value miss
OP_INSERT, OP_DELETE, OP_POINT, OP_SUCCESSOR, OP_NOP, OP_RANGE = 0, 1, 2, 3, 4, 5

# the per-op result fields and the dense RANGE output of one batch
PER_OP = ("value", "succ_key", "range_start", "range_count")
DENSE = ("range_key", "range_val")


class SortedModel:
    """Live pairs as ascending ``k`` with values ``v``."""

    def __init__(self, keys, vals):
        order = np.argsort(np.asarray(keys), kind="stable")
        self.k = np.asarray(keys, np.int32)[order]
        self.v = np.asarray(vals, np.int32)[order]

    def _find(self, q):
        pos = np.searchsorted(self.k, q)
        hit = pos < self.k.size
        hit[hit] = self.k[pos[hit]] == q[hit]
        return pos, hit

    def apply(self, tag, key, val, *, max_results: int) -> dict:
        """Apply one batch given in submission order; return every result
        in submission order (the dense RANGE arrays in the batch's key
        order, as the index packs them)."""
        tag = np.asarray(tag, np.int32)
        key = np.asarray(key, np.int32)
        val = np.asarray(val, np.int32)
        n = key.size

        put = tag == OP_INSERT
        if put.any():
            pk, pv = key[put], val[put]
            pos, hit = self._find(pk)
            self.v[pos[hit]] = pv[hit]
            order = np.argsort(pk[~hit], kind="stable")
            nk, nv = pk[~hit][order], pv[~hit][order]
            at = np.searchsorted(self.k, nk)
            self.k, self.v = np.insert(self.k, at, nk), np.insert(self.v, at, nv)
        dels = key[tag == OP_DELETE]
        if dels.size:
            pos, hit = self._find(dels)
            keep = np.ones(self.k.size, bool)
            keep[pos[hit]] = False
            self.k, self.v = self.k[keep], self.v[keep]

        value = np.full(n, MISS, np.int32)
        succ_key = np.full(n, EMPTY, np.int32)
        is_p = tag == OP_POINT
        pos, hit = self._find(key[is_p])
        last = max(self.k.size - 1, 0)
        value[is_p] = np.where(hit, self.v[np.minimum(pos, last)], MISS)
        is_s = tag == OP_SUCCESSOR
        pos = np.searchsorted(self.k, key[is_s])
        found = pos < self.k.size
        succ_key[is_s] = np.where(found, self.k[np.minimum(pos, last)], EMPTY)
        value[is_s] = np.where(found, self.v[np.minimum(pos, last)], MISS)

        # RANGE [lo, hi): in the batch's stable key order, earlier ops win
        # the max_results budget and each keeps a prefix of its keys
        rk = np.full(max_results, EMPTY, np.int32)
        rv = np.full(max_results, MISS, np.int32)
        range_start = np.zeros(n, np.int32)
        range_count = np.zeros(n, np.int32)
        if (tag == OP_RANGE).any():
            by_key = np.argsort(key, kind="stable")
            is_r = (tag == OP_RANGE)[by_key]
            lo = np.searchsorted(self.k, key[by_key])
            hi = np.searchsorted(self.k, val[by_key])
            full = np.where(is_r, np.maximum(hi - lo, 0), 0).astype(np.int64)
            start = np.minimum(np.cumsum(full) - full, max_results)
            emit = np.minimum(full, max_results - start)
            for i in np.flatnonzero(is_r & (emit > 0)):
                s, c = int(start[i]), int(emit[i])
                rk[s : s + c] = self.k[lo[i] : lo[i] + c]
                rv[s : s + c] = self.v[lo[i] : lo[i] + c]
            range_start[by_key] = np.where(is_r, start, 0)
            range_count[by_key] = np.where(is_r, emit, 0)
        return {
            "value": value,
            "succ_key": succ_key,
            "range_start": range_start,
            "range_count": range_count,
            "range_key": rk,
            "range_val": rv,
        }


def result_mismatches(got: dict, want: dict) -> int:
    """Ops whose per-op results differ, plus dense RANGE slots that differ.
    A missing or misshapen field counts every entry it should hold."""
    bad = np.zeros(want["value"].size, bool)
    for name in PER_OP:
        g = np.asarray(got.get(name, ()))
        if g.shape != want[name].shape:
            return int(want["value"].size + want["range_key"].size)
        bad |= g != want[name]
    n = int(bad.sum())
    for name in DENSE:
        g = np.asarray(got.get(name, ()))
        if g.shape != want[name].shape:
            return n + int(want[name].size)
        n += int((g != want[name]).sum())
    return n


def live_pair_mismatches(keys, vals, model: SortedModel) -> int:
    """Pairs in one set and not the other, counted by key, plus keys in
    both with different values.  ``keys``/``vals`` are the index's live
    pairs in any order; a key held twice counts as a mismatch."""
    keys = np.asarray(keys, np.int32)
    vals = np.asarray(vals, np.int32)
    order = np.argsort(keys, kind="stable")
    keys, vals = keys[order], vals[order]
    dup = int((keys[1:] == keys[:-1]).sum()) if keys.size else 0
    common, gi, wi = np.intersect1d(keys, model.k, assume_unique=False, return_indices=True)
    only = (keys.size - common.size) + (model.k.size - common.size)
    return int(only + dup + (vals[gi] != model.v[wi]).sum())
