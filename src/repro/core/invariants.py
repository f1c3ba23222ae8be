"""Structural invariant checker for FliXState (I1–I6, see state.py).

Host-side (numpy) and O(total slots) — intended for tests and debugging,
not the hot path.  ``check_invariants`` raises ``AssertionError`` with the
first violated invariant; every mutating operation (build, insert, delete,
merge_underfull, restructure, apply_ops) must preserve I1–I5 whenever its
input satisfies them and no overflow was flagged.

I6 (expiry liveness, DESIGN.md §14) applies when the state carries an
expiry column: empty slots must hold ``NO_EXPIRY`` (reclaimed slots are
zeroed to the sentinel, so stale deadlines cannot leak back in), and —
when the caller supplies the engine-threaded ``now`` — no live row may
hold ``exp <= now``: every expired row must have been physically
reclaimed by the update pass, i.e. no read can ever observe one.
"""

from __future__ import annotations

import numpy as np

from repro.core.state import EMPTY, MAX_VALID, NOT_FOUND, FliXState


def check_invariants(st: FliXState, now: int | None = None) -> None:
    """Assert I1–I6 hold for ``st`` (see the state.py module docstring).

    ``now`` enables the liveness half of I6: it must be the same explicit
    virtual time the engine was last stepped with (the checker never reads
    the wall clock).
    """
    keys = np.asarray(st.keys)
    counts = np.asarray(st.node_count)
    nmax = np.asarray(st.node_max)
    nn = np.asarray(st.num_nodes)
    mkba = np.asarray(st.mkba)
    nb, npb, ns = keys.shape
    E = int(EMPTY)
    for b in range(nb):
        prev_max = None
        for j in range(npb):
            row = keys[b, j]
            c = counts[b, j]
            if j >= nn[b]:
                assert c == 0 and (row == E).all(), f"inactive slot {b},{j} dirty"
                continue
            assert c > 0, f"active empty node {b},{j}"
            valid = row[:c]
            assert (np.diff(valid) > 0).all(), f"I1 violated at {b},{j}"
            assert (row[c:] == E).all(), f"I1 padding violated at {b},{j}"
            assert nmax[b, j] == valid[-1], f"I4 violated at {b},{j}"
            if prev_max is not None:
                assert valid[0] > prev_max, f"I2 violated at {b},{j}"
            prev_max = valid[-1]
            lf = mkba[b - 1] if b else np.iinfo(np.int32).min
            assert valid[0] > lf and valid[-1] <= mkba[b], f"I3 violated at {b}"
    assert (np.diff(mkba.astype(np.int64)) >= 0).all(), "I5 violated"
    assert mkba[-1] == int(MAX_VALID), "I5 violated: mkba[-1] != MAX_VALID"
    if st.exps is not None:
        from repro.core.expiry import NO_EXPIRY

        exps = np.asarray(st.exps)
        assert exps.shape == keys.shape, "I6 violated: expiry column shape"
        empty = keys == E
        assert (exps[empty] == int(NO_EXPIRY)).all(), (
            "I6 violated: reclaimed/empty slot carries a stale expiry deadline"
        )
        if now is not None:
            leaked = (~empty) & (exps <= int(now))
            assert not leaked.any(), (
                "I6 violated: live row(s) past their expiry deadline "
                f"(keys {keys[leaked][:8].tolist()} expired at "
                f"{exps[leaked][:8].tolist()} <= now={int(now)})"
            )


def check_tiered_invariants(tiered, now: int | None = None) -> None:
    """Assert I7 for a ``core.residency.TieredFliX`` (DESIGN.md §15).

    I7: every live row is reachable in **exactly one** tier — resident
    buckets are authoritative on device, all others in the host mirror, and
    the assembled full view satisfies I1–I6; the device-tier footprint is
    within the budget after commit (one bucket is always allowed: a smaller
    budget cannot execute any op).  Additionally pins the residency
    bookkeeping the engine's correctness argument relies on: sorted/unique
    resident ids, the packed fence array mirroring the full fences (except
    the forced ``MAX_VALID`` terminator), and fresh per-bucket metadata.
    """
    nb = tiered.num_buckets
    ids = np.asarray(tiered.resident_ids)
    assert (np.diff(ids) > 0).all() if len(ids) > 1 else True, (
        "I7: resident_ids not sorted/unique"
    )
    if len(ids):
        assert ids[0] >= 0 and ids[-1] < nb, "I7: resident id out of range"
    packed = tiered._packed
    if packed is None:
        assert len(ids) == 0, "I7: resident ids without a packed state"
    else:
        assert packed.num_buckets == len(ids), (
            "I7: packed bucket count != resident id count"
        )
        pm = np.asarray(packed.mkba)
        assert (pm[:-1] == np.asarray(tiered.h_mkba)[ids[:-1]]).all(), (
            "I7: packed fences diverge from the full fence array"
        )
        assert pm[-1] == int(MAX_VALID), "I7: packed mkba not MAX_VALID-terminated"
    if tiered.budget_bytes is not None:
        cap = max(int(tiered.budget_bytes), tiered.bucket_bytes)
        assert tiered.memory_bytes_resident() <= cap, (
            f"I7: resident bytes {tiered.memory_bytes_resident()} > budget {cap}"
        )
    # exactly-one-tier: assemble the authoritative full view and check I1–I6
    view = tiered.host_view()  # sync() makes the mirror authoritative
    check_invariants(view, now=now)
    # metadata freshness (the prefetch pre-pass trusts these unconditionally)
    live = np.asarray(view.node_count).sum(axis=1)
    assert (live == np.asarray(tiered.h_live)).all(), "I7: stale live metadata"
    if view.exps is None:
        from repro.core.expiry import NO_EXPIRY

        assert (np.asarray(tiered.h_min_exp) == int(NO_EXPIRY)).all(), (
            "I7: min-expiry metadata without an expiry column"
        )
    else:
        from repro.core.expiry import NO_EXPIRY

        me = np.where(
            np.asarray(view.keys) != int(EMPTY), np.asarray(view.exps), int(NO_EXPIRY)
        ).min(axis=(1, 2))
        assert (me == np.asarray(tiered.h_min_exp)).all(), (
            "I7: stale min-expiry metadata"
        )


def check_range_results(ops, results, *, max_results: int) -> None:
    """Structural checks on a batch's dense RANGE output (DESIGN.md §10).

    For every RANGE op in the sorted batch: its segment of the dense arrays
    is strictly ascending (hence duplicate-free), every key lies inside the
    op's ``[lo, hi)``, segments are packed consecutively from offset 0 in
    batch order, and slots beyond the emitted total hold EMPTY / NOT_FOUND.
    Differential tests pin the *values*; this checker is the cheap
    post-apply sanity used by
    ``apply_ops_safe(config=ExecConfig(validate_ranges=True))``.
    """
    from repro.core.ops import OP_RANGE

    tag = np.asarray(ops.tag)
    lo = np.asarray(ops.key)
    hi = np.asarray(ops.val)
    keys = np.asarray(results["range_key"])
    vals = np.asarray(results["range_val"])
    start = np.asarray(results["range_start"])
    count = np.asarray(results["range_count"])
    assert keys.shape == (max_results,) and vals.shape == (max_results,)

    is_range = tag == OP_RANGE
    assert (start[~is_range] == 0).all(), "non-RANGE op with a range offset"
    assert (count[~is_range] == 0).all(), "non-RANGE op with range results"

    cursor = 0
    for i in np.nonzero(is_range)[0]:
        c = int(count[i])
        assert 0 <= c <= max_results, f"op {i}: count {c} out of budget"
        assert start[i] == cursor, (
            f"op {i}: segment start {start[i]} != packed cursor {cursor}"
        )
        seg = keys[cursor : cursor + c].astype(np.int64)
        assert (np.diff(seg) > 0).all(), f"op {i}: segment not strictly ascending"
        assert ((seg >= int(lo[i])) & (seg < int(hi[i]))).all(), (
            f"op {i}: key outside [{lo[i]}, {hi[i]})"
        )
        cursor += c
    assert (keys[cursor:] == int(EMPTY)).all(), "dirty keys beyond emitted total"
    assert (vals[cursor:] == int(NOT_FOUND)).all(), "dirty vals beyond emitted total"
