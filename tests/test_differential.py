"""Differential parity: Pallas kernels vs jnp oracles, mixed vs per-type.

Three families of proofs:

  1. Every Pallas kernel (flix_query, flix_insert, flix_delete,
     flix_successor) matches its jnp oracle bit-for-bit in interpret mode on
     *adversarial* batches — duplicate queries, all-miss batches, boundary
     keys (0 and MAX_VALID), and states with emptied buckets and multi-node
     chains.
  2. ``apply_ops`` on a mixed batch is byte-identical — state arrays and
     per-op results — to sequential per-type application of the present op
     classes (insert → delete → point → successor on sorted sub-batches).
  3. The fused compute-to-bucket apply kernel (``kernels/flix_apply``,
     ``apply_ops(impl="fused")``) matches the reference engine on the same
     adversarial batches across every op-mix ratio — RANGE included, from
     single-class extremes to the fig-style 90/10 read/update shape — with
     byte-identical dense range output, and a RANGE in a mixed batch
     observes that batch's inserts and deletes (update-then-read), incl.
     overflow + restructure retries (live-position vals, like the
     per-kernel proofs: vals at EMPTY slots are unspecified for the jnp
     merge).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core.invariants import check_invariants
from repro.core.state import EMPTY, MAX_VALID, NOT_FOUND
from repro.kernels import ref
from repro.core.query import locate_stored
from repro.kernels.flix_apply import _surviving_min
from repro.kernels.flix_delete import flix_delete_pallas
from repro.kernels.flix_insert import flix_insert_pallas
from repro.kernels.flix_query import flix_point_query_pallas
from repro.kernels.flix_successor import flix_successor_pallas
from repro.core.config import ExecConfig

STATE_FIELDS = ("keys", "vals", "node_count", "node_max", "num_nodes", "mkba")


def _assert_states_identical(a: core.FliXState, b: core.FliXState):
    for f in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), err_msg=f
        )
    assert bool(a.needs_restructure) == bool(b.needs_restructure)


@pytest.fixture
def adversarial(rng):
    """A state with boundary keys, multi-node chains, and emptied buckets."""
    keys = rng.choice(120000, size=2500, replace=False).astype(np.int32)
    keys = np.unique(np.concatenate([keys, [0, int(MAX_VALID)]])).astype(np.int32)
    st = core.build(
        keys, np.arange(len(keys), dtype=np.int32), node_size=8, nodes_per_bucket=8
    )
    # grow chains so several buckets hold multiple nodes
    extra = np.setdiff1d(
        rng.choice(120000, 5000).astype(np.int32), keys
    )[:1500]
    sk, sv = core.sort_batch(
        jnp.asarray(extra), jnp.asarray(np.arange(1500, dtype=np.int32))
    )
    st, _ = core.insert_safe(st, sk, sv)
    # empty out a key range spanning whole buckets
    st, _ = core.delete(st, jnp.asarray(np.arange(30000, 60000, dtype=np.int32)))
    check_invariants(st)
    live = np.unique(np.concatenate([keys, extra]))
    live = live[(live < 30000) | (live >= 60000)].astype(np.int32)
    return st, live


def _adversarial_query_batches(rng, live):
    absent = np.setdiff1d(
        np.arange(0, 130000, 7, dtype=np.int32), live
    )
    return {
        "duplicates": np.sort(np.repeat(rng.choice(live, 40), 8)).astype(np.int32),
        "all_miss": np.sort(rng.choice(absent, 300)).astype(np.int32),
        "boundary": np.array(
            [0, 0, 1, int(MAX_VALID) - 1, int(MAX_VALID), int(MAX_VALID)], np.int32
        ),
        "empty_buckets": np.arange(29000, 61000, 50, dtype=np.int32),
        "mixed": np.sort(
            np.concatenate([rng.choice(live, 200), rng.choice(absent, 200)])
        ).astype(np.int32),
    }


def test_point_query_kernel_adversarial(adversarial, rng):
    st, live = adversarial
    for name, q in _adversarial_query_batches(rng, live).items():
        qj = jnp.asarray(q)
        want = ref.flix_point_query_ref(st.keys, st.vals, st.node_max, st.mkba, qj)
        got = flix_point_query_pallas(
            st.keys, st.vals, st.node_max, st.mkba, qj, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got), err_msg=name)
        # oracle itself agrees with the core form
        np.testing.assert_array_equal(
            np.asarray(want), np.asarray(core.point_query(st, qj)), err_msg=name
        )


def test_successor_kernel_adversarial(adversarial, rng):
    st, live = adversarial
    for name, q in _adversarial_query_batches(rng, live).items():
        qj = jnp.asarray(q)
        wk, wv = ref.flix_successor_ref(st.keys, st.vals, st.node_max, st.mkba, qj)
        gk, gv = flix_successor_pallas(
            st.keys, st.vals, st.node_max, st.mkba, qj, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(wk), np.asarray(gk), err_msg=name)
        np.testing.assert_array_equal(np.asarray(wv), np.asarray(gv), err_msg=name)
        ck, cv = core.successor_query(st, qj)
        np.testing.assert_array_equal(np.asarray(wk), np.asarray(ck), err_msg=name)
        np.testing.assert_array_equal(np.asarray(wv), np.asarray(cv), err_msg=name)


def test_insert_kernel_adversarial(adversarial, rng):
    st, live = adversarial
    absent = np.setdiff1d(np.arange(0, 130000, 11, dtype=np.int32), live)
    batches = {
        # upserts of stored keys mixed with fresh keys, incl. boundary keys
        "upsert_mix": np.concatenate(
            [rng.choice(live, 150, replace=False), absent[:150], [0, int(MAX_VALID)]]
        ),
        # aimed at the emptied bucket range
        "empty_buckets": np.arange(31000, 59000, 120, dtype=np.int32),
    }
    for name, b in batches.items():
        b = np.unique(b).astype(np.int32)
        v = np.arange(len(b), dtype=np.int32) + 7_000_000
        sk, sv = core.sort_batch(jnp.asarray(b), jnp.asarray(v))
        want, _ = core.insert(st, sk, sv)
        got, _ = flix_insert_pallas(st, sk, sv, interpret=True)
        # vals at EMPTY slots are unspecified for the jnp merge (garbage from
        # the re-sort) — compare live positions exactly, like test_kernels
        for f in ("keys", "node_count", "node_max", "num_nodes", "mkba"):
            np.testing.assert_array_equal(
                np.asarray(getattr(want, f)), np.asarray(getattr(got, f)), err_msg=name
            )
        mask = np.asarray(want.keys) != int(EMPTY)
        np.testing.assert_array_equal(
            np.asarray(want.vals)[mask], np.asarray(got.vals)[mask], err_msg=name
        )
        assert bool(want.needs_restructure) == bool(got.needs_restructure)


def test_delete_kernel_adversarial(adversarial, rng):
    st, live = adversarial
    absent = np.setdiff1d(np.arange(0, 130000, 13, dtype=np.int32), live)
    batches = {
        "all_miss": np.sort(absent[:400]),
        "duplicates": np.sort(np.repeat(rng.choice(live, 60, replace=False), 5)),
        "boundary": np.array([0, int(MAX_VALID)], np.int32),
        "skewed_range": np.arange(60000, 90000, dtype=np.int32),
    }
    for name, b in batches.items():
        bj = jnp.asarray(b.astype(np.int32))
        want, _ = core.delete(st, bj)
        got = flix_delete_pallas(st, bj, interpret=True)
        # vals at freed slots are unspecified (jnp keeps garbage, the kernel
        # zeroes) — compare live positions exactly
        for f in ("keys", "node_count", "node_max", "num_nodes", "mkba"):
            np.testing.assert_array_equal(
                np.asarray(getattr(want, f)), np.asarray(getattr(got, f)), err_msg=name
            )
        mask = np.asarray(want.keys) != int(EMPTY)
        np.testing.assert_array_equal(
            np.asarray(want.vals)[mask], np.asarray(got.vals)[mask], err_msg=name
        )
        check_invariants(got)


# ---------------------------------------------------------------------------
# apply_ops: mixed == sequential per-type, byte-identical
# ---------------------------------------------------------------------------


def _sequential(state, tags, keys, vals):
    """Reference semantics: apply present op classes in engine order."""
    s = state
    ins = tags == core.OP_INSERT
    if ins.any():
        sk, sv = core.sort_batch(jnp.asarray(keys[ins]), jnp.asarray(vals[ins]))
        s, _ = core.insert(s, sk, sv)
    dels = tags == core.OP_DELETE
    if dels.any():
        s, _ = core.delete(s, jnp.asarray(np.sort(keys[dels])))
    points = np.sort(keys[tags == core.OP_POINT])
    pv = core.point_query(s, jnp.asarray(points)) if points.size else None
    succs = np.sort(keys[tags == core.OP_SUCCESSOR])
    sk_sv = core.successor_query(s, jnp.asarray(succs)) if succs.size else None
    return s, (points, pv), (succs, sk_sv)


def _compare_mixed_vs_sequential(st, tags, keys, vals, *, pad_to=None):
    ops, perm = core.make_ops(tags, keys, vals, pad_to=pad_to)
    s_mixed, res, _ = core.apply_ops(st, ops)
    s_seq, (points, pv), (succs, ssv) = _sequential(st, tags, keys, vals)
    _assert_states_identical(s_mixed, s_seq)

    # results: gather mixed results back to submission order and compare
    # against the sorted per-type query answers
    val_in = np.asarray(core.unsort(res["value"], perm))[: len(keys)]
    key_in = np.asarray(core.unsort(res["succ_key"], perm))[: len(keys)]
    if pv is not None:
        mine = np.sort(val_in[tags == core.OP_POINT])
        np.testing.assert_array_equal(mine, np.sort(np.asarray(pv)))
    if ssv is not None:
        order = np.argsort(keys[tags == core.OP_SUCCESSOR], kind="stable")
        np.testing.assert_array_equal(
            key_in[tags == core.OP_SUCCESSOR][order], np.asarray(ssv[0])
        )
        np.testing.assert_array_equal(
            val_in[tags == core.OP_SUCCESSOR][order], np.asarray(ssv[1])
        )
    # non-read ops report no results
    upd = (tags == core.OP_INSERT) | (tags == core.OP_DELETE)
    assert (val_in[upd] == int(NOT_FOUND)).all()
    assert (key_in[upd] == int(EMPTY)).all()


def test_apply_ops_matches_sequential_full_mix(adversarial, rng):
    st, live = adversarial
    absent = np.setdiff1d(np.arange(0, 130000, 3, dtype=np.int32), live)
    ins = rng.choice(absent, 300, replace=False).astype(np.int32)
    iv = rng.integers(0, 1 << 30, 300).astype(np.int32)
    dels = rng.choice(live, 250, replace=False).astype(np.int32)
    reads = rng.integers(0, 130000, 500).astype(np.int32)
    tags = np.concatenate([
        np.full(300, core.OP_INSERT), np.full(250, core.OP_DELETE),
        np.full(250, core.OP_POINT), np.full(250, core.OP_SUCCESSOR),
    ]).astype(np.int32)
    keys = np.concatenate([ins, dels, reads]).astype(np.int32)
    vals = np.concatenate([iv, np.zeros(750, np.int32)])
    _compare_mixed_vs_sequential(st, tags, keys, vals, pad_to=2048)


@pytest.mark.parametrize(
    "present",
    [
        (core.OP_INSERT,),
        (core.OP_DELETE,),
        (core.OP_POINT,),
        (core.OP_SUCCESSOR,),
        (core.OP_INSERT, core.OP_POINT),
        (core.OP_DELETE, core.OP_SUCCESSOR),
        (core.OP_POINT, core.OP_SUCCESSOR),
    ],
)
def test_apply_ops_partial_mixes(adversarial, rng, present):
    """Absent op classes are skipped — state must match exactly, including
    the lax.cond fast paths (no insert / no delete)."""
    st, live = adversarial
    absent_keys = np.setdiff1d(np.arange(0, 130000, 5, dtype=np.int32), live)
    chunks = {"tags": [], "keys": [], "vals": []}
    pools = {
        core.OP_INSERT: rng.choice(absent_keys, 120, replace=False),
        core.OP_DELETE: rng.choice(live, 120, replace=False),
        core.OP_POINT: rng.integers(0, 130000, 120),
        core.OP_SUCCESSOR: rng.integers(0, 130000, 120),
    }
    for t in present:
        k = pools[t].astype(np.int32)
        chunks["tags"].append(np.full(len(k), t, np.int32))
        chunks["keys"].append(k)
        chunks["vals"].append(
            np.arange(len(k), dtype=np.int32) if t == core.OP_INSERT
            else np.zeros(len(k), np.int32)
        )
    tags = np.concatenate(chunks["tags"])
    keys = np.concatenate(chunks["keys"])
    vals = np.concatenate(chunks["vals"])
    _compare_mixed_vs_sequential(st, tags, keys, vals, pad_to=512)


# ---------------------------------------------------------------------------
# fused apply kernel: apply_ops(impl="fused") == apply_ops(impl="reference")
# ---------------------------------------------------------------------------


def _assert_fused_matches_reference(
    st, tags, keys, vals, *, pad_to, max_results=128, pipeline="auto", now=None
):
    """Returns ``(ops, reference results, fused stats, fused state)``."""
    ops, _ = core.make_ops(tags, keys, vals, pad_to=pad_to)
    s_ref, r_ref, stats_ref = core.apply_ops(
        st, ops, now=now, config=ExecConfig(impl="reference", max_results=max_results)
    )
    s_f, r_f, stats_f = core.apply_ops(
        st,
        ops,
        now=now,
        config=ExecConfig(impl="fused", max_results=max_results, pipeline=pipeline),
    )
    fields = ("keys", "node_count", "node_max", "num_nodes", "mkba")
    for f in fields + (("exps",) if s_ref.exps is not None else ()):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_ref, f)), np.asarray(getattr(s_f, f)), err_msg=f
        )
    mask = np.asarray(s_ref.keys) != int(EMPTY)
    np.testing.assert_array_equal(
        np.asarray(s_ref.vals)[mask], np.asarray(s_f.vals)[mask]
    )
    assert bool(s_ref.needs_restructure) == bool(s_f.needs_restructure)
    for k in ("value", "succ_key", "range_key", "range_val",
              "range_start", "range_count"):
        np.testing.assert_array_equal(
            np.asarray(r_ref[k]), np.asarray(r_f[k]), err_msg=k
        )
    for k in stats_ref:
        assert int(stats_ref[k]) == int(stats_f[k]), k
    if not bool(s_f.needs_restructure):
        check_invariants(s_f, now=now)
        core.check_range_results(ops, r_f, max_results=max_results)
    return ops, r_ref, stats_f, s_f


@pytest.mark.parametrize(
    "present",
    [
        (core.OP_INSERT,),
        (core.OP_DELETE,),
        (core.OP_POINT,),
        (core.OP_SUCCESSOR,),
        (core.OP_RANGE,),
        (core.OP_INSERT, core.OP_POINT),
        (core.OP_DELETE, core.OP_SUCCESSOR),
        (core.OP_POINT, core.OP_SUCCESSOR),
        (core.OP_INSERT, core.OP_RANGE),
        (core.OP_DELETE, core.OP_RANGE),
        (core.OP_RANGE, core.OP_SUCCESSOR),
    ],
)
def test_fused_apply_partial_mixes(adversarial, rng, present):
    """Every op-mix ratio, including the single-class extremes — the fused
    kernel has no per-phase skip conds, so absent classes must fall out of
    the math (empty tiles merge/delete to identity)."""
    st, live = adversarial
    absent_keys = np.setdiff1d(np.arange(0, 130000, 5, dtype=np.int32), live)
    pools = {
        core.OP_INSERT: rng.choice(absent_keys, 120, replace=False),
        core.OP_DELETE: rng.choice(live, 120, replace=False),
        core.OP_POINT: rng.integers(0, 130000, 120),
        core.OP_SUCCESSOR: rng.integers(0, 130000, 120),
        core.OP_RANGE: np.sort(rng.integers(0, 125000, 40)),
    }
    tags, keys, vals = [], [], []
    for t in present:
        k = pools[t].astype(np.int32)
        tags.append(np.full(len(k), t, np.int32))
        keys.append(k)
        if t == core.OP_INSERT:
            vals.append(np.arange(len(k), dtype=np.int32) + 3_000_000)
        elif t == core.OP_RANGE:
            vals.append((k + rng.integers(0, 2000, len(k))).astype(np.int32))
        else:
            vals.append(np.zeros(len(k), np.int32))
    _assert_fused_matches_reference(
        st,
        np.concatenate(tags),
        np.concatenate(keys),
        np.concatenate(vals),
        pad_to=512,
        max_results=256,
    )


def test_fused_apply_full_mix_adversarial(adversarial, rng):
    """Full mix on the adversarial state: upserts of stored keys, deletions,
    duplicate + boundary + emptied-bucket reads, ranges spanning emptied and
    boundary regions, multi-window batch."""
    st, live = adversarial
    absent = np.setdiff1d(np.arange(0, 130000, 3, dtype=np.int32), live)
    ins = np.concatenate(
        [rng.choice(absent, 200, replace=False), rng.choice(live, 100, replace=False)]
    ).astype(np.int32)  # upserts included
    iv = rng.integers(0, 1 << 30, 300).astype(np.int32)
    dels = np.setdiff1d(rng.choice(live, 250, replace=False), ins).astype(np.int32)
    reads = np.concatenate([
        np.repeat(rng.choice(live, 30), 4),
        rng.choice(absent, 100),
        [0, int(MAX_VALID) - 1, int(MAX_VALID)],
        np.arange(29000, 61000, 250),
    ]).astype(np.int32)
    rlo = np.concatenate([
        rng.integers(0, 125000, 24),
        [0, 29500, int(MAX_VALID) - 5],        # boundary + emptied regions
    ]).astype(np.int32)
    rhi = np.concatenate([
        rlo[:24] + rng.integers(0, 3000, 24),
        [50, 60500, int(EMPTY)],
    ]).astype(np.int32)
    tags = np.concatenate([
        np.full(len(ins), core.OP_INSERT),
        np.full(len(dels), core.OP_DELETE),
        np.where(np.arange(len(reads)) % 2 == 0, core.OP_POINT, core.OP_SUCCESSOR),
        np.full(len(rlo), core.OP_RANGE),
    ]).astype(np.int32)
    keys = np.concatenate([ins, dels, reads, rlo]).astype(np.int32)
    vals = np.concatenate(
        [iv, np.zeros(len(dels) + len(reads), np.int32), rhi]
    )
    _assert_fused_matches_reference(
        st, tags, keys, vals, pad_to=2048, max_results=512
    )


def test_fused_apply_overflow_flag_and_state(rng):
    """An overflowing batch: the pre-retry states (untrustworthy buckets
    included) and the restructure flag agree between the two executors."""
    keys = np.arange(0, 640, 10, dtype=np.int32)
    st = core.build(keys, keys, node_size=4, nodes_per_bucket=2)
    flood = np.arange(1, 200, 2, dtype=np.int32)
    tags = np.concatenate([
        np.full(len(flood), core.OP_INSERT),
        np.full(len(keys), core.OP_POINT),
    ]).astype(np.int32)
    bkeys = np.concatenate([flood, keys]).astype(np.int32)
    bvals = np.concatenate([flood, np.zeros(len(keys), np.int32)])
    ops, _ = core.make_ops(tags, bkeys, bvals, pad_to=256)
    s_ref, _, stats_ref = core.apply_ops(st, ops, config=ExecConfig(impl="reference"))
    s_f, _, stats_f = core.apply_ops(st, ops, config=ExecConfig(impl="fused"))
    assert bool(s_ref.needs_restructure) and bool(s_f.needs_restructure)
    assert int(stats_ref["overflowed_buckets"]) == int(stats_f["overflowed_buckets"])
    for f in ("keys", "node_count", "node_max", "num_nodes", "mkba"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_ref, f)), np.asarray(getattr(s_f, f)), err_msg=f
        )


def test_fused_apply_range_heavy_90_10(adversarial, rng):
    """The fig-style 90/10 read/update shape with RANGE carrying the read
    side: 90% range+point reads, 10% updates — byte-identical executors."""
    st, live = adversarial
    absent = np.setdiff1d(np.arange(0, 130000, 3, dtype=np.int32), live)
    n = 400
    n_upd = n // 10
    ins = rng.choice(absent, n_upd // 2, replace=False).astype(np.int32)
    dels = rng.choice(live, n_upd - n_upd // 2, replace=False).astype(np.int32)
    n_read = n - n_upd
    n_rng = n_read // 2
    rlo = np.sort(rng.integers(0, 125000, n_rng)).astype(np.int32)
    rhi = (rlo + rng.integers(0, 1500, n_rng)).astype(np.int32)
    points = rng.integers(0, 130000, n_read - n_rng).astype(np.int32)
    tags = np.concatenate([
        np.full(len(ins), core.OP_INSERT),
        np.full(len(dels), core.OP_DELETE),
        np.full(n_rng, core.OP_RANGE),
        np.full(len(points), core.OP_POINT),
    ]).astype(np.int32)
    keys = np.concatenate([ins, dels, rlo, points]).astype(np.int32)
    vals = np.concatenate([
        np.arange(len(ins), dtype=np.int32) + 5_000_000,
        np.zeros(len(dels), np.int32),
        rhi,
        np.zeros(len(points), np.int32),
    ])
    _assert_fused_matches_reference(
        st, tags, keys, vals, pad_to=512, max_results=1024
    )


def test_range_observes_same_batch_updates(adversarial, rng):
    """Update-then-read inside one batch: a RANGE must see that batch's
    inserts and must not see its deletes — on both executors."""
    st, live = adversarial
    absent = np.setdiff1d(np.arange(70000, 90000, 3, dtype=np.int32), live)
    ins = rng.choice(absent, 40, replace=False).astype(np.int32)
    iv = (ins + 1_000_000).astype(np.int32)
    dels = live[(live >= 70000) & (live < 90000)][:40].astype(np.int32)
    # one range covering exactly the churned region, plus tight ranges
    # pinned on individual inserted and deleted keys
    rlo = np.concatenate([[70000], ins[:5], dels[:5]]).astype(np.int32)
    rhi = np.concatenate([[90000], ins[:5] + 1, dels[:5] + 1]).astype(np.int32)
    tags = np.concatenate([
        np.full(len(ins), core.OP_INSERT),
        np.full(len(dels), core.OP_DELETE),
        np.full(len(rlo), core.OP_RANGE),
    ]).astype(np.int32)
    keys = np.concatenate([ins, dels, rlo]).astype(np.int32)
    vals = np.concatenate([iv, np.zeros(len(dels), np.int32), rhi])
    ops, r_ref, _, _ = _assert_fused_matches_reference(
        st, tags, keys, vals, pad_to=512, max_results=2048
    )
    # model the post-update region contents
    region = set(
        live[(live >= 70000) & (live < 90000)].tolist()
    ) - set(dels.tolist()) | set(ins.tolist())
    t = np.asarray(ops.tag)
    kk, vv = np.asarray(ops.key), np.asarray(ops.val)
    rs = np.asarray(r_ref["range_start"])
    rc = np.asarray(r_ref["range_count"])
    dk = np.asarray(r_ref["range_key"])
    dv = np.asarray(r_ref["range_val"])
    val_of = dict(zip(ins.tolist(), iv.tolist()))
    for i in np.nonzero(t == core.OP_RANGE)[0]:
        seg = dk[rs[i] : rs[i] + rc[i]]
        expect = np.array(
            sorted(k for k in region if kk[i] <= k < vv[i]), np.int32
        )
        np.testing.assert_array_equal(seg, expect, err_msg=f"op {i}")
        for j in range(rc[i]):  # inserted keys carry this batch's values
            k = int(dk[rs[i] + j])
            if k in val_of:
                assert dv[rs[i] + j] == val_of[k]
        assert not set(seg.tolist()) & set(dels.tolist())


def test_apply_ops_safe_overflow_recovery(rng):
    """A flooding mixed batch triggers restructure-and-retry, after which the
    state answers every op of the batch correctly."""
    keys = np.arange(0, 640, 10, dtype=np.int32)
    st = core.build(keys, keys, node_size=4, nodes_per_bucket=2)
    flood = np.arange(1, 200, 2, dtype=np.int32)
    points = np.arange(0, 640, 10, dtype=np.int32)
    tags = np.concatenate([
        np.full(len(flood), core.OP_INSERT), np.full(len(points), core.OP_POINT)
    ]).astype(np.int32)
    ops, perm = core.make_ops(
        tags, np.concatenate([flood, points]),
        np.concatenate([flood, np.zeros(len(points), np.int32)]),
    )
    st2, res, stats = core.apply_ops_safe(st, ops)
    assert not bool(st2.needs_restructure)
    check_invariants(st2)
    res_in = np.asarray(core.unsort(res["value"], perm))
    np.testing.assert_array_equal(res_in[len(flood):], points)
    got = np.asarray(core.point_query(st2, jnp.asarray(np.sort(flood))))
    np.testing.assert_array_equal(got, np.sort(flood))

# ---------------------------------------------------------------------------
# fused kernel write-through: a bucket with no INSERT and no present DELETE
# skips the merge/delete phases and keeps its stripe
# ---------------------------------------------------------------------------


def _updated_buckets(st, tags, keys):
    """Buckets holding an INSERT, or a DELETE of a stored key, under the
    pre-batch fences — in numpy, apart from the executors."""
    mkba = np.asarray(st.mkba)
    stored = np.asarray(st.keys)
    stored = stored[stored != int(EMPTY)]
    upd = (tags == core.OP_INSERT) | ((tags == core.OP_DELETE) & np.isin(keys, stored))
    b = np.minimum(np.searchsorted(mkba, keys[upd], side="left"), mkba.size - 1)
    return np.unique(b)


def _state_of_kind(kind, rng):
    """``(state, live keys, now)``: a fresh build, or one after deletes,
    after a restructure, or after an expiry pass — each satisfying I1-I6."""
    keys = np.unique(rng.choice(120000, 3000, replace=False)).astype(np.int32)
    st = core.build(keys, keys + 11, node_size=8, nodes_per_bucket=8)
    now = None
    if kind in ("deleted", "restructured"):
        gone = np.concatenate([
            keys[(keys >= 40000) & (keys < 52000)],   # whole buckets emptied
            rng.choice(keys, 300, replace=False),
        ])
        st, _ = core.delete(st, jnp.asarray(np.unique(gone).astype(np.int32)))
        keys = np.setdiff1d(keys, gone).astype(np.int32)
    if kind == "restructured":
        st = core.restructure(st, num_buckets=st.num_buckets // 3)
    if kind == "expired":
        now = 100
        live = np.asarray(st.keys) != int(EMPTY)
        exps = np.where(live, rng.integers(0, 400, st.keys.shape), int(core.NO_EXPIRY))
        st, _ = core.expire_state(
            core.attach_expiry(st, jnp.asarray(exps.astype(np.int32))), now
        )
        keys = np.sort(np.asarray(st.keys)[np.asarray(st.keys) != int(EMPTY)])
    check_invariants(st, now=now)
    return st, keys, now


def _minority_batch(rng, live):
    """Updates in a few buckets (a present and an absent DELETE, fresh
    INSERTs, an upsert), reads and ranges spread over the whole table."""
    absent = np.setdiff1d(np.arange(0, 125000, 7, dtype=np.int32), live)
    ins = rng.choice(absent, 10, replace=False)
    ups = rng.choice(live, 3, replace=False)
    dels = rng.choice(np.setdiff1d(live, ups), 10, replace=False)
    gone = rng.choice(np.setdiff1d(absent, ins), 6, replace=False)
    reads = np.concatenate([rng.choice(live, 60), rng.integers(0, 125000, 40)])
    rlo = np.sort(rng.integers(0, 120000, 6))
    tags = np.concatenate([
        np.full(13, core.OP_INSERT), np.full(16, core.OP_DELETE),
        np.where(np.arange(100) % 3 == 0, core.OP_SUCCESSOR, core.OP_POINT),
        np.full(6, core.OP_RANGE),
    ]).astype(np.int32)
    keys = np.concatenate([ins, ups, dels, gone, reads, rlo]).astype(np.int32)
    vals = np.concatenate([
        np.arange(13) + 7_000_000, np.zeros(116), rlo + rng.integers(0, 900, 6)
    ]).astype(np.int32)
    return tags, keys, vals


@pytest.mark.parametrize("pipeline", ["on", "off"])
@pytest.mark.parametrize("kind", ["fresh", "deleted", "restructured", "expired"])
def test_fused_write_through_keeps_untouched_buckets(rng, kind, pipeline):
    """A batch that updates a minority of buckets: the fused executor equals
    the reference, and every bucket it does not update comes out with its
    keys, node counts, node maxima and node count byte-equal to the input
    and its values equal at every stored key (0 at EMPTY slots, the merge
    phase's canonical output, which deletes and expiry passes do not
    write)."""
    st, live, now = _state_of_kind(kind, rng)
    tags, keys, vals = _minority_batch(rng, live)
    updated = _updated_buckets(st, tags, keys)
    assert 0 < updated.size < st.num_buckets // 10
    _, _, stats, s_f = _assert_fused_matches_reference(
        st, tags, keys, vals, pad_to=256, max_results=512, pipeline=pipeline, now=now
    )
    assert int(stats["updated_buckets"]) == updated.size
    same = np.setdiff1d(np.arange(st.num_buckets), updated)
    for f in ("keys", "node_count", "node_max", "num_nodes"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_f, f))[same], np.asarray(getattr(st, f))[same],
            err_msg=f,
        )
    stored = np.asarray(st.keys)[same] != int(EMPTY)
    np.testing.assert_array_equal(
        np.asarray(s_f.vals)[same], np.where(stored, np.asarray(st.vals)[same], 0)
    )
    if st.exps is not None:
        np.testing.assert_array_equal(np.asarray(s_f.exps)[same], np.asarray(st.exps)[same])


@pytest.mark.parametrize("batch", ["minority", "read_only", "every_bucket"])
def test_fused_updated_buckets_counter(rng, batch):
    """``stats["updated_buckets"]`` counts the buckets that ran the merge
    and delete phases: those holding an INSERT or a present DELETE under
    the pre-batch fences — none for a read-only batch forced onto the
    fused executor, all of them when every bucket takes an INSERT."""
    st, live, _ = _state_of_kind("fresh", rng)
    if batch == "minority":
        tags, keys, vals = _minority_batch(rng, live)
    elif batch == "read_only":
        keys = np.sort(rng.choice(live, 300, replace=False)).astype(np.int32)
        tags = np.full(keys.size, core.OP_POINT, np.int32)
        vals = np.zeros(keys.size, np.int32)
    else:  # an upsert of its smallest key in every bucket
        keys = np.asarray(st.keys)[:, 0, 0].astype(np.int32)
        tags = np.full(keys.size, core.OP_INSERT, np.int32)
        vals = (keys + 5).astype(np.int32)
    want = _updated_buckets(st, tags, keys).size
    if batch != "minority":
        assert want == (0 if batch == "read_only" else st.num_buckets)
    ops, _ = core.make_ops(tags, keys, vals)
    _, _, stats = core.apply_ops(st, ops, config=ExecConfig(impl="fused"))
    assert int(stats["updated_buckets"]) == want


# ---------------------------------------------------------------------------
# post-update fence rows: each bucket's surviving minimum, from the slots of
# the batch's present deletes
# ---------------------------------------------------------------------------


def _stored_rows(st):
    """Each bucket's stored keys, ascending (I1-I2)."""
    k = np.asarray(st.keys).reshape(st.num_buckets, -1)
    return [r[r != int(EMPTY)] for r in k]


def _gapped_state(rng):
    """A fresh build less every bucket's largest key, so that a SUCCESSOR
    of ``mkba[b]`` finds nothing in bucket ``b`` and falls through to the
    post-update minimum of the next bucket that holds a key."""
    st, _, _ = _state_of_kind("fresh", rng)
    tops = np.array([r[-1] for r in _stored_rows(st)], np.int32)
    st, _ = core.delete(st, jnp.asarray(tops))
    check_invariants(st)
    return st, _stored_rows(st)


@pytest.mark.parametrize("pipeline", ["on", "off"])
@pytest.mark.parametrize(
    "case",
    [
        "bucket_min",
        "first_k",
        "whole_bucket",
        "min_under_smaller_insert",
        "absent",
        "adjacent",
        "min_valued_not_found",
    ],
)
def test_fused_fence_rows_under_deletes(rng, case, pipeline):
    """Deletes that change a bucket's minimum, then SUCCESSORs of fences,
    each falling through its bucket to the next one: the fused
    executor equals the reference (and the post-state keeps I1-I5).  An
    emptied bucket must be skipped; a smaller key inserted beside the
    deleted minimum must win; a stored key deletes whatever its value,
    ``NOT_FOUND`` included."""
    st, rows = _gapped_state(rng)
    nb = st.num_buckets
    mkba = np.asarray(st.mkba)
    b = next(
        c
        for c in range(nb // 3, nb - 3)
        if min(len(rows[c + i]) for i in range(3)) >= 3 and rows[c][0] > mkba[c - 1] + 1
    )
    ins = np.zeros(0, np.int32)
    if case == "bucket_min":
        dels = rows[b][:1]
    elif case == "first_k":
        dels = rows[b][:2]
    elif case == "whole_bucket":
        dels = rows[b]
    elif case == "min_under_smaller_insert":
        dels, ins = rows[b][:1], np.array([mkba[b - 1] + 1], np.int32)
    elif case == "absent":
        span = np.arange(mkba[b - 1] + 1, mkba[b + 2], dtype=np.int32)
        absent = np.setdiff1d(span, np.concatenate(rows + [mkba]))
        dels = rng.choice(absent, 8, replace=False)
    elif case == "adjacent":
        dels = np.concatenate([rows[b], rows[b + 1][:1], rows[b + 2][:2]])
    else:  # min_valued_not_found
        st = dataclasses.replace(st, vals=st.vals.at[b, 0, 0].set(NOT_FOUND))
        dels = rows[b][:1]
    # the fences around ``b`` and a spread of others
    succ = mkba[np.union1d(np.arange(b - 2, b + 4), rng.choice(nb, 120))]
    tags = np.concatenate([
        np.full(dels.size, core.OP_DELETE), np.full(ins.size, core.OP_INSERT),
        np.full(succ.size, core.OP_SUCCESSOR),
    ]).astype(np.int32)
    keys = np.concatenate([dels, ins, succ]).astype(np.int32)
    vals = np.concatenate([np.zeros(dels.size), ins + 5_000_000, np.zeros(succ.size)])
    assert tags.size <= 256
    _assert_fused_matches_reference(
        st, tags, keys, vals.astype(np.int32), pad_to=256, pipeline=pipeline
    )


@jax.jit
def _surviving_min_of(st, sorted_dels):
    hit, bkt, node, pos = locate_stored(st, sorted_dels)
    nb = st.num_buckets
    return _surviving_min(
        st.keys.reshape(nb, -1), st.vals.reshape(nb, -1), hit, bkt,
        node * st.node_size + pos,
    )


@pytest.mark.parametrize("kind", ["fresh", "deleted", "restructured", "expired"])
def test_surviving_min_matches_numpy(rng, kind):
    """The wrapper's surviving minimum (and its value, and the count of
    buckets it recomputed) equals a brute-force NumPy minimum over every
    stored slot, for delete sets of present keys, whole buckets and absent
    keys."""
    st, live, _ = _state_of_kind(kind, rng)
    nb = st.num_buckets
    keys2d = np.asarray(st.keys).reshape(nb, -1)
    vals2d = np.asarray(st.vals).reshape(nb, -1)
    rows = _stored_rows(st)
    absent = np.setdiff1d(np.arange(0, 125000, 3, dtype=np.int32), live)
    for _ in range(4):
        whole = rng.choice(nb, 3, replace=False)
        dels = np.unique(np.concatenate(
            [rng.choice(live, rng.integers(0, 200))]
            + [rows[c] for c in whole]
            + [rng.choice(absent, 40)]
        )).astype(np.int32)
        batch = np.full(512, int(EMPTY), np.int32)
        batch[: dels.size] = dels
        got_min, got_val, fixups = _surviving_min_of(st, jnp.asarray(batch))
        gone = np.isin(keys2d, dels) & (keys2d != int(EMPTY))
        masked = np.where(gone, int(EMPTY), keys2d)
        want = masked.min(axis=1)
        np.testing.assert_array_equal(np.asarray(got_min), want)
        held = want != int(EMPTY)
        want_val = vals2d[np.arange(nb), masked.argmin(axis=1)]
        np.testing.assert_array_equal(np.asarray(got_val)[held], want_val[held])
        assert int(fixups) == int(gone.any(axis=1).sum())


@pytest.mark.parametrize("batch", ["no_deletes", "few_buckets", "every_bucket"])
def test_fused_delete_min_fixups_counter(rng, batch):
    """``stats["delete_min_fixups"]`` counts the buckets whose post-update
    minimum the wrapper recomputed: those holding a DELETE of a stored key
    under the pre-batch fences — none without deletes, exactly the few
    buckets of a minority batch's present deletes, all of them when every
    bucket loses its minimum."""
    st, live, _ = _state_of_kind("fresh", rng)
    if batch == "every_bucket":
        keys = np.asarray(st.keys)[:, 0, 0].astype(np.int32)
        tags = np.full(keys.size, core.OP_DELETE, np.int32)
        vals = np.zeros(keys.size, np.int32)
    else:
        tags, keys, vals = _minority_batch(rng, live)
        if batch == "no_deletes":
            keep = tags != core.OP_DELETE
            tags, keys, vals = tags[keep], keys[keep], vals[keep]
    is_del = tags == core.OP_DELETE
    want = _updated_buckets(st, np.where(is_del, tags, core.OP_POINT), keys).size
    if batch != "few_buckets":
        assert want == (0 if batch == "no_deletes" else st.num_buckets)
    else:
        assert 0 < want < st.num_buckets // 10
    ops, _ = core.make_ops(tags, keys, vals)
    _, _, stats = core.apply_ops(st, ops, config=ExecConfig(impl="fused"))
    assert int(stats["delete_min_fixups"]) == want


# ---------------------------------------------------------------------------
# pipelined fused kernel: double-buffered staging == single-buffer, byte-exact
# ---------------------------------------------------------------------------


def _fused_both_pipelines(st, ops, *, max_results=128, now=None):
    """Run the fused executor with the double-buffered kernel forced on and
    forced off; assert the two runs are byte-identical; return the on-run."""
    outs = {}
    for mode in ("on", "off"):
        outs[mode] = core.apply_ops(
            st,
            ops,
            now=now,
            config=ExecConfig(impl="fused", pipeline=mode, max_results=max_results),
        )
    s_on, r_on, t_on = outs["on"]
    s_off, r_off, t_off = outs["off"]
    for f in STATE_FIELDS + (("exps",) if s_on.exps is not None else ()):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_on, f)), np.asarray(getattr(s_off, f)), err_msg=f
        )
    assert bool(s_on.needs_restructure) == bool(s_off.needs_restructure)
    for k in r_on:
        np.testing.assert_array_equal(
            np.asarray(r_on[k]), np.asarray(r_off[k]), err_msg=k
        )
    for k in t_on:
        assert int(t_on[k]) == int(t_off[k]), k
    return outs["on"]


@pytest.mark.parametrize(
    "present",
    [
        (core.OP_INSERT,),
        (core.OP_DELETE,),
        (core.OP_INSERT, core.OP_POINT),
        (core.OP_RANGE, core.OP_SUCCESSOR),
        (core.OP_INSERT, core.OP_DELETE, core.OP_POINT,
         core.OP_SUCCESSOR, core.OP_RANGE),
    ],
)
def test_pipelined_kernel_partial_mixes(adversarial, rng, present):
    """The double-buffered DMA kernel forced on (interpret mode) matches the
    reference engine on the adversarial mixes — same grid as the fused
    proofs, now through the explicit two-slot staging path."""
    st, live = adversarial
    absent_keys = np.setdiff1d(np.arange(0, 130000, 5, dtype=np.int32), live)
    pools = {
        core.OP_INSERT: rng.choice(absent_keys, 120, replace=False),
        core.OP_DELETE: rng.choice(live, 120, replace=False),
        core.OP_POINT: rng.integers(0, 130000, 120),
        core.OP_SUCCESSOR: rng.integers(0, 130000, 120),
        core.OP_RANGE: np.sort(rng.integers(0, 125000, 40)),
    }
    tags, keys, vals = [], [], []
    for t in present:
        k = pools[t].astype(np.int32)
        tags.append(np.full(len(k), t, np.int32))
        keys.append(k)
        if t == core.OP_INSERT:
            vals.append(np.arange(len(k), dtype=np.int32) + 3_000_000)
        elif t == core.OP_RANGE:
            vals.append((k + rng.integers(0, 2000, len(k))).astype(np.int32))
        else:
            vals.append(np.zeros(len(k), np.int32))
    _assert_fused_matches_reference(
        st,
        np.concatenate(tags),
        np.concatenate(keys),
        np.concatenate(vals),
        pad_to=512,
        max_results=256,
        pipeline="on",
    )
    ops, _ = core.make_ops(
        np.concatenate(tags), np.concatenate(keys), np.concatenate(vals), pad_to=512
    )
    _fused_both_pipelines(st, ops, max_results=256)


def test_pipelined_kernel_overflow_restructure(rng):
    """An overflowing batch through the double-buffered kernel: the pre-retry
    state bytes and the restructure flag agree with the single-buffer path,
    and the safe driver recovers identically on top of it."""
    keys = np.arange(0, 640, 10, dtype=np.int32)
    st = core.build(keys, keys, node_size=4, nodes_per_bucket=2)
    flood = np.arange(1, 200, 2, dtype=np.int32)
    tags = np.concatenate([
        np.full(len(flood), core.OP_INSERT),
        np.full(len(keys), core.OP_POINT),
    ]).astype(np.int32)
    bkeys = np.concatenate([flood, keys]).astype(np.int32)
    bvals = np.concatenate([flood, np.zeros(len(keys), np.int32)])
    ops, perm = core.make_ops(tags, bkeys, bvals, pad_to=256)
    s_on, _, _ = _fused_both_pipelines(st, ops)
    assert bool(s_on.needs_restructure)
    s2, res, _ = core.apply_ops_safe(
        st, ops, config=ExecConfig(impl="fused", pipeline="on")
    )
    assert not bool(s2.needs_restructure)
    check_invariants(s2)
    res_in = np.asarray(core.unsort(res["value"], perm))
    np.testing.assert_array_equal(res_in[len(flood) : len(flood) + len(keys)], keys)


def test_pipelined_kernel_ttl_batch(adversarial, rng):
    """TTL batches (expiry column + EXPIRE ops + now) through the pipelined
    kernel: both TTL planes ride the same double-buffered apply, so on/off
    must agree byte-for-byte including the expiry column."""
    from repro.core.expiry import NO_EXPIRY, attach_expiry

    st, live = adversarial
    st = attach_expiry(st)
    absent = np.setdiff1d(np.arange(0, 130000, 7, dtype=np.int32), live)
    now = 100
    ins = rng.choice(absent, 60, replace=False).astype(np.int32)
    exp_new = rng.choice(live, 60, replace=False).astype(np.int32)  # get-or-set
    points = rng.choice(live, 60, replace=False).astype(np.int32)
    rlo = np.sort(rng.integers(0, 125000, 20)).astype(np.int32)
    rhi = (rlo + rng.integers(0, 3000, 20)).astype(np.int32)
    tags = np.concatenate([
        np.full(len(ins), core.OP_INSERT),
        np.full(len(exp_new), core.OP_EXPIRE),
        np.full(len(points), core.OP_POINT),
        np.full(len(rlo), core.OP_RANGE),
    ]).astype(np.int32)
    keys = np.concatenate([ins, exp_new, points, rlo]).astype(np.int32)
    vals = np.concatenate([
        ins + 1_000_000,
        exp_new + 2_000_000,
        np.zeros(len(points), np.int32),
        rhi,
    ]).astype(np.int32)
    exps = np.concatenate([
        now + 5 + (ins % 50),                     # TTL'd inserts
        np.full(len(exp_new), now + 40),          # EXPIRE deadlines
        np.full(len(points) + len(rlo), int(NO_EXPIRY)),
    ]).astype(np.int64)
    ops, _ = core.make_ops(tags, keys, vals, exps=jnp.asarray(exps), pad_to=512)
    s_on, r_on, t_on = _fused_both_pipelines(st, ops, max_results=256, now=now)
    # and the pipelined TTL run matches the reference engine exactly
    s_ref, r_ref, t_ref = core.apply_ops(
        st, ops, now=now, config=ExecConfig(impl="reference", max_results=256)
    )
    for f in ("keys", "exps", "node_count", "node_max", "num_nodes", "mkba"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_ref, f)), np.asarray(getattr(s_on, f)), err_msg=f
        )
    mask = np.asarray(s_ref.keys) != int(EMPTY)
    np.testing.assert_array_equal(
        np.asarray(s_ref.vals)[mask], np.asarray(s_on.vals)[mask]
    )
    for k in r_ref:
        np.testing.assert_array_equal(
            np.asarray(r_ref[k]), np.asarray(r_on[k]), err_msg=k
        )
    for k in t_ref:
        assert int(t_ref[k]) == int(t_on[k]), k
