"""Restructure paths that the rest of the suite never exercises.

Three cold paths from ``core.restructure`` / ``core.ops``:

  1. ``restructure_grow``'s pathological-skew *widening* branch: when a
     single bucket may have to absorb the whole incoming batch
     (``p + extra_keys > cap``), the host widens ``nodes_per_bucket`` so one
     bucket can hold it — the §3.4 adaptive compute-to-bucket analogue.
  2. ``apply_ops_safe``'s restructure-and-replay round trip: a mixed batch
     that overflows mid-mix is replayed in full on the regrown pre-batch
     state, and every op class of the batch must come back correct.
  3. ``replay_on_overflow``, the one overflow → restructure → replay
     driver, reached through every engine that calls it.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core.invariants import check_invariants
from repro.core.restructure import restructure_grow
from repro.core.state import EMPTY, NOT_FOUND
from repro.core.config import ExecConfig


def _tiny_state():
    """cap = 8 (node_size 4 × npb 2), p = 2 — easy to overflow."""
    keys = np.arange(0, 1000, 50, dtype=np.int32)  # 20 keys, spread out
    return core.build(keys, keys, node_size=4, nodes_per_bucket=2), keys


def test_restructure_grow_widening_branch():
    st, keys = _tiny_state()
    cap = st.bucket_capacity
    p = st.node_size // 2
    extra = 100
    assert p + extra > cap, "precondition: this must hit the widening branch"

    grown = restructure_grow(st, extra_keys=extra)
    # geometry: nodes_per_bucket widened so one bucket can absorb the batch
    assert grown.nodes_per_bucket == math.ceil((p + extra) / st.node_size)
    assert grown.nodes_per_bucket > st.nodes_per_bucket
    assert grown.num_buckets == max(1, math.ceil((len(keys) + extra) / p))
    check_invariants(grown)
    # contents preserved
    got = np.asarray(core.point_query(grown, jnp.asarray(keys)))
    np.testing.assert_array_equal(got, keys)


def test_widening_branch_absorbs_single_bucket_flood():
    """All extra keys landing between two adjacent fences must fit after the
    widening restructure — the exact skew the branch exists for."""
    st, keys = _tiny_state()
    flood = np.arange(101, 148, dtype=np.int32)  # 47 keys inside one gap
    assert st.node_size // 2 + len(flood) > st.bucket_capacity

    sk, sv = core.sort_batch(jnp.asarray(flood), jnp.asarray(flood * 2))
    st1, _ = core.insert(st, sk, sv)
    assert bool(st1.needs_restructure)  # the direct insert must overflow

    st2, _ = core.insert_safe(st, sk, sv)
    assert not bool(st2.needs_restructure)
    assert st2.nodes_per_bucket > st.nodes_per_bucket
    check_invariants(st2)
    allk = np.sort(np.concatenate([keys, flood]))
    got = np.asarray(core.point_query(st2, jnp.asarray(allk)))
    want = np.where(np.isin(allk, flood), allk * 2, allk)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("impl", ["reference", "fused"])
def test_apply_ops_safe_replay_full_mix(impl):
    """A mid-mix overflow triggers restructure-and-replay; afterwards every
    op class of the batch (insert, delete, point, successor) is correct."""
    st, keys = _tiny_state()
    flood = np.arange(1, 200, 2, dtype=np.int32)          # overflowing inserts
    dels = keys[::4].astype(np.int32)                     # present deletions
    points = keys[1::4].astype(np.int32)                  # survivors
    succs = (keys[2::4] + 1).astype(np.int32)             # between stored keys

    tags = np.concatenate([
        np.full(len(flood), core.OP_INSERT),
        np.full(len(dels), core.OP_DELETE),
        np.full(len(points), core.OP_POINT),
        np.full(len(succs), core.OP_SUCCESSOR),
    ]).astype(np.int32)
    bkeys = np.concatenate([flood, dels, points, succs]).astype(np.int32)
    bvals = np.concatenate(
        [flood * 10, np.zeros(len(dels) + len(points) + len(succs), np.int32)]
    )
    ops, perm = core.make_ops(tags, bkeys, bvals, pad_to=256)

    st2, res, stats = core.apply_ops_safe(st, ops, config=ExecConfig(impl=impl))
    assert not bool(st2.needs_restructure)
    check_invariants(st2)

    res_v = np.asarray(core.unsort(res["value"], perm))[: len(bkeys)]
    res_k = np.asarray(core.unsort(res["succ_key"], perm))[: len(bkeys)]

    # point results observe the post-update state (deletes already applied)
    np.testing.assert_array_equal(res_v[tags == core.OP_POINT], points)
    # successor results: model = (stored ∪ flood) − dels, next key ≥ q
    model = np.sort(
        np.setdiff1d(np.union1d(keys.astype(np.int64), flood), dels)
    )
    for q, sk_got, sv_got in zip(
        succs,
        res_k[tags == core.OP_SUCCESSOR],
        res_v[tags == core.OP_SUCCESSOR],
    ):
        j = np.searchsorted(model, q)
        want_k = int(model[j])
        assert sk_got == want_k
        assert sv_got == (want_k * 10 if want_k in flood else want_k)
    # post-state: floods stored, deletions gone
    got = np.asarray(core.point_query(st2, jnp.asarray(np.sort(flood))))
    np.testing.assert_array_equal(got, np.sort(flood) * 10)
    gone = np.asarray(core.point_query(st2, jnp.asarray(np.sort(dels))))
    assert (gone == int(NOT_FOUND)).all()
    assert int(stats["inserted"]) == len(flood)
    assert int(stats["deleted"]) == len(dels)
    # the retry is VISIBLE: the replay must not reset the surfaced counter
    # (the gateway metrics and bench rows report it — DESIGN.md §13)
    assert int(stats["restructure_retries"]) == 1


def test_apply_ops_safe_counter_zero_without_overflow():
    """The surfaced retry counter exists (as 0) on the no-retry path too,
    so downstream accumulation never KeyErrors."""
    st, keys = _tiny_state()
    ops, _ = core.make_ops(
        np.full(4, core.OP_POINT, np.int32), keys[:4].astype(np.int32)
    )
    _, _, stats = core.apply_ops_safe(st, ops)
    assert int(stats["restructure_retries"]) == 0


def test_apply_ops_safe_replay_reference_fused_identical():
    """The replayed (post-restructure) states of both executors match."""
    st, keys = _tiny_state()
    flood = np.arange(3, 150, 2, dtype=np.int32)
    tags = np.concatenate([
        np.full(len(flood), core.OP_INSERT),
        np.full(len(keys), core.OP_SUCCESSOR),
    ]).astype(np.int32)
    bkeys = np.concatenate([flood, keys]).astype(np.int32)
    bvals = np.concatenate([flood, np.zeros(len(keys), np.int32)])
    ops, _ = core.make_ops(tags, bkeys, bvals, pad_to=256)

    s_ref, r_ref, _ = core.apply_ops_safe(st, ops, config=ExecConfig(impl="reference"))
    s_f, r_f, _ = core.apply_ops_safe(st, ops, config=ExecConfig(impl="fused"))
    for f in ("keys", "node_count", "node_max", "num_nodes", "mkba"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_ref, f)), np.asarray(getattr(s_f, f)), err_msg=f
        )
    mask = np.asarray(s_ref.keys) != int(EMPTY)
    np.testing.assert_array_equal(
        np.asarray(s_ref.vals)[mask], np.asarray(s_f.vals)[mask]
    )
    np.testing.assert_array_equal(
        np.asarray(r_ref["value"]), np.asarray(r_f["value"])
    )
    np.testing.assert_array_equal(
        np.asarray(r_ref["succ_key"]), np.asarray(r_f["succ_key"])
    )


# ---------------------------------------------------------------------------
# the one driver, through each of its callers
# ---------------------------------------------------------------------------


def _contents(state):
    """Stored ``{key: value}`` of a FliXState (any placement)."""
    k = np.asarray(state.keys).reshape(-1)
    v = np.asarray(state.vals).reshape(-1)
    live = k != int(EMPTY)
    return dict(zip(k[live].tolist(), v[live].tolist()))


def _shard_index(st, mesh):
    """The tiny store's contents, range-partitioned over ``mesh``."""
    from repro.core.distributed import shard_build

    got = _contents(st)
    keys = np.array(sorted(got), np.int32)
    vals = np.array([got[k] for k in keys.tolist()], np.int32)
    return shard_build(
        jnp.asarray(keys), jnp.asarray(vals), mesh, node_size=4, nodes_per_bucket=2
    )


def _run_local(st, batches, tmp_path):
    out = []
    for ops in batches:
        st, res, stats = core.apply_ops_safe(st, ops)
        out.append((res, stats))
    return out, _contents(st), None


def _run_sharded(routing):
    def run(st, batches, tmp_path):
        from repro.core.distributed import make_shard_mesh, shard_apply_ops_safe

        mesh = make_shard_mesh(1)
        idx = _shard_index(st, mesh)
        cfg = ExecConfig(routing=routing)
        out = []
        for ops in batches:
            idx, res, stats = shard_apply_ops_safe(idx, ops, mesh, config=cfg)
            out.append((res, stats))
        return out, _contents(idx.state), None

    return run


def _run_tiered(st, batches, tmp_path):
    from repro.core.residency import TieredFliX

    tiered = TieredFliX.from_state(st, budget_bytes=max(1, st.memory_bytes() // 4))
    out = [tiered.apply(ops)[:2] for ops in batches]
    return out, _contents(tiered.materialize()), None


def _run_durable(make_engine):
    def run(st, batches, tmp_path):
        from repro.checkpoint import DurableFliX

        engine = make_engine()
        handle = _shard_index(st, engine.mesh) if engine.kind == "sharded" else st
        dur = DurableFliX.create(tmp_path / "idx", handle, engine=engine)
        epoch0 = dur.epoch
        out = [dur.apply(ops) for ops in batches]
        contents = _contents(engine.flix(dur.handle))
        dur.close()
        return out, contents, dur.epoch - epoch0

    return run


def _local_engine():
    from repro.checkpoint import LocalEngine

    return LocalEngine(node_size=4, nodes_per_bucket=2)


def _a2a_shard_engine():
    from repro.checkpoint import ShardEngine
    from repro.core.distributed import make_shard_mesh

    return ShardEngine(
        make_shard_mesh(1),
        config=ExecConfig(routing="a2a"),
        node_size=4,
        nodes_per_bucket=2,
    )


@pytest.mark.parametrize(
    "run",
    [
        pytest.param(_run_local, id="apply_ops_safe"),
        pytest.param(_run_sharded("replicated"), id="shard_safe_replicated"),
        pytest.param(_run_sharded("a2a"), id="shard_safe_a2a"),
        pytest.param(_run_tiered, id="tiered_under_budget"),
        pytest.param(_run_durable(_local_engine), id="durable_local"),
        pytest.param(_run_durable(_a2a_shard_engine), id="durable_shard_a2a"),
    ],
)
def test_overflow_replays_once(run, tmp_path):
    """A batch whose inserts overflow a bucket is replayed exactly once on
    the regrown pre-batch handle, through every caller of
    ``replay_on_overflow``; results and stored contents match a dict model
    over both batches, and a durable index bumps its fence epoch."""
    st, keys = _tiny_state()
    model = {int(k): int(k) for k in keys}
    flood = np.arange(101, 148, dtype=np.int32)  # 47 keys inside one gap
    dels = keys[::4].astype(np.int32)
    points = keys[1::4].astype(np.int32)
    tags1 = np.concatenate([
        np.full(len(flood), core.OP_INSERT),
        np.full(len(dels), core.OP_DELETE),
        np.full(len(points), core.OP_POINT),
    ]).astype(np.int32)
    keys1 = np.concatenate([flood, dels, points]).astype(np.int32)
    vals1 = np.concatenate([flood * 3, np.zeros(len(dels) + len(points), np.int32)])
    ops1, perm1 = core.make_ops(tags1, keys1, vals1, pad_to=128)
    for k in flood:
        model[int(k)] = int(k) * 3
    for k in dels:
        del model[int(k)]
    # the second batch reads every key either batch mentioned
    reads = np.unique(np.concatenate([keys, flood])).astype(np.int32)
    ops2, perm2 = core.make_ops(
        np.full(len(reads), core.OP_POINT, np.int32), reads, pad_to=128
    )

    (
        ((res1, stats1), (res2, stats2)),
        contents,
        epoch_bumps,
    ) = run(st, [ops1, ops2], tmp_path)

    assert int(stats1["restructure_retries"]) == 1
    assert int(stats2["restructure_retries"]) == 0
    assert int(stats1["inserted"]) == len(flood)
    assert int(stats1["deleted"]) == len(dels)
    got1 = np.asarray(core.unsort(res1["value"], perm1))[: len(keys1)]
    np.testing.assert_array_equal(got1[tags1 == core.OP_POINT], points)
    got2 = np.asarray(core.unsort(res2["value"], perm2))[: len(reads)]
    want2 = [model.get(int(k), int(NOT_FOUND)) for k in reads]
    np.testing.assert_array_equal(got2, want2)
    assert contents == model
    if epoch_bumps is not None:
        assert epoch_bumps == 1
