"""TTL as a first-class mixed-batch op: expiry-fraction × TTL-skew sweep.

The caching workload (DESIGN.md §14): per-key deadlines ride a third state
column, EXPIRE is get-or-set-with-TTL in the same sorted batch as every
other op class, and a lazy expiry pre-pass physically reclaims dead rows
at the batch's virtual ``now``.  This suite measures what that costs
inside the engine.  The grid:

  * **expire fraction** — share of the batch that is EXPIRE ops (half
    hits refreshing deadlines, half misses inserting), from
    expire-light (10%) to the memcached-shaped get-or-set-heavy mix
    (90%); the rest is 50% POINT / 25% TTL'd INSERT / 25% DELETE.
  * **TTL skew** — fraction of STORED rows already past their deadline
    at the measured ``now`` (``light`` ≈ 1%, ``heavy`` ≈ 25%), which
    moves the work from deadline bookkeeping to the expiry pre-pass's
    physical reclamation (in-node shift + chain compaction).

Timed forms:

  * ``apply_ops(config=ExecConfig(impl="reference"), now=...)`` — the jnp
    engine running the expiry pre-pass + two-plane TTL execution.
  * ``apply_ops(config=ExecConfig(impl="fused"), now=...)`` — the
    compute-to-bucket Pallas kernel under the same TTL planes, at one sweep
    point (interpret mode on CPU hosts: the recorded "speedup" < 1 is the
    honest interpret-vs-jnp ratio — the number to watch on real hardware).
  * ``expire_state`` alone — the pre-pass's marginal cost per skew level.

``benchmarks.run`` lifts the ``ttl_mix_fused_*`` / ``ttl_mix_ref_*``
pairs into the ``ttl_fused_speedup`` field of the bench artifact.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import BUILD_SIZE, KEY_SPACE, emit, keyset, time_call
from repro import core
from repro.checkpoint.serialize import state_from_pairs
from repro.core.expiry import NO_EXPIRY, expire_state
from repro.core.config import ExecConfig

TTL_SKEW = {"light": 0.01, "heavy": 0.25}  # stored rows already expired
EXPIRE_FRACTIONS = (10, 50, 90)            # percent of the batch
FUSED_POINT = (90, "heavy")                # one interpret-mode fused sample
MAX_RESULTS = 256
NOW = 1 << 20                              # the sweep's virtual clock


def _ttl_state(rng, keys, vals, dead_frac):
    """Stored deadlines: ``dead_frac`` already past NOW, a third due in
    the future, the rest immortal."""
    r = rng.random(len(keys))
    exps = np.full(len(keys), int(NO_EXPIRY), np.int64)
    exps[r < dead_frac] = NOW - rng.integers(1, 1000, int((r < dead_frac).sum()))
    future = (r >= dead_frac) & (r < dead_frac + 0.33)
    exps[future] = NOW + rng.integers(1, 1 << 20, int(future.sum()))
    return state_from_pairs(
        keys, vals, exps.astype(np.int32), node_size=32, nodes_per_bucket=16
    )


def _batch(rng, keys, absent, batch, ef_pct):
    """ef% EXPIRE (half hit / half miss), rest 50/25/25 POINT/INSERT/DEL."""
    n_exp = batch * ef_pct // 100
    n_hit = n_exp // 2
    n_miss = n_exp - n_hit
    n_rest = batch - n_exp
    n_point = n_rest // 2
    n_ins = (n_rest - n_point) // 2
    n_del = n_rest - n_point - n_ins

    hit = rng.choice(keys, size=n_hit, replace=False).astype(np.int32)
    miss = absent[:n_miss]
    ins = absent[n_miss : n_miss + n_ins]
    dels = rng.choice(
        np.setdiff1d(keys, hit), size=n_del, replace=False
    ).astype(np.int32)
    points = rng.integers(0, KEY_SPACE, n_point).astype(np.int32)

    tags = np.concatenate([
        np.full(n_exp, core.OP_EXPIRE), np.full(n_point, core.OP_POINT),
        np.full(n_ins, core.OP_INSERT), np.full(n_del, core.OP_DELETE),
    ]).astype(np.int32)
    bkeys = np.concatenate([hit, miss, points, ins, dels]).astype(np.int32)
    bvals = np.concatenate([
        np.arange(n_exp, dtype=np.int32), np.zeros(n_point, np.int32),
        np.arange(n_ins, dtype=np.int32), np.zeros(n_del, np.int32),
    ]).astype(np.int32)
    bexps = np.full(batch, int(NO_EXPIRY), np.int32)
    bexps[:n_exp] = NOW + rng.integers(1, 1 << 16, n_exp)
    bexps[n_exp + n_point : n_exp + n_point + n_ins] = NOW + rng.integers(
        1, 1 << 16, n_ins
    )
    return (
        jnp.asarray(tags),
        jnp.asarray(bkeys),
        jnp.asarray(bvals),
        jnp.asarray(bexps),
    )


def run() -> None:
    rng = np.random.default_rng(42)
    n = BUILD_SIZE
    batch = max(512, n // 32)
    keys = np.sort(keyset(rng, n))  # state_from_pairs wants sorted triples
    vals = np.arange(n, dtype=np.int32)
    absent = np.setdiff1d(
        rng.integers(0, KEY_SPACE, 4 * batch).astype(np.int32), keys
    )

    for skew_name, dead_frac in TTL_SKEW.items():
        st = _ttl_state(rng, keys, vals, dead_frac)

        # the expiry pre-pass alone: reclamation cost per skew level
        t_expire = time_call(lambda: expire_state(st, jnp.int32(NOW)))
        _, n_dead = expire_state(st, jnp.int32(NOW))
        emit(
            f"ttl_mix_expire_pass_{skew_name}",
            t_expire,
            f"reclaimed={int(n_dead)};stored={n}",
        )

        for ef in EXPIRE_FRACTIONS:
            jt, jk, jv, je = _batch(rng, keys, absent, batch, ef)

            def reference():
                ops, _ = core.make_ops(jt, jk, jv, exps=je)
                return core.apply_ops(
                    st, ops, now=NOW, config=ExecConfig(impl="reference", max_results=MAX_RESULTS)
                )

            t_ref = time_call(reference)
            _, res, stats = reference()
            hits = int(jnp.sum(res["value"] != int(core.NOT_FOUND)))
            emit(
                f"ttl_mix_ref_ef{ef}_{skew_name}",
                t_ref,
                f"batch={batch};expired={int(stats['expired'])};hits={hits}",
            )

            if (ef, skew_name) == FUSED_POINT:

                def fused():
                    ops, _ = core.make_ops(jt, jk, jv, exps=je)
                    return core.apply_ops(
                        st, ops, now=NOW, config=ExecConfig(impl="fused", max_results=MAX_RESULTS)
                    )

                t_fused = time_call(fused, iters=1)
                emit(
                    f"ttl_mix_fused_ef{ef}_{skew_name}",
                    t_fused,
                    f"batch={batch};speedup_vs_reference="
                    f"{t_ref / t_fused:.2f}x",
                )
