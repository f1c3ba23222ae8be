"""Mixed-operation batch engine: fused kernel vs reference vs per-type.

The paper's execution model is one sorted batch of mixed operations per
step.  This suite sweeps the update ratio (0% = read-only … 100% = pure
updates) on a fixed-size batch and times

  * ``apply_ops(config=ExecConfig(impl="reference"))`` — the unified jnp
    engine: one global sort, one bucket routing, but still four device
    passes over the state (insert merge, delete, point, successor),
  * ``apply_ops(config=ExecConfig(impl="fused"))`` — the compute-to-bucket
    Pallas kernel (``kernels/flix_apply.py``): one VMEM-resident pass per
    bucket executes the whole update-then-read sequence.  Compiled on TPU;
    in *interpret mode* on CPU hosts, where the recorded "speedup"
    is the honest interpret-vs-jnp ratio (< 1) — the number to watch on real
    hardware.
    Measured at the read-heavy (0%) and update-heavy (100%) sweep ends so
    the interpret-mode cost stays bounded.
  * ``sequential`` — the pre-engine serving path: sort + route the inserts,
    sort + route the deletes, sort the reads, four separate passes.

All three produce identical states and results (tests/test_differential.py),
so the deltas are pure execution-structure overhead — routing/sort cost for
``sequential`` vs ``apply_ops``, HBM sweep count for reference vs fused.
``benchmarks.run`` lifts the ``mixed_batch_apply_fused_upd*`` /
``mixed_batch_apply_ops_upd*`` pairs into the ``apply_ops_fused_speedup``
field of the bench artifact (DESIGN.md §7), and since PR 10 the
``mixed_batch_apply_pipelined_upd*`` rows (double-buffered fused kernel,
``pipeline="on"``) into ``pipelined_speedup`` (DESIGN.md §16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BUILD_SIZE, KEY_SPACE, emit, keyset, time_call
from repro import core
from repro.core.config import ExecConfig

FUSED_SWEEP_POINTS = (0, 100)  # read-heavy and update-heavy ends


def run() -> None:
    rng = np.random.default_rng(21)
    n = BUILD_SIZE
    batch = max(1024, n // 8)
    keys = keyset(rng, n)
    vals = np.arange(n, dtype=np.int32)
    st = core.build(keys, vals, node_size=32, nodes_per_bucket=16)
    absent = np.setdiff1d(
        rng.integers(0, KEY_SPACE, 4 * batch).astype(np.int32), keys
    )

    for upd_pct in (0, 25, 50, 75, 100):
        n_upd = batch * upd_pct // 100
        n_ins, n_del = n_upd // 2, n_upd - n_upd // 2
        n_read = batch - n_upd
        n_point, n_succ = n_read // 2, n_read - n_read // 2

        ins = absent[:n_ins]
        dels = rng.choice(keys, size=n_del, replace=False).astype(np.int32)
        points = rng.integers(0, KEY_SPACE, n_point).astype(np.int32)
        succs = rng.integers(0, KEY_SPACE, n_succ).astype(np.int32)

        tags = np.concatenate([
            np.full(n_ins, core.OP_INSERT), np.full(n_del, core.OP_DELETE),
            np.full(n_point, core.OP_POINT), np.full(n_succ, core.OP_SUCCESSOR),
        ]).astype(np.int32)
        bkeys = np.concatenate([ins, dels, points, succs]).astype(np.int32)
        bvals = np.zeros(batch, np.int32)
        bvals[:n_ins] = np.arange(n_ins)
        jt, jk, jv = jnp.asarray(tags), jnp.asarray(bkeys), jnp.asarray(bvals)

        def mixed():
            ops, _ = core.make_ops(jt, jk, jv)
            return core.apply_ops(st, ops, config=ExecConfig(impl="reference"))

        jins_k, jins_v = jnp.asarray(ins), jnp.asarray(bvals[:n_ins])
        jdel = jnp.asarray(dels)
        jpoint, jsucc = jnp.asarray(points), jnp.asarray(succs)

        def sequential():
            s2 = st
            if n_ins:
                sk, sv = core.sort_batch(jins_k, jins_v)
                s2, _ = core.insert(s2, sk, sv)
            if n_del:
                s2, _ = core.delete(s2, jnp.sort(jdel))
            pv = sks = svs = None
            if n_point:
                pv = core.point_query(s2, jnp.sort(jpoint))
            if n_succ:
                sks, svs = core.successor_query(s2, jnp.sort(jsucc))
            return s2, pv, sks, svs

        t_mixed = time_call(mixed)
        t_seq = time_call(sequential)
        emit(
            f"mixed_batch_apply_ops_upd{upd_pct}",
            t_mixed,
            f"batch={batch};ops_per_s={batch / t_mixed * 1e6:.0f}",
        )
        emit(
            f"mixed_batch_sequential_upd{upd_pct}",
            t_seq,
            f"batch={batch};speedup={t_seq / t_mixed:.2f}x",
        )

        if upd_pct in FUSED_SWEEP_POINTS:
            # pipeline="off" IS the pre-pipelining fused path — it stays the
            # fused row so the committed speedup trend is apples-to-apples
            def fused():
                ops, _ = core.make_ops(jt, jk, jv)
                return core.apply_ops(
                    st, ops, config=ExecConfig(impl="fused", pipeline="off")
                )

            t_fused = time_call(fused, iters=1)
            emit(
                f"mixed_batch_apply_fused_upd{upd_pct}",
                t_fused,
                f"batch={batch};speedup_vs_reference={t_mixed / t_fused:.2f}x",
            )

            # double-buffered variant: a real DMA/compute overlap exists only
            # on TPU.  In interpret mode the async copies are emulated
            # serially, so a CPU wall clock of pipeline="on" measures the
            # emulation, not the kernel — on non-TPU hosts the fused time is
            # re-emitted under the pipelined row (ratio exactly 1.0) and the
            # row is an honest "no TPU on this host" marker, while the
            # byte-identity still holds (tests/test_differential.py).
            if jax.default_backend() == "tpu":

                def pipelined():
                    ops, _ = core.make_ops(jt, jk, jv)
                    return core.apply_ops(
                        st, ops, config=ExecConfig(impl="fused", pipeline="on")
                    )

                t_pipe = time_call(pipelined, iters=1)
            else:
                t_pipe = t_fused
            emit(
                f"mixed_batch_apply_pipelined_upd{upd_pct}",
                t_pipe,
                f"batch={batch};speedup_vs_fused={t_fused / t_pipe:.2f}x"
                f";backend={jax.default_backend()}",
            )
