"""RANGE as a first-class mixed-batch op: range-fraction × selectivity sweep.

The paper's central claim over unordered GPU hash tables is that FliX keeps
comparison-based order and therefore answers range queries at all; this
suite measures what that costs inside the batch engine.  The grid:

  * **range fraction** — share of the batch that is RANGE ops (the rest is
    the fig-style serving mix: half POINT reads, a quarter INSERT, a
    quarter DELETE), from range-light (10%) to range-heavy (90%, the
    90/10 read/update shape).
  * **selectivity** — expected stored keys per range (``narrow`` ≈ 16,
    ``wide`` ≈ 256), which moves the work from offset bookkeeping to
    result scatter.

Timed forms:

  * ``apply_ops(config=ExecConfig(impl="reference"))`` — the jnp engine (its
    range phase is the dense two-pass oracle: rank fences + exclusive-scan
    offsets + one gather).
  * ``apply_ops(config=ExecConfig(impl="fused"))`` — the compute-to-bucket
    Pallas kernel with the in-VMEM range phase, at one sweep point
    (interpret mode on CPU hosts: the recorded "speedup" < 1 is the honest
    interpret-vs-jnp ratio — the number to watch on real hardware).
  * ``flix_range_pallas`` — the standalone two-pass count/scatter kernel on
    a pure range batch, same caveat.

``benchmarks.run`` lifts the ``range_mix_fused_*`` / ``range_mix_ref_*``
pairs into the ``range_fused_speedup`` field of BENCH_PR3.json (DESIGN.md
§7/§10).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import BUILD_SIZE, KEY_SPACE, emit, keyset, time_call
from repro import core
from repro.core.config import ExecConfig

SELECTIVITY = {"narrow": 16, "wide": 256}   # expected stored keys per range
RANGE_FRACTIONS = (10, 50, 90)              # percent of the batch
FUSED_POINT = (90, "narrow")                # one interpret-mode fused sample
MAX_RESULTS = 2048                          # per-batch dense output budget


def _batch(rng, keys, absent, batch, rf_pct, span_keys):
    """One mixed batch: rf% RANGE, rest = 50% POINT / 25% INSERT / 25% DEL."""
    n_range = batch * rf_pct // 100
    n_rest = batch - n_range
    n_point = n_rest // 2
    n_ins = (n_rest - n_point) // 2
    n_del = n_rest - n_point - n_ins

    gap = KEY_SPACE // len(keys)            # mean key spacing
    los = rng.integers(0, KEY_SPACE - span_keys * gap, n_range).astype(np.int32)
    his = (los + span_keys * gap).astype(np.int32)
    points = rng.integers(0, KEY_SPACE, n_point).astype(np.int32)
    ins = absent[:n_ins]
    dels = rng.choice(keys, size=n_del, replace=False).astype(np.int32)

    tags = np.concatenate([
        np.full(n_range, core.OP_RANGE), np.full(n_point, core.OP_POINT),
        np.full(n_ins, core.OP_INSERT), np.full(n_del, core.OP_DELETE),
    ]).astype(np.int32)
    bkeys = np.concatenate([los, points, ins, dels]).astype(np.int32)
    bvals = np.concatenate([
        his, np.zeros(n_point, np.int32),
        np.arange(n_ins, dtype=np.int32), np.zeros(n_del, np.int32),
    ]).astype(np.int32)
    return jnp.asarray(tags), jnp.asarray(bkeys), jnp.asarray(bvals)


def run() -> None:
    rng = np.random.default_rng(42)
    n = BUILD_SIZE
    batch = max(512, n // 32)
    keys = keyset(rng, n)
    vals = np.arange(n, dtype=np.int32)
    st = core.build(keys, vals, node_size=32, nodes_per_bucket=16)
    absent = np.setdiff1d(
        rng.integers(0, KEY_SPACE, 4 * batch).astype(np.int32), keys
    )

    for sel_name, span in SELECTIVITY.items():
        for rf in RANGE_FRACTIONS:
            jt, jk, jv = _batch(rng, keys, absent, batch, rf, span)

            def reference():
                ops, _ = core.make_ops(jt, jk, jv)
                return core.apply_ops(
                    st, ops, config=ExecConfig(impl="reference", max_results=MAX_RESULTS)
                )

            t_ref = time_call(reference)
            _, res, stats = reference()
            emitted = int(jnp.sum(res["range_count"]))
            emit(
                f"range_mix_ref_rf{rf}_{sel_name}",
                t_ref,
                f"batch={batch};emitted={emitted};"
                f"truncated_ops={int(stats['range_truncated'])}",
            )

            if (rf, sel_name) == FUSED_POINT:

                def fused():
                    ops, _ = core.make_ops(jt, jk, jv)
                    return core.apply_ops(
                        st, ops, config=ExecConfig(impl="fused", max_results=MAX_RESULTS)
                    )

                t_fused = time_call(fused, iters=1)
                emit(
                    f"range_mix_fused_rf{rf}_{sel_name}",
                    t_fused,
                    f"batch={batch};speedup_vs_reference="
                    f"{t_ref / t_fused:.2f}x",
                )

    # standalone two-pass kernel on a pure sorted range batch (narrow)
    from repro.kernels.flix_range import flix_range_pallas

    n_pure = min(256, batch)
    gap = KEY_SPACE // n
    los = np.sort(
        rng.integers(0, KEY_SPACE - 16 * gap, n_pure).astype(np.int32)
    )
    his = (los + 16 * gap).astype(np.int32)
    jlo, jhi = jnp.asarray(los), jnp.asarray(his)

    def standalone():
        return flix_range_pallas(
            st.keys, st.vals, st.mkba, jlo, jhi,
            max_results=MAX_RESULTS, interpret=True,
        )

    t_kernel = time_call(standalone, iters=1)

    import functools
    import jax

    oracle_fn = jax.jit(
        functools.partial(core.dense_range_scan, max_results=MAX_RESULTS)
    )
    ones = jnp.ones((n_pure,), bool)

    def oracle():
        return oracle_fn(st, ones, jlo, jhi)

    t_oracle = time_call(oracle)
    emit(
        "range_mix_kernel_pure256_narrow",
        t_kernel,
        f"oracle_us={t_oracle:.1f};speedup_vs_oracle={t_oracle / t_kernel:.2f}x",
    )
