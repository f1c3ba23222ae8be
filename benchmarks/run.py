# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver — one module per paper table/figure (DESIGN.md §7).

  python -m benchmarks.run             # everything
  python -m benchmarks.run fig9 fig13  # substring filter

Besides the CSV rows on stdout, every run writes ``BENCH_PR10.json`` — the
repo's machine-readable perf-trajectory artifact (schema ``flix-bench-v1``,
DESIGN.md §7): per-suite ``name → us_per_call`` maps plus the
fused-vs-reference ``apply_ops`` speedups extracted from the
``mixed_batch`` suite, the pipelined-vs-fused speedups from the same suite
(DESIGN.md §16), the RANGE-op speedups from ``range_mix``, the
TTL-mix speedups from ``ttl_mix``, the sharded-vs-single speedups from
``sharded_mix``, the delta-vs-full snapshot write-volume ratios from
``durability``, the goodput-under-overload ratios from ``gateway``, the
oversubscription-degradation ratios from ``tiered_scale``, and the
deterministic autotuner tile table + sweep record
(``kernels/autotune.py``).  (``BENCH_PR*.json`` in
the repo root are committed per-PR snapshots — ``benchmarks.compare``
diffs against them; don't overwrite them outside a snapshot refresh.)
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

from benchmarks import (
    build_query_grid,
    common,
    delete_rounds,
    dist_shift,
    durability,
    gateway,
    heatmap,
    insert_rounds,
    mixed_batch,
    query_qtmf,
    range_mix,
    restructure_recovery,
    sharded_mix,
    sort_cost,
    successor,
    tiered_scale,
    ttl_mix,
    unsorted_queries,
)

SUITES = {
    "table1_sort": sort_cost,
    "fig5_heatmap": heatmap,
    "fig7_insert_rounds": insert_rounds,
    "fig8_delete_rounds": delete_rounds,
    "fig9_query_qtmf": query_qtmf,
    "fig10_build_query_grid": build_query_grid,
    "fig11_dist_shift": dist_shift,
    "fig12_unsorted_queries": unsorted_queries,
    "fig13_successor": successor,
    "mixed_batch_engine": mixed_batch,
    "range_mix_engine": range_mix,
    "sharded_mix_engine": sharded_mix,
    "ttl_mix_engine": ttl_mix,
    "table4_restructure": restructure_recovery,
    "durability_engine": durability,
    "gateway_engine": gateway,
    "tiered_scale_engine": tiered_scale,
}

BENCH_JSON = os.environ.get("REPRO_BENCH_JSON", "BENCH_PR10.json")


def _speedups(
    rows: dict[str, float], fused_prefix: str, ref_prefix: str, key_prefix: str = ""
) -> dict[str, float]:
    """Fused-vs-reference speedup per measured sweep point: every
    ``<fused_prefix><point>`` row is paired with ``<ref_prefix><point>``."""
    out = {}
    for name, us in rows.items():
        if name.startswith(fused_prefix) and us > 0:
            point = name[len(fused_prefix):]
            ref = rows.get(f"{ref_prefix}{point}")
            if ref is not None:
                out[f"{key_prefix}{point}"] = ref / us
    return out


def _sharded_speedups(rows: dict[str, float]) -> dict[str, float]:
    """Sharded-vs-single speedup per sweep point: every
    ``sharded_mix_{rep|a2a}_s{S}_upd{U}`` row is normalized to its
    ``sharded_mix_single_upd{U}`` baseline."""
    out = {}
    for name, us in rows.items():
        if not name.startswith(("sharded_mix_rep_", "sharded_mix_a2a_")) or us <= 0:
            continue
        point = name[len("sharded_mix_"):]          # e.g. rep_s4_upd50
        upd = point.rsplit("_", 1)[-1]              # upd50
        single = rows.get(f"sharded_mix_single_{upd}")
        if single is not None:
            out[point] = single / us
    return out


def _autotune_record() -> dict:
    """Model-mode tile sweep over the bench grid (kernels/autotune.py).

    Pure integer arithmetic — identical on every host — so it is safe to
    embed in the committed artifact and re-derive in CI.  The grid covers
    the suites' build size and the batch sizes the mixed/sharded sweeps
    actually run; geometry matches the bench builds (node_size=32,
    nodes_per_bucket=16)."""
    from repro.kernels.autotune import autotune

    batch = max(1024, common.BUILD_SIZE // 8)
    _, record = autotune(
        (common.BUILD_SIZE // 16, common.BUILD_SIZE),
        (256, batch),
        node_size=32,
        nodes_per_bucket=16,
    )
    return record


def write_bench_json(
    suites: dict[str, dict[str, dict]],
    failed: list[str] = (),
    path: str = BENCH_JSON,
):
    """Serialize the run (schema: DESIGN.md §7, ``flix-bench-v1``)."""
    mixed = {
        name: row["us_per_call"]
        for name, row in suites.get("mixed_batch_engine", {}).items()
    }
    ranges = {
        name: row["us_per_call"]
        for name, row in suites.get("range_mix_engine", {}).items()
    }
    sharded = {
        name: row["us_per_call"]
        for name, row in suites.get("sharded_mix_engine", {}).items()
    }
    durab = {
        name: row["us_per_call"]
        for name, row in suites.get("durability_engine", {}).items()
    }
    gw = {
        name: row["us_per_call"]
        for name, row in suites.get("gateway_engine", {}).items()
    }
    ttl = {
        name: row["us_per_call"]
        for name, row in suites.get("ttl_mix_engine", {}).items()
    }
    tiered = {
        name: row["us_per_call"]
        for name, row in suites.get("tiered_scale_engine", {}).items()
    }
    payload = {
        "schema": "flix-bench-v1",
        "scale": common.SCALE,
        "build_size": common.BUILD_SIZE,
        "suites": suites,
        # non-empty means partial data: these suites threw mid-run, so their
        # row maps are truncated — don't trend against such an artifact
        "failed": list(failed),
        "apply_ops_fused_speedup": _speedups(
            mixed, "mixed_batch_apply_fused_upd", "mixed_batch_apply_ops_upd",
            key_prefix="upd",
        ),
        # double-buffered fused kernel vs the single-buffer fused baseline
        # (the PR9 path, pinned pipeline="off").  On non-TPU hosts the suite
        # re-emits the fused time under the pipelined row, so the ratio is
        # exactly 1.0 — the ≥ 1.0 compare gate then certifies "no
        # regression" portably and the real overlap win shows up on TPU
        "pipelined_speedup": _speedups(
            mixed,
            "mixed_batch_apply_pipelined_upd",
            "mixed_batch_apply_fused_upd",
            key_prefix="upd",
        ),
        # deterministic model-mode tile sweep (kernels/autotune.py): the
        # tuned TileTable rows plus the full per-bucket candidate sweeps,
        # so the artifact documents *why* each tile was chosen
        "autotune": _autotune_record(),
        "range_fused_speedup": _speedups(
            ranges, "range_mix_fused_", "range_mix_ref_"
        ),
        "ttl_fused_speedup": _speedups(
            ttl, "ttl_mix_fused_", "ttl_mix_ref_"
        ),
        "sharded_speedup": _sharded_speedups(sharded),
        # payload-volume ratio (full bytes / delta bytes per churn level):
        # deterministic by construction, so the compare gate never flakes
        # on I/O timing jitter — the wall-time rows stay ungated records
        "durability_delta_speedup": _speedups(
            durab,
            "durability_snap_delta_bytes_churn",
            "durability_snap_full_bytes_churn",
            key_prefix="churn",
        ),
        # goodput(overload)/goodput(base) per traffic point — deterministic
        # request counts on the harness's virtual clock (never wall time),
        # so overload collapsing useful throughput trips the compare gate
        "gateway_goodput_ratio": _speedups(
            gw, "gateway_goodput_base_", "gateway_goodput_overload_"
        ),
        # goodput(10× oversubscribed)/goodput(1×) per read-heavy point —
        # same wall-clock sweep both sides, so the ratio is host-portable
        "tiered_degradation_ratio": _speedups(
            tiered, "tiered_goodput_base_", "tiered_goodput_over_"
        ),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {path}", flush=True)
    return payload


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    filters = sys.argv[1:]
    print("name,us_per_call,derived")
    failed = []
    suites: dict[str, dict[str, dict]] = {}
    for name, mod in SUITES.items():
        if filters and not any(f in name for f in filters):
            continue
        t0 = time.time()
        mark = len(common.RESULTS)
        print(f"# suite {name}", flush=True)
        try:
            mod.run()
        except Exception:  # noqa: BLE001 — keep other suites running
            failed.append(name)
            traceback.print_exc()
        suites[name] = {
            row_name: {"us_per_call": us, "derived": derived}
            for row_name, us, derived in common.RESULTS[mark:]
        }
        print(f"# suite {name} done in {time.time()-t0:.1f}s", flush=True)
    # a filtered run only writes the artifact when asked for explicitly
    # (REPRO_BENCH_JSON) — otherwise `benchmarks.run fig13` would clobber a
    # committed full-run BENCH_PR2.json with a partial one
    if not filters or "REPRO_BENCH_JSON" in os.environ:
        write_bench_json(suites, failed)
    else:
        print(
            "# filtered run: set REPRO_BENCH_JSON=<path> to write the JSON "
            "artifact",
            flush=True,
        )
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
