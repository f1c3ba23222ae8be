#!/usr/bin/env python3
"""The FliX chip benchmark: one run of one cell.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  With no TPU, with fewer chips, or without the repository's sources
beside ``BENCHMARK.json``, it exits non-zero and prints no result.  In one
process it finds the chip, turns on JAX's compile cache (``.jax_cache/`` in
the checkout unless ``JAX_COMPILATION_CACHE_DIR`` is set), makes the cell's
data from ``--seed``, compiles every program the window calls without
running any, measures for ``--seconds`` (the batch in flight then
completes), checks every answer of the window against the plain reference,
and prints one JSON line last: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (with ``--trace 1`` also ``busy_s``/``window_s``
and ``breakdown``) and ``compared``, the numbers the check compared, each
beside its limit.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, from a profiler trace of the window.

Adding to the benchmark is adding files; the harness finds each by the
name ``BENCHMARK.json`` gives it:

  * a configuration: ``configs/<name>.json`` (the ``file`` of its entry)
    holds ``source``, ``system`` (the module that runs it,
    ``systems/<system>.py``),
    the sizes and geometry as run, ``guarantees``, ``reduced`` (each key
    changed from the source, with both values and why) and ``assumed``
    (each size the source does not give), and ``reference``, the plain
    reference beside it under ``reference/``;
  * a traffic mix: ``traffic/<traffic>.json``, parameters only, read by
    the system's one generator (``generator.py`` for the store: op counts
    per batch, the share of point reads that hit, the range width);
  * a metric: ``metrics/<metric>.py`` with ``read(run)``, which returns
    the number from the run's spans, counters or trace, or ``None`` when
    it finds nothing to read (the metric is then left out of the line).
    Its ``BENCHMARK.json`` entry declares ``unit``, ``better``,
    ``source``, for a per-layer metric ``layer`` and ``moves`` (the
    end-to-end metric it should move), and ``workloads`` (its cells).

Chip peaks live in ``peaks.json`` by ``device_kind``; a kind that is not
there is an error.  ``prove.py`` runs a cell with a fault planted under
the timed path, to show the check fails.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, fault=None) -> int:
    t_start = time.perf_counter()
    args = parse(argv)
    if not (ROOT / "src" / "repro" / "core").is_dir():
        print(f"chipbench: no FliX sources under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import harness
    from repro.compile_cache import enable_compile_cache

    import jax

    cache = enable_compile_cache()
    # every program goes to the cache, so that only a checkout's first run
    # compiles anything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # a Pallas kernel's lowered body holds the absolute path of its source
    # file, which is part of the cache key; without the checkout's root the
    # key is the same wherever the checkout lies
    jax.config.update("jax_hlo_source_file_canonicalization_regex", "^" + re.escape(f"{ROOT}/"))
    print(f"chipbench: compile cache {cache}", file=sys.stderr)
    try:
        cell = harness.load_cell(args.workload)
        result = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), fault=fault, t_start=t_start
        )
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
