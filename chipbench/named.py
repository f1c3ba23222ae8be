#!/usr/bin/env python3
"""A traced run of one cell, reduced again under the program's own names.

    python chipbench/named.py --workload <cell> --seed <n> --seconds <s>

The run is ``run.py --trace 1``, in this process; its result line is
printed last, unchanged.  From the same trace file, before the harness
deletes it, this also reads the engine's ``flix:`` host spans, and prints
on stderr one line ``named: {...}``:

  * ``breakdown``: the device's longest ops, each named by its ``flix.*``
    scope where it has one, and its longest idle gaps, each named by the
    innermost span around it, ``cb:`` or ``flix:`` (``progtrace.breakdown``);
  * ``scoped_ms``: device ms per batch of the outermost ops of each scope;
  * ``engine_idle_ms``: device idle inside the union of the ``flix:``
    spans, ms per batch;
  * ``host_syncs``: the ``flix:sync.*`` spans per batch;
  * ``idle_ms``: device idle ms per batch by the innermost span, ``cb:``
    or ``flix:``, open while it lasted (``progtrace.idle_by_span``).

``devtrace.load``, which the benchmark's readers see, keeps only the
``cb:`` spans; this tool wraps it for its one run.
"""

from __future__ import annotations

import contextlib
import io
import json
import re
import sys

from run import ROOT, main, parse


def named(trace, spans, cell, batches: int) -> dict:
    from chipbench import progtrace

    dev = trace.devices[0]
    programs = {}
    for impl, pattern in progtrace.PROGRAMS.items():
        if any(re.search(pattern, m.name) for m in trace.modules[dev]):
            programs[pattern] = progtrace.op_scopes(progtrace.executor_text(cell, impl))
    labels = progtrace.label_ops(trace, dev, programs)
    lo, hi = trace.window
    scoped = progtrace.scope_ns(labels, lo, hi)
    return {
        "breakdown": progtrace.breakdown(trace, spans, labels),
        "scoped_ms": {k: v / 1e6 / batches for k, v in sorted(scoped.items())},
        "engine_idle_ms": progtrace.idle_in_spans_ns(trace.ops[dev], spans, lo, hi)
        / 1e6 / batches,
        "host_syncs": progtrace.syncs(spans, lo, hi) / batches,
        "idle_ms": {
            k: v / 1e6 / batches
            for k, v in sorted(
                progtrace.idle_by_span(trace.ops[dev], trace.spans + list(spans), lo, hi).items(),
                key=lambda kv: -kv[1],
            )
        },
    }


def run_named(argv) -> int:
    argv = list(argv) + ["--trace", "1"]
    args = parse(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import devtrace, harness, progtrace

    seen = {}
    load = devtrace.load

    def keep(path):
        seen["trace"] = load(path)
        seen["spans"] = progtrace.load_spans(path)
        return seen["trace"]

    devtrace.load = keep
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = main(argv)
    finally:
        devtrace.load = load
    try:
        if rc == 0 and seen.get("trace") is not None and seen["trace"].devices:
            cell = harness.load_cell(args.workload)
            result = json.loads(out.getvalue().strip().splitlines()[-1])
            batches = result["attempted"] // progtrace.executor_shapes(cell)[3]
            line = named(seen["trace"], seen["spans"], cell, batches)
            print("named: " + json.dumps(line), file=sys.stderr, flush=True)
    finally:
        print(out.getvalue(), end="", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(run_named(sys.argv[1:]))
