#!/usr/bin/env python3
"""Device time of the fused kernel on one batch that updates a few buckets
and on one that updates none.

    PYTHONPATH=src python tools/kernel_probe.py [--log2-keys 23] [--seed 1]
        [--pipeline auto|on|off]

Builds the store of ``chipbench/configs/paper-store-8m.json`` (its geometry
and key gaps, ``--log2-keys`` keys), draws one 4 096-op YCSB A batch from
``chipbench/traffic/ycsb-a-4k.json`` and a second batch of the same keys,
all point reads, and runs each once through the fused executor
(``impl="fused"``, compiled before the run) under the profiler.  Prints one
JSON line per batch: the kernel's and the whole program's device ms, the
executor's ``updated_buckets``, and the same count taken in numpy from the
batch and the pre-batch fences.  The read batch updates no bucket, so its
kernel time is the grid's floor: every step the kernel takes with no
merge/delete compute.  Run from the repository root, on a TPU for device
times (elsewhere the kernel runs in interpret mode and no time is read).
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
KERNEL = r"^%flix_apply_pallas[.0-9]* = .*tpu_custom_call"
PROGRAM = r"^jit_flix_apply_pallas"


def updated_in_numpy(mkba, tag, key, ops_mod) -> int:
    """Buckets that hold an INSERT, or a DELETE of a stored key, under the
    pre-batch fences (the generator deletes stored keys only)."""
    import numpy as np

    upd = (tag == ops_mod.OP_INSERT) | (tag == ops_mod.OP_DELETE)
    b = np.minimum(np.searchsorted(mkba, key[upd], side="left"), mkba.size - 1)
    return int(np.unique(b).size)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--log2-keys", type=int, default=23)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--pipeline", choices=("auto", "on", "off"), default="auto")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    import numpy as np

    from chipbench import devtrace
    from chipbench.generator import StoreTraffic, seeded_pairs
    from repro.core import build, ops as ops_mod
    from repro.core.config import ExecConfig

    conf = json.loads((ROOT / "chipbench/configs/paper-store-8m.json").read_text())
    mix = json.loads((ROOT / "chipbench/traffic/ycsb-a-4k.json").read_text())
    g, gap = conf["geometry"], tuple(conf["key_gap"])
    rng = np.random.default_rng(args.seed)
    keys, vals = seeded_pairs(1 << args.log2_keys, gap, rng)
    traffic = StoreTraffic(mix, keys, rng, int(keys[-1]) + gap[1])
    state = build(
        keys, vals,
        node_size=g["node_size"], nodes_per_bucket=g["nodes_per_bucket"], fill=g["fill"],
    )
    mkba = np.asarray(state.mkba)
    tag, key, val = traffic.batch()
    batches = {
        "ycsb-a": (tag, key, val),
        "reads": (np.full_like(tag, ops_mod.OP_POINT), key, np.zeros_like(val)),
    }
    cfg = ExecConfig(impl="fused", donate=False, pipeline=args.pipeline)
    on_tpu = jax.default_backend() == "tpu"
    for name, (t, k, v) in batches.items():
        ops, _ = ops_mod.make_ops(t, k, v)
        fn, fargs, kwargs = ops_mod.plain_executor(state, ops, impl="fused", cfg=cfg)
        run = fn.lower(*fargs, **kwargs).compile()
        with tempfile.TemporaryDirectory(prefix="kernel-probe-") as tdir:
            jax.profiler.start_trace(tdir)
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
                _, res, stats = run(*fargs)
                jax.block_until_ready((res, stats))
            jax.profiler.stop_trace()
            trace = devtrace.load(next(Path(tdir).rglob("*.xplane.pb")))
        line = {
            "batch": name,
            "pipeline": kwargs["pipeline"],
            "buckets": state.num_buckets,
            "updated_buckets": int(stats["updated_buckets"]),
            "updated_in_numpy": updated_in_numpy(mkba, t, k, ops_mod),
            "kernel_ms": None,
            "program_ms": None,
        }
        if on_tpu and trace.devices:
            dev = trace.devices[0]
            lo, hi = trace.window
            line["kernel_ms"] = devtrace.matching_ns(trace.ops[dev], KERNEL, lo, hi) / 1e6
            line["program_ms"] = (
                devtrace.matching_ns(trace.modules[dev], PROGRAM, lo, hi) / 1e6
            )
        print(json.dumps(line), flush=True)
        del res, stats
    return 0


if __name__ == "__main__":
    sys.exit(main())
