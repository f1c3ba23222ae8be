#!/usr/bin/env python3
"""A run of a cell with a fault planted under its timed path.

    python chipbench/prove.py --fault <name> --workload <cell> --seed <n> --seconds <s>

The same run as ``run.py`` in every other respect; its ``correct`` must
come out false.  ``--fault f32_read_keys`` is the control: every read
searches with its key rounded to float32, which breaks "exact results" for
keys above 2^24.  ``--fault stale_reads`` answers the reads from the
pre-batch table, which breaks update-then-read.  The faults are listed in
``systems/store.py``; the benchmark's own runs never plant one.
"""

from __future__ import annotations

import argparse
import sys

from run import main

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True)
    args, rest = ap.parse_known_args()
    sys.exit(main(rest, fault=args.fault))
