"""The store range-partitioned over the cell's chips: ``shard_build``, then
closed-loop batches through ``shard_apply_ops_safe``.

Everything but the table's placement is the store's (``systems/store.py``):
the data, the mix, the first batch, the faults and the check, which replays
every batch on the single-table reference and compares every result and
every live pair.  Set-up builds each shard on its own chip over a mesh of
the devices the harness finds (four on the chip, whatever the CPU has in a
rehearsal) and compiles, without running, the sharded executor that
``shard_apply_ops_safe(config=ExecConfig())`` picks for the first batch.
Each batch's ``stats["shard_updates"]`` (updates a shard owns, one entry a
shard) is kept in ``run.batches``.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench.generator import StoreTraffic, seeded_pairs
from chipbench.harness import find_devices
from chipbench.systems import store

FAULTS = store.FAULTS
AXIS = "shards"


def memory_peaks(devs) -> list[int | None]:
    """Each device's ``peak_bytes_in_use``, where the runtime reports it."""
    out = []
    for d in devs:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


class System(store.System):
    # ---- set-up -----------------------------------------------------------
    def setup(self):
        import jax

        from repro.core.config import ExecConfig
        from repro.core.distributed import shard_build, shard_executor
        from repro.core.ops import make_ops, resolve_impl

        c = self.cell.config
        self.devs = find_devices(self.cell.chips, require_chip=False)
        self.mesh = jax.sharding.Mesh(np.array(self.devs), (AXIS,))
        t0 = time.perf_counter()
        rng = np.random.default_rng(self.seed)
        gap = tuple(c["key_gap"])
        keys, vals = seeded_pairs(1 << int(c["log2_keys"]), gap, rng)
        self.initial = (keys, vals)
        self.traffic = StoreTraffic(self.cell.traffic, keys, rng, int(keys[-1]) + gap[1])
        self.config = (self.exec_config or ExecConfig()).replace(routing=c["routing"])
        g = c["geometry"]
        t1 = time.perf_counter()
        self.idx = shard_build(
            keys, vals, self.mesh, axis=AXIS,
            node_size=g["node_size"], nodes_per_bucket=g["nodes_per_bucket"], fill=g["fill"],
        )
        jax.block_until_ready(self.idx)
        self.state = self.idx.state
        # the fences move only when a batch restructures the table
        self.fences = np.asarray(self.state.mkba)
        t2 = time.perf_counter()
        self.pending = self.traffic.batch()
        ops, _ = make_ops(*self.pending)
        run_cfg = self.config.replace(donate=False)
        impl = resolve_impl(run_cfg.impl, ops)
        fn, args, kwargs = shard_executor(self.idx, ops, self.mesh, cfg=run_cfg.replace(impl=impl))
        t3 = time.perf_counter()
        lowered = fn.lower(*args, **kwargs)
        t4 = time.perf_counter()
        # per chip: an SPMD program's analysis counts one device's bytes
        analysis = lowered.compile().memory_analysis()
        self.temp_bytes = None if analysis is None else int(analysis.temp_size_in_bytes)
        t5 = time.perf_counter()
        self.log(
            f"setup: {len(self.devs)} shards, data {t1 - t0:.3f} s, build {t2 - t1:.3f} s, "
            f"first batch and make_ops {t3 - t2:.3f} s, lower ({impl}) {t4 - t3:.3f} s, "
            f"compile {t5 - t4:.3f} s"
        )
        self.log(f"setup: peak_bytes_in_use per chip {memory_peaks(self.devs)}")
        self.read_config = self.config.replace(impl=impl)
        self._shard_updates = []

    # ---- the timed path ---------------------------------------------------
    def _apply(self, state, ops, config=None):
        import jax

        from repro.core.distributed import shard_apply_ops_safe

        with self.spans.span("shard_apply_ops_safe"):
            idx, res, stats = shard_apply_ops_safe(
                self.idx._replace(state=state), ops, self.mesh, config=config or self.config
            )
            jax.block_until_ready(res)
        # the fences a restructure moved
        self.idx = idx
        return idx.state, res, stats

    def step(self, tag, key, val):
        res, perm, stats = super().step(tag, key, val)
        self._shard_updates.append(stats.get("shard_updates"))
        return res, perm, stats

    def window(self, seconds: float, run):
        super().window(seconds, run)
        for b, counts in zip(run.batches, self._shard_updates):
            if counts is not None:
                b["shard_updates"] = np.asarray(counts)
        self.log(f"window: peak_bytes_in_use per chip {memory_peaks(self.devs)}")
