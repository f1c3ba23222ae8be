"""The store: ``core.build`` of seeded keys, then closed-loop batches.

Set-up builds the table from the configuration's sizes and the seed, makes
the mix's first batch and compiles (never runs) the executor that
``apply_ops_safe(config=ExecConfig())`` picks for it.  The window runs
batches one at a time through ``make_ops`` and ``apply_ops_safe`` — the
entry points a user calls — until ``seconds`` have passed; the batch in
flight then completes and is counted whole.  The check replays every
batch of the window on the plain reference of the configuration
(``reference/sorted_model.py``) and compares every result of every op, and
the live pairs left in the table.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench.generator import StoreTraffic, seeded_pairs
from chipbench.reference.sorted_model import (
    EMPTY,
    OP_DELETE,
    OP_INSERT,
    OP_NOP,
    OP_RANGE,
    PER_OP,
    SortedModel,
    live_pair_mismatches,
    result_mismatches,
)

# Faults that the tests plant under the timed path to see the check fail;
# each breaks one guarantee the configuration states.  ``f32_read_keys`` is
# the control run on the chip: every read searches with its key (and a
# range's end) rounded to float32, as a search done in float arithmetic
# would, which breaks "exact results" for keys above 2^24.
FAULTS = (
    "f32_read_keys", "stale_reads", "state_unchanged", "answer_altered",
    "pair_altered", "half_batch_dropped",
)


def _f32(a):
    top = np.iinfo(np.int32).max - 1
    return np.minimum(a.astype(np.float32).astype(np.int64), top)


class System:
    def __init__(self, cell, seed: int, spans, *, exec_config=None, fault=None, log=print):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
        self.cell = cell
        self.seed = int(seed)
        self.spans = spans
        self.exec_config = exec_config
        self.fault = fault
        self.log = log
        self.failed = 0
        self.temp_bytes = None

    # ---- set-up -----------------------------------------------------------
    def setup(self):
        import jax

        from repro.core import build
        from repro.core.config import ExecConfig
        from repro.core.ops import make_ops, plain_executor, resolve_impl

        c = self.cell.config
        t0 = time.perf_counter()
        rng = np.random.default_rng(self.seed)
        gap = tuple(c["key_gap"])
        keys, vals = seeded_pairs(1 << int(c["log2_keys"]), gap, rng)
        self.initial = (keys, vals)
        self.traffic = StoreTraffic(self.cell.traffic, keys, rng, int(keys[-1]) + gap[1])
        self.config = self.exec_config or ExecConfig()
        g = c["geometry"]
        t1 = time.perf_counter()
        self.state = build(
            keys, vals,
            node_size=g["node_size"], nodes_per_bucket=g["nodes_per_bucket"], fill=g["fill"],
        )
        jax.block_until_ready(self.state)
        # the fences move only when a batch restructures the table
        self.fences = np.asarray(self.state.mkba)
        t2 = time.perf_counter()
        # the first batch: made now, sorted now (which compiles make_ops'
        # programs), and its executor compiled for the window to find
        self.pending = self.traffic.batch()
        ops, _ = make_ops(*self.pending)
        run_cfg = self.config.replace(donate=False, validate=False, validate_ranges=False)
        impl = resolve_impl(run_cfg.impl, ops)
        fn, args, kwargs = plain_executor(self.state, ops, impl=impl, cfg=run_cfg)
        t3 = time.perf_counter()
        lowered = fn.lower(*args, **kwargs)
        t4 = time.perf_counter()
        # the runtime's peak_bytes_in_use leaves out a program's temporaries,
        # which the compiler sizes here
        analysis = lowered.compile().memory_analysis()
        self.temp_bytes = None if analysis is None else int(analysis.temp_size_in_bytes)
        t5 = time.perf_counter()
        self.log(
            f"setup: data {t1 - t0:.3f} s, build {t2 - t1:.3f} s, first batch "
            f"and make_ops {t3 - t2:.3f} s, lower ({impl}) {t4 - t3:.3f} s, "
            f"compile {t5 - t4:.3f} s"
        )
        # the stale_reads fault answers the batch's reads from the pre-batch
        # table on the executor the batch itself runs, one program for both
        self.read_config = self.config.replace(impl=impl)
        jax.block_until_ready(self.state)

    # ---- the timed path ---------------------------------------------------
    def _make(self, tag, key, val):
        from repro.core.ops import make_ops

        with self.spans.span("make_ops"):
            return make_ops(tag, key, val)

    def _apply(self, state, ops, config=None):
        import jax

        from repro.core.ops import apply_ops_safe

        with self.spans.span("apply_ops_safe"):
            state, res, stats = apply_ops_safe(state, ops, config=config or self.config)
            jax.block_until_ready(res)
        return state, res, stats

    @staticmethod
    def _updates(tag):
        return (tag == OP_INSERT) | (tag == OP_DELETE)

    @staticmethod
    def _nop(tag, key, val, mask):
        """The batch with the ops under ``mask`` turned into padding."""
        return (
            np.where(mask, OP_NOP, tag).astype(np.int32),
            np.where(mask, EMPTY, key).astype(np.int32),
            np.where(mask, 0, val).astype(np.int32),
        )

    def step(self, tag, key, val):
        """One batch through the entry points: ``(results, perm, stats)``,
        results in the sorted batch's order and ``perm`` mapping each
        submitted op to its sorted position."""
        import dataclasses

        import jax.numpy as jnp

        pre = self.state
        if self.fault == "half_batch_dropped":
            tag, key, val = self._nop(tag, key, val, np.arange(tag.size) % 2 == 1)
        elif self.fault == "f32_read_keys":
            reads = ~self._updates(tag)
            key = np.where(reads, _f32(key), key).astype(np.int32)
            val = np.where(tag == OP_RANGE, _f32(val), val).astype(np.int32)
        if self.fault == "stale_reads":
            # first, so that its table is freed before the batch's own runs
            ops_r, perm_r = self._make(*self._nop(tag, key, val, self._updates(tag)))
            _, res_r, _ = self._apply(pre, ops_r, self.read_config)
        ops, perm = self._make(tag, key, val)
        self.state, res, stats = self._apply(pre, ops)
        if self.fault == "stale_reads":
            res, perm = res_r, perm_r
        elif self.fault == "state_unchanged":
            self.state = pre
        elif self.fault == "answer_altered":
            res = dict(res, value=res["value"].at[0].add(1))
        elif self.fault == "pair_altered":
            live = jnp.argmax((self.state.keys != EMPTY).reshape(-1))
            vals = self.state.vals.reshape(-1).at[live].add(1).reshape(self.state.vals.shape)
            self.state = dataclasses.replace(self.state, vals=vals)
        return res, perm, stats

    def window(self, seconds: float, run):
        import jax

        t0 = time.perf_counter()
        while True:
            with self.spans.span("generate"):
                batch = self.pending if self.pending is not None else self.traffic.batch()
                self.pending = None
            b0 = time.perf_counter()
            res, perm, stats = self.step(*batch)
            # a batch is done when its answers are on the host; the device's
            # memory then holds no more than one batch's results
            with self.spans.span("results_to_host"):
                res, perm = jax.device_get((res, perm))
            run.batches.append({
                "tag": batch[0], "key": batch[1], "val": batch[2],
                "results": res, "perm": perm, "mkba": self.fences,
                "t0": b0, "t1": time.perf_counter(),
            })
            run.ops += int(batch[0].size)
            if int(stats["restructure_retries"]):
                run.restructures += int(stats["restructure_retries"])
                self.fences = np.asarray(self.state.mkba)
            if time.perf_counter() - t0 >= seconds:
                break
        run.window_s = time.perf_counter() - t0
        g = self.cell.config["geometry"]
        run.geometry = (g["nodes_per_bucket"], g["node_size"])
        run.max_results = self.config.max_results

    # ---- the check, after the window -------------------------------------
    def check(self, run):
        import jax

        got_batches = []
        for b in run.batches:
            res = {k: np.asarray(v) for k, v in jax.device_get(b.pop("results")).items()}
            perm = np.asarray(b.pop("perm"))[: b["tag"].size]
            for name in PER_OP:
                res[name] = res[name][perm]
            b["mkba"] = np.asarray(b["mkba"])
            got_batches.append(res)
        keys = np.asarray(self.state.keys).reshape(-1)
        vals = np.asarray(self.state.vals).reshape(-1)
        self.state = None
        live = keys != EMPTY
        keys, vals = keys[live], vals[live]

        model = SortedModel(*self.initial)
        wrong = 0
        for b, got in zip(run.batches, got_batches):
            want = model.apply(b["tag"], b["key"], b["val"], max_results=run.max_results)
            n = result_mismatches(got, want)
            wrong += n
            self.failed += int(b["tag"].size) if n else 0
        pairs = live_pair_mismatches(keys, vals, model)
        return [("result_mismatches", wrong, 0), ("live_pair_mismatches", pairs, 0)]
