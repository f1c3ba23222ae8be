#!/usr/bin/env python3
"""On-chip smoke test of the FliX index's main path.

    python chip_smoke.py              # one chip: store + served phases
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

Runs in one process and refuses to run anywhere but a TPU: with no TPU (or
outside a checkout of this repository) it exits non-zero and prints no
result.  Every phase drives the entry points a user calls and checks what
comes out against a plain model that shares no code with the index:

  * store phase — ``core.build`` of 2^k seeded keys at the default geometry,
    then update-heavy, read-only and TTL batches through
    ``apply_ops_safe(config=ExecConfig())``, so ``impl`` / ``pipeline`` /
    ``donate`` resolve as "auto" does on the chip (the fused Pallas kernel
    for batches with updates).  Every result and the canonical live pairs
    (``checkpoint.serialize``) after every batch must equal a numpy
    sorted-array model.
  * served phase — gateway → ``KVPageIndex`` (with a WAL directory): allocs,
    lookups, frees and page enumerations through the gateway, plus TTL
    allocs and get-or-set steps on the index, checked against a dict.
    Every ticket must come back ``ok``.
  * sharded phase (``--chips 4``) — ``shard_apply_ops_safe`` on a 4-device
    mesh with replicated and a2a routing at 4x the one-chip key count,
    checked against the same numpy model.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# 2^22 keys is the largest power of two at which every executor this smoke
# runs compiles within one v5e's HBM at the default geometry (node_size 32,
# 16 nodes per bucket, fill 1/2): the jnp reference executor's temporaries
# pass 15.75 GiB from 2^23 keys on (PERF.md, "Where the time goes").
DEFAULT_LOG2_KEYS = 22
SHARDS = 4

EMPTY = np.int32(np.iinfo(np.int32).max)
MISS = np.int32(-1)
OP_INSERT, OP_DELETE, OP_POINT, OP_SUCCESSOR, OP_RANGE, OP_EXPIRE = 0, 1, 2, 3, 5, 6


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the independent model: sorted numpy arrays, searchsorted / union / setdiff
# ---------------------------------------------------------------------------
class SortedModel:
    """The index's semantics on three sorted numpy arrays.

    One batch is update-then-read: rows whose deadline is ``<= now`` go
    first (TTL only), then EXPIRE (get-or-set with TTL), INSERT (upsert)
    and DELETE apply, then POINT / SUCCESSOR / RANGE read the result.
    """

    def __init__(self, keys, vals):
        self.k = np.asarray(keys, np.int32)
        self.v = np.asarray(vals, np.int32)
        self.e = None  # expiry deadlines, once a TTL batch arrived

    def _find(self, q):
        pos = np.searchsorted(self.k, q)
        hit = pos < self.k.size
        hit[hit] = self.k[pos[hit]] == q[hit]
        return pos, hit

    def apply(self, tag, key, val, exp=None, now=None, max_results=128):
        if exp is not None and self.e is None:
            self.e = np.full(self.k.shape, EMPTY, np.int32)
        if now is not None and self.e is not None:
            live = self.e > now
            self.k, self.v, self.e = self.k[live], self.v[live], self.e[live]
        n = key.size
        value = np.full(n, MISS, np.int32)
        succ_key = np.full(n, EMPTY, np.int32)

        # EXPIRE: present → keep the value, take the new deadline; absent →
        # insert (val, exp).  Either way it is an upsert of some value.
        is_x = tag == OP_EXPIRE
        put_v = val.copy()
        if is_x.any():
            pos, hit = self._find(key[is_x])
            stored = np.where(hit, self.v[np.minimum(pos, self.k.size - 1)], MISS)
            value[is_x] = stored
            put_v[np.flatnonzero(is_x)[hit]] = stored[hit]
        put = (tag == OP_INSERT) | is_x
        pk, pv = key[put], put_v[put]
        keep = ~np.isin(self.k, pk, assume_unique=True)
        k = np.concatenate([self.k[keep], pk])
        v = np.concatenate([self.v[keep], pv])
        order = np.argsort(k, kind="stable")
        if self.e is not None:
            pe = exp[put] if exp is not None else np.full(pk.size, EMPTY, np.int32)
            self.e = np.concatenate([self.e[keep], pe])[order]
        self.k, self.v = k[order], v[order]

        dk = key[tag == OP_DELETE]
        gone = np.isin(self.k, dk, assume_unique=True)
        deleted = int(gone.sum())
        self.k, self.v = self.k[~gone], self.v[~gone]
        if self.e is not None:
            self.e = self.e[~gone]

        is_p = tag == OP_POINT
        pos, hit = self._find(key[is_p])
        value[is_p] = np.where(hit, self.v[np.minimum(pos, self.k.size - 1)], MISS)
        is_s = tag == OP_SUCCESSOR
        pos = np.searchsorted(self.k, key[is_s])
        found = pos < self.k.size
        pc = np.minimum(pos, self.k.size - 1)
        succ_key[is_s] = np.where(found, self.k[pc], EMPTY)
        value[is_s] = np.where(found, self.v[pc], MISS)

        # RANGE [lo, hi): earlier sorted ops win the budget, each keeps a
        # prefix of its smallest keys
        is_r = tag == OP_RANGE
        lo = np.searchsorted(self.k, key)
        hi = np.searchsorted(self.k, val)
        full = np.where(is_r, np.maximum(hi - lo, 0), 0).astype(np.int64)
        start_full = np.cumsum(full) - full
        start = np.minimum(start_full, max_results)
        emit = np.minimum(full, max_results - start)
        rk = np.full(max_results, EMPTY, np.int32)
        rv = np.full(max_results, MISS, np.int32)
        for i in np.flatnonzero(is_r & (emit > 0)):
            s, c = int(start[i]), int(emit[i])
            rk[s : s + c] = self.k[lo[i] : lo[i] + c]
            rv[s : s + c] = self.v[lo[i] : lo[i] + c]
        return {
            "value": value,
            "succ_key": succ_key,
            "range_key": rk,
            "range_val": rv,
            "range_start": np.where(is_r, start, 0).astype(np.int32),
            "range_count": np.where(is_r, emit, 0).astype(np.int32),
        }, deleted


def check_results(got: dict, want: dict, what: str) -> int:
    """Every result array equal to the model's; returns the values compared."""
    n = 0
    for name, w in want.items():
        g = np.asarray(got[name])
        if g.shape != w.shape or not np.array_equal(g, w):
            bad = np.flatnonzero(g.reshape(-1) != w.reshape(-1))[:5]
            raise AssertionError(
                f"{what}: {name} differs from the model at {bad.tolist()}: "
                f"got {g.reshape(-1)[bad].tolist()} want {w.reshape(-1)[bad].tolist()}"
            )
        n += w.size
    return n


def check_live_pairs(state, model: SortedModel, what: str) -> int:
    """The index's canonical live pairs equal the model's; returns the count."""
    from repro.checkpoint.serialize import bucket_segments

    _, ks, vs, es = bucket_segments(state)
    ks, vs, es = (np.asarray(a, np.int32) for a in (ks, vs, es))
    want_e = model.e if model.e is not None else np.full(model.k.shape, EMPTY, np.int32)
    for name, g, w in (("keys", ks, model.k), ("vals", vs, model.v), ("exps", es, want_e)):
        if not np.array_equal(g, w):
            raise AssertionError(
                f"{what}: live {name} differ from the model "
                f"({g.size} vs {w.size} pairs)"
            )
    return int(ks.size)


# ---------------------------------------------------------------------------
# traffic, made from the seed
# ---------------------------------------------------------------------------
def seeded_keys(n: int, rng):
    """n distinct ascending int32 keys with random gaps (room for inserts
    between them) and random int32 values — made in bulk, O(n)."""
    keys = np.cumsum(rng.integers(1, 64, size=n, dtype=np.int64)).astype(np.int32)
    vals = rng.integers(0, 2**31 - 1, size=n, dtype=np.int64).astype(np.int32)
    return keys, vals


def fresh_keys(model: SortedModel, n: int, rng, space: int):
    """n distinct keys absent from the model."""
    cand = np.unique(rng.integers(0, space, size=2 * n + 64, dtype=np.int64))
    cand = cand.astype(np.int32)
    _, hit = model._find(cand)
    cand = rng.permutation(cand[~hit])
    assert cand.size >= n
    return cand[:n]


def update_heavy(model, n, rng, space):
    """50% insert/delete, 50% point/successor."""
    q = n // 4
    ins = fresh_keys(model, q, rng, space)
    dels = rng.choice(model.k, size=q, replace=False)
    pts = np.concatenate([rng.choice(model.k, size=q // 2), rng.integers(0, space, q - q // 2)])
    succ = rng.integers(0, space, size=n - 3 * q)
    tag = np.repeat([OP_INSERT, OP_DELETE, OP_POINT, OP_SUCCESSOR], [q, q, q, n - 3 * q])
    key = np.concatenate([ins, dels, pts, succ]).astype(np.int32)
    val = rng.integers(0, 2**31 - 1, size=n, dtype=np.int64).astype(np.int32)
    return tag, key, val, None


def read_only(model, n, rng, space, n_ranges=64, width=96):
    """Point, successor and range reads; ~3 keys per range, so the default
    128-slot range budget truncates some of them."""
    r = n_ranges
    q = (n - r) // 2
    pts = np.concatenate([rng.choice(model.k, size=q // 2), rng.integers(0, space, q - q // 2)])
    succ = rng.integers(0, space, size=n - r - q)
    lo = rng.integers(0, space, size=r)
    tag = np.repeat([OP_POINT, OP_SUCCESSOR, OP_RANGE], [q, n - r - q, r])
    key = np.concatenate([pts, succ, lo]).astype(np.int32)
    val = np.zeros(n, np.int32)
    val[tag == OP_RANGE] = (lo + width).astype(np.int32)
    return tag, key, val, None


def ttl_batch(model, n, rng, space, now, ttl_keys):
    """TTL traffic at clock ``now``: inserts with deadlines, get-or-set
    (EXPIRE) on present and absent keys, and point reads of keys that
    earlier TTL batches gave deadlines (some have passed by now)."""
    q = n // 4
    ins = fresh_keys(model, 2 * q, rng, space)
    present = np.setdiff1d(model.k, ttl_keys, assume_unique=True)
    refresh = rng.choice(present, size=q // 2, replace=False)
    xkeys = np.concatenate([refresh, ins[q : q + q - q // 2]])
    reads = np.concatenate([ttl_keys, rng.choice(model.k, size=n)])[: n - 2 * q]
    tag = np.repeat([OP_INSERT, OP_EXPIRE, OP_POINT], [q, xkeys.size, reads.size])
    key = np.concatenate([ins[:q], xkeys, reads]).astype(np.int32)
    val = rng.integers(0, 2**31 - 1, size=n, dtype=np.int64).astype(np.int32)
    exp = np.full(n, EMPTY, np.int32)
    upd = (tag == OP_INSERT) | (tag == OP_EXPIRE)
    exp[upd] = now + rng.integers(1, 2000, size=int(upd.sum()))
    return tag, key, val, exp


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def compiled_peak(state, ops, config):
    """Compile (not run) the executor ``apply_ops`` picks for ``ops`` and
    report its device bytes and whether the Pallas kernel is in it."""
    from repro.core.ops import OpBatch, plain_executor, resolve_impl

    # a TTL batch runs the same plain executor on the value plane
    state = dataclasses.replace(state.drop_volatile(), exps=None)
    ops = OpBatch(tag=ops.tag, key=ops.key, val=ops.val)
    impl = resolve_impl(config.impl, ops)
    fn, args, kwargs = plain_executor(state, ops, impl=impl, cfg=config)
    compiled = fn.lower(*args, **kwargs).compile()
    m = compiled.memory_analysis()
    peak = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    name = impl if impl == "reference" else f"fused(pipeline={kwargs['pipeline']})"
    return name, peak, "tpu_custom_call" in compiled.as_text()


def store_phase(log2_keys: int, seed: int, config, *, sizes=None, report_compiles=True):
    """Build 2^log2_keys seeded keys, run the mixes, check every batch."""
    import jax

    from repro.core import build
    from repro.core.ops import apply_ops_safe, make_ops

    rng = np.random.default_rng(seed)
    n_keys = 1 << log2_keys
    keys, vals = seeded_keys(n_keys, rng)
    space = int(keys[-1]) + 64
    t0 = time.perf_counter()
    state = build(keys, vals)
    jax.block_until_ready(state.keys)
    model = SortedModel(keys, vals)
    log(
        f"store: built {n_keys} keys (2^{log2_keys}) in "
        f"{time.perf_counter() - t0:.1f} s; geometry {state.geometry}, "
        f"state {state.memory_bytes() / 2**30:.3f} GiB"
    )
    sizes = sizes or {"update": (1 << 12, 1 << 14), "read": 1 << 16, "ttl": 1 << 12}
    plan = [("update-heavy", update_heavy, n) for n in sizes["update"]]
    plan += [("read-only", read_only, sizes["read"])]
    plan += [("ttl", None, sizes["ttl"]), ("ttl", None, sizes["ttl"])]

    summary = {"ops": 0, "matched": 0, "live_pairs": 0, "batches": 0, "fused": False}
    ttl_keys = np.zeros(0, np.int32)
    now = None
    for i, (mix, make, n) in enumerate(plan):
        if mix == "ttl":
            now = 1000 * (1 + (now is not None))
            tag, key, val, exp = ttl_batch(model, n, rng, space, now, ttl_keys)
        else:
            tag, key, val, exp = make(model, n, rng, space)
        ops, _ = make_ops(tag, key, val, exps=exp)
        stag, skey, sval, sexp = ops.to_host()
        if mix == "ttl":
            ttl_keys = np.unique(skey[(stag == OP_INSERT) | (stag == OP_EXPIRE)])
        if report_compiles:
            name, peak, kernel = compiled_peak(state, ops, config)
            summary["fused"] |= kernel
            log(
                f"store: batch {i} {mix} n={n}: executor {name}, compiled peak "
                f"{peak / 2**30:.3f} GiB, tpu_custom_call={kernel}"
            )
        t0 = time.perf_counter()
        state, res, stats = apply_ops_safe(state, ops, config=config, now=now)
        jax.block_until_ready(res["value"])
        dt = time.perf_counter() - t0
        want, deleted = model.apply(
            stag, skey, sval, sexp, now=now, max_results=config.max_results
        )
        matched = check_results(res, want, f"batch {i} ({mix})")
        if int(stats["deleted"]) != deleted:
            raise AssertionError(f"batch {i}: deleted {int(stats['deleted'])} != {deleted}")
        live = check_live_pairs(state, model, f"batch {i} ({mix})")
        summary["ops"] += n
        summary["matched"] += matched
        summary["live_pairs"] += live
        summary["batches"] += 1
        log(
            f"store: batch {i} {mix} n={n} now={now}: {matched} result values "
            f"and {live} live pairs match the model; deleted={deleted} "
            f"restructures={stats['restructure_retries']} wall {dt:.2f} s"
        )
    return summary


def served_phase(seed: int, config, *, steps: int = 36, wal_dir: str):
    """Gateway → KVPageIndex with a WAL directory, checked against a dict."""
    from repro.serve.gateway import Gateway, Request
    from repro.serve.kv_index import PAGE_BITS, KVPageIndex

    rng = np.random.default_rng(seed + 1)
    idx = KVPageIndex(config=config, durability_dir=wal_dir)
    gw = Gateway(idx, max_batch_ops=512, max_queue_ops=1 << 14, max_pages=16,
                 default_rate=1e9, default_burst=1e9)
    model: dict[int, int] = {}            # (seq << PAGE_BITS | page) -> slot
    deadline: dict[int, int] = {}         # TTL'd keys -> deadline
    active: dict[int, int] = {}           # gateway seq -> pages allocated
    next_seq, next_slot, ttl_seq = 1, 0, 1 << 18
    tickets = ok = 0
    for step in range(steps):
        now = float(step)
        for _ in range(int(rng.integers(1, 4))):
            active[next_seq] = 0
            next_seq += 1
        expect = []
        for s in list(active):
            if active[s] < 16 and rng.random() < 0.6:
                p, slot = active[s], next_slot
                active[s] += 1
                next_slot += 1
                req = Request("t0", f"a{s}.{p}", "alloc", (s,), (p,), (slot,))
                expect.append((gw.submit(req, now=now), {"applied": True}))
                model[(s << PAGE_BITS) | p] = slot
        allocated = {k for k in model if (k >> PAGE_BITS) in active}
        look = [k for k in allocated if rng.random() < 0.5][:64]
        if look:
            seqs = tuple(k >> PAGE_BITS for k in look)
            pages = tuple(k & ((1 << PAGE_BITS) - 1) for k in look)
            req = Request("t1", f"l{step}", "lookup", seqs, pages)
            expect.append((gw.submit(req, now=now), [model[k] for k in look]))
        done = [s for s in active if active[s] > 0 and rng.random() < 0.15]
        done = [s for s in done if not any((k >> PAGE_BITS) == s for k in look)]
        for s in done[:2]:
            req = Request("t0", f"f{s}", "free", (s,))
            expect.append((gw.submit(req, now=now), {"applied": True}))
        probe = [s for s in active if s not in done[:2]][:2]
        if probe:
            req = Request("t1", f"p{step}", "pages", tuple(probe))
            expect.append((gw.submit(req, now=now), ("pages", tuple(probe))))
        gw.drain(now=now)
        for s in done[:2]:
            for p in range(active.pop(s)):
                model.pop((s << PAGE_BITS) | p, None)
        for tk, want in expect:
            tickets += 1
            if not tk.ok:
                raise AssertionError(f"served step {step}: ticket {tk.request.key} {tk.status} {tk.error}")
            if isinstance(want, tuple):
                for s, got in zip(want[1], tk.value):
                    mine = sorted(k for k in model if (k >> PAGE_BITS) == s)
                    pages = [k & ((1 << PAGE_BITS) - 1) for k in mine]
                    if list(np.asarray(got["pages"])) != pages or list(
                        np.asarray(got["slots"])
                    ) != [model[k] for k in mine]:
                        raise AssertionError(f"served step {step}: pages of seq {s} differ")
            elif isinstance(want, list):
                if list(np.asarray(tk.value)) != want:
                    raise AssertionError(f"served step {step}: lookup {tk.request.key} differs")
            ok += 1
        # TTL traffic straight on the index every fourth step: pages with
        # deadlines, then a get-or-set at a later clock
        if step % 4 == 3:
            clock = 100 * step
            for k in [k for k, d in deadline.items() if d <= clock]:
                del deadline[k]
                model.pop(k, None)
            seqs = np.full(4, ttl_seq)
            pages = np.arange(4)
            slots = np.arange(4) + next_slot
            dls = clock + np.array([50, 150, 250, 10_000])
            res = idx.step(allocs=(seqs, pages, slots, dls), now=clock)
            for p in range(4):
                model[(ttl_seq << PAGE_BITS) | p] = int(slots[p])
                deadline[(ttl_seq << PAGE_BITS) | p] = int(dls[p])
            next_slot += 4
            later = clock + 200
            for k in [k for k, d in deadline.items() if d <= later]:
                del deadline[k]
                model.pop(k, None)
            got = idx.getset(seqs, pages, slots + 1000, np.full(4, later + 5000), now=later)
            want = [model.get((ttl_seq << PAGE_BITS) | p, -1) for p in range(4)]
            if list(np.asarray(got)) != want:
                raise AssertionError(f"served step {step}: getset {list(np.asarray(got))} != {want}")
            for p in range(4):
                k = (ttl_seq << PAGE_BITS) | p
                model.setdefault(k, int(slots[p]) + 1000)
                deadline[k] = later + 5000
            ttl_seq += 1
        if idx.live_pages() != len(model):
            raise AssertionError(f"served step {step}: {idx.live_pages()} live pages != {len(model)}")
    stats = dict(gw.metrics)
    gw.close(now=float(steps))
    failures = stats["engine_failures"]
    if failures:
        raise AssertionError(f"served: {failures} engine failures")
    log(
        f"served: {steps} steps, {tickets} tickets, {ok} ok, {stats['batches']} batches, "
        f"{stats['committed_ops']} ops, {len(model)} live pages match the dict, "
        f"restructures={stats['restructure_retries']}"
    )
    return {"tickets": tickets, "ok": ok, "pages": len(model)}


def sharded_phase(
    log2_keys: int, seed: int, n_shards: int, config, *, sizes=(1 << 12,)
):
    """shard_apply_ops_safe on an n_shards mesh, both routings, vs the model."""
    import jax

    from repro.core.distributed import (
        make_shard_mesh, shard_apply_ops_safe, shard_batch, shard_build,
    )
    from repro.core.ops import make_ops

    rng = np.random.default_rng(seed)
    n_keys = 1 << log2_keys
    keys, vals = seeded_keys(n_keys, rng)
    space = int(keys[-1]) + 64
    mesh = make_shard_mesh(n_shards)
    t0 = time.perf_counter()
    idx = shard_build(keys, vals, mesh)
    jax.block_until_ready(idx.state.keys)
    devs = sorted({d.id for d in idx.state.keys.sharding.device_set})
    log(
        f"sharded: built {n_keys} keys (2^{log2_keys}) over {n_shards} shards in "
        f"{time.perf_counter() - t0:.1f} s; state on devices {devs}, per-shard "
        f"keys shape {idx.state.keys.addressable_shards[0].data.shape}"
    )
    if len(devs) != n_shards:
        raise AssertionError(f"sharded state lives on {devs}, not {n_shards} devices")
    model = SortedModel(keys, vals)
    summary = {"ops": 0, "matched": 0, "live_pairs": 0}
    for routing in ("replicated", "a2a"):
        cfg = dataclasses.replace(config, routing=routing)
        for n in sizes:
            for mix, make in (("update-heavy", update_heavy), ("read-only", read_only)):
                tag, key, val, _ = make(model, n, rng, space)
                ops, _ = make_ops(tag, key, val)
                stag, skey, sval, _ = ops.to_host()
                run = shard_batch(ops, mesh) if routing == "a2a" else ops
                t0 = time.perf_counter()
                idx, res, stats = shard_apply_ops_safe(idx, run, mesh, config=cfg)
                jax.block_until_ready(res["value"])
                dt = time.perf_counter() - t0
                want, _ = model.apply(stag, skey, sval, max_results=cfg.max_results)
                what = f"sharded {routing} {mix} n={n}"
                matched = check_results(res, want, what)
                summary["ops"] += n
                summary["matched"] += matched
                log(
                    f"{what}: {matched} result values match the model; "
                    f"a2a_retries={stats['a2a_retries']} "
                    f"restructures={stats['restructure_retries']} wall {dt:.2f} s"
                )
    # one canonicalization of the whole sharded state (a host sort of every
    # bucket row) covers the updates of every batch above
    summary["live_pairs"] = check_live_pairs(idx.state, model, "sharded final state")
    log(f"sharded: final state's {summary['live_pairs']} live pairs match the model")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDS), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro" / "core").is_dir():
        print(f"chip_smoke: no FliX sources under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 3
    count = len(jax.devices())
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {count} devices", file=sys.stderr)
        return 3
    log(f"device: {dev.platform} {dev.device_kind} x{count}; jax {jax.__version__}; "
        f"compile cache {cache}")

    from repro.core.config import ExecConfig

    t0 = time.perf_counter()
    if args.chips == SHARDS:
        log2 = DEFAULT_LOG2_KEYS + 2
        log(f"sharded phase: 2^{log2} keys = 4x the one-chip count 2^{DEFAULT_LOG2_KEYS}")
        s = sharded_phase(log2, args.seed, SHARDS, ExecConfig())
        log(f"sharded: {s['ops']} ops, {s['matched']} values, {s['live_pairs']} live pairs matched")
    else:
        log(f"store phase: 2^{DEFAULT_LOG2_KEYS} keys (largest power of two whose "
            f"executors fit one chip's HBM at the default geometry)")
        s = store_phase(DEFAULT_LOG2_KEYS, args.seed, ExecConfig())
        if not s["fused"]:
            raise AssertionError("the fused kernel never compiled into a batch")
        log(f"store: {s['batches']} batches, {s['ops']} ops, {s['matched']} values, "
            f"{s['live_pairs']} live pairs matched; fused kernel ran compiled "
            f"(tpu_custom_call) on {dev.device_kind}")
        with tempfile.TemporaryDirectory(prefix="flix-wal-", dir=str(ROOT)) as wal:
            v = served_phase(args.seed, ExecConfig(), wal_dir=wal)
        log(f"served: all {v['tickets']} tickets ok")
    log(f"total wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
