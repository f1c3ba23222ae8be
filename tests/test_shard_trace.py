"""The sharded engine's names, counter, executor hook and per-shard build,
on 4 fake host devices.

One subprocess (the main test process keeps its single device) compiles
both sharded programs for a tiny batch with a RANGE op, runs a mixed batch
under the profiler, and builds tables both ways; each test reads its part
of what the subprocess reports."""

from __future__ import annotations

import json

import pytest

from repro.core.trace import SPANS
from tests.conftest import run_with_devices

CHECKS = r"""
import json, math
import jax, jax.numpy as jnp, numpy as np
from repro import core
from repro.core import distributed as dist
from repro.core.build import build_from_sorted
from repro.core.config import ExecConfig
from repro.core.expiry import NO_EXPIRY
from repro.core.state import EMPTY, MIN_KEY
from chipbench import progtrace

out = {}
mesh = dist.make_shard_mesh(4)
rng = np.random.default_rng(5)
keys = np.sort(rng.choice(100_000, 1024, replace=False)).astype(np.int32)
idx = dist.shard_build(keys, keys, mesh, node_size=16, nodes_per_bucket=8)
n = 256
tag = rng.choice([core.OP_INSERT, core.OP_DELETE, core.OP_POINT, core.OP_SUCCESSOR,
                  core.OP_RANGE], n).astype(np.int32)
live = rng.choice(keys, n, replace=False)
fresh = rng.choice(np.setdiff1d(np.arange(100_000), keys), n, replace=False)
key = np.where(tag == core.OP_INSERT, fresh, live).astype(np.int32)
val = np.where(tag == core.OP_RANGE, key + 500, key).astype(np.int32)
ops, _ = core.make_ops(tag, key, val)

# the compiled program of each routing, its name and its scopes
out["scopes"], out["module"] = {}, {}
for routing in ("replicated", "a2a"):
    fn, args, kwargs = dist.shard_executor(idx, ops, mesh, cfg=ExecConfig(routing=routing))
    text = fn.lower(*args, **kwargs).compile().as_text()
    out["module"][routing] = text.split(",")[0]
    out["scopes"][routing] = sorted(set(progtrace.op_scopes(text).values()))

# the counter: one entry a shard, the updates whose key the shard's fences hold
fences = np.asarray(idx.part_fences)
is_upd = np.isin(tag, [core.OP_INSERT, core.OP_DELETE])
want = np.bincount(np.searchsorted(fences, key[is_upd], side="left"), minlength=4)
out["updates"] = {"want": want.tolist(), "total": int(is_upd.sum())}
for routing in ("replicated", "a2a"):
    run = ops if routing == "replicated" else dist.shard_batch(ops, mesh)
    _, _, stats = dist.shard_apply_ops_safe(idx, run, mesh, config=ExecConfig(routing=routing))
    out["updates"][routing] = np.asarray(stats["shard_updates"]).tolist()

# shard_apply_ops runs what shard_executor hands back
seen = []
real = dist.shard_executor
def spy(*a, **k):
    got = real(*a, **k)
    seen.append(got)
    return got
dist.shard_executor = spy
dist.shard_apply_ops(idx, ops, mesh, config=ExecConfig())
dist.shard_executor = real
(fn, args, kwargs), = seen
fn2, args2, kwargs2 = dist.shard_executor(idx, ops, mesh, cfg=ExecConfig())
out["executor_same"] = (fn is fn2) and (
    fn.lower(*args, **kwargs).as_text() == fn2.lower(*args2, **kwargs2).as_text())
state, res, _ = fn2(*args2, **kwargs2)
_, want_res, _ = dist.shard_apply_ops(idx, ops, mesh, config=ExecConfig())
out["executor_results"] = all(
    bool((np.asarray(res[k]) == np.asarray(want_res[k])).all()) for k in want_res)

# shard_apply_ops_safe's spans, in a CPU profiler trace
import tempfile, pathlib
with tempfile.TemporaryDirectory() as d:
    jax.profiler.start_trace(d)
    _, res, _ = dist.shard_apply_ops_safe(idx, ops, mesh)
    jax.block_until_ready(res)
    jax.profiler.stop_trace()
    spans = progtrace.load_spans(next(pathlib.Path(d).rglob("*.xplane.pb")))
out["spans"] = sorted(s.name for s in spans)

# per-shard build against the whole-table build placed over the mesh
def whole(k, v, extra, e, ns=16, npb=8, fill=0.5):
    p = int(ns * fill)
    nb = max(1, math.ceil(math.ceil((int((k != EMPTY).sum()) + extra) / p) / 4)) * 4
    st = build_from_sorted(jnp.asarray(k), jnp.asarray(v), num_buckets=nb,
                           nodes_per_bucket=npb, node_size=ns, fill=fill)
    arrays = {f: np.asarray(getattr(st, f)) for f in
              ("keys", "vals", "node_count", "node_max", "num_nodes", "mkba")}
    if e is not None:
        be = build_from_sorted(jnp.asarray(k), jnp.asarray(e), num_buckets=nb,
                               nodes_per_bucket=npb, node_size=ns, fill=fill)
        arrays["exps"] = np.where(arrays["keys"] == EMPTY, NO_EXPIRY, np.asarray(be.vals))
    pf = arrays["mkba"].reshape(4, -1)[:, -1]
    arrays["part_fences"] = pf
    arrays["lower_fence"] = np.concatenate([[MIN_KEY], pf[:-1]])
    return arrays

out["build"] = {}
cases = {"even": (4096, 0, False, 0), "uneven": (1000, 0, False, 0),
         "extra_keys": (1000, 700, False, 0), "exps": (777, 300, True, 13)}
for name, (nk, extra, with_e, pad) in cases.items():
    k = np.sort(rng.choice(10**6, nk, replace=False)).astype(np.int32)
    v = rng.integers(0, 1000, nk).astype(np.int32)
    k = np.concatenate([k, np.full(pad, EMPTY, np.int32)])
    v = np.concatenate([v, rng.integers(1, 9, pad).astype(np.int32)])
    e = rng.integers(0, 10**6, k.size).astype(np.int32) if with_e else None
    got = dist.shard_build(k, v, mesh, node_size=16, nodes_per_bucket=8,
                           extra_keys=extra, sorted_exps=e)
    want = whole(k, v, extra, e)
    arrays = {f: getattr(got.state, f) for f in want if f not in ("part_fences", "lower_fence")}
    arrays.update(part_fences=got.part_fences, lower_fence=got.lower_fence)
    out["build"][name] = {
        "equal": {f: bool(np.array_equal(np.asarray(arrays[f]), want[f])) for f in want},
        "devices": sorted({str(sh.device) for sh in got.state.keys.addressable_shards}),
        "shard_shape": list(got.state.keys.addressable_shards[0].data.shape),
        "needs_restructure": bool(got.state.needs_restructure),
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def checks():
    return json.loads(run_with_devices(CHECKS, n_devices=4).strip().splitlines()[-1])


ALL = {"flix.shard.mask", "flix.shard.apply", "flix.shard.combine", "flix.shard.range_counts"}


@pytest.mark.parametrize(
    "routing, scopes", [("replicated", ALL), ("a2a", ALL | {"flix.shard.exchange"})]
)
def test_every_shard_scope_reaches_the_compiled_program(checks, routing, scopes):
    got = set(checks["scopes"][routing])
    assert scopes <= got, scopes - got
    # the inner executor's own scopes stay innermost
    assert any(s.startswith("flix.reference.") for s in got)


@pytest.mark.parametrize("routing", ["replicated", "a2a"])
def test_the_program_keeps_its_stable_name(checks, routing):
    assert checks["module"][routing] == f"HloModule jit_flix_shard_{routing}"


def test_the_sharded_spans_exist():
    for name in ("shard_apply_ops_safe", "sync.has_ranges", "dispatch.sharded"):
        assert name in SPANS


def test_shard_apply_ops_safe_writes_its_spans(checks):
    assert checks["spans"] == sorted([
        "flix:shard_apply_ops_safe", "flix:sync.has_ranges",
        "flix:dispatch.sharded", "flix:sync.needs_restructure",
    ])


@pytest.mark.parametrize("routing", ["replicated", "a2a"])
def test_shard_updates_counts_the_updates_each_shard_owns(checks, routing):
    got = checks["updates"][routing]
    assert got == checks["updates"]["want"]
    assert sum(got) == checks["updates"]["total"] > 0


def test_shard_executor_lowers_what_shard_apply_ops_runs(checks):
    assert checks["executor_same"] is True
    assert checks["executor_results"] is True


@pytest.mark.parametrize("case", ["even", "uneven", "extra_keys", "exps"])
def test_per_shard_build_equals_the_whole_table_build(checks, case):
    got = checks["build"][case]
    assert all(got["equal"].values()), got["equal"]
    assert len(got["devices"]) == 4
    assert got["shard_shape"][1:] == [8, 16]
    assert got["needs_restructure"] is False
