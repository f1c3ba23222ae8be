"""The sharded cell rehearsed on 4 fake CPU devices at a tiny size, through
the harness's own functions: the fused kernel forced on (interpret mode)
under ``shard_apply_ops_safe``, the check passing on the system's output and
failing on each planted fault, the control failing, the same batches giving
the single-table store's answers, and the cell's readers reading nothing a
CPU run cannot give.  The runs go to subprocesses (the main test process
keeps its single device); each test reads its part of what they report."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import devtrace, harness  # noqa: E402
from tests.conftest import run_with_devices  # noqa: E402

CELL = "sharded-mixed-4k"
FAULTS = {
    "answer_altered": "result_mismatches",
    "pair_altered": "live_pair_mismatches",
    "state_unchanged": "live_pair_mismatches",
    "half_batch_dropped": "live_pair_mismatches",
    "stale_reads": "result_mismatches",
}
DEVICE_READERS = [
    "shard_program_ms.sharded", "shard_combine_ms.sharded", "fused_kernel_ms.sharded",
    "fused_roofline.sharded", "device_idle_pct.sharded",
]

PRELUDE = r"""
import json, sys
sys.path.insert(0, ".")
from chipbench import harness
from repro.core.config import ExecConfig

FUSED = ExecConfig(impl="fused", pipeline="on")
BENCH = harness.load_bench()

def tiny(name="sharded-mixed-4k", *, log2_keys=10, wide_keys=False):
    cell = harness.load_cell(name, BENCH)
    cell.config["log2_keys"] = log2_keys
    if wide_keys:
        cell.config["key_gap"] = [1 << 15, 1 << 16]
    cell.traffic["ops"] = {k: -(-v // 64) for k, v in cell.traffic["ops"].items()}
    return cell

def run(cell, *, seed=2**33 + 5, trace=False, fault=None, config=FUSED):
    return harness.run_cell(cell, seed, 0.2, trace, bench=BENCH, require_chip=False,
                            exec_config=config, fault=fault, log=lambda _msg: None)
"""

RUNS = PRELUDE + r"""
out = {"trace": {}, "faults": {}}
for trace in (False, True):
    out["trace"][str(trace)] = run(tiny(), trace=trace)
for fault in %s:
    # 2^6 keys: a quarter of the table changes per batch, so that reads of
    # keys the same batch updates are sure to occur
    out["faults"][fault] = run(tiny(log2_keys=6), fault=fault)["compared"]
print(json.dumps(out))
""" % sorted(FAULTS)

SAME = PRELUDE + r"""
import jax, numpy as np
from chipbench.reference.sorted_model import EMPTY, PER_OP
from chipbench.systems import sharded, store

out = {"control": []}
for seed in (11, 2**31 + 3, 2**32 + 9):
    r = run(tiny(wide_keys=True), seed=seed, fault="f32_read_keys", config=None)
    out["control"].append([r["correct"], r["compared"]["result_mismatches"]["value"]])

def answers(module):
    sut = module.System(tiny(), 2**31 + 7, harness.Spans(False), exec_config=FUSED,
                        log=lambda _msg: None)
    sut.setup()
    got = []
    for i in range(3):
        batch = sut.pending if i == 0 else sut.traffic.batch()
        res, perm, _ = sut.step(*batch)
        res, perm = jax.device_get((res, perm))
        perm = np.asarray(perm)[: batch[0].size]
        got.append({k: np.asarray(res[k])[perm].tolist() for k in PER_OP})
    keys = np.asarray(sut.state.keys).reshape(-1)
    vals = np.asarray(sut.state.vals).reshape(-1)
    live = keys != EMPTY
    order = np.argsort(keys[live])
    shards = len(getattr(sut, "devs", [None]))
    return got, keys[live][order].tolist(), vals[live][order].tolist(), shards

out["store"] = answers(store)
out["sharded"] = answers(sharded)

# the combine reader's program, compiled from the cell's shapes alone, names
# the instructions of the program the system runs as that program does
from chipbench import progtrace, shardtrace
from repro.core.distributed import shard_executor
from repro.core.ops import make_ops

sut = sharded.System(tiny(), 5, harness.Spans(False), log=lambda _msg: None)
sut.setup()
ops, _ = make_ops(*sut.pending)
fn, args, kwargs = shard_executor(sut.idx, ops, sut.mesh, cfg=sut.config.replace(donate=False))
ran = progtrace.op_scopes(fn.lower(*args, **kwargs).compile().as_text())
fresh = progtrace.op_scopes(shardtrace.program_text(sut.cell))
out["program_scopes"] = [ran == fresh, sorted(set(fresh.values()))]
print(json.dumps(out))
"""


def _child(code):
    return json.loads(run_with_devices(code, n_devices=4).strip().splitlines()[-1])


@pytest.fixture(scope="module")
def runs():
    return _child(RUNS)


@pytest.fixture(scope="module")
def same():
    return _child(SAME)


@pytest.mark.parametrize("trace", [False, True])
def test_the_check_passes_on_the_system_output(runs, trace):
    r = runs["trace"][str(trace)]
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"] == {
        "result_mismatches": {"value": 0, "limit": 0},
        "live_pair_mismatches": {"value": 0, "limit": 0},
    }
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 4


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_check_fails_on_a_planted_fault(runs, fault):
    compared = runs["faults"][fault]
    broken = compared[FAULTS[fault]]
    assert broken["value"] > broken["limit"]


def test_the_control_fails_the_check_on_three_seeds(same):
    for correct, mismatches in same["control"]:
        assert correct is False and mismatches > 0


def test_the_shards_answer_as_the_single_table_store(same):
    got, keys, vals, shards = same["sharded"]
    want, want_keys, want_vals, _ = same["store"]
    assert shards == 4
    assert got == want
    assert keys == want_keys and vals == want_vals and len(keys) > 0


def test_a_cpu_run_reports_no_device_metric(runs):
    assert "shard_update_skew.sharded" in runs["trace"]["True"]["metrics"]
    for trace in ("False", "True"):
        assert not set(DEVICE_READERS) & set(runs["trace"][trace]["metrics"])


@pytest.mark.parametrize("name", DEVICE_READERS)
def test_device_time_readers_read_nothing_without_a_device(name):
    run = harness.Run(cell=harness.load_cell(CELL), setup_s=0.0,
                      batches=[{"shard_updates": [1, 1, 1, 1]}])
    reader = harness.load_reader(name)
    assert reader.read(run) is None
    run.trace = devtrace.Trace(ops={}, modules={}, spans=[])
    assert reader.read(run) is None


def test_the_reader_compiles_the_program_the_cell_runs(same):
    equal, scopes = same["program_scopes"]
    assert equal is True
    assert {"flix.shard.mask", "flix.shard.apply", "flix.shard.combine"} <= set(scopes)


MS = 1_000_000
KERNEL = '%flix_apply_pallas.1 = (s32[8]) custom-call(), custom_call_target="tpu_custom_call"'


def two_chip_trace():
    """Two batches on two chips, named as a v5e names them: each chip runs
    the sharded program twice; chip 1's kernel and combine take longer."""
    from chipbench.devtrace import Event, Trace

    ops, modules = {}, {}
    for chip, (kernel_ms, combine_ms) in enumerate([(4, 1), (6, 3)]):
        dev = f"/device:TPU:{chip}"
        ops[dev], modules[dev] = [], []
        for start in (0, 20 * MS):
            ops[dev] += [
                Event(KERNEL, start + MS, start + (1 + kernel_ms) * MS),
                Event("%all-reduce.7 = s32[4] all-reduce()", start + 8 * MS,
                      start + (8 + combine_ms) * MS),
            ]
            modules[dev].append(Event("jit_flix_shard_replicated(42)", start, start + 12 * MS))
    return Trace(ops=ops, modules=modules, spans=[Event("cb:window", 0, 40 * MS)])


def test_device_readers_take_the_largest_chip_per_batch(monkeypatch):
    from chipbench import shardtrace

    text = 'ROOT %all-reduce.7 = s32[4] all-reduce(), metadata={op_name="jit(f)/flix.shard.combine/psum"}'
    monkeypatch.setattr(shardtrace, "program_text", lambda cell: text)
    mkba = [10, 20, 30, 2**31 - 2]
    batch = {"mkba": mkba, "tag": [2, 0], "key": [5, 25], "val": [0, 1]}
    run = harness.Run(cell=harness.load_cell(CELL), setup_s=0.0, trace=two_chip_trace(),
                      batches=[batch, batch], geometry=(16, 32), max_results=8,
                      peaks={"hbm_bytes_per_s": 1e9})
    read = {n: harness.load_reader(n).read(run) for n in DEVICE_READERS}
    assert read["shard_program_ms.sharded"] == pytest.approx(12.0)
    assert read["fused_kernel_ms.sharded"] == pytest.approx(6.0)
    assert read["shard_combine_ms.sharded"] == pytest.approx(3.0)
    # busy: chip 0 10 of 40 ms, chip 1 18 of 40
    assert read["device_idle_pct.sharded"] == pytest.approx(100 * (1 - 14 / 40))
    from chipbench import roofline

    need = 2 * roofline.batch_bytes(mkba, 16, 32, [2, 0], [5, 25], [0, 1], max_results=8)
    assert read["fused_roofline.sharded"] == pytest.approx(
        100 * need / (4 * 1e9) / (12 * MS / 1e9))


def test_update_skew_reads_the_counter():
    reader = harness.load_reader("shard_update_skew.sharded")
    run = harness.Run(cell=None, setup_s=0.0, batches=[
        {"shard_updates": [3, 1, 2, 2]}, {"shard_updates": [2, 2, 2, 2]}, {"tag": None},
    ])
    assert reader.read(run) == pytest.approx((1.5 + 1.0) / 2)
    assert reader.read(harness.Run(cell=None, setup_s=0.0, batches=[{}])) is None
