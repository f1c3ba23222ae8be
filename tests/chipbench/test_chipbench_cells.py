"""Each cell rehearsed on the CPU at a tiny size through the harness's own
functions: the fused kernel forced on (interpret mode) under the entry
points the chip run drives, the check passing on the system's output and
failing on each fault planted under the timed path.  The command itself
must refuse the CPU and a directory that holds only the benchmark."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402
from repro.core.config import ExecConfig  # noqa: E402

FUSED = ExecConfig(impl="fused", pipeline="on")
BENCH = harness.load_bench()
CELLS = [w["name"] for w in BENCH["workloads"]]


def tiny(name: str, *, log2_keys: int = 10, wide_keys: bool = False) -> harness.Cell:
    """The cell at 2^10 keys with every op count cut 64-fold.  ``wide_keys``
    spreads the keys past 2^24, where float32 no longer holds every int32
    key, so that the control bites at this size as it does at 2^22."""
    cell = harness.load_cell(name, BENCH)
    cell.config["log2_keys"] = log2_keys
    if wide_keys:
        cell.config["key_gap"] = [1 << 15, 1 << 16]
    cell.traffic["ops"] = {k: -(-v // 64) for k, v in cell.traffic["ops"].items()}
    return cell


def run(cell, *, seed=2**33 + 5, trace=False, fault=None, config=FUSED):
    return harness.run_cell(
        cell, seed, 0.2, trace, bench=BENCH, require_chip=False,
        exec_config=config, fault=fault, log=lambda _msg: None,
    )


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_the_check_passes_on_the_system_output(name, trace):
    r = run(tiny(name), trace=trace)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device"] + (
        ["breakdown", "compared"] if trace else ["compared"]
    )
    assert r["compared"] == {
        "result_mismatches": {"value": 0, "limit": 0},
        "live_pair_mismatches": {"value": 0, "limit": 0},
    }
    assert r["device"]["platform"] == "cpu"
    # no CPU number is reported under a device metric
    assert not any(k.endswith((".store", ".read", "roofline")) for k in r["metrics"])


@pytest.mark.parametrize(
    "name, fault, broken",
    [
        ("store-mixed-4k", "answer_altered", "result_mismatches"),
        ("store-mixed-4k", "pair_altered", "live_pair_mismatches"),
        ("store-mixed-4k", "state_unchanged", "live_pair_mismatches"),
        ("store-mixed-4k", "half_batch_dropped", "live_pair_mismatches"),
        ("store-mixed-4k", "stale_reads", "result_mismatches"),
        ("store-read-64k", "answer_altered", "result_mismatches"),
        ("store-read-64k", "pair_altered", "live_pair_mismatches"),
        ("store-read-64k", "half_batch_dropped", "result_mismatches"),
    ],
)
def test_the_check_fails_on_a_planted_fault(name, fault, broken):
    # 2^6 keys: a quarter of the table changes per mixed batch, so that
    # reads of keys the same batch updates are sure to occur
    r = run(tiny(name, log2_keys=6), fault=fault)
    assert r["correct"] is False
    assert r["compared"][broken]["value"] > r["compared"][broken]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_the_control_fails_the_check(name):
    """The control — reads searched with float32-rounded keys — on three
    seeds; on the CPU the reference executor runs (faster than interpret
    mode, and the control does not depend on the executor)."""
    for seed in (11, 2**31 + 3, 2**32 + 9):
        r = run(tiny(name, wide_keys=True), seed=seed, fault="f32_read_keys", config=None)
        assert r["correct"] is False
        assert r["compared"]["result_mismatches"]["value"] > 0


def test_the_same_seed_makes_the_same_batches():
    def batches(seed):
        from chipbench.systems.store import System

        s = System(tiny("store-mixed-4k"), seed, harness.Spans(False))
        import numpy as np
        from chipbench.generator import StoreTraffic, seeded_pairs

        rng = np.random.default_rng(seed)
        keys, _ = seeded_pairs(1 << 10, (1, 64), rng)
        t = StoreTraffic(s.cell.traffic, keys, rng, int(keys[-1]) + 64)
        return [t.batch() for _ in range(3)]

    a, b, c = batches(2**31 + 1), batches(2**31 + 1), batches(2**31 + 2)
    for x, y in zip(a, b):
        for u, v in zip(x, y):
            assert (u == v).all()
    assert any((u != v).any() for x, y in zip(a, c) for u, v in zip(x, y))
    assert all(x[0].size == y[0].size for x, y in zip(a, c))


def _cli(cwd, *args):
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_")}
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


ARGS = ("--workload", "store-mixed-4k", "--seed", "1", "--seconds", "1", "--trace", "0")


def test_the_command_refuses_the_cpu():
    p = _cli(ROOT, *ARGS)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_the_command_refuses_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, *ARGS)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_an_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", BENCH)


def test_a_device_kind_without_peaks_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_peak_hbm_counts_the_executor_temporaries():
    import dataclasses

    reader = harness.load_reader("peak_hbm_gib")
    run = harness.Run(cell=None, setup_s=0.0, peak_bytes=2**30, temp_bytes=3 * 2**30)
    assert reader.read(run) == 4.0
    assert reader.read(dataclasses.replace(run, temp_bytes=None)) is None


def test_setup_sizes_the_executor_temporaries():
    from chipbench.systems.store import System

    sut = System(tiny("store-mixed-4k"), 7, harness.Spans(False), exec_config=FUSED,
                 log=lambda _msg: None)
    sut.setup()
    assert sut.temp_bytes is not None and sut.temp_bytes > 0
