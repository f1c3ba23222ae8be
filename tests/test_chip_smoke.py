"""``chip_smoke.py`` off the chip: its phases at a tiny size, and its refusals.

The store and served phases run here in interpret mode (the fused kernel
forced on, double-buffered) against the script's own numpy / dict models —
everything the chip run checks except that the device is a TPU.  The script
itself must refuse the CPU and a directory that holds nothing of the repo,
exiting non-zero without printing a result.
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core.config import ExecConfig
from tests.conftest import run_with_devices

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
FUSED = ExecConfig(impl="fused", pipeline="on")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_store_phase_matches_model_in_interpret_mode(smoke):
    s = smoke.store_phase(
        10,
        0,
        FUSED,
        sizes={"update": (128,), "read": 256, "ttl": 128},
        report_compiles=False,
    )
    assert s["batches"] == 4
    assert s["ops"] == 128 + 256 + 2 * 128
    assert s["matched"] > 0 and s["live_pairs"] > 4 * 1000


def test_served_phase_matches_dict_in_interpret_mode(smoke, tmp_path):
    v = smoke.served_phase(0, FUSED, steps=8, wal_dir=str(tmp_path / "wal"))
    assert v["ok"] == v["tickets"] > 0
    assert v["pages"] > 0


def test_sharded_phase_matches_model_on_four_host_devices():
    """The ``--chips 4`` phase on 4 fake CPU devices: shard_build spreads the
    state over all four, both routings run the fused kernel per shard, and
    every result and the final live pairs match the numpy model."""
    out = run_with_devices(
        f"""
        import importlib.util
        from repro.core.config import ExecConfig
        spec = importlib.util.spec_from_file_location("chip_smoke", {str(SCRIPT)!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        cfg = ExecConfig(impl="fused", pipeline="on")
        s = smoke.sharded_phase(10, 0, 4, cfg, sizes=(128,))
        print("SUMMARY", s["ops"], s["matched"], s["live_pairs"])
        """,
        n_devices=4,
    )
    assert "state on devices [0, 1, 2, 3]" in out
    ops, matched, live = map(int, out.split("SUMMARY")[1].split())
    assert ops == 2 * 2 * 128
    assert matched > 0 and live > 1000


def test_model_truncates_ranges_like_the_contract(smoke):
    """The model's RANGE budget split: earlier ops win, prefixes are kept."""
    import numpy as np

    m = smoke.SortedModel(np.arange(0, 100, 2), np.arange(50))
    tag = np.array([smoke.OP_RANGE, smoke.OP_RANGE, smoke.OP_POINT])
    key = np.array([0, 10, 12], np.int32)
    val = np.array([8, 20, 0], np.int32)
    want, _ = m.apply(tag, key, val, max_results=6)
    assert want["range_start"].tolist() == [0, 4, 0]
    assert want["range_count"].tolist() == [4, 2, 0]
    assert want["range_key"].tolist() == [0, 2, 4, 6, 10, 12]
    assert want["value"].tolist() == [-1, -1, 6]


def _run(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script)],
        cwd=cwd,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )


def test_script_refuses_the_cpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_script_alone_refuses_to_run(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    out = _run(alone, tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.mark.parametrize("env_dir", [None, "given"], ids=["default", "env"])
def test_compile_cache_location(tmp_path, env_dir):
    """The cache lives in $JAX_COMPILATION_CACHE_DIR when set (JAX reads it
    itself), else in .jax_cache/ at the repo root."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = ROOT / ".jax_cache"
    if env_dir:
        want = tmp_path / env_dir
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    code = (
        "from repro.compile_cache import enable_compile_cache as e; d = e(); "
        "import jax; print(d); print(jax.config.jax_compilation_cache_dir)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=120, check=True,
    )
    assert out.stdout.split() == [str(want), str(want)]
