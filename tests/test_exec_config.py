"""ExecConfig surface: frozen and validated, StepResult is attribute-only,
TileTable lookups (DESIGN.md §16)."""

import numpy as np
import pytest

from repro.core.config import ExecConfig, TileTable
from repro.serve.kv_index import KVPageIndex, StepResult


def test_frozen_hashable_validated():
    cfg = ExecConfig(impl="fused", max_results=64)
    assert hash(cfg) == hash(ExecConfig(impl="fused", max_results=64))
    with pytest.raises(Exception):
        cfg.impl = "reference"  # frozen
    for bad in (dict(impl="nope"), dict(pipeline="maybe"), dict(routing="ring")):
        with pytest.raises(ValueError):
            ExecConfig(**bad)
    # replace returns a new validated instance
    assert cfg.replace(impl="reference").impl == "reference"
    assert cfg.impl == "fused"


def test_kv_page_index_accepts_config(rng):
    idx = KVPageIndex(config=ExecConfig(impl="reference"))
    assert idx.impl == "reference"
    res = idx.step(allocs=([1, 2], [0, 0], [10, 20]), lookups=([1], [0]))
    assert isinstance(res, StepResult)
    assert np.asarray(res.slots).tolist() == [10]
    assert res.range_out is None


def test_step_result_not_iterable():
    """Stale three-tuple unpacking must fail loudly, not silently misbind."""
    r = StepResult(slots=np.zeros(0), range_out=None, stats={})
    with pytest.raises(TypeError):
        a, b, c = r
    with pytest.raises(TypeError):
        r[1]


def test_tile_table_lookup_nearest():
    t = TileTable(entries=((1024, 128, 128, 2), (65536, 1024, 512, 8)))
    assert t.lookup(1000, 100) == (128, 2)          # exact bucket
    assert t.lookup(70000, 2000) == (512, 8)        # rounds up + nearest
    assert t.lookup(8192, 256) == (128, 2)          # octave distance tie-break
    assert TileTable().lookup(1, 1) is None
