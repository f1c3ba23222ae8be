"""The store generator against YCSB's definitions and against what each
mix file states: fixed op counts, distinct updates, reads that see the
batch's own updates."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench.generator import (  # noqa: E402
    OP_INSERT,
    OP_POINT,
    YCSB_ZETAN,
    StoreTraffic,
    fnvhash64,
    scrambled_zipfian,
    seeded_pairs,
)

MIXES = sorted(p.stem for p in (ROOT / "chipbench" / "traffic").glob("*.json"))


def fnv_by_hand(x: int) -> int:
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ (x & 0xFF)) * 1099511628211) % 2**64
        x >>= 8
    return abs(h - 2**64 if h >= 2**63 else h)


@pytest.mark.parametrize("x", [0, 1, 255, 256, 12345678901, 2**40 + 17])
def test_fnvhash64_is_ycsbs(x):
    assert int(fnvhash64([x])[0]) == fnv_by_hand(x)


def test_scrambled_zipfian_puts_rank_zero_at_its_hash():
    rng = np.random.default_rng(2**31 + 11)
    items = 1 << 16
    draw = scrambled_zipfian(rng, 200_000, items)
    assert draw.min() >= 0 and draw.max() < items
    counts = np.bincount(draw, minlength=items)
    hot = fnv_by_hand(0) % items
    assert counts.argmax() == hot
    # rank 0 takes 1 / zeta(10^10, 0.99) of the draws
    assert abs(counts[hot] / draw.size - 1 / YCSB_ZETAN) < 0.005


@pytest.mark.parametrize("mix", MIXES)
def test_each_mix_names_its_source_and_keeps_its_counts(mix):
    spec = json.loads((ROOT / "chipbench" / "traffic" / f"{mix}.json").read_text())
    assert spec["source"].startswith("YCSB") and "https://" in spec["source"]
    rng = np.random.default_rng(2**32 + 3)
    keys, _ = seeded_pairs(1 << 16, (1, 64), rng)
    t = StoreTraffic(spec, keys, rng, int(keys[-1]) + 64)
    for _ in range(3):
        tag, key, val = t.batch()
        assert tag.size == t.batch_ops == sum(spec["ops"].values())
        puts = key[tag == OP_INSERT]
        assert np.unique(puts).size == puts.size
        if spec["point_hit_share"] == 1.0:
            assert np.isin(key[tag == OP_POINT], keys).all()


def test_ycsb_a_reads_see_their_own_batch():
    spec = json.loads((ROOT / "chipbench" / "traffic" / "ycsb-a-4k.json").read_text())
    rng = np.random.default_rng(2**31 + 5)
    keys, _ = seeded_pairs(1 << 18, (1, 64), rng)
    tag, key, _ = StoreTraffic(spec, keys, rng, int(keys[-1]) + 64).batch()
    # the skew makes hundreds of a batch's reads hit keys the batch updates
    assert np.isin(key[tag == OP_POINT], key[tag == OP_INSERT]).sum() > 100


def test_too_few_live_keys_for_distinct_updates_is_an_error():
    rng = np.random.default_rng(1)
    keys, _ = seeded_pairs(64, (1, 64), rng)
    t = StoreTraffic({"ops": {"update": 40}}, keys, rng, int(keys[-1]) + 64)
    with pytest.raises(ValueError):
        t.batch()
