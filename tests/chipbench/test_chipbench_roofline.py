"""The bytes a batch needs, counted by hand on a 4-bucket state: they
depend on the batch and the pre-batch fences alone, never on a grid."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import roofline  # noqa: E402

INS, DEL, PT, SUCC, NOP, RNG = 0, 1, 2, 3, 4, 5
MKBA = np.array([100, 200, 300, 2**31 - 2], np.int32)  # bucket b holds (mkba[b-1], mkba[b]]
NPB, NS, MR = 2, 4, 8
STRIPE = 4 * (2 * NPB * NS + 2 * NPB + 2)  # 4 * (16 + 4 + 2) = 88


def need(tag, key, val=None):
    tag, key = np.asarray(tag), np.asarray(key)
    val = np.zeros_like(key) if val is None else np.asarray(val)
    return roofline.batch_bytes(MKBA, NPB, NS, tag, key, val, max_results=MR)


def io(n_ops):
    return 4 * 7 * n_ops + 4 * 2 * MR


def test_stripe_bytes_by_hand():
    assert roofline.stripe_bytes(NPB, NS) == STRIPE == 88


@pytest.mark.parametrize(
    "tag, key, stripes",
    [
        ([PT, SUCC, PT], [101, 150, 200], 1),   # three reads, all in bucket 1
        ([INS], [150], 2),                      # one insert: read and write bucket 1
        ([INS, DEL, PT], [120, 180, 199], 2),   # updates share one bucket
        ([PT, PT], [5, 250], 2),                # reads in buckets 0 and 2
        ([INS, PT], [5, 250], 3),               # bucket 0 read+written, bucket 2 read
    ],
)
def test_touched_and_updated_buckets_count_once(tag, key, stripes):
    assert need(tag, key) == stripes * STRIPE + io(len(key))


def test_a_range_reads_every_bucket_it_spans():
    # hi is exclusive: [150, 302) reaches key 301 in bucket 3, [150, 301)
    # stops at 300 in bucket 2, [150, 201) stays in bucket 1
    assert need([RNG], [150], [302]) == 3 * STRIPE + io(1)
    assert need([RNG], [150], [301]) == 2 * STRIPE + io(1)
    assert need([RNG], [150], [201]) == 1 * STRIPE + io(1)


def test_padding_moves_nothing():
    empty = 2**31 - 1
    assert need([PT, NOP, NOP], [5, empty, empty]) == STRIPE + io(1)


def test_count_depends_on_the_batch_and_fences_only():
    """The same batch in another submission order, or against a state with
    other contents but the same fences, needs the same bytes."""
    rng = np.random.default_rng(7)
    tag = rng.choice([INS, DEL, PT, SUCC], size=64).astype(np.int32)
    key = rng.choice(np.arange(1, 400), size=64, replace=False).astype(np.int32)
    order = rng.permutation(64)
    assert need(tag, key) == need(tag[order], key[order])
    assert need(tag, key) <= 2 * 4 * STRIPE + io(64)
