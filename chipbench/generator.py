"""The one generator of store traffic: a mix file in, seeded batches out.

A mix (``traffic/<name>.json``) is data only::

    {"source": "...",
     "ops": {"insert": 0, "delete": 0, "update": 2048, "point": 2048,
             "successor": 0, "range": 0},
     "request_distribution": "zipfian", "zipfian_constant": 0.99,
     "point_hit_share": 1.0, "range_width": 0}

Every batch of a mix holds exactly those counts, whatever the seed:

* ``insert``: fresh keys (absent from the table), uniform over the key space;
* ``delete`` and ``update``: distinct live keys, one op per key in a batch,
  drawn by ``request_distribution``; an update writes a new value to a key
  the table holds (an upsert of a live key);
* ``point``: ``point_hit_share`` of them live keys drawn by
  ``request_distribution``, the rest uniform keys of the key space;
* ``successor``: uniform keys; ``range``: ``[lo, lo + range_width)`` with
  uniform ``lo``.

``request_distribution`` is ``uniform`` or ``zipfian``.  ``zipfian`` is
YCSB's scrambled Zipfian (``ScrambledZipfianGenerator``, constant 0.99):
a Zipfian rank over 10^10 items, hashed by 64-bit FNV-1a into a record
index, so the hot records lie spread over the key space.  Records are the
table's live keys in ascending order.  The generator keeps the live key
set itself, so the table's size stays level when inserts equal deletes.
"""

from __future__ import annotations

import numpy as np

OP_INSERT, OP_DELETE, OP_POINT, OP_SUCCESSOR, OP_RANGE = 0, 1, 2, 3, 5
KINDS = ("insert", "delete", "update", "point", "successor", "range")
TAGS = (OP_INSERT, OP_DELETE, OP_INSERT, OP_POINT, OP_SUCCESSOR, OP_RANGE)

# YCSB's ScrambledZipfianGenerator: ITEM_COUNT, ZETAN (zeta of ITEM_COUNT
# items at theta 0.99), and 64-bit FNV (Utils.fnvhash64)
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZETAN = 26.46902820178302
FNV_OFFSET_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def seeded_pairs(n: int, gap: tuple[int, int], rng):
    """n distinct ascending int32 keys with uniform gaps in ``[gap[0],
    gap[1])`` (room for inserts between them) and uniform int32 values."""
    keys = np.cumsum(rng.integers(gap[0], gap[1], size=n, dtype=np.int64))
    if keys[-1] >= np.iinfo(np.int32).max - gap[1]:
        raise ValueError(f"{n} keys with gaps {gap} overflow int32")
    vals = rng.integers(0, 2**31 - 1, size=n, dtype=np.int64)
    return keys.astype(np.int32), vals.astype(np.int32)


def fnvhash64(x):
    """YCSB's ``Utils.fnvhash64`` of each int64 in ``x`` (FNV over its eight
    bytes, low first; ``Math.abs`` of the signed result)."""
    v = np.asarray(x, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_64)
    octet = np.empty_like(v)
    with np.errstate(over="ignore"):
        for _ in range(8):
            np.bitwise_and(v, np.uint64(0xFF), out=octet)
            h ^= octet
            h *= FNV_PRIME_64
            v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def scrambled_zipfian(rng, n: int, items: int, theta: float = 0.99):
    """``n`` record indices in ``[0, items)`` as YCSB's
    ``ScrambledZipfianGenerator(0, items - 1)`` draws them."""
    if theta != 0.99:
        raise ValueError("YCSB's scrambled Zipfian is defined for theta 0.99")
    count = YCSB_ITEM_COUNT + 1
    zeta2 = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / count) ** (1.0 - theta)) / (1.0 - zeta2 / YCSB_ZETAN)
    u = rng.random(n)
    uz = u * YCSB_ZETAN
    rank = (count * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    rank = np.where(uz < zeta2, 1, rank)
    rank = np.where(uz < 1.0, 0, rank)
    return fnvhash64(rank) % items


class StoreTraffic:
    """Batches of one mix against a table that starts as ``keys``."""

    def __init__(self, mix: dict, keys, rng, space: int):
        unknown = set(mix["ops"]) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown op kinds in mix: {sorted(unknown)}")
        self.counts = {k: int(mix["ops"].get(k, 0)) for k in KINDS}
        self.dist = mix.get("request_distribution", "uniform")
        if self.dist not in ("uniform", "zipfian"):
            raise ValueError(f"unknown request_distribution {self.dist!r}")
        self.theta = float(mix.get("zipfian_constant", 0.99))
        self.hit_share = float(mix.get("point_hit_share", 0.5))
        self.width = int(mix.get("range_width", 0))
        self.live = np.sort(np.asarray(keys, np.int32))
        self.rng = rng
        self.space = int(space)

    @property
    def batch_ops(self) -> int:
        return sum(self.counts.values())

    def _fresh(self, n: int):
        cand = np.unique(self.rng.integers(0, self.space, size=2 * n + 64, dtype=np.int64))
        cand = cand.astype(np.int32)
        pos = np.minimum(np.searchsorted(self.live, cand), self.live.size - 1)
        cand = self.rng.permutation(cand[self.live[pos] != cand])
        if cand.size < n:
            raise ValueError("key space too full for fresh inserts")
        return cand[:n]

    def _records(self, n: int):
        """``n`` live-record indices drawn by the request distribution."""
        if self.dist == "uniform":
            return self.rng.integers(0, self.live.size, size=n)
        return scrambled_zipfian(self.rng, n, self.live.size, self.theta)

    def _distinct(self, n: int, exclude=()):
        """``n`` distinct live-record indices, none in ``exclude``: draws in
        order, a repeat skipped, until ``n`` are found."""
        got = np.empty(0, np.int64)
        taken = np.asarray(exclude, np.int64)
        if 2 * (n + taken.size) > self.live.size:
            raise ValueError(f"{n} distinct keys asked of {self.live.size} live keys")
        while got.size < n:
            draw = self._records(2 * (n - got.size) + 16)
            _, first = np.unique(draw, return_index=True)
            draw = draw[np.sort(first)]
            draw = draw[~np.isin(draw, np.concatenate([got, taken]))]
            got = np.concatenate([got, draw[: n - got.size]])
        return got

    def batch(self):
        """``(tag, key, val)`` of the next batch, in submission order."""
        c, rng, space = self.counts, self.rng, self.space
        ins = self._fresh(c["insert"])
        dels = self._distinct(c["delete"])
        upd = self._distinct(c["update"], exclude=dels)
        hits = int(round(c["point"] * self.hit_share))
        pts = np.concatenate(
            [self.live[self._records(hits)], rng.integers(0, space, c["point"] - hits)]
        )
        succ = rng.integers(0, space, size=c["successor"])
        lo = rng.integers(0, space, size=c["range"])
        tag = np.repeat(TAGS, [c[k] for k in KINDS]).astype(np.int32)
        key = np.concatenate(
            [ins, self.live[dels], self.live[upd], pts, succ, lo]
        ).astype(np.int32)
        val = np.zeros(key.size, np.int32)
        puts = ins.size + dels.size + upd.size
        val[: ins.size] = rng.integers(0, 2**31 - 1, size=ins.size, dtype=np.int64)
        val[ins.size + dels.size : puts] = rng.integers(0, 2**31 - 1, size=upd.size, dtype=np.int64)
        val[tag == OP_RANGE] = (lo + self.width).astype(np.int32)
        if dels.size:
            keep = np.ones(self.live.size, bool)
            keep[dels] = False
            self.live = self.live[keep]
        if ins.size:
            ins = np.sort(ins)
            self.live = np.insert(self.live, np.searchsorted(self.live, ins), ins)
        return tag, key, val
