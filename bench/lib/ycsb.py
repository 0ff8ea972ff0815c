"""YCSB op streams for the benchmark, generated from a traffic file and a seed.

Copied from the repository's generator (scrambled Zipfian keys, YCSB core
mixes) so that no later change to the program can move the yardstick.  Two
departures from that copy: values are drawn over the whole int32 range
(so that a wrong value can never match by chance), and a mix names its
operations by word, as the traffic files do.

Every seed draws the same number of operations from the same key
distribution and mix; the seed only changes which keys and values come in
which order.
"""
from __future__ import annotations

import numpy as np

from .ops import OP_DELETE, OP_READ, OP_RMW, OP_UPSERT

OPS_BY_NAME = {"read": OP_READ, "upsert": OP_UPSERT, "rmw": OP_RMW,
               "delete": OP_DELETE}


class Zipf:
    """Classic (YCSB) zipfian sampler over [0, n) with scrambling: rank r
    is drawn with weight r**-theta, then hashed onto a key so that the
    hot keys are spread over the key space (YCSB's scrambled zipfian)."""

    def __init__(self, n: int, theta: float):
        self.n = n
        ranks = np.arange(1, n + 1, dtype=np.float64)
        w = ranks ** (-theta)
        self.cdf = np.cumsum(w) / np.sum(w)

    def sample(self, rng: np.random.Generator, size: int) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(size))
        x = r.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
        return ((x >> np.uint64(33)) % np.uint64(self.n)).astype(np.int32)


def key_sampler(traffic: dict, n_keys: int):
    dist = traffic["keys"]
    if dist == "zipf":
        z = Zipf(n_keys, float(traffic["theta"]))
        return z.sample
    if dist == "uniform":
        return lambda rng, size: rng.integers(0, n_keys, size,
                                              dtype=np.int32)
    raise ValueError(f"unknown key distribution {dist!r}")


def make_stream(rng: np.random.Generator, traffic: dict, n_keys: int,
                value_width: int, length: int):
    """`length` ops of the traffic's mix: (keys int32 [L], ops int32 [L],
    vals int32 [L, V])."""
    mix = traffic["mix"]
    names = sorted(mix)
    probs = np.array([float(mix[k]) for k in names])
    if abs(probs.sum() - 1.0) > 1e-9:
        raise ValueError(f"mix fractions sum to {probs.sum()}, not 1")
    codes = np.array([OPS_BY_NAME[k] for k in names], np.int32)
    ops = codes[rng.choice(len(names), size=length, p=probs)]
    keys = key_sampler(traffic, n_keys)(rng, length)
    vals = rng.integers(0, np.iinfo(np.int32).max, (length, value_width),
                        dtype=np.int32)
    return keys, ops, vals
