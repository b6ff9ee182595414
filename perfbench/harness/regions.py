"""A deployment's stake and its peers' regions, made from a few numbers.

A configuration with a ``generator`` block (``perfbench/configs/hub180.json``)
holds what this makes written out: the stake of every validator, a power law
s_i ~ i^-a in validator order, and the validators of each region, drawn by
a seed. A served cell of it holds the peers' delays, one a validator, from
the regions (``harness/peers.py`` makes one frame of each distinct delay).
The harness reads the written-out lists only; this file says where they came
from, and ``tests/test_hub180_served.py`` holds the files to it (and runs
the same generator cut to 15 validators). Without a network the shape is
``assumed``: the configuration says so.
"""

from __future__ import annotations

import random


def power_law(n: int, total: int, exponent: float) -> list[int]:
    """n whole stakes s_i ~ i^-exponent (i = 1..n) summing to about total."""
    weights = [i ** -exponent for i in range(1, n + 1)]
    scale = total / sum(weights)
    return [max(1, round(w * scale)) for w in weights]


def top_share(stake: list[int], top: int) -> float:
    return sum(sorted(stake, reverse=True)[:top]) / sum(stake)


def fit_exponent(n: int, total: int, top: int, share: float, step: float) -> float:
    """The least exponent on a grid of ``step`` at which the ``top`` largest
    of n hold more than ``share`` of the stake (a Nakamoto coefficient)."""
    k = 0
    while top_share(power_law(n, total, k * step), top) <= share:
        k += 1
    return round(k * step, 10)


def draw(n: int, sizes: list[int], own: int, seed: int) -> list[list[int]]:
    """The validators of each region: ``own`` in the first, the others
    shuffled by the seed and dealt out by ``sizes`` (the first size counts
    ``own``), each region's list in validator order."""
    if sum(sizes) != n:
        raise ValueError(f"regions hold {sum(sizes)} validators, the set has {n}")
    others = [v for v in range(n) if v != own]
    random.Random(seed).shuffle(others)
    out, at = [], 0
    for k, size in enumerate(sizes):
        take = size - 1 if k == 0 else size
        group = others[at : at + take] + ([own] if k == 0 else [])
        at += take
        out.append(sorted(group))
    return out


def cumulative(stake: list[int], regions: list[list[int]]) -> list[float]:
    """The share of the stake delivered once each region's frame is in, in
    order of the regions (the node's own vote is in the first)."""
    total, got, out = sum(stake), 0, []
    for region in regions:
        got += sum(stake[v] for v in region)
        out.append(got / total)
    return out


def first_seed(stake: list[int], sizes: list[int], own: int, two_at_most: float,
               three_at_least: float, limit: int = 100_000) -> int:
    """The first seed whose draw delivers no more than ``two_at_most`` of the
    stake in the first two frames and at least ``three_at_least`` in the
    first three: the quorum then completes in the third frame, with room."""
    for seed in range(limit):
        c = cumulative(stake, draw(len(stake), sizes, own, seed))
        if c[1] <= two_at_most and c[2] >= three_at_least:
            return seed
    raise ValueError("no seed qualifies")


def delays(n: int, regions: list[list[int]], delays_ms: list[float]) -> list[float]:
    """Every validator's delay by index: its region's."""
    out = [None] * n
    for region, delay in zip(regions, delays_ms):
        for v in region:
            out[v] = delay
    return out


def build(gen: dict) -> dict:
    """What a ``generator`` block makes: the exponent (fitted where the block
    gives none), the stake, the seed, the regions and the delay list. A
    region is ``[name, validators, delay in ms]``; the first holds ``own``."""
    n, total = int(gen["validators"]), int(gen["total_stake"])
    exponent = gen.get("exponent")
    if exponent is None:
        exponent = fit_exponent(n, total, int(gen["nakamoto"]), 1 / 3, gen["exponent_step"])
    stake = power_law(n, total, exponent)
    sizes = [int(r[1]) for r in gen["regions"]]
    own = int(gen["own"])
    seed = first_seed(stake, sizes, own, gen["two_frames_at_most"], gen["three_frames_at_least"])
    regions = draw(n, sizes, own, seed)
    return {
        "exponent": exponent, "stake": stake, "seed": seed, "regions": regions,
        "peer_delay_ms": delays(n, regions, [float(r[2]) for r in gen["regions"]]),
    }
