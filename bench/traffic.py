"""The one generator of serving traffic, read from a cell's ``traffic``
parameters.

Every seed gets the same work: the request sizes and the gaps between
arrivals are drawn once from the traffic's own ``shape_seed``; the run's
seed only shuffles their order. So runs with different seeds differ in
order, never in how much they ask.

- arrivals: ``round(rate_per_s * seconds)`` requests, at sorted uniform
  times over the window (a Poisson process conditioned on its count);
- sizes: images per request, a discretised log-normal with the stated
  median and shape, clipped to [min, max].
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    due_s: np.ndarray        # (n,) offsets from the window's start
    sizes: np.ndarray        # (n,) images per request
    first_image: np.ndarray  # (n,) offset of each request's images in the pool


def sizes_from(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["kind"] != "lognormal":
        raise ValueError(f"unknown size distribution {spec['kind']!r}")
    z = rng.standard_normal(n)
    s = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(s, spec["min"], spec["max"]).astype(np.int64)


def schedule(traffic: dict, seconds: float, seed: int) -> Schedule:
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    shape = np.random.default_rng(traffic["shape_seed"])
    times = np.sort(shape.uniform(0.0, seconds, n))
    gaps = np.diff(np.concatenate([[0.0], times]))
    sizes = sizes_from(traffic["sizes"], n, shape)
    run = np.random.default_rng(seed)
    due = np.cumsum(gaps[run.permutation(n)])
    sizes = sizes[run.permutation(n)]
    pool = int(traffic["image_pool"])
    first = run.integers(0, pool - sizes.max() + 1, n)
    return Schedule(due, sizes, first)


def class_names(spec: dict) -> list:
    """``count`` distinct three-word class names, '<first> <second>
    <noun>', in a fixed order."""
    out = []
    for noun in spec["nouns"]:
        for a in spec["first"]:
            for b in spec["second"]:
                if a != b:
                    out.append(f"{a} {b} {noun}")
    if len(out) < spec["count"]:
        raise ValueError(f"only {len(out)} class names, need "
                         f"{spec['count']}")
    step = len(out) / spec["count"]
    return [out[int(i * step)] for i in range(spec["count"])]


def image_pool(n: int, side: int, channels: int, seed: int) -> np.ndarray:
    """(n, side, side, channels) float32 pixels: a smooth seeded field
    plus noise per image, standing in for decoded photos."""
    rng = np.random.default_rng(seed)
    g = 8
    coarse = rng.standard_normal((n, g, g, channels)).astype(np.float32)
    rep = side // g
    img = np.repeat(np.repeat(coarse, rep, axis=1), rep, axis=2)
    img += 0.25 * rng.standard_normal(img.shape, dtype=np.float32)
    return img
