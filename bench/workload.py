"""The general traffic generator: items, inputs and arrival schedules.

A configuration names the kernels and sizes its tenants run and how
popular each is; a traffic file says how they arrive:

* ``"mode": "batch"`` -- closed loop: each pass submits every item once
  (one tenant per item) and drains; passes repeat until the window is
  over.
* ``"mode": "open"`` -- open loop at ``load`` times the configuration's
  ``knee_launches_per_s``: one Poisson stream per item (tenant), each
  conditioned on its launch count, its popularity share of
  ``rate * seconds`` (largest remainder), so that every seed sends the
  same launches at other instants.  Every launch due in the window is
  waited for.

Every input memory is drawn from ``(seed, launch index)``.
"""
from __future__ import annotations

import importlib
import math
from dataclasses import dataclass

import numpy as np


@dataclass
class Item:
    key: str            # "<kernel>.<n>"
    kernel: object      # bench.kernels.<kernel>
    n: int
    code: np.ndarray
    grid: tuple
    block_dim: tuple
    gmem_len: int
    weight: int         # reference warp-instructions of one launch

    def inputs(self, seed: int, index: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
        return self.kernel.make_gmem(rng, self.n)


def item_keys(config: dict) -> list:
    return [f"{k}.{n}" for k in sorted(config["sizes"])
            for n in config["sizes"][k]]


def load_items(config: dict, weights: dict) -> list:
    items = []
    for key in item_keys(config):
        kname, n = key.rsplit(".", 1)
        n = int(n)
        kern = importlib.import_module(f"bench.kernels.{kname}")
        grid, bd = kern.launch(n)
        g = kern.make_gmem(np.random.default_rng(0), n)
        items.append(Item(key, kern, n, np.asarray(kern.build(n), np.int32),
                          tuple(grid), tuple(bd), int(g.shape[0]),
                          int(weights[key]) if weights else 0))
    return items


def popularity(config: dict, items: list) -> np.ndarray:
    """Share of launches per item: Zipf over the configuration's fixed
    ranking (rank 1 most popular), or uniform without one."""
    pop = config.get("popularity")
    if not pop:
        return np.full(len(items), 1.0 / len(items))
    rank = {key: r for r, key in enumerate(pop["ranking"], start=1)}
    w = np.array([rank[it.key] ** -float(pop["zipf_s"]) for it in items])
    return w / w.sum()


def rate(config: dict, traffic: dict) -> float:
    return float(traffic["load"]) * float(config["knee_launches_per_s"])


def counts(share: np.ndarray, total: int) -> np.ndarray:
    """Largest-remainder split of ``total`` launches by ``share``."""
    raw = share * total
    c = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - c), kind="stable")[:total - c.sum()]:
        c[i] += 1
    return c


def open_schedule(share: np.ndarray, rate_hz: float, seconds: float,
                  seed: int):
    """``(due offsets, item indices)`` of the open loop, in due order.

    Given its count, a Poisson stream's arrival instants are independent
    and uniform over the window; each item draws its own from a
    generator of its own, as ``runtime/loadgen.py`` ``build_arrivals``
    seeds one per tenant, so the seed moves every launch and never
    changes how many there are."""
    c = counts(share, max(1, int(round(rate_hz * seconds))))
    due = [np.random.default_rng(np.random.SeedSequence([seed, 1 << 20, i]))
           .uniform(0.0, seconds, k) for i, k in enumerate(c)]
    idx = np.repeat(np.arange(len(share)), c)
    due = np.concatenate(due)
    order = np.argsort(due, kind="stable")
    return due[order], idx[order]


def warm_batches(share: np.ndarray, window: int,
                 tail: float = 1e-6) -> np.ndarray:
    """Per item, the most launches of it one drain window of ``window``
    FIFO launches can hold, but for a ``tail`` chance: each count 1..L
    is its own set of compiled shapes, so set-up warms all of them."""
    out = []
    for p in share:
        cdf, L = 0.0, 0
        while L < window:
            cdf += math.comb(window, L) * p ** L * (1 - p) ** (window - L)
            if 1.0 - cdf < tail:
                break
            L += 1
        out.append(max(1, L))
    return np.array(out)
