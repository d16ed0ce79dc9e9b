"""Seeded traffic: planning families.

One general generator for every cell.  A cell's workload file names
the parameters (sizes, ranges, pool); nothing here knows a cell by
name.  The family draw follows the one the repo's TPU bring-up proved
(``planning_specs``), copied so that a later change to the program
cannot move it.

Every seed gives the same work.  A planning cell runs a fixed pool of
families (drawn from the cell's ``pool_seed``) in a fixed order, each
call's lanes in a seeded order; the values of its lanes with few
sources (``fresh_sources``) are drawn anew from the run's seed, at the
pool's sizes.  Those lanes converge in fewer interior-point iterations
than the call's slowest lane, which sets the time of a call, so the
seed changes the answers the check compares and not the time.
"""

from __future__ import annotations

import itertools

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator of one ``stream`` of a run's ``seed``.

    ``seed`` may be any non-negative integer, however large.
    """
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def spread_sizes(lanes: int, lo: int, hi: int) -> np.ndarray:
    """``lanes`` sizes spread evenly over ``lo..hi``, both ends included."""
    return np.round(np.linspace(lo, hi, lanes)).astype(int)


def cycle_sizes(lanes: int, lo: int, hi: int) -> np.ndarray:
    """``lo..hi`` repeated to ``lanes`` entries (each about equally often)."""
    return np.resize(np.arange(lo, hi + 1), lanes)


def planning_family(rng: np.random.Generator, cfg: dict, lanes: int) -> list:
    """One ragged Sec 3.2 family as ``(G, R, A, J)`` tuples.

    Source counts cycle over ``cfg["sources"]`` and processor counts
    spread over ``cfg["processors"]``, paired at random, so each family
    holds the largest of both and pads to the same shape.  The values
    are drawn as the bring-up's ``planning_specs`` draws them.
    """
    (n_lo, n_hi), (m_lo, m_hi) = cfg["sources"], cfg["processors"]
    ns = rng.permutation(cycle_sizes(lanes, n_lo, n_hi))
    ms = rng.permutation(spread_sizes(lanes, m_lo, m_hi))
    return [planning_lane(rng, cfg, n, m) for n, m in zip(ns, ms)]


def planning_lane(rng: np.random.Generator, cfg: dict, n: int, m: int) -> tuple:
    """One scenario of ``n`` sources and ``m`` processors, its values
    drawn uniformly over the configuration's ranges (releases sorted)."""
    g, r, a, j = (cfg[k] for k in ("G", "R", "A", "J"))
    return (rng.uniform(*g, n), np.sort(rng.uniform(*r, n)),
            rng.uniform(*a, m), float(rng.uniform(*j)))


def planning_pool(cfg: dict, traffic: dict) -> list:
    """The cell's fixed set of families: ``pool_calls`` families of
    ``lanes_per_call`` scenarios, drawn from ``pool_seed``."""
    rng = rng_for(traffic["pool_seed"], 0)
    return [planning_family(rng, cfg, cfg["lanes_per_call"])
            for _ in range(traffic["pool_calls"])]


def planning_calls(seed: int, cfg: dict, mix: dict):
    """A planning window's calls as ``(family, fresh)``: the pool's
    families in turn, over and over, each with its lanes in a seeded
    order, and those of at most ``fresh_sources`` sources drawn anew from
    ``seed`` at their sizes; ``fresh`` marks those lanes."""
    pool = planning_pool(cfg, mix)
    order, values = rng_for(seed, 2), rng_for(seed, 4)
    for k in itertools.count():
        fam = pool[k % len(pool)]
        lanes = [fam[i] for i in order.permutation(len(fam))]
        fresh = [len(g) <= mix["fresh_sources"] for g, _, _, _ in lanes]
        yield [planning_lane(values, cfg, len(lane[0]), len(lane[2]))
               if new else lane for lane, new in zip(lanes, fresh)], fresh
