"""The one traffic generator: a mix's parameters (``traffic/<mix>.json``),
a deployment (``configs/<name>.json``) and a seed give the plan the
producer puts — one ``records.PLAN`` record per unit.

The seed changes the units' ids, their tags and the order in which unit
sizes come; never how many units there are, how large they are in sum,
or when they are due. So two seeds offer the same work in another order.

Mix parameters (all optional but ``put_routing``):

``put_routing``  ``"home"`` (every put enters the producer's home server)
                 or ``"round_robin"``; handed to the client library.
``pace``         0 (default): the producer puts as fast as it can and the
                 backlog is sized so that it outlasts the window whatever
                 the system does. Otherwise the share of the fleet's
                 capacity at which puts are due, evenly spaced.
``flush_every``  0 (default): synchronous puts, one round trip each. k > 0:
                 pipelined puts, acknowledged at a flush every k puts.
``work_mult``    ``[[multiplier, share], ...]`` of the deployment's unit
                 time (default ``[[1.0, 1.0]]``); shares sum to 1.
``units_x``      1 (default): an unpaced producer's backlog is capacity x
                 (``fed_warm_s`` + seconds) units. An integer k > 1: k
                 times that, for a mix whose flood is what is timed and
                 has to last long enough to be read. Units left when the
                 window closes cost their workers nothing, so the drain
                 after it is as fast as fetches.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.reduce.records import PLAN

_MASK64 = (1 << 64) - 1


def _work_mult(mix: dict) -> list:
    return mix.get("work_mult", [[1.0, 1.0]])


def capacity_units_per_s(config: dict, mix: dict) -> float:
    """What the workers could do if never blocked: no system outruns it."""
    workers = config["app_ranks"] - 1
    mean_mult = sum(m * share for m, share in _work_mult(mix))
    return workers / (config["work_us"] * 1e-6 * mean_mult)


def n_units(config: dict, mix: dict, seconds: float) -> int:
    cap = capacity_units_per_s(config, mix)
    pace = float(mix.get("pace", 0))
    if pace > 0:
        return math.ceil(pace * cap * (config["warm_s"] + seconds))
    backlog = math.ceil(cap * (config["fed_warm_s"] + seconds))
    return backlog * int(mix.get("units_x", 1))


def tag_of(ids: np.ndarray, seed: int) -> np.ndarray:
    """32 bits that only the holder of the seed can tell from the id
    (splitmix64's finalizer): an altered unit does not keep a valid tag."""
    z = ids.astype(np.uint64) ^ np.uint64(seed & _MASK64)
    with np.errstate(over="ignore"):
        z = (z + np.uint64(0x9E3779B97F4A7C15))
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return (z & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def make_plan(config: dict, mix: dict, seed: int, seconds: float
              ) -> np.ndarray:
    n = n_units(config, mix, seconds)
    rng = np.random.default_rng(seed & _MASK64)
    base = int(rng.integers(1, 1 << 40))
    plan = np.zeros(n, dtype=PLAN)
    plan["id"] = base + rng.permutation(n)
    plan["tag"] = tag_of(plan["id"], seed)
    # the same multiset of unit sizes for every seed, in a seeded order
    mults = np.empty(n, dtype=np.float64)
    at = 0
    shares = _work_mult(mix)
    for k, (mult, share) in enumerate(shares):
        end = n if k == len(shares) - 1 else at + int(round(share * n))
        mults[at:end] = mult
        at = end
    rng.shuffle(mults)
    plan["work_us"] = np.rint(config["work_us"] * mults).astype(np.int32)
    pace = float(mix.get("pace", 0))
    if pace > 0:
        plan["due_s"] = np.arange(n) / (pace * capacity_units_per_s(config,
                                                                   mix))
    return plan
