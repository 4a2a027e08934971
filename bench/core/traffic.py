"""The one traffic generator: reads a mix's parameters and makes requests.

The schedule is fixed by the mix: prompt lengths, output lengths and
interarrival gaps are quantiles of the stated distributions, put in one
order drawn from the mix's own ``order_seed``.  A run's seed draws only the
token ids (and, elsewhere, the weights).  So every seed does the same work
at the same times, and the spread between runs is the system's, not the
draw's: a 95th percentile of some tens of requests moves by tens of
percent when the order of a bursty schedule changes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
from scipy import special, stats


@dataclass
class Request:
    """One generated request: its prompt ids, its output budget and when
    it is due, in seconds after the arrival process starts (0 for an
    offline queue)."""
    idx: int
    prompt: np.ndarray
    max_new: int
    due: float


def quantile_points(n: int) -> np.ndarray:
    """Midpoints of ``n`` equal-probability strata."""
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from a length spec: lognormal quantiles, clipped to
    [min, max], then rounded up to the first grid point at or above each,
    where the spec has a grid."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = math.sqrt(2.0) * special.erfinv(2.0 * quantile_points(n) - 1.0)
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    out = np.clip(np.ceil(raw), spec["min"], spec["max"]).astype(np.int64)
    grid = spec.get("grid")
    if grid:
        g = np.asarray(sorted(grid), np.int64)
        if out.max() > g[-1]:
            raise ValueError(f"length {out.max()} above the grid {grid}")
        out = g[np.searchsorted(g, out)]
    return out


def gaps(arrival: dict, n: int) -> np.ndarray:
    """``n`` interarrival gaps in seconds, with mean exactly 1 / rate:
    gamma quantiles with the stated coefficient of variation (1 is a
    Poisson process)."""
    rate, cv = float(arrival["rate"]), float(arrival.get("cv", 1.0))
    shape = 1.0 / (cv * cv)
    g = stats.gamma.ppf(quantile_points(n), shape)
    return g * (n / rate) / g.sum()


def request_count(mix: dict, seconds: float) -> int:
    """How many requests a run draws: the queue depth of an offline mix,
    or enough arrivals to cover the warm-up, the window and a margin."""
    arrival = mix["arrival"]
    if arrival["kind"] == "offline":
        return int(mix["requests"])
    span = float(mix.get("warm_s", 0.0)) + float(seconds)
    return int(math.ceil(float(arrival["rate"]) * span * 1.25)) + 16


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             scale: dict | None = None) -> list[Request]:
    """The requests of one run.  ``scale`` shrinks lengths for a CPU
    rehearsal: ``{"prompt_grid": [...], "output_max": n}``."""
    n = request_count(mix, seconds)
    p_spec, o_spec = dict(mix["prompt"]), dict(mix["output"])
    if scale:
        grid = list(scale["prompt_grid"])
        p_spec.update(min=grid[0], max=grid[-1], median=grid[len(grid) // 2],
                      grid=grid)
        o_spec.update(max=scale["output_max"],
                      min=min(o_spec["min"], scale["output_max"]),
                      median=min(o_spec["median"], scale["output_max"]))
    order = np.random.default_rng(int(mix["order_seed"]))
    prompts = order.permutation(lengths(p_spec, n))
    outputs = order.permutation(lengths(o_spec, n))
    arrival = mix["arrival"]
    if arrival["kind"] == "offline":
        due = np.zeros(n)
    elif arrival["kind"] in ("gamma", "poisson"):
        due = np.cumsum(order.permutation(gaps(arrival, n)))
    else:
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab, int(prompts[i]), dtype=np.int64),
                    int(outputs[i]), float(due[i])) for i in range(n)]
