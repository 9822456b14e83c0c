"""One general generator for every traffic mix.

A mix is a data file of parameters (``benchmarks/traffic/<name>.json``);
this module turns it and ``--seed`` into a schedule of requests. The work of
a run is fixed by the mix, not by the seed: lengths and inter-arrival gaps
are the distribution's own quantiles (a stratified sample, so every seed
gets the same multiset of sizes and the same multiset of gaps), and the seed
decides their order and the tokens. Seeds therefore differ in which request
meets which, not in how much work there is.

Distributions (copied in kind from ``ray_lightning_tpu/workloads/traces.py``:
clipped Pareto lengths, Poisson or bursty arrivals):

- ``{"dist": "pareto", "alpha": a, "min": m, "max": M}``: m (1-u)^(-1/a),
  clipped to M
- ``{"dist": "uniform", "min": m, "max": M}``: whole numbers m..M

Arrivals: ``{"kind": "poisson"}`` or ``{"kind": "bursty", "factor": f,
"burst_s": b, "period_s": p}`` (every ``p`` seconds the rate is ``f`` times
the quiet rate for ``b`` seconds; the mean over a period is ``rate_per_s``).

A closed loop has no arrivals; two keys keep its windows alike. ``block``:
the list is dealt so that every ``block`` consecutive requests hold one
length from each of ``block`` strata of the distribution (a window of a
hundred requests otherwise draws a tenth more or fewer tokens than the
next). ``stagger_first``: the first so many requests, the ones that fill
the empty engine together, are cut to evenly spread fractions of their new
tokens, so that rows free up at a steady rate from the start as they do in
a steady state, and not in step.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, List, Sequence

import numpy as np


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float  # open loop: seconds after the window opens; closed loop: 0
    prompt: tuple  # token ids
    new_tokens: int
    counted: bool  # False: sent after the window to keep the load up


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, stream])


def quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """n whole numbers: the distribution's quantiles at (i + 1/2) / n."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "pareto":
        x = spec["min"] * (1.0 - u) ** (-1.0 / spec["alpha"])
        x = np.minimum(x, spec["max"])
    elif kind == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"])
        x = np.minimum(np.floor(x), spec["max"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.maximum(np.round(x), 1).astype(np.int64)


def _warp(tau: np.ndarray, arrivals: Dict[str, Any], rate: float) -> np.ndarray:
    """Operational time (unit rate) -> clock time under the arrival kind."""
    kind = arrivals.get("kind", "poisson")
    if kind == "poisson":
        return tau / rate
    if kind != "bursty":
        raise ValueError(f"unknown arrivals {kind!r}")
    f, b, p = arrivals["factor"], arrivals["burst_s"], arrivals["period_s"]
    quiet = rate * p / (f * b + (p - b))  # so that a period's mean is rate
    grid = np.arange(0.0, tau[-1] / quiet + 2 * p, 0.01)
    lam = np.where((grid % p) < b, f * quiet, quiet)
    cum = np.concatenate([[0.0], np.cumsum(lam[:-1] * 0.01)])
    return np.interp(tau, cum, grid)


def due_times(traffic: Dict[str, Any], seed: int, seconds: float, stream: int) -> np.ndarray:
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)  # unit-rate exponential quantiles
    gaps *= n / gaps.sum()
    gaps = _rng(seed, stream).permutation(gaps)
    tau = np.cumsum(gaps) - gaps[0]
    return _warp(tau, traffic.get("arrivals", {"kind": "poisson"}), rate)


def _dealt(values: np.ndarray, block: int, rng: np.random.Generator) -> np.ndarray:
    """``values`` in an order in which every ``block`` consecutive ones hold
    one value from each of ``block`` strata (the sorted values cut into
    ``block`` equal runs); the seed decides which of a stratum and where in
    its block. The multiset is unchanged."""
    n = len(values)
    if n % block:
        raise ValueError(f"a list of {n} requests is not whole blocks of {block}")
    strata = rng.permuted(np.sort(values).reshape(block, n // block), axis=1)
    return rng.permuted(strata.T, axis=1).reshape(-1)


def _requests(traffic: Dict[str, Any], seed: int, n: int, vocab: int, stream: int,
              due: Sequence[float], counted: bool, first: int,
              block: int = 0) -> List[Request]:
    rng = _rng(seed, stream)
    order = (lambda v: _dealt(v, block, rng)) if block else rng.permutation
    prompts = order(quantiles(traffic["prompt_len"], n))
    news = order(quantiles(traffic["new_tokens"], n))
    out = []
    for i in range(n):
        out.append(Request(
            index=first + i, due_s=float(due[i]),
            prompt=tuple(rng.integers(1, vocab, size=int(prompts[i])).tolist()),
            new_tokens=int(news[i]), counted=counted,
        ))
    return out


def open_loop(traffic: Dict[str, Any], seed: int, seconds: float, vocab: int,
              ramp_s: float = 0.0) -> List[Request]:
    """``ramp_s`` seconds of the mix before the window (due times below 0,
    not counted: an empty engine is no steady state), the requests due
    inside the window (counted), then as many seconds again (not counted)
    so that the last counted requests finish under the load they arrived
    in. In order of due time."""
    out: List[Request] = []
    if ramp_s > 0:
        ramp_due = due_times(traffic, seed, ramp_s, 5) - ramp_s
        out += _requests(traffic, seed, len(ramp_due), vocab, 6, ramp_due, False, 0)
    due = due_times(traffic, seed, seconds, 1)
    out += _requests(traffic, seed, len(due), vocab, 2, due, True, len(out))
    tail_due = due_times(traffic, seed, seconds, 3) + seconds
    out += _requests(traffic, seed, len(tail_due), vocab, 4, tail_due, False, len(out))
    return out


def closed_loop(traffic: Dict[str, Any], seed: int, vocab: int) -> List[Request]:
    """The list the clients draw from, in order; cycled if it runs out."""
    n = int(traffic["request_list"])
    plan = _requests(traffic, seed, n, vocab, 2, [0.0] * n, True, 0,
                     block=int(traffic.get("block", 0)))
    k = int(traffic.get("stagger_first", 0))
    cut = _rng(seed, 8).permutation((np.arange(k) + 0.5) / k) if k else ()
    for i, share in enumerate(cut):
        plan[i] = replace(plan[i], new_tokens=max(1, int(round(plan[i].new_tokens * share))))
    return plan
