"""The one traffic generator: every mix is a data file under
``benchmark/traffic/`` that this module reads.

A mix's keys:

- ``driver``: the file under ``benchmark/drivers/`` that serves it; the
  driver decides whether ε is drawn once per run or per request.
- ``rate_per_s``: open-loop arrival rate (absent for closed loops).
- ``arrivals``: ``"poisson"`` — exponential gaps at ``rate_per_s``.
- ``drain_s``: how long after the window's close the client waits for
  answers that are due.
- ``trace_seconds`` (optional): the window of a ``--trace 1`` run, where
  the full window would make the profiler's file too large to read in
  time.

Every seed gets the same work in another order: the gaps are the
exponential distribution's quantiles at (i + ½)/n, scaled to fill the
window, and the ε of the requests cycle through the configuration's
choices, both shuffled by the seed. So two seeds differ in arrangement,
not in amount.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int) -> np.random.Generator:
    """A generator for any whole number, negative or above 64 bits."""
    seed = int(seed)
    words = [1 if seed < 0 else 0]
    seed = abs(seed)
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            break
    return np.random.default_rng(words)


def eps_for_run(config: dict, seed: int):
    """The ε of a ``per_run`` mix: one of the configuration's choices."""
    choices = config["eps_choices"]
    return choices[int(rng_for(seed).integers(len(choices)))]


def request_eps(config: dict, seed: int, n: int) -> list:
    """n per-request ε values: each choice equally often, seed-shuffled."""
    choices = config["eps_choices"]
    eps = [choices[i % len(choices)] for i in range(n)]
    order = rng_for(seed).permutation(n)
    return [eps[i] for i in order]


def gaps(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """The gaps, in seconds, between n = round(rate × seconds) requests:
    the exponential distribution's quantiles, scaled to fill the window
    exactly and shuffled by the seed. Every seed sends the same number of
    requests in the window."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    g *= seconds / g.sum()
    return g[rng_for(seed).permutation(n)]


def arrival_times(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Scheduled arrival offsets in [0, seconds): the first at 0, then
    one after each gap but the last."""
    return np.concatenate([[0.0], np.cumsum(gaps(mix, seed, seconds)[:-1])])


def schedule(config: dict, mix: dict, seed: int, seconds: float) -> list:
    """[(offset_s, ε)] of an open-loop mix, the arrivals in the window."""
    times = arrival_times(mix, seed, seconds)
    # a second stream from the seed, so ε does not echo the gap order
    eps = request_eps(config, seed + 1, len(times))
    return list(zip(times.tolist(), eps))
