"""The one generator of offered load.

A traffic mix is a data file, ``bench/traffic/<name>.json``, that this
module reads; a new mix is a new file, never new code.  Its keys:

* ``arrivals``: ``"closed"``, one client that offers the next job when
  the previous one returns (a scheduler draining a job queue), or
  ``"poisson"``, jobs offered at seeded exponential gaps whatever the
  system is doing (an open loop);
* ``rate_per_s`` (``poisson`` only): offered jobs per second;
* ``burst`` (``poisson`` only, default 1): jobs offered together at
  each arrival;
* ``trace_min_jobs``, ``trace_min_s``: how much of the window a
  ``--trace 1`` run captures, from the window's second job on;
* ``note``: what the mix stands for.

Jobs run one at a time, in the order offered, as the program's resident
server runs them.  A closed window ends at the first job boundary after
``--seconds``; an open one offers arrivals until ``--seconds`` and ends
when the last job offered has returned, so every job's wait counts.
"""

from __future__ import annotations

import numpy as np

KEYS = {"arrivals", "rate_per_s", "burst", "trace_min_jobs", "trace_min_s",
        "note"}


class TrafficError(ValueError):
    """The mix's file asks for what the generator does not offer."""


class Offer:
    """When each job of one run is offered, in seconds from the start of
    the window.  The same parameters and seed give the same times."""

    def __init__(self, params: dict, seed: int, seconds: float):
        unknown = set(params) - KEYS
        if unknown:
            raise TrafficError(f"unknown traffic keys {sorted(unknown)}")
        self.kind = params.get("arrivals")
        self.seconds = float(seconds)
        self.trace_min_jobs = int(params["trace_min_jobs"])
        self.trace_min_s = float(params["trace_min_s"])
        if self.kind == "closed":
            self.times = None
        elif self.kind == "poisson":
            self.times = poisson_times(float(params["rate_per_s"]),
                                       int(params.get("burst", 1)), seed,
                                       self.seconds)
        else:
            raise TrafficError(f"arrivals {self.kind!r}: not 'closed' or "
                               "'poisson'")

    def at(self, i: int, now: float) -> float | None:
        """When job ``i`` is offered, ``now`` being the time the previous
        job returned; None when the window offers no more jobs."""
        if self.times is None:
            return now if now < self.seconds else None
        return float(self.times[i]) if i < len(self.times) else None


def poisson_times(rate: float, burst: int, seed: int, seconds: float
                  ) -> np.ndarray:
    """Arrival times under ``seconds``: bursts of ``burst`` jobs at
    exponential gaps of mean ``burst / rate``, drawn from the seed."""
    rng = np.random.default_rng([int(seed), 2])
    n = int(rate * seconds / burst * 2) + 16
    t = np.cumsum(rng.exponential(burst / rate, n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(burst / rate, n))])
    return np.repeat(t[t < seconds], burst)
