"""The one generator of serving traffic: records and due times as a pure
function of the seed and the traffic file's parameters.

Every seed gets the same set of gaps between arrivals, in another order:
the gaps are the stratified quantiles of the exponential distribution at
the file's ``rate_per_s`` (a Poisson stream's gaps, drawn without sampling
noise), shuffled by the seed. ``arrivals: "backlog"`` has no schedule: the
feeder keeps ``outstanding`` records in flight.
"""

import math

import numpy as np


def record_pool(cfg_vocab: int, seq_len: int, pool: int, seed: int):
    """``pool`` distinct records of ``seq_len`` token ids from the seed."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg_vocab, (pool, seq_len), dtype=np.int32)


def poisson_gaps(rate_per_s: float, horizon_s: float, seed: int):
    """The gaps between arrivals: one fixed set for a rate and a horizon,
    in the order the seed gives."""
    n = max(1, int(math.ceil(rate_per_s * horizon_s)))
    quantiles = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-quantiles) / rate_per_s
    np.random.default_rng(seed).shuffle(gaps)
    return gaps


def poisson_due_times(rate_per_s: float, horizon_s: float, seed: int):
    """Due times in [0, horizon_s), in seconds from the stream's start."""
    due = np.cumsum(poisson_gaps(rate_per_s, horizon_s, seed))
    return due[due < horizon_s]


def latencies_ms(due, answered):
    """Per record: (time the answer was readable - time it was due), in
    ms; a record never answered counts as over any limit (``inf``)."""
    return [float("inf") if a is None else (a - d) * 1e3
            for d, a in zip(due, answered)]
