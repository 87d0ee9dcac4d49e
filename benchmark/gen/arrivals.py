"""Open-loop arrival times: the same sequence of gaps for every seed.

``obs/loadgen.build_schedule`` draws Poisson gaps from the seed, so two
seeds give different numbers of arrivals and different bursts, and the
latency of a cell then follows the seed and not the program: which short
gaps fall next to each other decides how long the queue gets. Here a block
of ``block`` arrivals holds a fixed multiset of gaps, the quantiles of the
exponential distribution (what independent users produce), in an order
fixed by the traffic file (shuffled once from its constant ``pattern``).
The sequence repeats block after block; ``--seed`` only picks where in the
block a run starts. Every block lasts exactly ``block / rate`` seconds, so
every run offers the same load in the same bursts, at another phase.
"""

from __future__ import annotations

import math
import random

from benchmark.gen import start_phase


def block_gaps(rate: float, block: int) -> list:
    """The multiset of ``block`` gaps (seconds), summing to block/rate."""
    expq = [-math.log(1.0 - (i + 0.5) / block) for i in range(block)]
    scale = block / sum(expq)  # quantile mid-points sum a little off
    mean = 1.0 / rate
    return [mean * (q * scale) for q in expq]


def arrival_times(traffic: dict, seed: int, seconds: float) -> list:
    """Due times in ``[0, seconds)`` for a traffic file's ``arrivals``."""
    arrivals = traffic["arrivals"]
    block = int(arrivals["block"])
    gaps = block_gaps(float(arrivals["rate_per_s"]), block)
    random.Random(f"{traffic['pattern']}:pattern").shuffle(gaps)
    i = start_phase(seed)
    out, t = [], 0.0
    while True:
        t += gaps[i % block]
        i += 1
        if t >= seconds:
            return out
        out.append(t)
