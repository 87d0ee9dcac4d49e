"""The benchmark's own generators: fleet, job mix, arrival times.

What ``--seed`` decides is little on purpose: the jobs' names and the
phase, one number, at which a run enters the traffic file's repeating
sequences of gaps and of job shapes. Both sequences take the same phase, so
with equal block lengths every seed sees the same (gap, shape) pairs.
"""

import random


def start_phase(seed: int) -> int:
    return random.Random(f"{seed}:phase").randrange(2**30)
