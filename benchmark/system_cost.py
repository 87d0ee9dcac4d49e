"""The least a system pass's scoring needs: operations and bytes from the
shapes, counted from the problem and not from the kernel.

One call of the dense score for a system job's group looks at every node
of the fleet once for one ask with ``D`` resource dimensions (a candidate
is "the group's allocation on node n": ``N`` of them).

- operations per candidate: ``D`` adds for the proposed usage and ``D``
  compares for the fit (2 D), two ``10**x``, their sum and the
  normalisation for the fit score (4): ``2 D + 4``;
- bytes: capacity and usage of the fleet read once (``2 x 4 N D``), the
  ask (``4 D``), one score and one fit flag a node written back (``5 N``).

The least time is the larger of operations over the peak operation rate
and bytes over the peak memory bandwidth (``peaks.json``).
"""

from __future__ import annotations

D = 4  # cpu, memory, disk, bandwidth: the dimensions the fleet carries


def score_cost(n_nodes: int) -> tuple:
    """(operations, bytes) one group's dense score needs at least."""
    ops = n_nodes * (2 * D + 4)
    nbytes = 2 * 4 * n_nodes * D + 4 * D + 5 * n_nodes
    return ops, nbytes


def least_seconds(peaks: dict, device_kind: str, calls: list) -> dict:
    """``calls``: the fleet's node count, one per scoring call."""
    if device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    peak = peaks["devices"][device_kind]
    costs = [score_cost(n) for n in calls]
    ops = sum(c[0] for c in costs)
    nbytes = sum(c[1] for c in costs)
    by_ops = ops / peak["flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {
        "ops": ops, "bytes": nbytes,
        "seconds": max(by_ops, by_bytes),
        "bound": "compute" if by_ops >= by_bytes else "memory",
    }
