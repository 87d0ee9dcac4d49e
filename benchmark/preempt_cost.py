"""The least a preemption ranking needs: operations and bytes from the
shapes, counted from the problem and not from the kernel.

One ranking orders the ``N`` nodes of the fleet for one ask with ``D``
resource dimensions, given at most ``V`` preemptible allocations a node
(the victim tensors' padded width: what the ranking is handed). A
candidate is "victim v of node n taken next": ``N x V`` of them.

- operations per candidate: ``D`` subtractions and ``D`` squares-and-adds
  for the distance to the ask (3 D), one compare to order it among the
  node's victims, ``D`` adds for the freed prefix, ``D`` adds and ``D``
  compares for the fit after it (3 D), one add for the net priority:
  ``6 D + 2``; per node, once: the fit score of the node after its prefix
  (two ``10**x``, their sum, the logistic: 8) and one compare for the
  order of the nodes.
- bytes: the victim tensors read once (``4 N V D`` resources, ``4 N V``
  priorities, ``4 N V`` device instances held, ``N V`` mask), capacity
  and usage of the fleet read once (``2 x 4 N D``), the instances the ask
  lacks on a node (``4 N``), the eligibility mask (``N``), and one score
  and one feasibility flag a node written back (``5 N``).

The least time is the larger of operations over the peak operation rate
and bytes over the peak memory bandwidth (``peaks.json``).
"""

from __future__ import annotations

D = 4  # cpu, memory, disk, bandwidth: the dimensions the fleet carries


def rank_cost(n_nodes: int, v: int) -> tuple:
    """(operations, bytes) one ranking needs at least."""
    ops = n_nodes * v * (6 * D + 2) + n_nodes * 9
    nbytes = n_nodes * v * (4 * D + 9) + 2 * 4 * n_nodes * D + 10 * n_nodes
    return ops, nbytes


def least_seconds(peaks: dict, device_kind: str, ranks: list) -> dict:
    """``ranks``: one ``(nodes, victim width)`` per ranking of the window."""
    if device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    peak = peaks["devices"][device_kind]
    costs = [rank_cost(n, v) for n, v in ranks]
    ops = sum(c[0] for c in costs)
    nbytes = sum(c[1] for c in costs)
    by_ops = ops / peak["flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {
        "ops": ops, "bytes": nbytes,
        "seconds": max(by_ops, by_bytes),
        "bound": "compute" if by_ops >= by_bytes else "memory",
    }
