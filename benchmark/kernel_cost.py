"""The least a placement needs: operations and bytes from the shapes.

Counted from the problem, not from any kernel: whatever implements the
placement has to look at every candidate once and read the fleet once.

For one job of ``count`` identical asks on a fleet of ``N`` nodes with
``D`` resource dimensions, a candidate is "instance j+1 on node n", with
``J = min(count, most instances of the ask a node of the largest class
holds)`` candidates per node:

- operations per candidate: ``D`` multiply-adds for the proposed usage and
  ``D`` compares for the fit (3 D), two ``10**x`` and their sum for the fit
  score (4), the anti-affinity term (3), the mean over components (2), and
  one compare for the selection: ``3 D + 10``.
- bytes per job: its eligibility mask (N bits), its affinity score per node
  where it has one (4 N), and ``count`` (row, score) pairs back (8 count).
- bytes per pass: capacity and usage of the fleet once, ``2 * 4 * N * D``,
  shared by every job of the pass.

The least time is the larger of operations over the peak operation rate and
bytes over the peak memory bandwidth (``peaks.json``, keyed by the device
kind; an unknown device is an error).
"""

from __future__ import annotations

D = 4  # cpu, memory, disk, bandwidth: the dimensions the fleet carries


def job_cost(n_nodes: int, count: int, ask_cpu: int, max_cpu: int,
             has_affinity: bool) -> tuple:
    """(operations, bytes) the placement of one job needs at least."""
    j = min(count, max_cpu // ask_cpu + 1)
    ops = n_nodes * j * (3 * D + 10)
    nbytes = n_nodes // 8 + (4 * n_nodes if has_affinity else 0) + 8 * count
    return ops, nbytes


def least_seconds(peaks: dict, device_kind: str, fleet: dict, traffic: dict,
                  n_jobs: int, n_passes: int) -> dict:
    if device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    peak = peaks["devices"][device_kind]
    n = int(fleet["n"])
    max_cpu = int(fleet["cpu"].max())
    shape = traffic["job"]
    per_cycle = [
        job_cost(n, int(shape["count"]), int(e["cpu"]), max_cpu,
                 bool(shape.get("affinity")))
        for e in traffic["cycle"]
    ]
    ops = n_jobs * sum(c[0] for c in per_cycle) / len(per_cycle)
    nbytes = n_jobs * sum(c[1] for c in per_cycle) / len(per_cycle)
    nbytes += n_passes * 2 * 4 * n * D
    by_ops = ops / peak["flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {
        "ops": ops, "bytes": nbytes,
        "seconds": max(by_ops, by_bytes),
        "bound": "compute" if by_ops >= by_bytes else "memory",
    }
