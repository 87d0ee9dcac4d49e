"""SchedulerAlgorithm plugin registry — the one seam for kernel dispatch.

The reference hard-codes two algorithms behind a config enum
(SchedulerConfiguration.SchedulerAlgorithm, nomad/structs/operator.go);
this build turns that enum into a registry so heterogeneity policies
(scheduler/hetero.py) and future experiments plug in without touching
the schedulers. Mirrors the ``register_scheduler``/BUILTIN_SCHEDULERS
idiom one layer up (scheduler/scheduler.py) at the kernel layer.

Everything that dispatches a placement kernel or the dense score matrix
MUST route through this module — enforced by lint rule NTA013: direct
``PlacementKernel(...)``/``score_matrix_kernel(...)`` calls inside
scheduler/server modules are findings. The payoffs: algorithm names
validate in ONE place (api/http.py asks ``available()``), the CP
dispatcher (ROADMAP item 5) inherits new policies for free, and the
registry is where per-algorithm host oracles pair with their device
kernels for parity pinning.
"""

from __future__ import annotations

import numpy as np


class UnknownAlgorithmError(ValueError):
    """Raised for algorithm names nothing registered (API surfaces 400)."""


ALGORITHMS: dict[str, "SchedulerAlgorithm"] = {}


class SchedulerAlgorithm:
    """One registered placement algorithm: a name plus a kernel factory.

    ``make_kernel`` must return an object with the PlacementKernel
    ``place(cluster, asks, **kwargs) -> list[PlacementResult]`` contract
    (device/score.py); the generic scheduler treats all algorithms
    uniformly through it.
    """

    name: str = ""
    description: str = ""
    # hetero algorithms only differentiate on fleets with device classes;
    # the API surfaces this so operators know what a selection changes
    requires_device_classes: bool = False

    def make_kernel(self, force_scan: bool = False, mesh=None):
        """``mesh`` is a utils.backend.MeshConfig override; None means
        the kernel binds the process-wide mesh (get_mesh()) — the seam
        through which the production scheduler path inherits multi-chip
        sharding without any per-scheduler wiring."""
        raise NotImplementedError


def register_algorithm(cls):
    """Class decorator: instantiate and index by ``name`` (last wins,
    like register_scheduler — tests override with instrumented doubles)."""
    inst = cls()
    if not inst.name:
        raise ValueError("SchedulerAlgorithm needs a non-empty name")
    ALGORITHMS[inst.name] = inst
    return cls


def available() -> list[str]:
    return sorted(ALGORITHMS)


def is_registered(name: str) -> bool:
    return name in ALGORITHMS


def get_algorithm(name: str) -> SchedulerAlgorithm:
    algo = ALGORITHMS.get(name)
    if algo is None:
        raise UnknownAlgorithmError(
            f"unknown scheduler algorithm {name!r}; "
            f"available: {', '.join(available())}"
        )
    return algo


def make_kernel(name: str, force_scan: bool = False, mesh=None):
    """The factory seam: scheduler_algorithm config string → kernel."""
    return get_algorithm(name).make_kernel(force_scan, mesh=mesh)


# -- built-ins ---------------------------------------------------------------


@register_algorithm
class BinpackAlgorithm(SchedulerAlgorithm):
    name = "binpack"
    description = "maximize per-node utilization (reference default)"

    def make_kernel(self, force_scan: bool = False, mesh=None):
        from ..device.score import PlacementKernel

        return PlacementKernel("binpack", force_scan, mesh=mesh)


@register_algorithm
class SpreadAlgorithm(SchedulerAlgorithm):
    name = "spread"
    description = "prefer empty nodes (inverse binpack fit)"

    def make_kernel(self, force_scan: bool = False, mesh=None):
        from ..device.score import PlacementKernel

        return PlacementKernel("spread", force_scan, mesh=mesh)


class _HeteroAlgorithm(SchedulerAlgorithm):
    requires_device_classes = True
    policy = ""

    def make_kernel(self, force_scan: bool = False, mesh=None):
        from .hetero import HeteroPlacementKernel

        return HeteroPlacementKernel(self.policy, force_scan, mesh=mesh)


@register_algorithm
class HeteroMaxMinAlgorithm(_HeteroAlgorithm):
    name = "hetero-maxmin"
    policy = "maxmin"
    description = "max-min fair normalized throughput across jobs (Gavel)"


@register_algorithm
class HeteroMakespanAlgorithm(_HeteroAlgorithm):
    name = "hetero-makespan"
    policy = "makespan"
    description = "minimize modeled batch makespan (LPT on class rates)"


@register_algorithm
class HeteroCostAlgorithm(_HeteroAlgorithm):
    name = "hetero-cost"
    policy = "cost"
    description = "maximize throughput per device-class cost"


@register_algorithm
class CpPackAlgorithm(SchedulerAlgorithm):
    name = "cp-pack"
    description = (
        "whole-batch joint placement: assignment relaxation over the "
        "score matrix, solved on device by iterated proportional rounding"
    )

    def make_kernel(self, force_scan: bool = False, mesh=None):
        from .cp import CpPlacementKernel

        return CpPlacementKernel(force_scan, mesh=mesh)


@register_algorithm
class CpGangAlgorithm(SchedulerAlgorithm):
    name = "cp-gang"
    description = (
        "cp-pack plus all-or-nothing gangs: topology-priced co/anti-"
        "location with atomic release of incomplete gangs"
    )

    def make_kernel(self, force_scan: bool = False, mesh=None):
        from .cp import CpGangPlacementKernel

        return CpGangPlacementKernel(force_scan, mesh=mesh)


# -- registry-routed score matrix -------------------------------------------


def score_group(
    ct,
    ga,
    desired_total: float,
    algorithm_spread: bool = False,
    explain: bool = False,
):
    """Dense score row for one flattened group ask — the registry-routed
    wrapper over score_matrix_kernel for matrix consumers (system
    scheduler, annotation). Feeds the heterogeneity axis when the ask
    carries one: coefficients normalize by the job's best eligible class
    so the score term lands in [0, 1] like every other component.

    Returns (finals f32[N], fits bool[N]) as numpy; with ``explain``
    (Python-gated like the throughput ``None`` gate: the kernel call
    below is untouched either way) the return grows a third element, an
    ``obs.explain.PlacementExplanation`` carrying top-k candidates and
    the feasibility-rejection histogram."""
    from ..device.score import score_matrix_kernel, used_device
    from ..utils.backend import get_mesh, shard_put

    cfg = get_mesh()
    throughputs = None
    if ga.has_throughputs and ga.throughputs is not None:
        tp = ga.throughputs.astype(np.float32)
        best = float(np.max(np.where(ga.eligible, tp, 0.0)))
        if best > 0.0:
            throughputs = (tp / np.float32(best))[None, :]
    finals, fits = score_matrix_kernel(
        shard_put(np.asarray(ct.capacity), ("nodes",), cfg),
        used_device(ct, np.asarray(ct.used), cfg),
        shard_put(ga.ask[None, :], ("groups",), cfg),
        shard_put(ga.eligible[None, :], ("groups", "nodes"), cfg),
        shard_put(ga.job_counts[None, :], ("groups", "nodes"), cfg),
        np.array([float(max(desired_total, 1))], dtype=np.float32),
        shard_put(ga.penalty_nodes[None, :], ("groups", "nodes"), cfg),
        shard_put(ga.affinity_scores[None, :], ("groups", "nodes"), cfg),
        np.array([ga.has_affinities]),
        np.array([ga.distinct_hosts]),
        np.asarray(algorithm_spread),
        None
        if throughputs is None
        else shard_put(throughputs, ("groups", "nodes"), cfg),
    )
    if not explain:
        return np.asarray(finals)[0], np.asarray(fits)[0]
    from ..obs.explain import explain_group
    from ..obs.trace import global_tracer as tracer

    with tracer.span("explain", tags={"step": "groups"}):
        ex = explain_group(
            ct,
            ga,
            np.asarray(ct.used),
            algorithm="spread" if algorithm_spread else "binpack",
            algorithm_spread=algorithm_spread,
            throughputs=throughputs[0] if throughputs is not None else None,
            desired_total=float(max(desired_total, 1)),
        )
    return np.asarray(finals)[0], np.asarray(fits)[0], ex
