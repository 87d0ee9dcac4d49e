"""Canonical fake objects for tests and benchmarks.

Reference: nomad/mock/mock.go (mock.Node, mock.Job, mock.Alloc,
mock.SystemJob, mock.Eval — 1,909 LoC of fixture factories that every
reference test builds on). Shapes are chosen to match the reference
fixtures' resource footprints so parity tests are comparable.
"""

from __future__ import annotations

import itertools
import uuid

import numpy as np

from .structs import (
    Affinity,
    Allocation,
    ComparableResources,
    Evaluation,
    Job,
    JOB_TYPE_BATCH,
    JOB_TYPE_SERVICE,
    JOB_TYPE_SYSTEM,
    NODE_STATUS_READY,
    Node,
    NodeResources,
    NodeReservedResources,
    Resources,
    Spread,
    Task,
    TaskGroup,
)

_counter = itertools.count()


def short_id(prefix: str) -> str:
    return f"{prefix}-{next(_counter):06d}-{uuid.uuid4().hex[:8]}"


def node(**overrides) -> Node:
    """mock.Node (mock.go:23-90): 4 GHz CPU, 8 GiB RAM, linux, dc1."""
    n = Node(
        id=str(uuid.uuid4()),
        name=short_id("node"),
        datacenter="dc1",
        node_class="",
        attributes={
            "kernel.name": "linux",
            "arch": "x86",
            "cpu.frequency": "2000",
            "cpu.numcores": "2",
            "driver.exec": "1",
            "driver.mock_driver": "1",
            "nomad.version": "1.2.3",
        },
        drivers={"exec": True, "mock_driver": True},
        node_resources=NodeResources(cpu=4000, memory_mb=8192, disk_mb=100 * 1024),
        reserved=NodeReservedResources(cpu=100, memory_mb=256, disk_mb=4 * 1024),
        status=NODE_STATUS_READY,
    )
    for k, v in overrides.items():
        setattr(n, k, v)
    n.compute_class()
    return n


def job(**overrides) -> Job:
    """mock.Job (mock.go:500-600): 1 service group × 10 allocs of
    web tasks at 500 MHz / 256 MiB."""
    j = Job(
        id=short_id("job"),
        name="my-job",
        type=JOB_TYPE_SERVICE,
        priority=50,
        datacenters=["dc1"],
        task_groups=[
            TaskGroup(
                name="web",
                count=10,
                tasks=[
                    Task(
                        name="web",
                        driver="exec",
                        resources=Resources(cpu=500, memory_mb=256),
                    )
                ],
            )
        ],
        status="pending",
        version=0,
    )
    for k, v in overrides.items():
        setattr(j, k, v)
    return j


def batch_job(**overrides) -> Job:
    j = job(type=JOB_TYPE_BATCH, name="batch-job", **overrides)
    j.task_groups[0].name = "worker"
    j.task_groups[0].tasks[0].name = "worker"
    return j


def system_job(**overrides) -> Job:
    """mock.SystemJob: runs on every feasible node."""
    j = Job(
        id=short_id("sysjob"),
        name="my-sysjob",
        type=JOB_TYPE_SYSTEM,
        priority=100,
        datacenters=["dc1"],
        task_groups=[
            TaskGroup(
                name="sys",
                count=1,
                tasks=[
                    Task(
                        name="sys",
                        driver="exec",
                        resources=Resources(cpu=100, memory_mb=64),
                    )
                ],
            )
        ],
    )
    for k, v in overrides.items():
        setattr(j, k, v)
    return j


def eval_for(j: Job, **overrides) -> Evaluation:
    e = Evaluation(
        namespace=j.namespace,
        priority=j.priority,
        type=j.type,
        job_id=j.id,
        triggered_by="job-register",
    )
    for k, v in overrides.items():
        setattr(e, k, v)
    return e


def alloc(j: Job | None = None, n: Node | None = None, **overrides) -> Allocation:
    """mock.Alloc: a placed instance of job's first group."""
    j = j or job()
    tg = j.task_groups[0]
    ask = tg.combined_resources()
    a = Allocation(
        id=str(uuid.uuid4()),
        namespace=j.namespace,
        name=f"{j.id}.{tg.name}[0]",
        job_id=j.id,
        job=j,
        job_version=j.version,
        task_group=tg.name,
        node_id=n.id if n else str(uuid.uuid4()),
        resources=ComparableResources(
            cpu=ask.cpu,
            memory_mb=ask.memory_mb,
            disk_mb=ask.disk_mb,
            bandwidth_mbits=ask.bandwidth_mbits(),
        ),
        desired_status="run",
        client_status="running",
    )
    for k, v in overrides.items():
        setattr(a, k, v)
    return a


# -- synthetic fleets and workloads (seeded; tests compare bytes built from
# them, so a change here changes what they compare) --------------------------


def build_cluster(n_nodes: int, seed: int = 42):
    """Synthetic heterogeneous cluster as resident device tensors
    (4/8/16-core classes, 3 datacenters), bypassing the Python struct
    walk — mirrors the design's steady state where device arrays are a
    derived cache refreshed incrementally (SURVEY.md §7 'latency floor')."""
    from .device.flatten import ClusterTensors, node_bucket

    rng = np.random.default_rng(seed)
    pn = node_bucket(n_nodes)
    classes = rng.integers(0, 3, size=n_nodes)
    cpu = np.choose(classes, [4000, 8000, 16000]).astype(np.float32)
    mem = np.choose(classes, [8192, 16384, 32768]).astype(np.float32)
    capacity = np.zeros((pn, 4), dtype=np.float32)
    capacity[:n_nodes, 0] = cpu
    capacity[:n_nodes, 1] = mem
    capacity[:n_nodes, 2] = 100 * 1024
    capacity[:n_nodes, 3] = 1000
    used = np.zeros_like(capacity)
    # pre-existing load: 0-40% of cpu/mem
    load = rng.uniform(0.0, 0.4, size=(n_nodes, 1)).astype(np.float32)
    used[:n_nodes, :2] = capacity[:n_nodes, :2] * load
    ready = np.zeros(pn, dtype=bool)
    ready[:n_nodes] = True
    return ClusterTensors(
        node_ids=[f"node-{i}" for i in range(n_nodes)],
        index=1,
        num_nodes=n_nodes,
        capacity=capacity,
        used=used,
        ready=ready,
        dc_ids=np.pad(rng.integers(0, 3, n_nodes).astype(np.int32), (0, pn - n_nodes)),
        class_ids=np.pad(classes.astype(np.int32), (0, pn - n_nodes)),
        dc_vocab={"dc1": 0, "dc2": 1, "dc3": 2},
        class_vocab={"small": 0, "medium": 1, "large": 2},
        class_rep=[0, 1, 2],
        node_row={f"node-{i}": i for i in range(n_nodes)},
    )


def build_asks(ct, n_jobs: int, count_per_job: int, seed: int = 7):
    from .device.flatten import GroupAsk

    rng = np.random.default_rng(seed)
    pn = ct.padded_n
    asks = []
    for j in range(n_jobs):
        cpu = float(rng.choice([250, 500, 1000]))
        mem = float(rng.choice([256, 512, 1024]))
        asks.append(
            GroupAsk(
                job_id=f"job-{j}",
                tg_name="web",
                count=count_per_job,
                desired_total=count_per_job,
                ask=np.array([cpu, mem, 300.0, 0.0], dtype=np.float32),
                eligible=ct.ready.copy(),
                job_counts=np.zeros(pn, dtype=np.int32),
                penalty_nodes=np.zeros(pn, dtype=bool),
                affinity_scores=np.zeros(pn, dtype=np.float32),
                has_affinities=False,
                distinct_hosts=False,
            )
        )
    return asks


def seed_fleet(server, n_nodes: int, racks: int = 25) -> None:
    """The config-3 fleet, upserted straight into state (set-up, not the
    measured path): ``racks`` racks round-robin, ssd on every 4th node,
    every 3rd node the double-size resource class."""
    for i in range(n_nodes):
        n = node()
        n.datacenter = "dc1"
        n.attributes["platform.rack"] = f"r{i % racks}"
        n.attributes["storage.type"] = "ssd" if i % 4 == 0 else "hdd"
        if i % 3 == 1:
            n.node_resources.cpu = 8000
            n.node_resources.memory_mb = 16384
        n.compute_class()
        server.store.upsert_node(i + 1, n)


JOB_CPU_CHOICES = (250, 500)  # MHz per alloc, drawn per job from its seed


def make_job(job_id: str, seed: int, per_job: int, spread_affinity=True):
    """One seeded mixed service/batch job of ``per_job`` allocs. With
    ``spread_affinity`` it is the config-3 job (rack spread weight 50 +
    ssd affinity weight 50 → the spread kernels); without, plain binpack
    (config-2 semantics → the closed-form kernel)."""
    j = batch_job() if seed % 3 == 2 else job()
    j.id = job_id
    tg = j.task_groups[0]
    tg.count = per_job
    tg.tasks[0].resources.cpu = int(
        np.random.default_rng(seed).choice(JOB_CPU_CHOICES)
    )
    if spread_affinity:
        j.spreads = [Spread(attribute="${attr.platform.rack}", weight=50)]
        j.affinities = [
            Affinity(
                l_target="${attr.storage.type}",
                r_target="ssd",
                operand="=",
                weight=50,
            )
        ]
    return j
