"""The fleet: ``gen/fleet.py``'s table (racks, two classes, reserve) plus
one device group ``nvidia/gpu`` on every ``gpu_every``-th node, and the
scheduler's configuration the deployment states."""

from __future__ import annotations

import numpy as np

from benchmark.gen.fleet import fleet_node_id, fleet_spec as base_spec

VENDOR, TYPE = "nvidia", "gpu"


def fleet_spec(fleet: dict) -> dict:
    """``gen.fleet.fleet_spec`` plus ``gpus``: instances per node."""
    out = base_spec(fleet)
    every = int(fleet["gpu_every"])
    out["gpus"] = np.where(
        np.arange(out["n"]) % every == 0, int(fleet["gpu"]["instances"]), 0
    ).astype(np.int64)
    out["gpu_model"] = fleet["gpu"]["model"]
    return out


def instance_id(row: int, k: int) -> str:
    """Fixed ids: the judge reads an instance's slot back from its id."""
    return f"GPU-{row:06d}-{k}"


def instance_slot(instance: str) -> int:
    return int(instance.rsplit("-", 1)[1])


def seed_fleet(server, config: dict) -> dict:
    """Upsert the fleet straight into state (set-up, not the measured
    path) and set the scheduler's configuration. Returns the plain table."""
    from nomad_tpu.state import SchedulerConfiguration
    from nomad_tpu.structs import (
        NODE_STATUS_READY,
        Node,
        NodeReservedResources,
        NodeResources,
    )
    from nomad_tpu.structs.resources import (
        NodeDeviceInstance,
        NodeDeviceResource,
    )

    fleet = config["fleet"]
    spec = fleet_spec(fleet)
    reserved = fleet["reserved"]
    for i in range(spec["n"]):
        devices = []
        if spec["gpus"][i]:
            devices.append(NodeDeviceResource(
                vendor=VENDOR, type=TYPE, name=spec["gpu_model"],
                instances=[
                    NodeDeviceInstance(id=instance_id(i, k), healthy=True)
                    for k in range(int(spec["gpus"][i]))
                ],
            ))
        node = Node(
            id=fleet_node_id(i),
            name=f"node-{i:06d}",
            datacenter="dc1",
            node_class="",
            attributes={
                "kernel.name": "linux",
                "arch": "x86",
                "cpu.frequency": "2000",
                "cpu.numcores": "2",
                "driver.exec": "1",
                "nomad.version": "1.2.3",
                "platform.rack": f"r{int(spec['rack'][i])}",
                "storage.type": "ssd" if spec["ssd"][i] else "hdd",
            },
            drivers={"exec": True},
            node_resources=NodeResources(
                cpu=int(spec["raw_cpu"][i]),
                memory_mb=int(spec["raw_memory_mb"][i]),
                disk_mb=int(spec["raw_disk_mb"][i]),
                devices=devices,
            ),
            reserved=NodeReservedResources(
                cpu=int(reserved["cpu"]),
                memory_mb=int(reserved["memory_mb"]),
                disk_mb=int(reserved["disk_mb"]),
            ),
            status=NODE_STATUS_READY,
        )
        node.compute_class()
        server.store.upsert_node(i + 1, node)
    server.store.set_scheduler_config(
        server.store.latest_index + 1,
        SchedulerConfiguration(**config["scheduler"]),
    )
    return spec
