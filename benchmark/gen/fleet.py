"""The fleet a configuration describes, as plain data and as program nodes.

Copied in shape from ``bench.seed_fleet`` (racks round-robin, ssd on every
4th node, every 3rd node the double-size class) so that later PRs may edit
``bench.py`` without moving the yardstick. ``fleet_spec`` is the plain
table the reference reads; ``seed_fleet`` hands the same table to the
program as ``Node`` objects. Nothing here depends on the seed: the fleet is
the configuration's, the seed only shapes the traffic.
"""

from __future__ import annotations

import numpy as np


def fleet_spec(fleet: dict) -> dict:
    """Per-node arrays for ``fleet`` (a configuration's ``fleet`` block).

    Returns ``{"n", "cpu", "memory_mb", "disk_mb"}`` as the capacity left
    after the reserve, plus ``rack`` (int) and ``ssd`` (bool) per node.
    Node ``i`` is named ``fleet_node_id(i)``.
    """
    n = int(fleet["nodes"])
    idx = np.arange(n)
    big_every = int(fleet.get("big_every", 0))
    big = (
        (idx % big_every) == int(fleet.get("big_offset", 0))
        if big_every
        else np.zeros(n, dtype=bool)
    )
    small_c, big_c = fleet["classes"]["small"], fleet["classes"].get("big")
    reserved = fleet["reserved"]
    out = {"n": n, "big": big}
    for dim in ("cpu", "memory_mb", "disk_mb"):
        raw = np.where(
            big, (big_c or small_c)[dim], small_c[dim]
        ).astype(np.int64)
        out[dim] = raw - int(reserved[dim])
        out[f"raw_{dim}"] = raw
    racks = int(fleet.get("racks", 0))
    out["rack"] = idx % racks if racks else np.zeros(n, dtype=np.int64)
    ssd_every = int(fleet.get("ssd_every", 0))
    out["ssd"] = (
        (idx % ssd_every) == 0 if ssd_every else np.zeros(n, dtype=bool)
    )
    return out


def fleet_node_id(i: int) -> str:
    """Fixed, sortable node ids: the reference maps an alloc's node back
    to its row without asking the program."""
    return f"00000000-0000-4000-8000-{i:012d}"


def seed_fleet(server, config: dict) -> dict:
    """Upsert the configuration's fleet straight into state (set-up, not
    the measured path), as ``bench.seed_fleet`` does. Returns the plain
    spec."""
    from nomad_tpu.structs import (
        NODE_STATUS_READY,
        Node,
        NodeReservedResources,
        NodeResources,
    )

    fleet = config["fleet"]
    spec = fleet_spec(fleet)
    reserved = fleet["reserved"]
    racks = int(fleet.get("racks", 0))
    for i in range(spec["n"]):
        attributes = {
            "kernel.name": "linux",
            "arch": "x86",
            "cpu.frequency": "2000",
            "cpu.numcores": "2",
            "driver.exec": "1",
            "nomad.version": "1.2.3",
        }
        if racks:
            attributes["platform.rack"] = f"r{int(spec['rack'][i])}"
            attributes["storage.type"] = "ssd" if spec["ssd"][i] else "hdd"
        node = Node(
            id=fleet_node_id(i),
            name=f"node-{i:06d}",
            datacenter="dc1",
            node_class="",
            attributes=attributes,
            drivers={"exec": True},
            node_resources=NodeResources(
                cpu=int(spec["raw_cpu"][i]),
                memory_mb=int(spec["raw_memory_mb"][i]),
                disk_mb=int(spec["raw_disk_mb"][i]),
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
    return spec
