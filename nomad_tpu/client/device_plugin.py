"""Out-of-process DEVICE plugin contract — the device.proto analog.

Reference: plugins/device/proto/device.proto + plugins/device/device.go:
a device plugin is a separate process the client talks to over a typed
contract with three calls — ``Fingerprint`` (stream of detected device
groups), ``Reserve`` (instance ids → container/env mutations), and
``Stats`` (per-instance usage). The reference speaks gRPC to a hashicorp
go-plugin binary; this build reuses the framework's NDJSON stdio plugin
transport (client/plugin.py's wire style), so device plugins get the
same lifecycle/reattach properties as driver plugins without a protobuf
toolchain.

Wire protocol (one JSON object per line):
  plugin → host  {"type": "handshake", "magic": ..., "version": 1,
                  "plugin": "<name>"}
  host → plugin  {"id": N, "method": "fingerprint" | "reserve" | "stats",
                  "params": {...}}
  plugin → host  {"id": N, "result": ...} | {"id": N, "error": "..."}

A plugin is any executable speaking this protocol; the builtin launcher
(``python -m nomad_tpu.client.device_plugin <name>``) serves the
plugins registered in BUILTIN_DEVICE_PLUGINS (the jax/TPU plugin and a
test fake), mirroring how driver plugins are spawned.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

from ..structs.resources import NodeDeviceInstance, NodeDeviceResource
from .stdio_plugin import StdioPluginClient, serve_stdio_plugin

DEVICE_PLUGIN_MAGIC = "NOMAD_TPU_DEVICE_V1"
DEVICE_PROTO_VERSION = 1


class DevicePlugin:
    """Base class for the plugin-side implementation."""

    name = "device"

    def fingerprint(self) -> list[dict]:
        """Detected device groups: [{vendor, type, name, instances:
        [{id, healthy}], attributes: {...}}]."""
        return []

    def reserve(self, device_ids: list[str]) -> dict:
        """Reservation response (device.proto ContainerReservation):
        {"envs": {...}, "mounts": [...], "devices": [...]}."""
        return {"envs": {}, "mounts": [], "devices": []}

    def stats(self) -> dict:
        """Per-instance stats: {instance_id: {...}}."""
        return {}


class JaxDevicePlugin(DevicePlugin):
    """The native accelerator plugin: surfaces the jax device table (the
    TPU) as a schedulable device group — the drivers/gpu analog for this
    framework's own hardware."""

    name = "jax"

    def fingerprint(self) -> list[dict]:
        # This runs in the plugin's OWN process. A chip belongs to one
        # process at a time, so under an agent whose server already
        # holds the chip ``jax.devices()`` fails here (or hangs): the
        # failure travels back as the call's error, never as "no
        # devices".
        import jax

        accel = [d for d in jax.devices() if d.platform not in ("cpu",)]
        if not accel:
            return []
        platform = accel[0].platform
        return [
            {
                "vendor": "google",
                "type": "tpu" if platform == "tpu" else platform,
                "name": getattr(
                    accel[0], "device_kind", platform
                ).replace(" ", "-").lower(),
                "instances": [
                    {"id": f"{platform}-{d.id}", "healthy": True}
                    for d in accel
                ],
                "attributes": {"count": len(accel)},
            }
        ]

    def reserve(self, device_ids: list[str]) -> dict:
        ordinals = ",".join(
            did.rsplit("-", 1)[-1] for did in device_ids
        )
        # the visibility knobs the runtimes actually honor: the TPU
        # runtime reads TPU_VISIBLE_CHIPS (newer) / TPU_VISIBLE_DEVICES
        # (older); CUDA backends read CUDA_VISIBLE_DEVICES
        return {
            "envs": {
                "TPU_VISIBLE_CHIPS": ordinals,
                "TPU_VISIBLE_DEVICES": ordinals,
                "CUDA_VISIBLE_DEVICES": ordinals,
            },
            "mounts": [],
            "devices": [],
        }


class FakeDevicePlugin(DevicePlugin):
    """Deterministic test plugin: devices configured via env."""

    name = "fake"

    def fingerprint(self) -> list[dict]:
        spec = os.environ.get("NOMAD_FAKE_DEVICES", "")
        if not spec:
            return []
        # "vendor/type/name:n"
        head, _, n = spec.partition(":")
        vendor, type_, name = head.split("/")
        return [
            {
                "vendor": vendor,
                "type": type_,
                "name": name,
                "instances": [
                    {"id": f"{name}-{i}", "healthy": True}
                    for i in range(int(n or 1))
                ],
                "attributes": {"memory_mb": 1024},
            }
        ]

    def reserve(self, device_ids: list[str]) -> dict:
        return {
            "envs": {"FAKE_VISIBLE_DEVICES": ",".join(device_ids)},
            "mounts": [],
            "devices": [f"/dev/fake/{d}" for d in device_ids],
        }

    def stats(self) -> dict:
        return {
            d["id"]: {"utilization": 0.0}
            for g in self.fingerprint()
            for d in g["instances"]
        }


BUILTIN_DEVICE_PLUGINS = {
    p.name: p for p in (JaxDevicePlugin(), FakeDevicePlugin())
}


# -- plugin (server) side ----------------------------------------------------


def serve_device_plugin(plugin: DevicePlugin, stdin=None, stdout=None):
    serve_stdio_plugin(
        DEVICE_PLUGIN_MAGIC,
        DEVICE_PROTO_VERSION,
        plugin.name,
        {
            "fingerprint": lambda p: plugin.fingerprint(),
            "reserve": lambda p: plugin.reserve(
                p.get("device_ids") or []
            ),
            "stats": lambda p: plugin.stats(),
        },
        stdin=stdin,
        stdout=stdout,
    )


# -- host (client) side ------------------------------------------------------


class DevicePluginClient(StdioPluginClient):
    """Spawns and drives one device plugin subprocess."""

    MAGIC = DEVICE_PLUGIN_MAGIC
    VERSION = DEVICE_PROTO_VERSION

    def default_argv(self, name: str) -> list[str]:
        return [
            sys.executable, "-m", "nomad_tpu.client.device_plugin", name,
        ]

    # -- contract ----------------------------------------------------------
    def fingerprint(self) -> list[NodeDeviceResource]:
        groups = self._call("fingerprint") or []
        out = []
        for g in groups:
            out.append(
                NodeDeviceResource(
                    vendor=g.get("vendor", ""),
                    type=g.get("type", ""),
                    name=g.get("name", ""),
                    instances=[
                        NodeDeviceInstance(
                            id=i.get("id", ""),
                            healthy=bool(i.get("healthy", True)),
                        )
                        for i in g.get("instances", [])
                    ],
                    attributes=dict(g.get("attributes") or {}),
                )
            )
        return out

    def reserve(self, device_ids: list[str]) -> dict:
        return self._call("reserve", {"device_ids": device_ids}) or {}

    def stats(self) -> dict:
        return self._call("stats") or {}


def _main() -> None:
    name = sys.argv[1] if len(sys.argv) > 1 else "fake"
    plugin = BUILTIN_DEVICE_PLUGINS.get(name)
    if plugin is None:
        print(f"unknown device plugin {name!r}", file=sys.stderr)
        raise SystemExit(2)
    serve_device_plugin(plugin)


if __name__ == "__main__":
    _main()
