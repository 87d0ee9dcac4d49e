"""Auxiliary surface: fingerprint
detector breadth (client/fingerprint/), the pprof + operator-debug
profiling surface (command/agent/http.go:331, command/operator_debug.go),
and the HCL agent config file (command/agent/config.go)."""

import json
import urllib.request

from nomad_tpu import mock
from nomad_tpu.agent_config import AgentConfig, load_agent_config, parse_agent_config
from nomad_tpu.client.fingerprint import fingerprint_node


class TestFingerprint:
    def test_detector_breadth(self, tmp_path):
        node = fingerprint_node(data_dir=str(tmp_path))
        a = node.attributes
        # cpu.go / memory.go / storage.go / host.go
        assert int(a["cpu.numcores"]) >= 1
        assert int(a["cpu.totalcompute"]) > 0
        assert int(a["memory.totalbytes"]) > 0
        assert a["kernel.name"] == "linux"
        assert a["unique.hostname"]
        assert int(a["unique.storage.bytestotal"]) > 0
        assert int(a["unique.storage.bytesfree"]) >= 0
        # network.go: speed always derived; cgroup.go on any modern linux
        assert int(a["network.speed"]) > 0
        assert a.get("unique.cgroup.version") in ("v1", "v2", None)
        # resources flow from the detectors
        assert node.node_resources.cpu > 0
        assert node.node_resources.memory_mb > 0
        assert node.node_resources.networks  # NIC speed as bandwidth

    def test_detector_failure_isolated(self, tmp_path, monkeypatch):
        """A crashing detector must not abort fingerprinting
        (fingerprint_manager.go per-fingerprinter error handling)."""
        import nomad_tpu.client.fingerprint as fp

        def boom(node, ctx):
            raise RuntimeError("probe exploded")

        monkeypatch.setattr(fp, "DETECTORS", (boom,) + fp.DETECTORS[1:])
        node = fp.fingerprint_node(data_dir=str(tmp_path))
        assert node.attributes["kernel.name"] == "linux"


class TestProfilingSurface:
    def test_pprof_and_debug_endpoints(self):
        from nomad_tpu.api.http import HTTPAgent
        from nomad_tpu.server import Server, ServerConfig

        srv = Server(ServerConfig(num_workers=1))
        srv.establish_leadership()
        http = HTTPAgent(srv, None, host="127.0.0.1", port=0)
        http.start()
        try:
            base = http.address

            def get(path):
                with urllib.request.urlopen(base + path, timeout=10) as r:
                    return json.loads(r.read())

            threads = get("/v1/agent/pprof/goroutine")
            assert any("worker" in name for name in threads)
            prof = get("/v1/agent/pprof/profile?seconds=0.2")
            assert prof["samples"] > 0
            heap1 = get("/v1/agent/pprof/heap")
            heap2 = get("/v1/agent/pprof/heap")
            assert heap1.get("started") or heap1.get("top") is not None
            assert heap2.get("top") is not None
            bundle = get("/v1/operator/debug")
            assert "metrics" in bundle and "threads" in bundle
            assert "device_cache" in bundle
        finally:
            http.stop()
            srv.shutdown()


AGENT_HCL = """
region     = "west"
datacenter = "dc7"
data_dir   = "/var/nomad"

ports {
  http = 5646
}

server {
  enabled        = true
  num_schedulers = 3
  heartbeat_grace = "30s"
}

client {
  enabled      = true
  servers      = ["10.0.0.1:4647", "10.0.0.2:4647"]
  driver_mode  = "plugin"
  gc_max_allocs = 25

  host_volume "certs" {
    path = "/etc/ssl/certs"
  }
}

telemetry {
  collection_interval = "5s"
  publish_allocation_metrics = true
}
"""


class TestAgentConfig:
    def test_parse_full_config(self):
        cfg = parse_agent_config(AGENT_HCL)
        assert cfg.region == "west"
        assert cfg.datacenter == "dc7"
        assert cfg.data_dir == "/var/nomad"
        assert cfg.http_port == 5646
        assert cfg.server.enabled and cfg.server.num_schedulers == 3
        assert cfg.server.heartbeat_ttl_s == 30.0
        assert cfg.client.enabled
        assert cfg.client.servers == ["10.0.0.1:4647", "10.0.0.2:4647"]
        assert cfg.client.driver_mode == "plugin"
        assert cfg.client.gc_max_allocs == 25
        assert cfg.client.host_volumes == {"certs": "/etc/ssl/certs"}
        assert cfg.telemetry.collection_interval_s == 5.0
        assert cfg.telemetry.publish_allocation_metrics is True

    def test_merge_order(self, tmp_path):
        """Later files override earlier ones; absent keys inherit
        (config.go LoadConfig merge)."""
        f1 = tmp_path / "a.hcl"
        f1.write_text('region = "east"\ndatacenter = "dc1"\n')
        f2 = tmp_path / "b.hcl"
        f2.write_text('datacenter = "dc2"\n')
        cfg = load_agent_config([str(f1), str(f2)])
        assert cfg.region == "east"  # inherited from f1
        assert cfg.datacenter == "dc2"  # overridden by f2
        assert cfg.bind_addr == "127.0.0.1"  # default preserved

    def test_defaults(self):
        cfg = AgentConfig()
        assert cfg.region == "global" and cfg.http_port == 4646
