"""Client lifecycle: heartbeatstop (stop_after_client_disconnect) and
terminal-alloc GC — two knobs that were once accepted but
ignored. Reference: client/heartbeatstop.go:11-40, client/gc.go."""

import os
import time

from nomad_tpu import mock
from nomad_tpu.client.client import Client
from nomad_tpu.server.server import Server, ServerConfig

from test_client import wait_until


def make_server():
    srv = Server(ServerConfig(num_workers=1))
    srv.establish_leadership()
    return srv


class FlakyRPC:
    """Wraps the in-process client RPC; heartbeats can be cut off to
    simulate a client↔server partition without stopping the servers."""

    def __init__(self, inner):
        self.inner = inner
        self.heartbeats_ok = True

    def register_node(self, node):
        return self.inner.register_node(node)

    def heartbeat(self, node_id):
        if not self.heartbeats_ok:
            raise ConnectionError("induced partition")
        return self.inner.heartbeat(node_id)

    def pull_allocs(self, node_id, min_index, timeout):
        return self.inner.pull_allocs(node_id, min_index, timeout)

    def update_allocs(self, updates):
        return self.inner.update_allocs(updates)


class TestHeartbeatStop:
    def test_alloc_stops_after_client_disconnect(self, tmp_path):
        """client/heartbeatstop.go:11-40: a group with
        stop_after_client_disconnect stops locally once server contact
        has been lost longer than the threshold."""
        srv = make_server()
        rpc = FlakyRPC(srv.client_rpc())
        client = Client(rpc, data_dir=str(tmp_path), heartbeat_interval=0.1)
        client.start()
        try:
            job = mock.job()
            job.task_groups[0].count = 1
            job.task_groups[0].stop_after_client_disconnect_s = 0.5
            t = job.task_groups[0].tasks[0]
            t.driver = "mock_driver"
            t.config = {"run_for": 60.0}
            srv.register_job(job)
            assert wait_until(
                lambda: any(
                    r.client_status() == "running"
                    for r in client.runners.values()
                )
            ), "alloc never started"
            runner = next(iter(client.runners.values()))
            # cut the heartbeat path only
            rpc.heartbeats_ok = False
            assert wait_until(
                lambda: all(
                    s.state == "dead" for s in runner.task_states.values()
                ),
                timeout=10,
            ), "alloc not stopped after disconnect threshold"
        finally:
            client.shutdown()
            srv.shutdown()

    def test_alloc_without_knob_survives_disconnect(self, tmp_path):
        srv = make_server()
        rpc = FlakyRPC(srv.client_rpc())
        client = Client(rpc, data_dir=str(tmp_path), heartbeat_interval=0.1)
        client.start()
        try:
            job = mock.job()
            job.task_groups[0].count = 1
            t = job.task_groups[0].tasks[0]
            t.driver = "mock_driver"
            t.config = {"run_for": 60.0}
            srv.register_job(job)
            assert wait_until(
                lambda: any(
                    r.client_status() == "running"
                    for r in client.runners.values()
                )
            )
            runner = next(iter(client.runners.values()))
            rpc.heartbeats_ok = False
            time.sleep(1.0)  # well past any sub-second threshold
            assert any(
                s.state == "running" for s in runner.task_states.values()
            ), "alloc without the knob must keep running through a partition"
        finally:
            client.shutdown()
            srv.shutdown()


class TestPrevAllocMigration:
    def test_ephemeral_disk_migrates_on_destructive_update(self, tmp_path):
        """client/allocwatcher + migrate_hook: a destructive update's
        replacement alloc inherits the previous alloc's shared dir when
        ephemeral_disk.migrate is set."""
        srv = make_server()
        client = Client(
            srv.client_rpc(), data_dir=str(tmp_path), heartbeat_interval=0.2
        )
        client.start()
        try:
            job = mock.job()
            job.task_groups[0].count = 1
            job.task_groups[0].ephemeral_disk.migrate = True
            t = job.task_groups[0].tasks[0]
            t.driver = "raw_exec"
            t.config = {
                "command": "/bin/sh",
                "args": ["-c", 'echo v1-data > "$NOMAD_ALLOC_DIR/state.txt"; sleep 60'],
            }
            srv.register_job(job)
            assert wait_until(
                lambda: any(
                    os.path.exists(
                        os.path.join(r.alloc_dir, "shared", "state.txt")
                    )
                    for r in client.runners.values()
                ),
                timeout=15,
            ), "v1 never wrote its state file"
            v1_ids = set(client.runners)

            # destructive update: changed resources force replacement
            import copy

            job2 = copy.deepcopy(job)
            job2.task_groups[0].tasks[0].resources.cpu += 100
            job2.task_groups[0].tasks[0].config = {
                "command": "/bin/sh",
                "args": ["-c", 'sleep 60'],
            }
            srv.register_job(job2)
            assert wait_until(
                lambda: any(
                    rid not in v1_ids
                    and r.client_status() == "running"
                    for rid, r in client.runners.items()
                ),
                timeout=20,
            ), "replacement alloc never ran"
            repl = next(
                r for rid, r in client.runners.items() if rid not in v1_ids
            )
            assert repl.alloc.previous_allocation in v1_ids
            migrated = os.path.join(repl.alloc_dir, "shared", "state.txt")
            assert wait_until(lambda: os.path.exists(migrated), timeout=10)
            with open(migrated) as f:
                assert f.read().strip() == "v1-data"
        finally:
            client.shutdown()
            srv.shutdown()


class TestClientGC:
    def test_terminal_alloc_dirs_reclaimed(self, tmp_path):
        """client/gc.go: terminal alloc dirs beyond the retention bound
        are destroyed, oldest first, and their runners dropped."""
        srv = make_server()
        client = Client(
            srv.client_rpc(), data_dir=str(tmp_path), heartbeat_interval=0.2
        )
        client.gc_max_terminal_allocs = 2
        client.start()
        try:
            jobs = []
            for i in range(4):
                job = mock.batch_job()
                job.id = f"gcjob-{i}"
                job.task_groups[0].count = 1
                t = job.task_groups[0].tasks[0]
                t.driver = "mock_driver"
                t.config = {"run_for": 0.05}
                srv.register_job(job)
                jobs.append(job)
            assert wait_until(
                lambda: sum(
                    1 for r in client.runners.values() if r.is_terminal()
                ) + (4 - len(client.runners)) >= 4,
                timeout=15,
            ), "batch allocs never completed"
            # sweep must retain at most the bound
            assert wait_until(
                lambda: len(
                    [r for r in client.runners.values() if r.is_terminal()]
                )
                <= 2,
                timeout=10,
            )
            # reclaimed dirs are gone from disk
            allocs_root = os.path.join(str(tmp_path), "allocs")
            live_dirs = (
                set(os.listdir(allocs_root))
                if os.path.isdir(allocs_root)
                else set()
            )
            assert len(live_dirs) <= 2 + 1  # bound (+1 for sweep race)
        finally:
            client.shutdown()
            srv.shutdown()


class TestLogmonRotation:
    def test_copy_truncate_rotation(self, tmp_path):
        """client/logmon retention: a stream file over its cap rotates to
        .0 (history shifting, oldest dropped) and the live file truncates
        without the writer reopening."""
        from nomad_tpu.client.logmon import rotate_if_needed

        path = tmp_path / "t.stdout"
        path.write_bytes(b"x" * (2 * 1024 * 1024))
        assert rotate_if_needed(str(path), max_files=3, max_file_size_mb=1)
        assert path.stat().st_size == 0
        assert (tmp_path / "t.stdout.0").stat().st_size == 2 * 1024 * 1024
        # MaxFiles counts the live file too: max_files=3 ⇒ 2 history
        # slots; the oldest content (x) drops off on the third rotation
        for marker in (b"a", b"b"):
            path.write_bytes(marker * (2 * 1024 * 1024))
            assert rotate_if_needed(str(path), 3, 1)
        assert (tmp_path / "t.stdout.0").read_bytes()[:1] == b"b"
        assert (tmp_path / "t.stdout.1").read_bytes()[:1] == b"a"
        assert not (tmp_path / "t.stdout.2").exists()
        # under the cap: no rotation
        path.write_bytes(b"small")
        assert not rotate_if_needed(str(path), 3, 1)
        # max_files=1: no history at all — pure truncation
        solo = tmp_path / "solo.stdout"
        solo.write_bytes(b"y" * (2 * 1024 * 1024))
        assert rotate_if_needed(str(solo), 1, 1)
        assert solo.stat().st_size == 0
        assert not (tmp_path / "solo.stdout.0").exists()

    def test_live_task_log_rotation_end_to_end(self, tmp_path):
        """A running task whose stdout crosses the cap keeps writing into
        the truncated live file after the client's sweep rotates it."""
        srv = make_server()
        client = Client(
            srv.client_rpc(), data_dir=str(tmp_path), heartbeat_interval=0.2
        )
        client.start()
        try:
            from nomad_tpu.structs.job import LogConfig

            job = mock.job()
            job.task_groups[0].count = 1
            t = job.task_groups[0].tasks[0]
            t.driver = "raw_exec"
            t.log_config = LogConfig(max_files=2, max_file_size_mb=1)
            # ~1.5 MiB burst, then keep the task alive
            t.config = {
                "command": "/bin/sh",
                "args": [
                    "-c",
                    "yes 0123456789012345678901234567890123456789 | head -c 1600000; sleep 60",
                ],
            }
            srv.register_job(job)
            assert wait_until(
                lambda: client.logmon_sweep() > 0, timeout=20
            ), "rotation never triggered"
            runner = next(iter(client.runners.values()))
            rotated = os.path.join(runner.alloc_dir, "web", "web.stdout.0")
            assert os.path.getsize(rotated) > 1024 * 1024
        finally:
            client.shutdown()
            srv.shutdown()
