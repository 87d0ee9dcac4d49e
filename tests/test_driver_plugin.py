"""Out-of-process driver plugin contract (client/plugin.py) — the
driver.proto analog: handshake, start/wait/stop through a subprocess,
and reattach-through-restart of BOTH the plugin and the client
(plugins/drivers/task_handle.go + drivers/shared/executor re-exec trick).
Plus the exec driver's isolation (setsid + rlimits + scrubbed env —
drivers/shared/executor's portable subset)."""

import os
import signal
import time

from nomad_tpu import mock
from nomad_tpu.client.drivers import ExecDriver, TaskHandle
from nomad_tpu.client.plugin import PluginDriverClient
from nomad_tpu.structs import Task

from test_client import wait_until


def sh_task(name, script, **res):
    t = Task(
        name=name,
        driver="raw_exec",
        config={"command": "/bin/sh", "args": ["-c", script]},
    )
    if res:
        for k, v in res.items():
            setattr(t.resources, k, v)
    return t


class TestPluginProtocol:
    def test_start_wait_through_plugin(self, tmp_path):
        d = PluginDriverClient("raw_exec")
        try:
            assert d.fingerprint()
            h = d.start(sh_task("t", "echo hi; exit 7"), {}, str(tmp_path))
            assert h.pid > 0
            code = d.wait(h, timeout=10)
            assert code == 7
            out = (tmp_path / "t.stdout").read_bytes()
            assert b"hi" in out
        finally:
            d.close()

    def test_stop_kills_task(self, tmp_path):
        d = PluginDriverClient("raw_exec")
        try:
            h = d.start(sh_task("t", "sleep 60"), {}, str(tmp_path))
            d.stop(h, kill_timeout=2.0)
            assert wait_until(
                lambda: not _alive(h.pid), timeout=5
            ), "task survived stop"
        finally:
            d.close()

    def test_mock_driver_through_plugin(self, tmp_path):
        d = PluginDriverClient("mock_driver")
        try:
            t = Task(name="m", driver="mock_driver", config={"run_for": 0.05, "exit_code": 3})
            h = d.start(t, {}, str(tmp_path))
            assert d.wait(h, timeout=10) == 3
        finally:
            d.close()

    def test_reattach_through_plugin_restart(self, tmp_path):
        """The done-criterion: raw_exec out-of-process with
        restart re-attach through the protocol. The task (own session)
        survives the plugin dying; a fresh plugin recovers the persisted
        handle and can still stop the task."""
        d1 = PluginDriverClient("raw_exec")
        h = d1.start(sh_task("t", "sleep 60"), {}, str(tmp_path))
        pid = h.pid
        # hard-kill the plugin process (not a graceful shutdown)
        d1._proc.kill()
        d1._proc.wait()
        assert _alive(pid), "task must survive the plugin dying"

        d2 = PluginDriverClient("raw_exec")
        try:
            assert d2.recover(h) is True
            d2.stop(h, kill_timeout=2.0)
            assert wait_until(lambda: not _alive(pid), timeout=5)
        finally:
            d2.close()

    def test_recover_rejects_dead_pid(self, tmp_path):
        d = PluginDriverClient("raw_exec")
        try:
            ghost = TaskHandle(id="x", driver="raw_exec", pid=2**22 - 1)
            assert d.recover(ghost) is False
        finally:
            d.close()


class TestClientPluginMode:
    def test_end_to_end_with_plugin_drivers(self, tmp_path):
        from nomad_tpu.client.client import Client
        from nomad_tpu.server.server import Server, ServerConfig

        srv = Server(ServerConfig(num_workers=1))
        srv.establish_leadership()
        client = Client(
            srv.client_rpc(),
            data_dir=str(tmp_path),
            heartbeat_interval=0.2,
            driver_mode="plugin",
        )
        client.start()
        try:
            job = mock.batch_job()
            job.task_groups[0].count = 1
            t = job.task_groups[0].tasks[0]
            t.driver = "raw_exec"
            t.config = {"command": "/bin/sh", "args": ["-c", "echo done"]}
            srv.register_job(job)
            assert wait_until(
                lambda: any(
                    a.client_status == "complete"
                    for a in srv.store.allocs_by_job("default", job.id)
                ),
                timeout=20,
            ), "plugin-mode batch job never completed"
        finally:
            client.shutdown()
            srv.shutdown()


class TestExecIsolation:
    def test_rlimits_applied(self, tmp_path):
        d = ExecDriver()
        t = sh_task("t", "ulimit -v")
        t.driver = "exec"
        t.resources.memory_mb = 256
        h = d.start(t, {}, str(tmp_path))
        assert d.wait(h, timeout=10) == 0
        kb = int((tmp_path / "t.stdout").read_text().strip())
        assert kb == (256 + 512) * 1024  # RLIMIT_AS in KiB

    def test_environment_scrubbed(self, tmp_path):
        os.environ["NOMAD_TPU_LEAK_CANARY"] = "secret"
        try:
            d = ExecDriver()
            t = sh_task("t", "env")
            t.driver = "exec"
            h = d.start(t, {"NOMAD_ALLOC_ID": "a1"}, str(tmp_path))
            assert d.wait(h, timeout=10) == 0
            env_out = (tmp_path / "t.stdout").read_text()
            assert "NOMAD_TPU_LEAK_CANARY" not in env_out
            assert "NOMAD_ALLOC_ID=a1" in env_out
        finally:
            os.environ.pop("NOMAD_TPU_LEAK_CANARY", None)

    def test_own_session(self, tmp_path):
        d = ExecDriver()
        t = sh_task("t", "ps -o sid= -p $$")
        t.driver = "exec"
        h = d.start(t, {}, str(tmp_path))
        assert d.wait(h, timeout=10) == 0
        sid = int((tmp_path / "t.stdout").read_text().strip())
        assert sid != os.getsid(0)  # not the agent's session


class TestNativeExecutor:
    """The C++ supervisor (native/executor.cpp — drivers/shared/executor
    analog): task ownership, durable exit codes, kill forwarding."""

    def test_supervised_start_and_exit_code(self, tmp_path):
        from nomad_tpu.client.drivers import native_executor

        assert native_executor(), "executor binary must build"
        d = ExecDriver()
        t = sh_task("t", "echo out; exit 9")
        t.driver = "exec"
        h = d.start(t, {}, str(tmp_path))
        assert h.meta.get("supervised")
        assert d.wait(h, timeout=10) == 9
        assert b"out" in (tmp_path / "t.stdout").read_bytes()
        assert (tmp_path / "t.status").read_text().strip() == "exit 9"

    def test_exit_code_durable_across_agent_restart(self, tmp_path):
        """Task finishes while the agent is 'down': a fresh driver
        recovers the handle and still observes the real exit code from
        the supervisor's status record — impossible without an owning
        process (the raw_exec reattach limitation)."""
        d1 = ExecDriver()
        t = sh_task("t", "exit 42")
        t.driver = "exec"
        h = d1.start(t, {}, str(tmp_path))
        status = tmp_path / "t.status"
        assert wait_until(
            lambda: status.exists() and "exit" in status.read_text(),
            timeout=10,
        )
        d2 = ExecDriver()  # simulated restart: empty proc table
        assert d2.recover(h) is True
        assert d2.wait(h, timeout=5) == 42

    def test_reattach_live_supervisor_and_stop(self, tmp_path):
        d1 = ExecDriver()
        t = sh_task("t", "sleep 60")
        t.driver = "exec"
        h = d1.start(t, {}, str(tmp_path))
        assert wait_until(
            lambda: (tmp_path / "t.status").exists(), timeout=10
        )
        d2 = ExecDriver()
        assert d2.recover(h) is True
        d2.stop(h, kill_timeout=2.0)
        # in-process "restart" leaves d1's un-reaped Popen as a zombie,
        # so liveness is judged by the durable status record, not the pid
        code = d2.wait(h, timeout=10)
        assert code is not None and code >= 128  # killed by signal
        status = (tmp_path / "t.status").read_text().strip()
        assert status == f"exit {code}"


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except (ProcessLookupError, PermissionError):
        return False
