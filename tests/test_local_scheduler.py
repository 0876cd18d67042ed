"""Local scheduler tests against real subprocesses (reference analog:
torchx/schedulers/test/local_scheduler_test.py — real Popen, no mocks)."""

import os
import time
from pathlib import Path

import pytest

from torchx_tpu.schedulers.api import Stream
from torchx_tpu.schedulers.local_scheduler import (
    CWDImageProvider,
    LocalDirectoryImageProvider,
    LocalScheduler,
    tpu_device_env,
)
from torchx_tpu.specs.api import (
    AppDef,
    AppState,
    Resource,
    Role,
    TpuSlice,
    macros,
)


@pytest.fixture
def sched():
    s = LocalScheduler(session_name="test", cache_size=10)
    yield s
    s.close()


def sh_role(name: str, script: str, num_replicas: int = 1, **kwargs) -> Role:
    return Role(
        name=name,
        image="",
        entrypoint="sh",
        args=["-c", script],
        num_replicas=num_replicas,
        **kwargs,
    )


def wait_terminal(sched: LocalScheduler, app_id: str, timeout: float = 30) -> AppState:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        desc = sched.describe(app_id)
        assert desc is not None
        if desc.state in (AppState.SUCCEEDED, AppState.FAILED, AppState.CANCELLED):
            return desc.state
        time.sleep(0.05)
    raise TimeoutError(f"app {app_id} did not finish")


class TestElasticRestart:
    """Elastic gangs (min_replicas) shrink-and-restart on replica death,
    resuming from the app's own checkpoint with a resized world
    (BASELINE config 4: elastic min/max rendezvous under preemption)."""

    def elastic_script(self, ckpt_dir: str) -> str:
        # replica 2 "is preempted" (exit 1) before the checkpoint reaches
        # step 5; after the elastic restart the world is smaller, replica 2
        # no longer exists, and survivors resume from the checkpoint
        return (
            f"CK={ckpt_dir}/progress; start=0; "
            '[ -f "$CK" ] && start=$(cat "$CK"); '
            'if [ "$TPX_REPLICA_ID" = "2" ] && [ "$start" -lt 5 ]; then '
            'echo 5 > "$CK"; exit 1; fi; '
            'echo "world=$TPX_NUM_REPLICAS start=$start"; '
            "sleep 0.5; "
            '[ "$TPX_REPLICA_ID" = "0" ] && echo 10 > "$CK"; exit 0'
        )

    def test_shrink_restart_resumes_from_checkpoint(self, sched, tmp_path):
        ckpt = tmp_path / "ckpt"
        ckpt.mkdir()
        app = AppDef(
            name="elastic",
            roles=[
                sh_role(
                    "w",
                    self.elastic_script(str(ckpt)),
                    num_replicas=3,
                    min_replicas=1,
                    max_retries=2,
                )
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.SUCCEEDED
        desc = sched.describe(app_id)
        assert desc.num_restarts == 1
        # the relaunched gang is 2 wide and resumed from the checkpoint
        out0 = (tmp_path / app_id / "w" / "0" / "stdout.log").read_text()
        assert "world=2 start=5" in out0
        # attempt-0 logs were rotated aside, not clobbered
        assert (tmp_path / app_id / "w" / "0" / "stdout.log.0").exists()
        # only 2 replicas in the final gang
        (rs,) = desc.roles_statuses
        assert len(rs.replicas) == 2
        assert (ckpt / "progress").read_text().strip() == "10"

    def test_no_restart_below_min(self, sched, tmp_path):
        ckpt = tmp_path / "ckpt"
        ckpt.mkdir()
        app = AppDef(
            name="floor",
            roles=[
                sh_role(
                    "w",
                    self.elastic_script(str(ckpt)),
                    num_replicas=3,
                    min_replicas=3,  # can't shrink below the floor
                    max_retries=2,
                )
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.FAILED
        assert sched.describe(app_id).num_restarts == 0

    def test_rigid_gang_restarts_full_size(self, sched, tmp_path):
        """No min_replicas, but max_retries with the default APPLICATION
        retry policy: the gang restarts at FULL size (the local analog of
        JobSet maxRestarts / slurm requeue)."""
        ckpt = tmp_path / "ckpt"
        ckpt.mkdir()
        app = AppDef(
            name="rigid",
            roles=[
                sh_role(
                    "w",
                    self.elastic_script(str(ckpt)),
                    num_replicas=3,
                    max_retries=2,
                )
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.SUCCEEDED
        desc = sched.describe(app_id)
        assert desc.num_restarts == 1
        (rs,) = desc.roles_statuses
        assert len(rs.replicas) == 3  # full size, not shrunk
        out0 = (tmp_path / app_id / "w" / "0" / "stdout.log").read_text()
        assert "world=3 start=5" in out0  # resumed from checkpoint

    def test_rigid_gang_fatal_without_retries(self, sched, tmp_path):
        ckpt = tmp_path / "ckpt"
        ckpt.mkdir()
        app = AppDef(
            name="rigid0",
            roles=[
                sh_role(
                    "w",
                    self.elastic_script(str(ckpt)),
                    num_replicas=3,  # max_retries defaults to 0
                )
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.FAILED
        assert sched.describe(app_id).num_restarts == 0

    def test_replica_retry_policy_is_fatal_for_gang(self, sched, tmp_path):
        from torchx_tpu.specs.api import RetryPolicy

        ckpt = tmp_path / "ckpt"
        ckpt.mkdir()
        app = AppDef(
            name="rep",
            roles=[
                sh_role(
                    "w",
                    self.elastic_script(str(ckpt)),
                    num_replicas=3,
                    max_retries=2,
                    retry_policy=RetryPolicy.REPLICA,
                )
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.FAILED

    def test_tpu_gang_shrinks_whole_slices(self, sched, tmp_path):
        """A TPU gang (2 slices x 2 hosts) losing one host must shrink to
        ONE whole slice (2 hosts), not 3 — and the relaunched world's env
        must be internally consistent (no stale multi-slice megascale env)."""
        script = (
            'if [ "$TPX_REPLICA_ID" = "3" ] && [ ! -f %s/died ]; then '
            "touch %s/died; exit 1; fi; "
            'echo "world=$TPX_NUM_REPLICAS slices=${MEGASCALE_NUM_SLICES:-none}'
            ' slice=${TPX_SLICE_ID:-none}"; sleep 0.5; exit 0'
        ) % (tmp_path, tmp_path)
        role = Role(
            name="w",
            image="",
            entrypoint="sh",
            args=["-c", script],
            num_replicas=2,  # slices
            min_replicas=1,
            max_retries=2,
            resource=Resource(cpu=1, memMB=256, tpu=TpuSlice("v5p", 8)),
        )
        app_id = sched.submit(AppDef(name="tpu-elastic", roles=[role]),
                              {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.SUCCEEDED
        desc = sched.describe(app_id)
        assert desc.num_restarts == 1
        (rs,) = desc.roles_statuses
        assert len(rs.replicas) == 2  # one whole slice, not 3 hosts
        out0 = (tmp_path / app_id / "w" / "0" / "stdout.log").read_text()
        assert "world=2 slices=none slice=none" in out0

    def test_role_scoped_restart_keeps_healthy_roles_running(self, sched, tmp_path):
        """RetryPolicy.ROLE: only the failed role relaunches; the healthy
        role's processes are left untouched (same pid across the restart)."""
        from torchx_tpu.specs.api import RetryPolicy

        flaky = (
            f"if [ ! -f {tmp_path}/fired ]; then touch {tmp_path}/fired;"
            ' exit 1; fi; echo "recovered"; exit 0'
        )
        steady = f'echo "pid=$$" >> {tmp_path}/steady.pids; sleep 3; exit 0'
        app = AppDef(
            name="rolescope",
            roles=[
                sh_role(
                    "flaky", flaky, num_replicas=1, max_retries=1,
                    retry_policy=RetryPolicy.ROLE,
                ),
                sh_role("steady", steady, num_replicas=1),
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.SUCCEEDED
        assert sched.describe(app_id).num_restarts == 1
        # the steady role ran exactly once — it was never killed/relaunched
        pids = (tmp_path / "steady.pids").read_text().strip().splitlines()
        assert len(pids) == 1
        # only the flaky role's logs were rotated
        assert (tmp_path / app_id / "flaky" / "0" / "stdout.log.0").exists()
        assert not (tmp_path / app_id / "steady" / "0" / "stdout.log.0").exists()

    def test_per_role_budget_not_pooled(self, sched, tmp_path):
        """A role's own max_retries bounds ITS restarts even when another
        role in the app carries a bigger budget."""
        always_fails = 'exit 1'
        app = AppDef(
            name="pooled",
            roles=[
                sh_role("a", always_fails, num_replicas=1, max_retries=1),
                sh_role("b", "sleep 5", num_replicas=1, max_retries=3),
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.FAILED
        # role a restarted once (its budget), NOT three times (b's budget)
        assert sched.describe(app_id).num_restarts == 1

    def test_budgets_are_per_role_both_directions(self, sched, tmp_path):
        """Role A's restart must not consume role B's budget: after A
        restarts once (its budget), B's FIRST failure still gets B's own
        retry. Both roles are ROLE-scoped with max_retries=1."""
        from torchx_tpu.specs.api import RetryPolicy

        a = (
            f"if [ ! -f {tmp_path}/a-fired ]; then touch {tmp_path}/a-fired;"
            " exit 1; fi; exit 0"
        )
        # b fails AFTER a recovered (ordering via marker file), once
        b = (
            f"while [ ! -f {tmp_path}/a-fired ]; do sleep 0.1; done; "
            f"if [ ! -f {tmp_path}/b-fired ]; then sleep 0.5;"
            f" touch {tmp_path}/b-fired; exit 1; fi; exit 0"
        )
        app = AppDef(
            name="two-budgets",
            roles=[
                sh_role("a", a, num_replicas=1, max_retries=1,
                        retry_policy=RetryPolicy.ROLE),
                sh_role("b", b, num_replicas=1, max_retries=1,
                        retry_policy=RetryPolicy.ROLE),
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.SUCCEEDED
        # each role consumed exactly its own single retry
        assert sched.describe(app_id).num_restarts == 2

    def test_restart_budget_exhausted(self, sched, tmp_path):
        # every attempt fails (replica 0 always dies) -> FAILED after
        # max_retries restarts
        app = AppDef(
            name="burn",
            roles=[
                sh_role(
                    "w",
                    'if [ "$TPX_REPLICA_ID" = "0" ]; then exit 1; fi; sleep 20',
                    num_replicas=3,
                    min_replicas=1,
                    max_retries=1,
                )
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.FAILED
        assert sched.describe(app_id).num_restarts == 1


class TestLocalScheduler:
    def test_submit_success(self, sched, tmp_path):
        app = AppDef(name="ok", roles=[sh_role("r", "echo hello")])
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id) == AppState.SUCCEEDED
        out = tmp_path / app_id / "r" / "0" / "stdout.log"
        assert out.read_text().strip() == "hello"
        # SUCCESS marker written
        assert (tmp_path / app_id / "SUCCESS").exists()

    def test_submit_failure_kills_gang(self, sched, tmp_path):
        app = AppDef(
            name="fail",
            roles=[
                sh_role("bad", "exit 3"),
                sh_role("slow", "sleep 30"),
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        state = wait_terminal(sched, app_id, timeout=20)
        assert state == AppState.FAILED
        # gang fail-fast: the sleeper must not still be running
        desc = sched.describe(app_id)
        slow = [rs for rs in desc.roles_statuses if rs.role == "slow"][0]
        assert all(r.state != AppState.RUNNING for r in slow.replicas)

    def test_macro_substitution(self, sched, tmp_path):
        app = AppDef(
            name="macro",
            roles=[
                sh_role(
                    "m",
                    f"echo replica={macros.replica_id} app={macros.app_id}",
                    num_replicas=2,
                )
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        wait_terminal(sched, app_id)
        out0 = (tmp_path / app_id / "m" / "0" / "stdout.log").read_text()
        out1 = (tmp_path / app_id / "m" / "1" / "stdout.log").read_text()
        assert f"replica=0 app={app_id}" in out0
        assert f"replica=1 app={app_id}" in out1

    def test_gang_env_injection(self, sched, tmp_path):
        app = AppDef(
            name="env",
            roles=[sh_role("e", "echo $TPX_REPLICA_ID/$TPX_NUM_REPLICAS-$TPX_COORDINATOR_HOST", num_replicas=2)],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        wait_terminal(sched, app_id)
        assert (tmp_path / app_id / "e" / "1" / "stdout.log").read_text().strip() == (
            "1/2-localhost"
        )

    def test_tpu_role_expands_to_hosts(self, sched, tmp_path):
        # v5p-32 = 16 chips = 4 hosts -> 4 replicas
        role = sh_role("t", "echo $TPX_NUM_REPLICAS")
        role.resource = Resource(cpu=1, memMB=512, tpu=TpuSlice("v5p", 16))
        app = AppDef(name="tpu", roles=[role])
        info = sched.submit_dryrun(app, {"log_dir": str(tmp_path)})
        assert len(info.request.role_params["t"]) == 4
        env = info.request.role_params["t"][0].env
        assert env["TPX_NUM_REPLICAS"] == "4"
        assert env["TPX_TPU_ACCELERATOR_TYPE"] == "v5p-32"
        # no local chips in CI: simulation env is set
        assert env.get("JAX_PLATFORMS") == "cpu"
        assert "xla_force_host_platform_device_count=4" in env.get("XLA_FLAGS", "")

    def test_multislice_megascale_env(self, sched, tmp_path):
        role = sh_role("t", "true")
        role.resource = Resource(cpu=1, memMB=512, tpu=TpuSlice("v5e", 8))
        role.num_replicas = 2  # 2 slices x 1 host
        app = AppDef(name="ms", roles=[role])
        info = sched.submit_dryrun(app, {"log_dir": str(tmp_path)})
        params = info.request.role_params["t"]
        assert len(params) == 2
        assert params[0].env["MEGASCALE_NUM_SLICES"] == "2"
        assert params[0].env["MEGASCALE_SLICE_ID"] == "0"
        assert params[1].env["MEGASCALE_SLICE_ID"] == "1"

    def test_cancel(self, sched, tmp_path):
        app = AppDef(name="c", roles=[sh_role("s", "sleep 60")])
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        time.sleep(0.2)
        sched.cancel(app_id)
        assert wait_terminal(sched, app_id) == AppState.CANCELLED

    def test_error_file_surfaced(self, sched, tmp_path):
        script = (
            'mkdir -p "$(dirname $TPX_ERROR_FILE)"; '
            'echo \'{"message": {"message": "kaboom", "extraInfo": {}}, "exitcode": 5, "hostname": "h"}\' > $TPX_ERROR_FILE; '
            "exit 5"
        )
        app = AppDef(name="err", roles=[sh_role("e", script)])
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id) == AppState.FAILED
        desc = sched.describe(app_id)
        assert "kaboom" in desc.structured_error_msg

    def test_log_iter(self, sched, tmp_path):
        app = AppDef(name="logs", roles=[sh_role("l", "echo a; echo b; echo c")])
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        wait_terminal(sched, app_id)
        lines = list(sched.log_iter(app_id, "l", 0, streams=Stream.STDOUT))
        assert lines == ["a", "b", "c"]

    def test_log_iter_tail(self, sched, tmp_path):
        app = AppDef(
            name="tail", roles=[sh_role("t", "echo first; sleep 0.8; echo last")]
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        lines = list(
            sched.log_iter(app_id, "t", 0, should_tail=True, streams=Stream.STDOUT)
        )
        assert lines == ["first", "last"]

    def test_log_iter_regex(self, sched, tmp_path):
        app = AppDef(name="re", roles=[sh_role("r", "echo keep; echo drop")])
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        wait_terminal(sched, app_id)
        lines = list(
            sched.log_iter(app_id, "r", 0, regex="keep", streams=Stream.STDOUT)
        )
        assert lines == ["keep"]

    def test_list(self, sched, tmp_path):
        app = AppDef(name="lst", roles=[sh_role("x", "true")])
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        wait_terminal(sched, app_id)
        listing = sched.list()
        assert any(a.app_id == app_id for a in listing)

    def test_lru_eviction(self, tmp_path):
        sched = LocalScheduler(session_name="lru", cache_size=2)
        try:
            ids = []
            for i in range(3):
                app = AppDef(name=f"a{i}", roles=[sh_role("r", "true")])
                app_id = sched.submit(app, {"log_dir": str(tmp_path)})
                wait_terminal(sched, app_id)
                ids.append(app_id)
            # evicted from the in-process cache, but still describable via
            # the on-disk state file (terminal state is authoritative)
            evicted = sched.describe(ids[0])
            assert evicted is not None and evicted.state == AppState.SUCCEEDED
            assert sched.describe(ids[2]) is not None
        finally:
            sched.close()

    def test_combined_stream(self, sched, tmp_path):
        app = AppDef(name="comb", roles=[sh_role("c", "echo out; echo err 1>&2")])
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        wait_terminal(sched, app_id)
        time.sleep(0.3)  # allow tee to drain
        combined = (tmp_path / app_id / "c" / "0" / "combined.log").read_text()
        assert "out" in combined and "err" in combined
        # every tee'd line leads with an epoch stamp (what log windows use)
        from torchx_tpu.schedulers.api import parse_epoch_stamp

        for raw in combined.splitlines():
            ts, payload = parse_epoch_stamp(raw)
            assert ts is not None and payload in ("out", "err")

    def test_log_windows_on_combined(self, sched, tmp_path):
        app = AppDef(name="win", roles=[sh_role("w", "echo early; echo late")])
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        wait_terminal(sched, app_id)
        time.sleep(0.3)  # allow tee to drain
        now = time.time()
        # stamps are stripped from the default (combined) stream
        lines = list(sched.log_iter(app_id, "w", 0))
        assert lines == ["early", "late"]
        # a window entirely in the past excludes everything
        assert list(sched.log_iter(app_id, "w", 0, until=now - 3600)) == []
        # a window entirely in the future excludes everything
        assert list(sched.log_iter(app_id, "w", 0, since=now + 3600)) == []
        # a window spanning now includes everything
        assert (
            list(sched.log_iter(app_id, "w", 0, since=now - 3600, until=now + 60))
            == ["early", "late"]
        )

    def test_dir_image_provider(self, tmp_path):
        img = tmp_path / "img"
        img.mkdir()
        (img / "hello.sh").write_text("#!/bin/sh\necho from-image\n")
        os.chmod(img / "hello.sh", 0o755)
        sched = LocalScheduler(
            session_name="dir", image_provider=LocalDirectoryImageProvider()
        )
        try:
            app = AppDef(
                name="img",
                roles=[
                    Role(name="r", image=str(img), entrypoint="hello.sh", args=[])
                ],
            )
            app_id = sched.submit(app, {"log_dir": str(tmp_path / "logs")})
            assert wait_terminal(sched, app_id) == AppState.SUCCEEDED
            out = tmp_path / "logs" / app_id / "r" / "0" / "stdout.log"
            assert out.read_text().strip() == "from-image"
        finally:
            sched.close()

    def test_dir_image_provider_rejects_missing(self):
        with pytest.raises(ValueError):
            LocalDirectoryImageProvider().fetch("/definitely/not/a/dir")


class TestCrossProcessState:
    def test_second_scheduler_reads_terminal_state(self, tmp_path, monkeypatch):
        monkeypatch.setattr(
            "torchx_tpu.schedulers.local_scheduler._registry_path",
            lambda: str(tmp_path / "registry"),
        )
        owner = LocalScheduler(session_name="owner")
        try:
            app = AppDef(name="xp", roles=[sh_role("r", "echo cross-process")])
            app_id = owner.submit(app, {"log_dir": str(tmp_path)})
            wait_terminal(owner, app_id)
        finally:
            owner.close()
        # a different scheduler instance (≈ another CLI process)
        other = LocalScheduler(session_name="other")
        try:
            desc = other.describe(app_id)
            assert desc is not None and desc.state == AppState.SUCCEEDED
            lines = list(other.log_iter(app_id, "r", 0, streams=Stream.STDOUT))
            assert lines == ["cross-process"]
        finally:
            other.close()

    def test_orphaned_running_state_reports_unknown(self, tmp_path, monkeypatch):
        import json

        monkeypatch.setattr(
            "torchx_tpu.schedulers.local_scheduler._registry_path",
            lambda: str(tmp_path / "registry"),
        )
        # forge a state file whose owner died mid-run (pid 1 is not ours;
        # use an impossible pid)
        log_dir = tmp_path / "ghost-app"
        log_dir.mkdir()
        (log_dir / ".tpx_state.json").write_text(
            json.dumps(
                {
                    "app_id": "ghost-app",
                    "state": "RUNNING",
                    "log_dir": str(log_dir),
                    "roles": {"r": [{"id": 0, "pid": 2**22 + 12345}]},
                }
            )
        )
        (tmp_path / "registry").write_text(f"ghost-app = {log_dir}\n")
        sched = LocalScheduler(session_name="reader")
        try:
            desc = sched.describe("ghost-app")
            assert desc is not None and desc.state == AppState.UNKNOWN
        finally:
            sched.close()


class TestCrossProcessCancelList:
    def test_cancel_from_other_process(self, tmp_path, monkeypatch):
        monkeypatch.setattr(
            "torchx_tpu.schedulers.local_scheduler._registry_path",
            lambda: str(tmp_path / "registry"),
        )
        owner = LocalScheduler(session_name="owner")
        other = LocalScheduler(session_name="other")
        try:
            app = AppDef(name="xc", roles=[sh_role("r", "sleep 60")])
            app_id = owner.submit(app, {"log_dir": str(tmp_path)})
            time.sleep(0.3)
            # cancel from the NON-owning scheduler
            other.cancel(app_id)
            desc = other.describe(app_id)
            assert desc.state == AppState.CANCELLED
            # the owner honors the on-disk CANCELLED mark rather than
            # recording its SIGTERM'd children as a failure
            assert wait_terminal(owner, app_id, timeout=15) == AppState.CANCELLED
        finally:
            owner.close()
            other.close()

    def test_list_includes_external(self, tmp_path, monkeypatch):
        monkeypatch.setattr(
            "torchx_tpu.schedulers.local_scheduler._registry_path",
            lambda: str(tmp_path / "registry"),
        )
        owner = LocalScheduler(session_name="owner")
        try:
            app = AppDef(name="xl", roles=[sh_role("r", "true")])
            app_id = owner.submit(app, {"log_dir": str(tmp_path)})
            wait_terminal(owner, app_id)
        finally:
            owner.close()
        other = LocalScheduler(session_name="other")
        try:
            listing = other.list()
            assert any(a.app_id == app_id for a in listing)
        finally:
            other.close()


class TestTpuDeviceEnv:
    ON_CHIP = {"JAX_PLATFORMS": "tpu,cpu"}  # never an inherited "cpu"

    def test_partitioning(self):
        env = tpu_device_env(4, replica_id=1, replicas_on_host=2, host_chips=8, simulate=True)
        assert env["TPU_VISIBLE_CHIPS"] == "4,5,6,7"
        # each process is its own world over its chips
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert env["JAX_PLATFORMS"] == "tpu,cpu"

    def test_one_chip_replicas_get_distinct_chips_and_ports(self):
        envs = [
            tpu_device_env(1, i, replicas_on_host=4, host_chips=4, simulate=False)
            for i in range(4)
        ]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4

    def test_single_replica_uses_all_chips(self):
        env = tpu_device_env(4, 0, replicas_on_host=1, host_chips=4, simulate=True)
        assert env == self.ON_CHIP

    def test_role_asking_one_chip_of_four_is_held_to_one(self):
        env = tpu_device_env(1, 0, replicas_on_host=1, host_chips=4, simulate=True)
        assert env["TPU_VISIBLE_CHIPS"] == "0"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"

    def test_partition_disabled_on_real_host(self):
        env = tpu_device_env(4, 0, replicas_on_host=2, host_chips=4, simulate=True, partition=False)
        assert env == self.ON_CHIP  # no CPU simulation forced on a host with chips

    def test_oversubscription_raises(self):
        import pytest as _pytest

        with _pytest.raises(ValueError):
            tpu_device_env(1, 5, replicas_on_host=8, host_chips=4, simulate=True)

    def test_simulation(self):
        env = tpu_device_env(4, 0, 1, host_chips=0, simulate=True)
        assert env["JAX_PLATFORMS"] == "cpu"
        assert "device_count=4" in env["XLA_FLAGS"]

    def test_no_sim_no_chips_is_refused(self):
        import pytest as _pytest

        with _pytest.raises(ValueError, match="no TPU chip"):
            tpu_device_env(4, 0, 1, host_chips=0, simulate=False)

    def test_no_sim_no_chips_fails_the_dryrun(self, tmp_path):
        from torchx_tpu.schedulers.local_scheduler import create_scheduler

        role = sh_role("t", "true")
        role.resource = Resource(cpu=1, memMB=512, tpu=TpuSlice("v5e", 1))
        sched = create_scheduler("no-chip")
        try:
            with pytest.raises(ValueError, match="no TPU chip"):
                sched.submit_dryrun(
                    AppDef(name="t", roles=[role]),
                    {"log_dir": str(tmp_path), "tpu_simulate": False},
                )
        finally:
            sched.close()

    def test_inherited_cpu_platform_does_not_reach_a_tpu_role(
        self, tmp_path, monkeypatch
    ):
        """The launching shell exports JAX_PLATFORMS=cpu (this sandbox
        does); on a host with chips a TPU role must not inherit it."""
        from torchx_tpu.schedulers import local_scheduler as ls

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setattr(ls, "local_tpu_chip_count", lambda: 1)
        role = sh_role("t", "true")
        role.resource = Resource(cpu=1, memMB=512, tpu=TpuSlice("v5e", 1))
        sched = ls.create_scheduler("chip")
        try:
            info = sched.submit_dryrun(
                AppDef(name="t", roles=[role]),
                {"log_dir": str(tmp_path), "tpu_simulate": False},
            )
        finally:
            sched.close()
        (rp,) = info.request.role_params["t"]
        assert rp.env["JAX_PLATFORMS"] == "tpu,cpu"


class TestManualResize:
    """Operator-driven `resize` (the manual counterpart of the elastic
    shrink-on-failure path): the role gang restarts with a coherent world
    and resumes from its checkpoint."""

    def resize_script(self, tmp_path) -> str:
        # each attempt logs its world, then waits long enough for the test
        # to resize mid-flight (the resized attempt exits promptly)
        return (
            f'echo "world=$TPX_NUM_REPLICAS id=$TPX_REPLICA_ID"; '
            f'if [ -f {tmp_path}/resized ]; then exit 0; fi; '
            "sleep 30"
        )

    def test_shrink_and_grow(self, sched, tmp_path):
        app = AppDef(
            name="manual",
            roles=[
                sh_role(
                    "w",
                    self.resize_script(tmp_path),
                    num_replicas=4,
                    min_replicas=2,
                )
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        # shrink 4 -> 2
        sched.resize(app_id, "w", 2)
        desc = sched.describe(app_id)
        (rs,) = desc.roles_statuses
        assert len(rs.replicas) == 2
        assert desc.num_restarts == 1
        # grow 2 -> 3 (local gangs can grow: they are just processes)
        (tmp_path / "resized").touch()
        sched.resize(app_id, "w", 3)
        assert wait_terminal(sched, app_id, timeout=30) == AppState.SUCCEEDED
        out0 = (tmp_path / app_id / "w" / "0" / "stdout.log").read_text()
        assert "world=3 id=0" in out0
        # both earlier attempts' logs were rotated aside
        assert (tmp_path / app_id / "w" / "0" / "stdout.log.0").exists()
        assert (tmp_path / app_id / "w" / "0" / "stdout.log.1").exists()

    def test_floor_enforced(self, sched, tmp_path):
        app = AppDef(
            name="floor",
            roles=[
                sh_role(
                    "w",
                    self.resize_script(tmp_path),
                    num_replicas=3,
                    min_replicas=2,
                )
            ],
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        with pytest.raises(ValueError, match="below its declared min_replicas"):
            sched.resize(app_id, "w", 1)
        sched.cancel(app_id)

    def test_tpu_role_resizes_in_slice_units(self, sched, tmp_path):
        script = (
            'echo "world=$TPX_NUM_REPLICAS slices=${MEGASCALE_NUM_SLICES:-none}"; '
            f'if [ -f {tmp_path}/resized ]; then exit 0; fi; sleep 30'
        )
        role = Role(
            name="w",
            image="",
            entrypoint="sh",
            args=["-c", script],
            num_replicas=3,  # slices of 2 hosts each
            min_replicas=1,
            resource=Resource(cpu=1, memMB=256, tpu=TpuSlice("v5p", 8)),
        )
        app_id = sched.submit(
            AppDef(name="tpu-resize", roles=[role]), {"log_dir": str(tmp_path)}
        )
        (tmp_path / "resized").touch()
        sched.resize(app_id, "w", 2)  # 3 slices -> 2 slices = 4 hosts
        desc = sched.describe(app_id)
        (rs,) = desc.roles_statuses
        assert len(rs.replicas) == 4
        assert wait_terminal(sched, app_id, timeout=30) == AppState.SUCCEEDED
        out0 = (tmp_path / app_id / "w" / "0" / "stdout.log").read_text()
        assert "world=4 slices=2" in out0

    def test_resize_unknown_app_or_role(self, sched, tmp_path):
        with pytest.raises(ValueError, match="unknown app"):
            sched.resize("ghost", "w", 2)
        app = AppDef(
            name="r", roles=[sh_role("w", "sleep 30", num_replicas=2)]
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        with pytest.raises(ValueError, match="has no role"):
            sched.resize(app_id, "ghost", 2)
        sched.cancel(app_id)

    def test_resize_terminal_app_raises(self, sched, tmp_path):
        app = AppDef(name="done", roles=[sh_role("w", "exit 0")])
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        assert wait_terminal(sched, app_id, timeout=30) == AppState.SUCCEEDED
        with pytest.raises(ValueError, match="terminal"):
            sched.resize(app_id, "w", 2)

    def test_noop_resize_keeps_gang(self, sched, tmp_path):
        app = AppDef(
            name="noop", roles=[sh_role("w", "sleep 30", num_replicas=2)]
        )
        app_id = sched.submit(app, {"log_dir": str(tmp_path)})
        sched.resize(app_id, "w", 2)  # same size: no restart
        assert sched.describe(app_id).num_restarts == 0
        sched.cancel(app_id)
