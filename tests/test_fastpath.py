"""Warm-launch fast-path tests: lazy CLI dispatch, the describe cache,
concurrent control-plane fan-out (list / logs / workspace builds), the
line-atomic log emitter, and the launch.breakdown span plumbing."""

import io
import json
import os
import subprocess
import sys
import threading
import time
from typing import Mapping, Optional

import pytest

from torchx_tpu.obs import metrics as obs_metrics
from torchx_tpu.runner.api import Runner, UnknownSchedulerError
from torchx_tpu.runner.describe_cache import DescribeCache, cache_ttl
from torchx_tpu.schedulers.api import DescribeAppResponse, ListAppResponse, Scheduler
from torchx_tpu.specs.api import (
    AppDef,
    AppDryRunInfo,
    AppState,
    CfgVal,
    Role,
    Workspace,
    runopts,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# =========================================================================
# Lazy CLI dispatch
# =========================================================================


def _probe_cli(argv: list[str], forbidden: list[str]) -> None:
    """Run ``main(argv)`` in a fresh interpreter and assert none of the
    ``forbidden`` modules were imported (the lazy-dispatch contract)."""
    code = f"""
import json, sys
from torchx_tpu.cli.main import main
try:
    main({argv!r})
except SystemExit:
    pass
print(json.dumps([m for m in {forbidden!r} if m in sys.modules]))
"""
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr
    leaked = json.loads(proc.stdout.strip().splitlines()[-1])
    assert leaked == [], f"lazily-dispatched CLI imported {leaked}"


class TestLazyCli:
    HEAVY = [
        "jax",
        "numpy",
        "torchx_tpu.cli.cmd_run",
        "torchx_tpu.cli.cmd_lint",
        "torchx_tpu.examples.train_llama",
        "torchx_tpu.parallel.aot_fit",
    ]

    def test_help_imports_no_subcommand_modules(self):
        _probe_cli(["--help"], self.HEAVY)

    def test_list_never_imports_jax(self, tmp_path):
        code = """
import json, sys
from torchx_tpu.cli.main import main
try:
    main(["list", "-s", "local"])
except SystemExit:
    pass
print(json.dumps([m for m in ("jax", "torchx_tpu.cli.cmd_run") if m in sys.modules]))
"""
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=120,
            cwd=REPO_ROOT,
            env={**os.environ, "HOME": str(tmp_path), "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr
        leaked = json.loads(proc.stdout.strip().splitlines()[-1])
        assert leaked == [], f"`tpx list` imported {leaked}"

    def test_peek_cmd(self):
        from torchx_tpu.cli.main import _peek_cmd

        assert _peek_cmd(["status", "x"]) == "status"
        assert _peek_cmd(["--log_level", "DEBUG", "list"]) == "list"
        assert _peek_cmd(["--log-level", "DEBUG", "list"]) == "list"
        assert _peek_cmd(["--log_level=DEBUG", "list"]) == "list"
        assert _peek_cmd(["--version"]) is None
        assert _peek_cmd([]) is None

    def test_create_parser_only_registers_one(self):
        from torchx_tpu.cli.main import create_parser

        parser = create_parser(only="status")
        args = parser.parse_args(["status", "local://s/app"])
        assert hasattr(args, "func")
        with pytest.raises(SystemExit):
            parser.parse_args(["list", "-s", "local"])

    def test_unknown_command_is_an_error(self):
        from torchx_tpu.cli.main import main

        with pytest.raises(SystemExit) as e:
            main(["definitely-not-a-command"])
        assert e.value.code not in (0, None)


# =========================================================================
# Describe cache
# =========================================================================


def _resp(state: AppState = AppState.RUNNING) -> DescribeAppResponse:
    return DescribeAppResponse(app_id="a1", state=state)


class TestDescribeCache:
    def test_ttl_shares_one_fetch(self):
        cache = DescribeCache(ttl=60.0)
        calls = []
        fetch = lambda: calls.append(1) or _resp()  # noqa: E731
        r1 = cache.get("stub", "a1", fetch)
        r2 = cache.get("stub", "a1", fetch)
        assert len(calls) == 1
        assert r1 is r2

    def test_fresh_bypasses_ttl(self):
        cache = DescribeCache(ttl=60.0)
        calls = []
        fetch = lambda: calls.append(1) or _resp()  # noqa: E731
        cache.get("stub", "a1", fetch)
        cache.get("stub", "a1", fetch, fresh=True)
        assert len(calls) == 2

    def test_terminal_state_pinned_even_for_fresh(self):
        cache = DescribeCache(ttl=0.0)
        calls = []
        fetch = lambda: calls.append(1) or _resp(AppState.SUCCEEDED)  # noqa: E731
        cache.get("stub", "a1", fetch)
        r = cache.get("stub", "a1", fetch, fresh=True)
        assert len(calls) == 1
        assert r.state == AppState.SUCCEEDED

    def test_zero_ttl_never_caches_nonterminal(self):
        cache = DescribeCache(ttl=0.0)
        calls = []
        fetch = lambda: calls.append(1) or _resp()  # noqa: E731
        cache.get("stub", "a1", fetch)
        cache.get("stub", "a1", fetch)
        assert len(calls) == 2

    def test_errors_never_cached(self):
        cache = DescribeCache(ttl=60.0)
        calls = []

        def boom():
            calls.append(1)
            raise RuntimeError("control plane down")

        with pytest.raises(RuntimeError):
            cache.get("stub", "a1", boom)
        ok = lambda: calls.append(1) or _resp()  # noqa: E731
        assert cache.get("stub", "a1", ok) is not None
        assert len(calls) == 2

    def test_none_drops_entry(self):
        cache = DescribeCache(ttl=60.0)
        assert cache.get("stub", "a1", lambda: None) is None
        calls = []
        cache.get("stub", "a1", lambda: calls.append(1) or _resp())
        assert len(calls) == 1  # nothing was cached for the None result

    def test_invalidate(self):
        cache = DescribeCache(ttl=60.0)
        calls = []
        fetch = lambda: calls.append(1) or _resp()  # noqa: E731
        cache.get("stub", "a1", fetch)
        cache.invalidate("stub", "a1")
        cache.get("stub", "a1", fetch)
        assert len(calls) == 2

    def test_concurrent_gets_coalesce_to_one_fetch(self):
        cache = DescribeCache(ttl=0.0)  # TTL off: coalescing does the work
        started = threading.Event()
        release = threading.Event()
        calls = []

        def slow_fetch():
            calls.append(1)
            started.set()
            assert release.wait(10)
            return _resp()

        results = []

        def get():
            results.append(cache.get("stub", "a1", slow_fetch, fresh=True))

        t1 = threading.Thread(target=get)
        t1.start()
        assert started.wait(10)
        t2 = threading.Thread(target=get)
        t2.start()
        time.sleep(0.05)  # let t2 reach the coalescing wait
        release.set()
        t1.join(10)
        t2.join(10)
        assert len(calls) == 1
        assert len(results) == 2
        assert all(r is not None and r.state == AppState.RUNNING for r in results)

    def test_cache_ttl_env_parsing(self, monkeypatch):
        from torchx_tpu import settings

        monkeypatch.delenv(settings.ENV_TPX_DESCRIBE_CACHE_TTL, raising=False)
        assert cache_ttl() == settings.DEFAULT_DESCRIBE_CACHE_TTL
        monkeypatch.setenv(settings.ENV_TPX_DESCRIBE_CACHE_TTL, "2.5")
        assert cache_ttl() == 2.5
        monkeypatch.setenv(settings.ENV_TPX_DESCRIBE_CACHE_TTL, "-1")
        assert cache_ttl() == 0.0
        monkeypatch.setenv(settings.ENV_TPX_DESCRIBE_CACHE_TTL, "nope")
        assert cache_ttl() == settings.DEFAULT_DESCRIBE_CACHE_TTL


# =========================================================================
# Runner integration: cache routing + fan-out
# =========================================================================


class CountingScheduler(Scheduler[dict]):
    """Stub backend that counts describe calls and supports logs."""

    def __init__(self, session_name: str, **kwargs):
        super().__init__("stub", session_name)
        self.apps: dict[str, AppState] = {}
        self.describe_calls = 0
        self.list_delay = 0.0
        self.log_lines_by_replica: dict[tuple[str, int], list[str]] = {}
        self._counter = 0

    def run_opts(self) -> runopts:
        return runopts()

    def _submit_dryrun(self, app: AppDef, cfg: Mapping[str, CfgVal]):
        return AppDryRunInfo({"app": app, "cfg": dict(cfg)})

    def schedule(self, dryrun_info) -> str:
        self._counter += 1
        app_id = f"stub_app_{self._counter}"
        self.apps[app_id] = AppState.RUNNING
        return app_id

    def describe(self, app_id: str) -> Optional[DescribeAppResponse]:
        self.describe_calls += 1
        if app_id not in self.apps:
            return None
        return DescribeAppResponse(app_id=app_id, state=self.apps[app_id])

    def _cancel_existing(self, app_id: str) -> None:
        self.apps[app_id] = AppState.CANCELLED

    def list(self):
        if self.list_delay:
            time.sleep(self.list_delay)
        return [ListAppResponse(app_id=a, state=s) for a, s in self.apps.items()]

    def log_iter(
        self,
        app_id,
        role_name,
        k=0,
        regex=None,
        since=None,
        until=None,
        should_tail=False,
        streams=None,
    ):
        lines = self.log_lines_by_replica.get((role_name, k))
        if lines is None:
            raise RuntimeError(f"no logs for {role_name}/{k}")
        for line in lines:
            time.sleep(0.001)
            yield line


def simple_app() -> AppDef:
    return AppDef(
        name="app",
        roles=[Role(name="r", image="i", entrypoint="echo", args=["hi"])],
    )


@pytest.fixture
def stub():
    return CountingScheduler("test")


@pytest.fixture
def runner(stub):
    r = Runner("test", {"stub": lambda session_name, **kw: stub})
    yield r
    r.close()


class TestRunnerCacheRouting:
    def test_status_polls_share_backend_call(self, runner, stub, monkeypatch):
        from torchx_tpu import settings

        monkeypatch.setenv(settings.ENV_TPX_DESCRIBE_CACHE_TTL, "60")
        handle = runner.run(simple_app(), "stub")
        base = stub.describe_calls
        h0 = obs_metrics.DESCRIBE_CACHE_HITS.value(scheduler="stub")
        m0 = obs_metrics.DESCRIBE_CACHE_MISSES.value(scheduler="stub")
        for _ in range(5):
            assert runner.status(handle).state == AppState.RUNNING
        assert stub.describe_calls == base + 1
        assert obs_metrics.DESCRIBE_CACHE_MISSES.value(scheduler="stub") == m0 + 1
        assert obs_metrics.DESCRIBE_CACHE_HITS.value(scheduler="stub") == h0 + 4

    def test_fresh_status_always_hits_backend(self, runner, stub, monkeypatch):
        from torchx_tpu import settings

        monkeypatch.setenv(settings.ENV_TPX_DESCRIBE_CACHE_TTL, "60")
        handle = runner.run(simple_app(), "stub")
        base = stub.describe_calls
        runner.status(handle, fresh=True)
        runner.status(handle, fresh=True)
        assert stub.describe_calls == base + 2

    def test_cancel_invalidates_cache(self, runner, stub, monkeypatch):
        from torchx_tpu import settings

        monkeypatch.setenv(settings.ENV_TPX_DESCRIBE_CACHE_TTL, "60")
        handle = runner.run(simple_app(), "stub")
        assert runner.status(handle).state == AppState.RUNNING
        runner.cancel(handle)
        # CANCELLED must be visible immediately despite the fat TTL
        assert runner.status(handle).state == AppState.CANCELLED

    def test_terminal_state_needs_no_backend_calls(self, runner, stub, monkeypatch):
        from torchx_tpu import settings

        monkeypatch.setenv(settings.ENV_TPX_DESCRIBE_CACHE_TTL, "0")
        handle = runner.run(simple_app(), "stub")
        app_id = handle.rsplit("/", 1)[-1]
        stub.apps[app_id] = AppState.SUCCEEDED
        runner.status(handle, fresh=True)
        base = stub.describe_calls
        for _ in range(3):
            assert runner.status(handle, fresh=True).state == AppState.SUCCEEDED
        assert stub.describe_calls == base


class TestListFanOut:
    def _runner(self, factories):
        return Runner("test", factories)

    def test_registry_order_regardless_of_completion(self):
        slow = CountingScheduler("test")
        slow.apps["slow_1"] = AppState.RUNNING
        slow.list_delay = 0.2
        fast = CountingScheduler("test")
        fast.apps["fast_1"] = AppState.SUCCEEDED
        r = self._runner(
            {
                "slow": lambda session_name, **kw: slow,
                "fast": lambda session_name, **kw: fast,
            }
        )
        try:
            results, errors = r.list_all()
        finally:
            r.close()
        assert errors == {}
        assert list(results) == ["slow", "fast"]  # registry order
        assert [a.app_id for a in results["slow"]] == ["slow_1"]
        assert [a.app_id for a in results["fast"]] == ["fast_1"]

    def test_one_broken_backend_does_not_hide_others(self):
        ok = CountingScheduler("test")
        ok.apps["ok_1"] = AppState.RUNNING

        class Broken(CountingScheduler):
            def list(self):
                raise RuntimeError("unreachable control plane")

        r = self._runner(
            {
                "broken": lambda session_name, **kw: Broken("test"),
                "ok": lambda session_name, **kw: ok,
            }
        )
        try:
            results, errors = r.list_all()
        finally:
            r.close()
        assert [a.app_id for a in results["ok"]] == ["ok_1"]
        assert "broken" in errors
        assert "unreachable" in str(errors["broken"])

    def test_unknown_scheduler_rejected(self, runner):
        with pytest.raises(UnknownSchedulerError):
            runner.list_all(schedulers=["nope"])

    def test_fanout_is_concurrent(self):
        barrier = threading.Barrier(2, timeout=10)

        class Meeting(CountingScheduler):
            def list(self):
                barrier.wait()  # deadlocks unless both lists run at once
                return super().list()

        r = self._runner(
            {
                "a": lambda session_name, **kw: Meeting("test"),
                "b": lambda session_name, **kw: Meeting("test"),
            }
        )
        try:
            results, errors = r.list_all()
        finally:
            r.close()
        assert errors == {}
        assert list(results) == ["a", "b"]


class TestLogMerge:
    def test_per_replica_order_preserved(self, runner, stub):
        handle = runner.run(simple_app(), "stub")
        stub.log_lines_by_replica = {
            ("r", 0): [f"r0 line {i}\n" for i in range(20)],
            ("r", 1): [f"r1 line {i}\n" for i in range(20)],
        }
        got = list(runner.log_lines_multi(handle, {"r": [0, 1]}))
        by_replica: dict[int, list[str]] = {0: [], 1: []}
        for role, rid, line in got:
            assert role == "r"
            assert not line.endswith("\n")
            by_replica[rid].append(line)
        assert by_replica[0] == [f"r0 line {i}" for i in range(20)]
        assert by_replica[1] == [f"r1 line {i}" for i in range(20)]

    def test_stream_error_is_isolated(self, runner, stub):
        handle = runner.run(simple_app(), "stub")
        stub.log_lines_by_replica = {("r", 0): ["ok\n"]}  # replica 1 missing
        got = list(runner.log_lines_multi(handle, {"r": [0, 1]}))
        lines = {(rid, line) for _, rid, line in got}
        assert (0, "ok") in lines
        assert any(rid == 1 and "log stream error" in line for rid, line in lines)

    def test_empty_replicas(self, runner, stub):
        handle = runner.run(simple_app(), "stub")
        assert list(runner.log_lines_multi(handle, {})) == []


# =========================================================================
# Parallel workspace builds
# =========================================================================


class BarrierWorkspace:
    """Mixin host whose builds must overlap to pass the barrier."""

    from torchx_tpu.workspace.api import WorkspaceMixin

    class Impl(WorkspaceMixin[dict]):
        def __init__(self, barrier=None):
            self.barrier = barrier
            self.builds: list[str] = []

        def build_workspace_and_update_role(self, role, workspace, cfg):
            if self.barrier is not None:
                self.barrier.wait()
            self.builds.append(role.image)
            role.image = f"built-{role.image}"


def _role(name: str, image: str, projects: dict) -> Role:
    return Role(
        name=name,
        image=image,
        entrypoint="echo",
        workspace=Workspace(projects=projects),
    )


class TestParallelWorkspaceBuilds:
    def test_distinct_keys_build_concurrently(self):
        barrier = threading.Barrier(2, timeout=10)
        ws = BarrierWorkspace.Impl(barrier)
        roles = [
            _role("a", "img-a", {"./src": "src"}),
            _role("b", "img-b", {"./src": "src"}),
        ]
        ws.build_workspaces(roles, {})  # serial builds would deadlock here
        assert roles[0].image == "built-img-a"
        assert roles[1].image == "built-img-b"

    def test_shared_key_builds_once(self):
        ws = BarrierWorkspace.Impl()
        roles = [
            _role("a", "img", {"./src": "src"}),
            _role("b", "img", {"./src": "src"}),
            _role("c", "other", {"./src": "src"}),
        ]
        ws.build_workspaces(roles, {})
        assert sorted(ws.builds) == ["img", "other"]  # one build per key
        assert roles[0].image == "built-img"
        assert roles[1].image == "built-img"  # cached result, same key
        assert roles[2].image == "built-other"

    def test_roles_without_workspace_untouched(self):
        ws = BarrierWorkspace.Impl()
        plain = Role(name="p", image="img", entrypoint="echo")
        ws.build_workspaces([plain], {})
        assert plain.image == "img"
        assert ws.builds == []

    def test_build_error_propagates(self):
        class Exploding(BarrierWorkspace.Impl):
            def build_workspace_and_update_role(self, role, workspace, cfg):
                raise RuntimeError("docker build failed")

        ws = Exploding()
        roles = [
            _role("a", "img-a", {"./src": "src"}),
            _role("b", "img-b", {"./src": "src"}),
        ]
        with pytest.raises(RuntimeError, match="docker build failed"):
            ws.build_workspaces(roles, {})


# =========================================================================
# Line-atomic log emitter
# =========================================================================


class TestLineEmitter:
    def test_concurrent_emits_never_tear_lines(self):
        from torchx_tpu.util.log_tee_helpers import LineEmitter

        out = io.StringIO()
        emitter = LineEmitter(out)
        n, writers = 200, 8

        def spam(tag: str):
            for i in range(n):
                emitter.emit(f"[{tag}]", f"line {i}")

        threads = [
            threading.Thread(target=spam, args=(f"w{w}",)) for w in range(writers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        lines = out.getvalue().splitlines()
        assert len(lines) == n * writers
        for line in lines:
            assert line.startswith("[w") and "] line " in line, line

    def test_strips_trailing_newline(self):
        from torchx_tpu.util.log_tee_helpers import LineEmitter

        out = io.StringIO()
        LineEmitter(out).emit("p", "hello\n")
        assert out.getvalue() == "p hello\n"

    def test_no_prefix(self):
        from torchx_tpu.util.log_tee_helpers import LineEmitter

        out = io.StringIO()
        LineEmitter(out).emit("", "bare")
        assert out.getvalue() == "bare\n"


# =========================================================================
# Launch breakdown plumbing
# =========================================================================


class TestLaunchBreakdown:
    def test_launch_span_noop_without_trace_id(self, monkeypatch):
        from torchx_tpu import settings
        from torchx_tpu.train.report import _launch_span
        from torchx_tpu.obs import sinks

        monkeypatch.delenv(settings.ENV_TPX_TRACE_ID, raising=False)
        with _launch_span("launch.test_stage"):
            pass
        assert not os.path.exists(sinks.trace_path())

    def test_launch_span_written_under_trace_id(self, monkeypatch):
        from torchx_tpu import settings
        from torchx_tpu.train.report import _launch_span
        from torchx_tpu.obs import sinks
        from torchx_tpu.obs import trace as obs_trace

        monkeypatch.setenv(settings.ENV_TPX_TRACE_ID, obs_trace.new_trace_id())
        with _launch_span("launch.test_stage", step=7):
            pass
        with open(sinks.trace_path()) as f:
            spans = [json.loads(line) for line in f if line.strip()]
        names = [s.get("name") for s in spans]
        assert "launch.test_stage" in names

    def test_launch_stage_histogram_registered(self):
        before_n = obs_metrics.LAUNCH_STAGE_SECONDS.count(stage="unit_test")
        before_s = obs_metrics.LAUNCH_STAGE_SECONDS.sum(stage="unit_test")
        obs_metrics.LAUNCH_STAGE_SECONDS.observe(1.25, stage="unit_test")
        assert obs_metrics.LAUNCH_STAGE_SECONDS.count(stage="unit_test") == before_n + 1
        assert obs_metrics.LAUNCH_STAGE_SECONDS.sum(stage="unit_test") == pytest.approx(
            before_s + 1.25
        )
