"""Control-plane resilience tests: failure taxonomy, retry policies, circuit
breakers, deterministic fault injection, the resilient call seam, poll-miss
absorption, and the crash-safe supervision ledger.

The two ISSUE acceptance scenarios live at the bottom: a fault-injected
``supervise`` against the real local scheduler that must complete with ZERO
resubmits (in-seam retries absorb the injected faults), and a SIGKILL of the
supervising client followed by ``Supervisor.resume`` reattaching to the same
live attempt and driving it to SUCCEEDED.
"""

import json
import logging
import os
import random
import shlex
import subprocess
import sys
import textwrap
import time
from pathlib import Path
from typing import Mapping, Optional

import pytest

from torchx_tpu import settings
from torchx_tpu.obs import metrics as obs_metrics
from torchx_tpu.resilience import (
    BreakerOpenError,
    BreakerState,
    CallPolicy,
    CircuitBreaker,
    FailureKind,
    FailureLedger,
    FaultInjector,
    FaultPlan,
    FaultRule,
    PermanentSchedulerError,
    TransientSchedulerError,
    classify_exception,
    classify_proc,
    classify_text,
    is_transient,
)
from torchx_tpu.resilience import faults as resilience_faults
from torchx_tpu.resilience.call import (
    TIMEOUT_RETURNCODE,
    breaker_for,
    control_plane_timeout,
    resilient_call,
    resilient_cmd,
)
from torchx_tpu.resilience.faults import GARBAGE_PAYLOAD, fault_plan_active
from torchx_tpu.resilience.policy import NON_IDEMPOTENT
from torchx_tpu.runner.api import Runner
from torchx_tpu.runner.events import get_events_logger
from torchx_tpu.runner.events.api import TpxEvent
from torchx_tpu.schedulers.api import DescribeAppResponse, Scheduler
from torchx_tpu.specs.api import (
    AppDef,
    AppDryRunInfo,
    AppState,
    CfgVal,
    FailureClass,
    Role,
    runopts,
)
from torchx_tpu.supervisor import (
    AttemptLedger,
    Supervisor,
    SupervisorPolicy,
    list_sessions,
)

REPO_ROOT = Path(__file__).resolve().parents[1]


def fast_call_policy(**kwargs) -> CallPolicy:
    defaults = dict(backoff_seconds=0.0, jitter=0.0)
    defaults.update(kwargs)
    return CallPolicy(**defaults)


def proc(rc: int, stderr: str = "", stdout: str = "") -> subprocess.CompletedProcess:
    return subprocess.CompletedProcess(
        args=["fake"], returncode=rc, stdout=stdout, stderr=stderr
    )


# -- classifier ------------------------------------------------------------


class TestClassifier:
    @pytest.mark.parametrize(
        "text,kind",
        [
            ("HTTP 429: Too Many Requests", FailureKind.RATE_LIMIT),
            ("Quota exceeded for quota metric 'TPU v5e'", FailureKind.QUOTA),
            ("RESOURCE_EXHAUSTED: out of capacity", FailureKind.QUOTA),
            ("DEADLINE_EXCEEDED while polling operation", FailureKind.TIMEOUT),
            ("request timed out", FailureKind.TIMEOUT),
            ("connection reset by peer", FailureKind.CONNECTION),
            ("Temporary failure in name resolution", FailureKind.CONNECTION),
            ("503 Service Unavailable", FailureKind.UNAVAILABLE),
            ("backend error, try again later", FailureKind.UNAVAILABLE),
            ("ERROR: permission denied on project", FailureKind.AUTH),
            ("401 Unauthorized", FailureKind.AUTH),
            ("404: job does not exist", FailureKind.NOT_FOUND),
            ("INVALID_ARGUMENT: bad topology", FailureKind.INVALID),
            ("segfault in the flux capacitor", FailureKind.UNKNOWN),
            ("", FailureKind.UNKNOWN),
        ],
    )
    def test_text_table(self, text, kind):
        assert classify_text(text) is kind

    def test_throttling_with_403_is_transient_not_auth(self):
        # ordered table: RATE_LIMIT is checked before AUTH so gcloud's
        # "403 rate limit exceeded" wording classifies retryable
        assert classify_text("403 rate limit exceeded for project") is (
            FailureKind.RATE_LIMIT
        )
        assert classify_text("403 Forbidden") is FailureKind.AUTH

    def test_proc_success_is_none(self):
        assert classify_proc(proc(0)) is None

    def test_proc_stderr_and_stdout_fallback(self):
        assert classify_proc(proc(1, stderr="quota exceeded")) is FailureKind.QUOTA
        # some gcloud verbs print the error on stdout
        assert classify_proc(proc(1, stdout="503 unavailable")) is (
            FailureKind.UNAVAILABLE
        )
        assert classify_proc(proc(1, stderr="boom")) is FailureKind.UNKNOWN

    def test_exception_taxonomy_kind_wins(self):
        e = TransientSchedulerError("x", kind=FailureKind.QUOTA)
        assert classify_exception(e) is FailureKind.QUOTA

    def test_exception_structural(self):
        assert classify_exception(
            subprocess.TimeoutExpired(cmd="gcloud", timeout=5)
        ) is FailureKind.TIMEOUT
        assert classify_exception(ConnectionResetError()) is FailureKind.CONNECTION
        assert classify_exception(TimeoutError()) is FailureKind.TIMEOUT

    def test_exception_status_attribute(self):
        class ApiException(Exception):
            status = 429

        assert classify_exception(ApiException("throttled")) is (
            FailureKind.RATE_LIMIT
        )

        class CodeError(Exception):
            code = 503

        assert classify_exception(CodeError()) is FailureKind.UNAVAILABLE

    def test_exception_typename_without_sdk_import(self):
        class NotFound(Exception):
            pass

        class ServiceUnavailable(Exception):
            pass

        assert classify_exception(NotFound("job gone")) is FailureKind.NOT_FOUND
        assert classify_exception(ServiceUnavailable()) is FailureKind.UNAVAILABLE

    def test_exception_message_fallback(self):
        assert classify_exception(
            RuntimeError("connection refused by endpoint")
        ) is FailureKind.CONNECTION
        assert classify_exception(RuntimeError("???")) is FailureKind.UNKNOWN

    def test_transient_split(self):
        for kind in (
            FailureKind.TIMEOUT,
            FailureKind.RATE_LIMIT,
            FailureKind.QUOTA,
            FailureKind.UNAVAILABLE,
            FailureKind.CONNECTION,
        ):
            assert is_transient(kind)
        for kind in (
            FailureKind.AUTH,
            FailureKind.NOT_FOUND,
            FailureKind.INVALID,
            FailureKind.UNKNOWN,
        ):
            assert not is_transient(kind)


# -- CallPolicy ------------------------------------------------------------


class TestCallPolicy:
    def test_defaults(self):
        p = CallPolicy()
        assert p.retries_for(FailureKind.UNAVAILABLE) == 2
        assert p.retries_for(FailureKind.RATE_LIMIT) == 3
        assert p.retries_for(FailureKind.TIMEOUT) == 1

    def test_permanent_kinds_never_retried(self):
        # even an explicit budget for a permanent kind is hard-zeroed
        p = CallPolicy(retries={FailureKind.AUTH: 5})
        assert p.retries_for(FailureKind.AUTH) == 0
        assert p.retries_for(FailureKind.UNKNOWN) == 0

    def test_missing_kind_is_zero(self):
        p = CallPolicy(retries={})
        assert p.retries_for(FailureKind.UNAVAILABLE) == 0

    def test_non_idempotent_policy_retries_nothing(self):
        for kind in FailureKind:
            assert NON_IDEMPOTENT.retries_for(kind) == 0

    def test_backoff_grows_and_caps(self):
        p = CallPolicy(
            backoff_seconds=1.0,
            backoff_factor=2.0,
            backoff_max_seconds=4.0,
            jitter=0.0,
        )
        assert [p.backoff_delay(n) for n in (1, 2, 3, 4)] == [1.0, 2.0, 4.0, 4.0]

    def test_jitter_bounds(self):
        p = CallPolicy(backoff_seconds=10.0, jitter=0.5)
        rng = random.Random(7)
        for _ in range(50):
            assert 5.0 <= p.backoff_delay(1, rng=rng) <= 15.0

    @pytest.mark.parametrize(
        "kwargs",
        [
            dict(timeout=0),
            dict(timeout=-1),
            dict(backoff_seconds=-1),
            dict(backoff_factor=0.5),
            dict(jitter=1.0),
            dict(jitter=-0.1),
            dict(retries={FailureKind.QUOTA: -1}),
        ],
    )
    def test_validation(self, kwargs):
        with pytest.raises(ValueError):
            CallPolicy(**kwargs)

    def test_retry_number_is_one_based(self):
        with pytest.raises(ValueError):
            CallPolicy().backoff_delay(0)


# -- CircuitBreaker --------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        return self.now


class TestCircuitBreaker:
    def make(self, **kwargs):
        clock = FakeClock()
        defaults = dict(trip_after=3, cooldown_seconds=10.0, clock=clock)
        defaults.update(kwargs)
        return CircuitBreaker("test", **defaults), clock

    def test_trips_after_consecutive_failures(self):
        b, _ = self.make()
        for _ in range(2):
            b.record_failure()
        assert b.state is BreakerState.CLOSED
        b.record_failure()
        assert b.state is BreakerState.OPEN
        assert not b.allow()

    def test_success_resets_the_streak(self):
        b, _ = self.make()
        b.record_failure()
        b.record_failure()
        b.record_success()
        b.record_failure()
        b.record_failure()
        assert b.state is BreakerState.CLOSED

    def test_cooldown_decays_to_half_open_and_admits_one_probe(self):
        b, clock = self.make()
        for _ in range(3):
            b.record_failure()
        clock.now = 9.9
        assert not b.allow()
        clock.now = 10.0
        assert b.state is BreakerState.HALF_OPEN
        assert b.allow()  # the probe
        assert not b.allow()  # only one probe at a time

    def test_probe_success_closes(self):
        b, clock = self.make()
        for _ in range(3):
            b.record_failure()
        clock.now = 10.0
        assert b.allow()
        b.record_success()
        assert b.state is BreakerState.CLOSED
        assert b.allow()

    def test_probe_failure_reopens_immediately(self):
        b, clock = self.make()
        for _ in range(3):
            b.record_failure()
        clock.now = 10.0
        assert b.allow()
        b.record_failure()  # one probe failure trips, not trip_after
        assert b.state is BreakerState.OPEN
        assert not b.allow()

    def test_abandoned_probe_does_not_wedge(self):
        # the prober dies without reporting; the cool-down restarted at
        # probe admission, so another probe is admitted one cool-down later
        b, clock = self.make()
        for _ in range(3):
            b.record_failure()
        clock.now = 10.0
        assert b.allow()
        clock.now = 20.0
        assert b.allow()

    def test_validation(self):
        with pytest.raises(ValueError):
            CircuitBreaker("x", trip_after=0)
        with pytest.raises(ValueError):
            CircuitBreaker("x", cooldown_seconds=-1)


class TestFailureLedger:
    def test_note_count_clear(self, tmp_path):
        led = FailureLedger(str(tmp_path / "fails"), threshold=2)
        assert led.failures() == {}
        led.note("a|b", ok=False)
        led.note("a|b", ok=False)
        led.note("c|d", ok=False)
        assert led.failures() == {"a|b": 2, "c|d": 1}
        assert led.tripped() == {"a|b"}
        led.note("a|b", ok=True)  # success clears only that key
        assert led.failures() == {"c|d": 1}
        assert led.tripped() == set()

    def test_success_without_failures_is_noop(self, tmp_path):
        path = tmp_path / "fails"
        led = FailureLedger(str(path), threshold=1)
        led.note("k", ok=True)
        assert not path.exists()

    def test_clear_is_an_append_only_tombstone(self, tmp_path):
        path = tmp_path / "fails"
        led = FailureLedger(str(path), threshold=1)
        led.note("k", ok=False)
        led.note("k", ok=True)
        # the success appended a tombstone; nothing was rewritten away
        assert path.read_text() == "k\nk|clear\n"
        assert led.failures() == {}
        # a failure landing AFTER the tombstone survives it (the rewrite
        # implementation could drop such a line racing the replace)
        led.note("k", ok=False)
        assert led.failures() == {"k": 1}
        assert led.tripped() == {"k"}

    def test_tombstone_only_clears_earlier_lines(self, tmp_path):
        path = tmp_path / "fails"
        with open(path, "w") as f:
            f.write("a|b\na|b|clear\na|b\nc|d\n")
        led = FailureLedger(str(path), threshold=1)
        assert led.failures() == {"a|b": 1, "c|d": 1}

    def test_validation(self):
        with pytest.raises(ValueError):
            FailureLedger("x", threshold=0)


# -- fault plans -----------------------------------------------------------


class TestFaultPlan:
    def test_parse_inline_list(self):
        plan = FaultPlan.parse(
            '[{"backend": "local", "op": "describe", "nth": 2, "times": 2}]'
        )
        assert len(plan.rules) == 1
        rule = plan.rules[0]
        assert (rule.backend, rule.op, rule.nth, rule.times) == (
            "local",
            "describe",
            2,
            2,
        )
        assert rule.mode == "transient"

    def test_parse_rules_object(self):
        plan = FaultPlan.parse('{"rules": [{"op": "submit", "mode": "timeout"}]}')
        assert plan.rules[0].mode == "timeout"

    def test_parse_file(self, tmp_path):
        f = tmp_path / "plan.json"
        f.write_text('[{"backend": "gke", "mode": "garbage"}]')
        plan = FaultPlan.parse(str(f))
        assert plan.rules[0].backend == "gke"

    @pytest.mark.parametrize(
        "raw",
        [
            "not json at all {",
            '"just a string"',
            '[{"backend": "x", "typo_key": 1}]',
            '[{"mode": "explode"}]',
            '[{"nth": 0}]',
            '[{"times": 0}]',
            "[42]",
        ],
    )
    def test_malformed_plans_fail_loudly(self, raw):
        with pytest.raises(ValueError):
            FaultPlan.parse(raw)

    def test_rule_matching_is_deterministic(self):
        rule = FaultRule(backend="loc*", op="describe", nth=2, times=2)
        fires = [rule.matches("local", "describe", n) for n in range(1, 6)]
        assert fires == [False, True, True, False, False]
        assert not rule.matches("gke", "describe", 2)
        assert not rule.matches("local", "submit", 2)

    def test_nth_omitted_fires_from_first_call(self):
        rule = FaultRule(times=3)
        assert [rule.matches("b", "o", n) for n in (1, 2, 3, 4)] == [
            True,
            True,
            True,
            False,
        ]

    def test_injector_counts_per_backend_op(self):
        plan = FaultPlan(rules=[FaultRule(backend="local", op="describe", nth=2)])
        inj = FaultInjector(plan)
        assert inj.check("local", "describe") is None  # call 1
        assert inj.check("local", "submit") is None  # independent counter
        assert inj.check("local", "describe") is not None  # call 2 fires
        assert inj.check("local", "describe") is None  # call 3

    def test_fire_modes(self):
        inj = FaultInjector(FaultPlan())
        with pytest.raises(TransientSchedulerError) as ei:
            inj.fire(FaultRule(mode="transient"), "b", "o")
        assert ei.value.kind is FailureKind.UNAVAILABLE
        with pytest.raises(PermanentSchedulerError):
            inj.fire(FaultRule(mode="permanent"), "b", "o")
        with pytest.raises(subprocess.TimeoutExpired):
            inj.fire(FaultRule(mode="timeout"), "b", "o")
        assert inj.fire(FaultRule(mode="garbage"), "b", "o") == GARBAGE_PAYLOAD

    def test_active_injector_cached_while_env_unchanged(self, monkeypatch):
        monkeypatch.setenv(
            settings.ENV_TPX_FAULT_PLAN, '[{"backend": "x", "nth": 1}]'
        )
        first = resilience_faults.active_injector()
        assert first is resilience_faults.active_injector()  # counters persist
        monkeypatch.setenv(settings.ENV_TPX_FAULT_PLAN, '[{"backend": "y"}]')
        assert resilience_faults.active_injector() is not first
        monkeypatch.delenv(settings.ENV_TPX_FAULT_PLAN)
        assert resilience_faults.active_injector() is None

    def test_fault_plan_active(self, monkeypatch):
        monkeypatch.delenv(settings.ENV_TPX_FAULT_PLAN, raising=False)
        assert not fault_plan_active()
        monkeypatch.setenv(settings.ENV_TPX_FAULT_PLAN, "[]")
        assert fault_plan_active()


# -- control-plane timeout knob --------------------------------------------


class TestControlPlaneTimeout:
    def test_default(self, monkeypatch):
        monkeypatch.delenv(settings.ENV_TPX_CONTROL_PLANE_TIMEOUT, raising=False)
        assert control_plane_timeout() == settings.DEFAULT_CONTROL_PLANE_TIMEOUT

    @pytest.mark.parametrize("raw", ["0", "off", "none", "NONE", "false", "-5"])
    def test_disabled(self, monkeypatch, raw):
        monkeypatch.setenv(settings.ENV_TPX_CONTROL_PLANE_TIMEOUT, raw)
        assert control_plane_timeout() is None

    def test_explicit_value(self, monkeypatch):
        monkeypatch.setenv(settings.ENV_TPX_CONTROL_PLANE_TIMEOUT, "12.5")
        assert control_plane_timeout() == 12.5

    def test_unparseable_falls_back_to_default(self, monkeypatch):
        monkeypatch.setenv(settings.ENV_TPX_CONTROL_PLANE_TIMEOUT, "soon")
        assert control_plane_timeout() == settings.DEFAULT_CONTROL_PLANE_TIMEOUT


# -- resilient_call --------------------------------------------------------


class TestResilientCall:
    def test_success_passthrough(self):
        before = obs_metrics.CONTROL_PLANE_CALLS.value(
            backend="tc1", op="describe", status="ok"
        )
        assert (
            resilient_call(lambda: 42, backend="tc1", op="describe") == 42
        )
        after = obs_metrics.CONTROL_PLANE_CALLS.value(
            backend="tc1", op="describe", status="ok"
        )
        assert after == before + 1

    def test_transient_retried_then_succeeds(self):
        calls = {"n": 0}
        sleeps: list[float] = []

        def fn():
            calls["n"] += 1
            if calls["n"] <= 2:
                raise TransientSchedulerError("x", kind=FailureKind.UNAVAILABLE)
            return "ok"

        result = resilient_call(
            fn,
            backend="tc2",
            op="describe",
            policy=fast_call_policy(),
            sleep=sleeps.append,
        )
        assert result == "ok"
        assert calls["n"] == 3
        assert len(sleeps) == 2

    def test_budget_exhausted_reraises_the_original(self):
        original = TransientSchedulerError("x", kind=FailureKind.UNAVAILABLE)

        def fn():
            raise original

        with pytest.raises(TransientSchedulerError) as ei:
            resilient_call(
                fn,
                backend="tc3",
                op="describe",
                policy=fast_call_policy(
                    retries={FailureKind.UNAVAILABLE: 1}
                ),
                sleep=lambda s: None,
            )
        assert ei.value is original  # identity: callers' except clauses work

    def test_permanent_raises_immediately_without_retry(self):
        sleeps: list[float] = []

        class NotFound(Exception):
            pass

        def fn():
            raise NotFound("gone")

        with pytest.raises(NotFound):
            resilient_call(
                fn, backend="tc4", op="describe", sleep=sleeps.append
            )
        assert sleeps == []
        # a permanent answer proves the backend reachable
        assert breaker_for("tc4").state is BreakerState.CLOSED

    def test_breaker_opens_and_rejects(self):
        def fn():
            raise TransientSchedulerError("x", kind=FailureKind.UNAVAILABLE)

        policy = fast_call_policy(retries={})
        for _ in range(5):  # default trip_after
            with pytest.raises(TransientSchedulerError):
                resilient_call(
                    fn, backend="tc5", op="describe", policy=policy,
                    sleep=lambda s: None,
                )
        assert breaker_for("tc5").state is BreakerState.OPEN
        before = obs_metrics.CONTROL_PLANE_CALLS.value(
            backend="tc5", op="describe", status="rejected"
        )
        with pytest.raises(BreakerOpenError):
            resilient_call(lambda: 1, backend="tc5", op="describe")
        after = obs_metrics.CONTROL_PLANE_CALLS.value(
            backend="tc5", op="describe", status="rejected"
        )
        assert after == before + 1
        # BreakerOpenError itself classifies transient (UNAVAILABLE), so
        # poll loops absorb it under their miss budget
        assert is_transient(classify_exception(BreakerOpenError("x")))


# -- resilient_cmd ---------------------------------------------------------


class TestResilientCmd:
    def test_default_deadline_injected(self, monkeypatch):
        monkeypatch.delenv(settings.ENV_TPX_CONTROL_PLANE_TIMEOUT, raising=False)
        seen = {}

        def run(cmd, **kwargs):
            seen.update(kwargs)
            return proc(0)

        resilient_cmd(run, ["x"], backend="cm1", op="describe")
        assert seen["timeout"] == settings.DEFAULT_CONTROL_PLANE_TIMEOUT

    def test_caller_timeout_wins(self):
        seen = {}

        def run(cmd, **kwargs):
            seen.update(kwargs)
            return proc(0)

        resilient_cmd(run, ["x"], backend="cm1", op="describe", timeout=7)
        assert seen["timeout"] == 7

    def test_disabled_deadline_means_no_timeout_kwarg(self, monkeypatch):
        monkeypatch.setenv(settings.ENV_TPX_CONTROL_PLANE_TIMEOUT, "off")
        seen = {"called": False}

        def run(cmd, **kwargs):
            seen["called"] = True
            assert "timeout" not in kwargs
            return proc(0)

        resilient_cmd(run, ["x"], backend="cm1", op="describe")
        assert seen["called"]

    def test_transient_exit_retried_then_succeeds(self):
        procs = [proc(1, stderr="503 unavailable"), proc(0, stdout="done")]
        sleeps: list[float] = []

        result = resilient_cmd(
            lambda cmd, **kw: procs.pop(0),
            ["x"],
            backend="cm2",
            op="describe",
            policy=fast_call_policy(),
            sleep=sleeps.append,
        )
        assert result.returncode == 0
        assert result.stdout == "done"
        assert len(sleeps) == 1

    def test_budget_exhausted_returns_last_failing_proc(self):
        last = proc(1, stderr="too many requests")
        sleeps: list[float] = []

        result = resilient_cmd(
            lambda cmd, **kw: last,
            ["x"],
            backend="cm3",
            op="describe",
            policy=fast_call_policy(retries={FailureKind.RATE_LIMIT: 2}),
            sleep=sleeps.append,
        )
        assert result is last  # returned, never raised: rc semantics hold
        assert len(sleeps) == 2

    def test_permanent_exit_returned_without_retry(self):
        sleeps: list[float] = []
        result = resilient_cmd(
            lambda cmd, **kw: proc(1, stderr="permission denied"),
            ["x"],
            backend="cm4",
            op="describe",
            policy=fast_call_policy(),
            sleep=sleeps.append,
        )
        assert result.returncode == 1
        assert sleeps == []
        assert breaker_for("cm4").state is BreakerState.CLOSED

    def test_hung_call_synthesizes_timeout_proc(self):
        def run(cmd, **kwargs):
            raise subprocess.TimeoutExpired(cmd=cmd, timeout=kwargs["timeout"])

        sleeps: list[float] = []
        result = resilient_cmd(
            run,
            ["x"],
            backend="cm5",
            op="describe",
            policy=fast_call_policy(retries={FailureKind.TIMEOUT: 1}),
            sleep=sleeps.append,
            timeout=0.5,
        )
        assert result.returncode == TIMEOUT_RETURNCODE
        assert settings.ENV_TPX_CONTROL_PLANE_TIMEOUT in result.stderr
        assert len(sleeps) == 1  # retried once, then degraded to a proc

    def test_garbage_fault_returns_unparseable_stdout(self, monkeypatch):
        monkeypatch.setenv(
            settings.ENV_TPX_FAULT_PLAN,
            '[{"backend": "cm6", "op": "list", "mode": "garbage"}]',
        )
        calls = {"n": 0}

        def run(cmd, **kwargs):
            calls["n"] += 1
            return proc(0, stdout="real output")

        result = resilient_cmd(run, ["x"], backend="cm6", op="list")
        assert calls["n"] == 0  # the real call never happened
        assert result.returncode == 0
        assert result.stdout == GARBAGE_PAYLOAD


# -- Runner.wait poll-miss budget ------------------------------------------


class FlakyScheduler(Scheduler[dict]):
    """``describe()`` raises the scripted exceptions first, then reports a
    terminal SUCCEEDED — a control plane that flakes mid-wait."""

    def __init__(self, session_name: str, failures=None, **kwargs):
        super().__init__("flaky", session_name)
        self.failures = list(failures or [])

    def run_opts(self) -> runopts:
        return runopts()

    def _submit_dryrun(self, app: AppDef, cfg: Mapping[str, CfgVal]):
        return AppDryRunInfo({"app": app})

    def schedule(self, dryrun_info) -> str:
        return "job_1"

    def describe(self, app_id: str) -> Optional[DescribeAppResponse]:
        if self.failures:
            raise self.failures.pop(0)
        return DescribeAppResponse(app_id=app_id, state=AppState.SUCCEEDED)

    def _cancel_existing(self, app_id: str) -> None:
        pass


class _CaptureEvents(logging.Handler):
    def __init__(self):
        super().__init__()
        self.events: list[TpxEvent] = []
        self.spans: list[dict] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        obj = json.loads(msg)
        if obj.get("kind") == "span":
            self.spans.append(obj)
        else:
            self.events.append(TpxEvent.deserialize(msg))


@pytest.fixture
def capture_pipeline():
    handler = _CaptureEvents()
    logger = get_events_logger()
    logger.addHandler(handler)
    yield handler
    logger.removeHandler(handler)


def flaky_wait(failures, budget):
    sched = FlakyScheduler("w", failures=failures)
    runner = Runner("w", {"flaky": lambda session_name, **kw: sched})
    with runner:
        return runner.wait(
            "flaky://w/job_1",
            wait_interval=0.01,
            sleep=lambda s: None,
            poll_miss_budget=budget,
        )


class TestPollMissBudget:
    def test_absorbs_transient_misses_within_budget(self, capture_pipeline):
        failures = [
            TransientSchedulerError("a", kind=FailureKind.UNAVAILABLE),
            TransientSchedulerError("b", kind=FailureKind.CONNECTION),
        ]
        status = flaky_wait(failures, budget=2)
        assert status is not None and status.state == AppState.SUCCEEDED
        degraded = [
            e
            for e in capture_pipeline.events
            if (e.app_metadata or {}).get("transition") == "poll_degraded"
        ]
        assert len(degraded) == 2
        assert degraded[0].app_metadata["miss"] == 1
        assert degraded[0].app_metadata["kind"] == str(FailureKind.UNAVAILABLE)
        assert degraded[1].app_metadata["miss"] == 2

    def test_budget_exceeded_raises(self):
        failures = [
            TransientSchedulerError("x", kind=FailureKind.UNAVAILABLE)
            for _ in range(3)
        ]
        with pytest.raises(TransientSchedulerError):
            flaky_wait(failures, budget=2)

    def test_consecutive_semantics_reset_on_success(self):
        # default budget of 0 absorbs nothing...
        with pytest.raises(TransientSchedulerError):
            flaky_wait(
                [TransientSchedulerError("x", kind=FailureKind.UNAVAILABLE)],
                budget=0,
            )

    def test_permanent_error_always_raises(self):
        failures = [PermanentSchedulerError("auth", kind=FailureKind.AUTH)]
        with pytest.raises(PermanentSchedulerError):
            flaky_wait(failures, budget=5)


# -- analyzer rules TPX501 / TPX502 ----------------------------------------


class TestResilienceRules:
    def run_rule(self, **kwargs):
        from torchx_tpu.analyze.rules import RuleContext, check_resilience

        app = kwargs.pop(
            "app",
            AppDef(
                name="a",
                roles=[
                    Role(
                        name="r",
                        image="i",
                        entrypoint="e",
                        max_retries=kwargs.pop("max_retries", 0),
                    )
                ],
            ),
        )
        return list(check_resilience(RuleContext(app=app, **kwargs)))

    def test_tpx501_multiplicative_budgets(self):
        from torchx_tpu.analyze.diagnostics import Severity
        from torchx_tpu.schedulers.api import SchedulerCapabilities

        diags = self.run_rule(
            max_retries=2,
            scheduler="gke",
            capabilities=SchedulerCapabilities(native_retries=True),
            policy=SupervisorPolicy(),
        )
        assert [d.code for d in diags] == ["TPX501"]
        assert diags[0].severity == Severity.WARNING
        # default policy budget 8+3+0=11, native 2 -> (11+1)*(2+1)-1 = 35
        assert "35 total restarts" in diags[0].message

    def test_tpx501_needs_all_three_layers(self):
        from torchx_tpu.schedulers.api import SchedulerCapabilities

        cap = SchedulerCapabilities(native_retries=True)
        assert self.run_rule(max_retries=0, scheduler="gke",
                             capabilities=cap, policy=SupervisorPolicy()) == []
        assert self.run_rule(max_retries=2, scheduler="gke",
                             capabilities=cap, policy=None) == []
        assert self.run_rule(
            max_retries=2,
            scheduler="tpu_vm",
            capabilities=SchedulerCapabilities(native_retries=False),
            policy=SupervisorPolicy(),
        ) == []
        zero = SupervisorPolicy(
            max_preemptions=0, max_infra_retries=0, max_app_retries=0
        )
        assert self.run_rule(max_retries=2, scheduler="gke",
                             capabilities=cap, policy=zero) == []

    def test_tpx502_fault_plan_on_real_backend(self, monkeypatch):
        from torchx_tpu.analyze.diagnostics import Severity

        monkeypatch.setenv(settings.ENV_TPX_FAULT_PLAN, "[]")
        diags = self.run_rule(scheduler="gke")
        assert [d.code for d in diags] == ["TPX502"]
        assert diags[0].severity == Severity.ERROR

    def test_tpx502_local_drills_allowed(self, monkeypatch):
        monkeypatch.setenv(settings.ENV_TPX_FAULT_PLAN, "[]")
        assert self.run_rule(scheduler="local") == []
        assert self.run_rule(scheduler="local_docker") == []
        monkeypatch.delenv(settings.ENV_TPX_FAULT_PLAN)
        assert self.run_rule(scheduler="gke") == []


# -- supervision ledger ----------------------------------------------------


class ScriptedScheduler(Scheduler[dict]):
    """Each ``schedule()`` consumes the next scripted terminal outcome."""

    def __init__(self, session_name: str, script=None, **kwargs):
        super().__init__("scripted", session_name)
        self.script = list(script or [])
        self.apps: dict[str, tuple[AppState, Optional[FailureClass]]] = {}
        self._counter = 0

    def run_opts(self) -> runopts:
        return runopts()

    def _submit_dryrun(self, app: AppDef, cfg: Mapping[str, CfgVal]):
        return AppDryRunInfo({"app": app})

    def schedule(self, dryrun_info) -> str:
        self._counter += 1
        app_id = f"job_{self._counter}"
        outcome = (
            self.script.pop(0) if self.script else (AppState.SUCCEEDED, None)
        )
        self.apps[app_id] = outcome
        return app_id

    def describe(self, app_id: str) -> Optional[DescribeAppResponse]:
        if app_id not in self.apps:
            return None
        state, fclass = self.apps[app_id]
        return DescribeAppResponse(
            app_id=app_id, state=state, failure_class=fclass
        )

    def _cancel_existing(self, app_id: str) -> None:
        self.apps[app_id] = (AppState.CANCELLED, None)


def make_runner(script=None):
    sched = ScriptedScheduler("sup", script=script)
    runner = Runner("sup", {"scripted": lambda session_name, **kw: sched})
    return runner, sched


def dryrun(runner):
    app = AppDef(
        name="train",
        roles=[Role(name="trainer", image="i", entrypoint="python")],
    )
    return runner.dryrun(app, "scripted")


def fast_policy(**kwargs) -> SupervisorPolicy:
    defaults = dict(
        backoff_seconds=1.0, backoff_factor=2.0, jitter=0.0, poll_interval=0.01
    )
    defaults.update(kwargs)
    return SupervisorPolicy(**defaults)


class TestAttemptLedger:
    @pytest.mark.parametrize("name", ["", "a/b", ".", ".."])
    def test_invalid_session_names(self, name):
        with pytest.raises(ValueError):
            AttemptLedger(name)

    def test_append_and_entries_round_trip(self):
        led = AttemptLedger("s1")
        led.append("submitted", "job_1", attempt=1, handle="x://s/job_1")
        led.append("finished", "job_1", state="SUCCEEDED")
        entries = list(led.entries())
        assert [e["transition"] for e in entries] == ["submitted", "finished"]
        assert entries[0]["handle"] == "x://s/job_1"
        assert entries[0]["time_usec"] > 0

    def test_torn_final_line_is_skipped(self):
        led = AttemptLedger("s2")
        led.append("submitted", "job_1")
        with open(os.path.join(led.path, "ledger.jsonl"), "a") as f:
            f.write('{"transition": "resub')  # writer died mid-append
        assert [e["transition"] for e in led.entries()] == ["submitted"]

    def test_meta_round_trip_and_missing(self):
        led = AttemptLedger("s3")
        assert not led.exists()
        led.write_meta({"scheduler": "local", "app": {}})
        assert led.exists()
        assert led.read_meta()["scheduler"] == "local"
        with pytest.raises(FileNotFoundError) as ei:
            AttemptLedger("nope").read_meta()
        assert "s3" in str(ei.value)  # known sessions listed in the error

    def test_list_sessions_newest_first(self):
        for name in ("old", "new"):
            AttemptLedger(name).write_meta({})
        root = os.environ["TPX_SUPERVISOR_DIR"]
        os.utime(os.path.join(root, "old", "meta.json"), (1, 1))
        os.utime(os.path.join(root, "new", "meta.json"), (2, 2))
        assert list_sessions() == ["new", "old"]


class TestSupervisorResume:
    def test_restore_replays_the_ledger(self):
        led = AttemptLedger("restore1")
        led.append("submitted", "job_1", attempt=1, resume_step=None,
                   handle="scripted://sup/job_1")
        led.append("resubmitting", "job_1",
                   failure_class=str(FailureClass.PREEMPTION))
        led.append("submitted", "job_2", attempt=2, resume_step=120,
                   handle="scripted://sup/job_2")
        runner, _ = make_runner()
        with runner:
            sup = Supervisor(runner, dryrun(runner), fast_policy(),
                             session="restore1")
            sup._restore(led)
        assert sup._resume_attempts == 2
        assert sup._resume_handle == "scripted://sup/job_2"
        assert sup._resume_retries[FailureClass.PREEMPTION] == 1
        assert sup._resume_retries[FailureClass.INFRA] == 0
        assert sup._resume_steps == [None, 120]

    def test_resume_reattaches_without_resubmitting(self, capture_pipeline):
        runner, sched = make_runner(script=[(AppState.SUCCEEDED, None)])
        with runner:
            sup = Supervisor(
                runner, dryrun(runner), fast_policy(), session="reatt",
                sleep=lambda s: None,
            )
            first = sup.run()
            assert first.succeeded and sched._counter == 1

            resumed = Supervisor.resume(runner, "reatt", sleep=lambda s: None)
            assert resumed.session == "reatt"
            result = resumed.run()
        assert result.succeeded
        assert result.attempts == 1
        assert result.handles == ["scripted://sup/job_1"]
        assert sched._counter == 1  # reattached; never submitted again
        reattached = [
            e
            for e in capture_pipeline.events
            if (e.app_metadata or {}).get("transition") == "reattached"
        ]
        assert len(reattached) == 1
        assert [e["transition"] for e in AttemptLedger("reatt").entries()].count(
            "submitted"
        ) == 1

    def test_resume_unknown_session_raises(self):
        runner, _ = make_runner()
        with runner:
            with pytest.raises(FileNotFoundError):
                Supervisor.resume(runner, "ghost")

    def test_resume_before_first_submit_raises(self):
        runner, _ = make_runner()
        with runner:
            sup = Supervisor(runner, dryrun(runner), fast_policy(),
                             session="early")
            sup._write_meta()  # client died between meta and first submit
            with pytest.raises(ValueError, match="no submitted attempt"):
                Supervisor.resume(runner, "early")


# -- ISSUE acceptance ------------------------------------------------------


class TestAcceptance:
    def test_fault_injected_supervise_completes_with_zero_resubmits(
        self, monkeypatch, capture_pipeline
    ):
        """ISSUE acceptance: two transient faults injected into local status
        polls are absorbed by in-seam retries — the supervised run succeeds
        on its FIRST attempt (no resubmits), with ``launcher.retry`` span
        and retry-metric evidence."""
        from torchx_tpu.schedulers.local_scheduler import LocalScheduler

        monkeypatch.setattr(
            "torchx_tpu.resilience.call.DEFAULT_POLICY", fast_call_policy()
        )
        monkeypatch.setenv(
            settings.ENV_TPX_FAULT_PLAN,
            '[{"backend": "local", "op": "describe", "nth": 2, "times": 2,'
            ' "mode": "transient", "message": "injected 503"}]',
        )
        retries_before = obs_metrics.CONTROL_PLANE_RETRIES.value(
            backend="local", op="describe", kind="UNAVAILABLE"
        )

        sched = LocalScheduler(session_name="acc", cache_size=10)
        runner = Runner(
            "acc", {"local": lambda session_name, **kw: sched}
        )
        app = AppDef(
            name="accjob",
            roles=[
                Role(
                    name="t", image="", entrypoint="sh",
                    args=["-c", "sleep 0.4"],
                )
            ],
        )
        with runner:
            info = runner.dryrun(app, "local")
            sup = Supervisor(
                runner, info, fast_policy(poll_interval=0.02),
                session="accsess",
            )
            result = sup.run()
        sched.close()

        assert result.succeeded
        assert result.attempts == 1  # ZERO resubmits
        assert len(result.handles) == 1
        assert all(n == 0 for n in result.retries.values())
        assert [e["transition"] for e in AttemptLedger("accsess").entries()].count(
            "resubmitting"
        ) == 0

        retries_after = obs_metrics.CONTROL_PLANE_RETRIES.value(
            backend="local", op="describe", kind="UNAVAILABLE"
        )
        assert retries_after - retries_before == 2
        retry_spans = [
            s
            for s in capture_pipeline.spans
            if s["name"] == "launcher.retry"
            and s["attrs"].get("backend") == "local"
            and s["attrs"].get("op") == "describe"
        ]
        assert len(retry_spans) == 2

    def test_sigkill_then_resume_reattaches_to_success(
        self, tmp_path, monkeypatch
    ):
        """ISSUE acceptance: SIGKILL the supervising client mid-run, then
        ``Supervisor.resume`` in a fresh process reattaches to the SAME
        handle (no duplicate submission) and drives it to SUCCEEDED."""
        from torchx_tpu.schedulers.local_scheduler import LocalScheduler

        # child + parent must share the local-scheduler app registry: the
        # child resolves it under $HOME, the parent's conftest monkeypatch
        # is re-pointed at the same file
        registry = tmp_path / ".tpx_local_apps"
        monkeypatch.setattr(
            "torchx_tpu.schedulers.local_scheduler._registry_path",
            lambda: str(registry),
        )
        child_src = textwrap.dedent(
            """
            from torchx_tpu.runner.api import Runner
            from torchx_tpu.schedulers.local_scheduler import LocalScheduler
            from torchx_tpu.specs.api import AppDef, Role
            from torchx_tpu.supervisor import Supervisor, SupervisorPolicy

            runner = Runner(
                "crash",
                {"local": lambda session_name, **kw: LocalScheduler(
                    session_name=session_name, cache_size=10)},
            )
            app = AppDef(
                name="crashjob",
                roles=[Role(name="t", image="", entrypoint="sh",
                            args=["-c", "until [ -e %s ]; do sleep 0.05; done"])],
            )
            info = runner.dryrun(app, "local")
            sup = Supervisor(
                runner, info,
                SupervisorPolicy(poll_interval=0.05),
                session="crashsess",
            )
            sup.run()
            """
            # the job stays open until the kill has happened: however slow
            # the machine, the supervisor dies with its job still running
            % shlex.quote(str(tmp_path / "supervisor_killed"))
        )
        script = tmp_path / "crash_child.py"
        script.write_text(child_src)
        # the child is a script outside the checkout: it finds the package
        # on PYTHONPATH or (stderr discarded) exits before it submits
        path = os.pathsep.join(
            filter(None, [str(REPO_ROOT), os.environ.get("PYTHONPATH")])
        )
        env = dict(os.environ, HOME=str(tmp_path), PYTHONPATH=path)
        child = subprocess.Popen(
            [sys.executable, str(script)],
            cwd=str(REPO_ROOT),
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        try:
            ledger_file = (
                Path(os.environ["TPX_SUPERVISOR_DIR"])
                / "crashsess"
                / "ledger.jsonl"
            )
            deadline = time.monotonic() + 30
            submitted = None
            while time.monotonic() < deadline and submitted is None:
                if ledger_file.exists():
                    for line in ledger_file.read_text().splitlines():
                        try:
                            entry = json.loads(line)
                        except ValueError:
                            continue
                        if entry.get("transition") == "submitted":
                            submitted = entry
                            break
                if child.poll() is not None:
                    pytest.fail("supervising child exited before the kill")
                time.sleep(0.02)
            assert submitted is not None, "child never submitted"
        finally:
            child.kill()  # SIGKILL: no cleanup handlers run
            child.wait()
            (tmp_path / "supervisor_killed").touch()  # now the job may end

        # the replica (its own session) survives the supervisor's death;
        # a fresh client reattaches to the recorded handle
        sched = LocalScheduler(session_name="crash", cache_size=10)
        runner = Runner("crash", {"local": lambda session_name, **kw: sched})
        with runner:
            sup = Supervisor.resume(runner, "crashsess")
            result = sup.run()
        sched.close()

        assert result.succeeded
        assert result.status is not None
        assert result.status.state == AppState.SUCCEEDED
        assert result.attempts == 1
        assert result.handles == [submitted["handle"]]  # the SAME attempt
        transitions = [
            e["transition"] for e in AttemptLedger("crashsess").entries()
        ]
        assert transitions.count("submitted") == 1  # never resubmitted
        assert "reattached" in transitions
        assert transitions[-1] == "finished"
