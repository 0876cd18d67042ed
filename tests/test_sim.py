"""Simulator tests: the virtual clock's driver/worker protocol, the
byte-identical determinism contract, the clock seams the sim threads
through the production control plane (reconciler, control client,
pipeline engine, legacy pipelines, serve engine/transfer, supervisor),
the FleetModel reverse index behind the market's units_of fast path,
fault-storm behavior (mid-canary rollback, SLO paging), the TPX604
scenario rule, the sim-hosted wall-clock self-lint, and the 1000-slice
failure-storm acceptance bar (slow-marked)."""

import json
import os
import threading
import time
import types

import pytest

from torchx_tpu.analyze.rules import check_sim_scenario
from torchx_tpu.sim import (
    BUNDLED_SCENARIOS,
    SimExecutor,
    SimHarness,
    SystemClock,
    VirtualClock,
    diurnal_trace,
    get_scenario,
    replay_trace,
)

# ---------------------------------------------------------------------------
# VirtualClock
# ---------------------------------------------------------------------------


class TestVirtualClock:
    def test_starts_at_zero_and_advances(self):
        vc = VirtualClock()
        assert vc() == 0.0
        vc.advance(5.0)
        assert vc.now() == 5.0
        vc.advance_to(3.0)  # past targets are no-ops
        assert vc.now() == 5.0
        vc.advance_to(10.5)
        assert vc() == 10.5

    def test_driver_sleep_advances_inline(self):
        vc = VirtualClock(start=100.0)
        t0 = time.perf_counter()
        vc.sleep(3600.0)  # an hour of virtual time, instantly
        assert time.perf_counter() - t0 < 1.0
        assert vc.now() == 3700.0

    def test_negative_sleep_and_advance_clamp(self):
        vc = VirtualClock()
        vc.sleep(-5.0)
        vc.advance(-5.0)
        assert vc.now() == 0.0

    def test_worker_parks_until_driver_advances(self):
        vc = VirtualClock()
        woke_at = []

        def worker():
            vc.sleep(10.0)
            woke_at.append(vc())

        t = threading.Thread(target=worker)
        t.start()
        assert vc.wait_parked(t)
        assert vc.next_wake() == 10.0
        vc.advance_to(5.0)
        assert not woke_at  # deadline not reached
        vc.advance_to(15.0)
        t.join(timeout=5.0)
        assert woke_at == [10.0]  # woken AT its deadline, not past it
        assert vc.now() == 15.0
        assert vc.next_wake() is None

    def test_sleepers_wake_in_deadline_order(self):
        vc = VirtualClock()
        order = []

        def worker(name, delay):
            vc.sleep(delay)
            order.append((name, vc()))

        threads = [
            threading.Thread(target=worker, args=("late", 20.0)),
            threading.Thread(target=worker, args=("early", 10.0)),
        ]
        for t in threads:
            t.start()
            assert vc.wait_parked(t)
        vc.advance_to(30.0)
        for t in threads:
            t.join(timeout=5.0)
        assert order == [("early", 10.0), ("late", 20.0)]

    def test_chained_worker_sleeps_settle_deterministically(self):
        vc = VirtualClock()
        stamps = []

        def worker():
            for _ in range(3):
                vc.sleep(10.0)
                stamps.append(vc())

        t = threading.Thread(target=worker)
        t.start()
        assert vc.wait_parked(t)
        vc.advance_to(100.0)
        t.join(timeout=5.0)
        # each wake re-parks before the driver advances further, so the
        # chain walks 10/20/30 — never skips to 100
        assert stamps == [10.0, 20.0, 30.0]

    def test_wait_parked_on_dead_thread(self):
        vc = VirtualClock()
        t = threading.Thread(target=lambda: None)
        t.start()
        t.join()
        assert vc.wait_parked(t)

    def test_system_clock_protocol(self):
        sc = SystemClock()
        a = sc.now()
        assert isinstance(a, float) and sc() >= a


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------


class TestTraffic:
    def test_diurnal_trace_deterministic(self):
        a = diurnal_trace(0.5, seed=3)
        b = diurnal_trace(0.5, seed=3)
        c = diurnal_trace(0.5, seed=4)
        assert a == b
        assert a != c
        assert all(j["arrival"] <= k["arrival"] for j, k in zip(a, a[1:]))

    def test_rate_scale_scales_arrivals(self):
        lo = diurnal_trace(1.0, seed=7, rate_scale=1.0)
        hi = diurnal_trace(1.0, seed=7, rate_scale=8.0)
        assert len(hi) > 4 * len(lo)

    def test_replay_trace_from_journal(self, tmp_path):
        path = tmp_path / "fleet.jsonl"
        rows = [
            {"kind": "submit", "job": "j1", "klass": "serve", "tenant": "t",
             "replicas": 2, "elastic": False, "time_usec": 1_000_000},
            {"kind": "place", "job": "j1", "time_usec": 2_000_000},
            {"kind": "terminal", "job": "j1", "time_usec": 62_000_000},
            {"kind": "submit", "job": "j2", "klass": "batch", "tenant": "t",
             "replicas": 1, "time_usec": 3_000_000},
        ]
        with open(path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
            f.write("{torn line\n")
        trace = replay_trace(str(path))
        by_job = {j["job"]: j for j in trace}
        assert by_job["j1"]["arrival"] == 0.0
        assert by_job["j1"]["duration"] == 60.0
        assert by_job["j2"]["arrival"] == 2.0
        assert by_job["j2"]["duration"] == 600.0  # no terminal: fallback


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------


def _run(scenario_name, seed, tmp_path, tag):
    sc = get_scenario(scenario_name)
    return SimHarness(sc, seed=seed, state_dir=str(tmp_path / tag)).run()


class TestDeterminism:
    def test_same_seed_byte_identical(self, tmp_path):
        a = _run("smoke-tiny", 7, tmp_path, "a")
        b = _run("smoke-tiny", 7, tmp_path, "b")
        assert a.journal_sha256 == b.journal_sha256
        raw_a = open(a.journal_path, "rb").read()
        raw_b = open(b.journal_path, "rb").read()
        assert raw_a == raw_b and raw_a

    def test_different_seed_differs(self, tmp_path):
        a = _run("smoke-tiny", 7, tmp_path, "a")
        c = _run("smoke-tiny", 8, tmp_path, "c")
        assert a.journal_sha256 != c.journal_sha256

    def test_journal_carries_no_wall_time(self, tmp_path):
        r = _run("smoke-tiny", 7, tmp_path, "a")
        rows = [json.loads(l) for l in open(r.journal_path)]
        assert rows[0]["kind"] == "begin"
        assert rows[-1]["kind"] == "end"
        for row in rows:
            assert "wall" not in json.dumps(row)
        # wall facts live on the report only
        assert r.wall_s > 0 and r.speedup > 1

    def test_report_stats_coherent(self, tmp_path):
        r = _run("smoke-tiny", 7, tmp_path, "a")
        s = r.stats
        assert s["completed"] == s["submitted"] > 0
        assert s["faults"] == 2
        assert 0.0 < s["utilization"] <= 1.0
        assert r.virtual_s > 1800.0  # the trace horizon


# ---------------------------------------------------------------------------
# clock seams through the production control plane
# ---------------------------------------------------------------------------


class TestClockSeams:
    def test_reconciler_wait_event_uses_injected_clock(self):
        from torchx_tpu.control.reconciler import Reconciler

        now = [50.0]
        rec = Reconciler(clock=lambda: now[0])
        # nothing recorded + zero budget: returns without a wall sleep
        t0 = time.perf_counter()
        assert rec.wait_event("local", "app-1", timeout=0.0) is None
        assert time.perf_counter() - t0 < 1.0

    def test_control_client_wait_deadline_on_injected_clock(self):
        from torchx_tpu.control.client import ControlClient

        now = [0.0]
        client = ControlClient("http://x", "tok", clock=lambda: now[0])
        calls = []

        def fake_request(path, payload=None, timeout=None):
            calls.append(path)
            now[0] += 31.0  # each long-poll consumes virtual budget
            return {"terminal": False, "state": "RUNNING"}

        client._request = fake_request
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError):
            client.wait("local://sim/app-1", timeout=60.0)
        assert time.perf_counter() - t0 < 2.0
        assert len(calls) == 2  # 60s budget / 31s polls

    def test_pipeline_engine_stamps_from_injected_clock(self, tmp_path):
        from torchx_tpu.pipelines.dag import PipelineSpec
        from torchx_tpu.pipelines.engine import PipelineEngine

        now = [1234.0]

        class Exe:
            def submit(self, tenant, pid, stage, args):
                return {"handle": "local://sim/app-9"}

            def resolve(self, handle):
                return None

            def cancel(self, handle):
                pass

        eng = PipelineEngine(
            str(tmp_path / "pl.jsonl"),
            executor=Exe(),
            clock=lambda: now[0],
            sleep=lambda s: None,
        )
        spec = PipelineSpec.from_dict({
            "name": "p",
            "stages": [
                {"name": "train", "kind": "train", "ckpt_dir": str(tmp_path)},
            ],
        })
        pid = eng.submit(spec, tenant="t")
        assert eng.status(pid)["stages"][0]["state"] == "RUNNING"
        srun = eng._runs[pid].stages["train"]
        assert srun.started_usec == int(1234.0 * 1e6)

    def test_legacy_run_pipeline_sleep_seam(self):
        from torchx_tpu.pipelines.api import Pipeline
        from torchx_tpu.pipelines.legacy import run_pipeline
        from torchx_tpu.specs.api import AppDef, AppState, Role

        app = AppDef(name="s", roles=[Role(name="r", image="", entrypoint="e")])
        pipe = Pipeline(name="p").stage("one", app)
        polls = [0]
        slept = []

        class FakeStatus:
            def __init__(self, state):
                self.state = state

            def is_terminal(self):
                return self.state == AppState.SUCCEEDED

        class FakeRunner:
            def run(self, app, scheduler, cfg=None, parent_run_id=None):
                return "local://s/1"

            def status(self, handle):
                polls[0] += 1
                return FakeStatus(
                    AppState.SUCCEEDED if polls[0] > 2 else AppState.RUNNING
                )

        t0 = time.perf_counter()
        run = run_pipeline(
            FakeRunner(), pipe, "local",
            wait_interval=30.0, sleep=slept.append,
        )
        assert time.perf_counter() - t0 < 2.0  # 30s polls, zero wall cost
        assert run.state == AppState.SUCCEEDED
        assert slept and all(s == 30.0 for s in slept)

    def test_file_transfer_polls_on_injected_clock(self, tmp_path):
        from torchx_tpu.serve.kv_transfer import FileTransfer, TransferError

        now = [0.0]
        slept = []

        def vsleep(s):
            slept.append(s)
            now[0] += s

        ft = FileTransfer(
            str(tmp_path), poll_s=5.0, clock=lambda: now[0], sleep=vsleep
        )
        payload = types.SimpleNamespace(
            request_id="r1", to_bytes=lambda: b"x" * 8
        )
        t0 = time.perf_counter()
        with pytest.raises(TransferError):
            ft.transfer(payload, str(tmp_path), timeout=20.0)
        assert time.perf_counter() - t0 < 2.0
        assert slept == [5.0] * 4  # 20s budget at 5s virtual polls

    def test_serve_engine_drain_on_injected_clock(self):
        from torchx_tpu.serve.engine import ServeEngine

        now = [0.0]
        slept = []

        def vsleep(s):
            slept.append(s)
            now[0] += s

        fake = types.SimpleNamespace(
            _lock=threading.Lock(),
            _draining=False,
            _waiting=[object()],  # never drains
            _handoffs=[],
            _admitting=[],
            _slots=[None],
            _clock=lambda: now[0],
            _sleep=vsleep,
        )
        t0 = time.perf_counter()
        assert ServeEngine.drain(fake, timeout=1.0) is False
        assert time.perf_counter() - t0 < 2.0  # a virtual second, not a wall one
        assert slept and fake._draining

    def test_supervisor_takes_clock_seam(self):
        import inspect

        from torchx_tpu.supervisor.api import Supervisor

        params = inspect.signature(Supervisor.__init__).parameters
        assert "clock" in params and "sleep" in params


# ---------------------------------------------------------------------------
# FleetModel reverse index (the market's units_of fast path)
# ---------------------------------------------------------------------------


class TestFleetModelIndex:
    def _model(self):
        from torchx_tpu.fleet import FleetModel

        return FleetModel.from_spec("a:v5e-4x3,b:v5e-4x2")

    def test_units_of_ordering_and_release(self):
        m = self._model()
        m.assign(["b/1", "a/0", "a/2"], "j1")
        assert [u.uid for u in m.units_of("j1")] == ["a/0", "a/2", "b/1"]
        m.release(["a/0"])
        assert [u.uid for u in m.units_of("j1")] == ["a/2", "b/1"]
        assert m.owner_of("a/0") is None

    def test_release_job_clears_index(self):
        m = self._model()
        m.assign(["a/1", "b/0"], "j1")
        freed = m.release_job("j1")
        assert sorted(freed) == ["a/1", "b/0"]
        assert m.units_of("j1") == []
        assert m.free_chips == m.total_chips

    def test_double_book_raises_and_keeps_index_consistent(self):
        m = self._model()
        m.assign(["a/0"], "j1")
        with pytest.raises(ValueError):
            m.assign(["a/0"], "j2")
        assert m.units_of("j2") == []
        assert [u.uid for u in m.units_of("j1")] == ["a/0"]

    def test_index_matches_owner_scan(self):
        m = self._model()
        m.assign(["a/0", "a/1"], "j1")
        m.assign(["b/0"], "j2")
        m.release(["a/1"])
        for job in ("j1", "j2"):
            scan = [u for u in m.units() if m.owner_of(u.uid) == job]
            assert m.units_of(job) == scan


# ---------------------------------------------------------------------------
# scenarios end-to-end
# ---------------------------------------------------------------------------


class TestScenarios:
    def test_bundled_scenarios_resolve(self):
        for name in BUNDLED_SCENARIOS:
            sc = get_scenario(name)
            assert sc["backend"] == "sim"
            sc["mutated"] = True
            assert "mutated" not in BUNDLED_SCENARIOS[name]  # deep copy

    def test_unknown_scenario_raises(self):
        with pytest.raises(ValueError, match="unknown scenario"):
            get_scenario("no-such-scenario")

    def test_scenario_from_json_file(self, tmp_path):
        path = tmp_path / "mine.json"
        path.write_text(json.dumps({"fleet": "sim:v5e-4x2", "hours": 0.1}))
        sc = get_scenario(str(path))
        assert sc["name"] == "mine"

    def test_canary_rolls_back_under_storm(self, tmp_path):
        r = _run("pipeline-canary-under-storm", 3, tmp_path, "a")
        assert r.stats["pipelines"] == {"pl_1": "ROLLED_BACK"}
        rows = [json.loads(l) for l in open(r.journal_path)]
        kinds = {row["kind"] for row in rows}
        assert {"pipeline_submit", "replica_roll", "router_weight",
                "slices_down", "slo_alert"} <= kinds
        # the rollback restores full weight on every canaried replica
        weights = [row for row in rows if row["kind"] == "router_weight"]
        assert weights[-1]["weight"] == 1.0
        # the storm lands before the canary's observation window closes,
        # so the burn gate sees degraded TTFT and rolls back
        roll = next(row for row in rows if row["kind"] == "replica_roll")
        fault = next(row for row in rows if row["kind"] == "slices_down")
        assert fault["t"] < roll["t"] < r.virtual_s

    def test_slo_pages_on_ttft_regression(self, tmp_path):
        r = _run("pipeline-canary-under-storm", 3, tmp_path, "a")
        rows = [json.loads(l) for l in open(r.journal_path)]
        alerts = [row for row in rows if row["kind"] == "slo_alert"]
        assert alerts, "storm must trip the TTFT SLO"
        page = next(
            (a for a in alerts
             if a["state"] == "firing" and a["severity"] == "page"),
            None,
        )
        assert page is not None, alerts
        assert page["burn_short"] > 1.0
        assert alerts[-1]["state"] == "resolved"
        assert r.stats["slo_alerts"] == len(alerts)

    def test_sim_metrics_exported(self, tmp_path):
        from torchx_tpu.obs import metrics as obs_metrics

        r = _run("smoke-tiny", 7, tmp_path, "a")
        assert obs_metrics.SIM_VIRTUAL_SECONDS.value() == pytest.approx(
            r.virtual_s
        )
        assert obs_metrics.SIM_SPEEDUP.value() > 1.0
        assert obs_metrics.SIM_EVENTS.value(kind="place") > 0

    @pytest.mark.slow
    def test_failure_storm_acceptance_under_60s(self, tmp_path):
        r = _run("failure-storm", 11, tmp_path, "a")
        assert r.wall_s < 60.0, f"failure-storm took {r.wall_s:.1f}s wall"
        assert r.stats["submitted"] > 2500
        assert r.stats["completed"] == r.stats["submitted"]
        assert r.stats["faults"] == 11
        assert r.stats["resubmitted"] > 0


# ---------------------------------------------------------------------------
# TPX604
# ---------------------------------------------------------------------------


class TestTpx604:
    def test_non_sim_backend_warns(self):
        diags = list(
            check_sim_scenario({"name": "x", "backend": "gke", "fleet": "f"})
        )
        assert [d.code for d in diags] == ["TPX604"]
        from torchx_tpu.analyze import Severity

        assert diags[0].severity is Severity.WARNING
        assert "gke" in diags[0].message

    def test_sim_or_absent_backend_silent(self):
        assert not list(check_sim_scenario({"backend": "sim"}))
        assert not list(check_sim_scenario({"fleet": "f"}))

    def test_bundled_scenarios_pass(self):
        for sc in BUNDLED_SCENARIOS.values():
            assert not list(check_sim_scenario(sc))

    def test_cli_surfaces_warning(self, tmp_path, capsys):
        from torchx_tpu.cli.main import main

        path = tmp_path / "prod.json"
        path.write_text(json.dumps({
            "backend": "gke", "fleet": "sim:v5e-4x2", "hours": 0.02,
            "rate_scale": 0.2, "metrics_interval_s": 60.0, "faults": [],
        }))
        main(["sim", "run", "--scenario", str(path),
              "--out", str(tmp_path / "st")])
        err = capsys.readouterr().err
        assert "TPX604" in err


# ---------------------------------------------------------------------------
# the sim-hosted wall-clock self-lint
# ---------------------------------------------------------------------------


class TestWallClockLint:
    def _check(self, tmp_path, source):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "lint_internal",
            os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "lint_internal.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        path = tmp_path / "mod.py"
        path.write_text(source)
        return mod.check_wall_clock(str(path))

    def test_raw_calls_flagged(self, tmp_path):
        out = self._check(
            tmp_path,
            "import time\n"
            "def f():\n"
            "    t = time.time()\n"
            "    time.sleep(1)\n"
            "    m = time.monotonic()\n",
        )
        assert len(out) == 3
        assert all("clock seam" in v for v in out)

    def test_default_arg_reference_allowed(self, tmp_path):
        # the injection idiom itself: attribute refs are not Call nodes
        out = self._check(
            tmp_path,
            "import time\n"
            "from typing import Callable\n"
            "def f(clock: Callable[[], float] = time.time,\n"
            "      sleep=time.sleep):\n"
            "    return clock()\n",
        )
        assert out == []

    def test_perf_counter_allowed(self, tmp_path):
        out = self._check(
            tmp_path,
            "import time\n"
            "def f():\n"
            "    return time.perf_counter()\n",
        )
        assert out == []

    def test_repo_is_clean(self):
        import subprocess
        import sys

        r = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "..", "scripts",
                          "lint_internal.py")],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# executor corner cases
# ---------------------------------------------------------------------------


class TestSimExecutor:
    def _job(self, name, replicas=2, cur=None):
        return types.SimpleNamespace(
            req=types.SimpleNamespace(job=name, replicas=replicas),
            cur_replicas=cur if cur is not None else replicas,
        )

    def test_cancel_banks_remaining_work(self):
        now = [0.0]
        ex = SimExecutor(lambda: now[0], {"j": 100.0})
        handle = ex.schedule(self._job("j"), "")
        now[0] = 40.0
        ex.cancel(handle)
        assert ex.work["j"] == pytest.approx(60.0)
        assert ex.next_finish() is None
        # resubmit at half width: remaining work at half speed
        h2 = ex.schedule(self._job("j", replicas=2, cur=1), "")
        assert ex.next_finish() == pytest.approx(40.0 + 120.0)
        now[0] = ex.next_finish()
        assert ex.pop_finished() == h2
        assert ex.finish(h2) == h2.rsplit("/", 1)[1]
        assert ex.job_of(h2) == "j"

    def test_launch_and_complete_latency(self):
        now = [0.0]
        ex = SimExecutor(
            lambda: now[0], {"j": 10.0},
            launch_latency_s=5.0, complete_latency_s=3.0,
        )
        ex.schedule(self._job("j"), "")
        assert ex.next_finish() == pytest.approx(18.0)
