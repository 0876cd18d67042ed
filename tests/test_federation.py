"""Federation tests: cell registry durability, burn/affinity routing,
spillover + circuit breaking, the daemon's drain lifecycle + rehydration
reporting, 429 Retry-After handling, ``wait`` across a daemon restart,
region-by-region promotion waves, TPX605, and the deterministic two-cell
sim scenario."""

import json
import os
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from types import SimpleNamespace

import pytest

from torchx_tpu import settings
from torchx_tpu.control.client import ControlClient, ControlClientError
from torchx_tpu.control.daemon import ControlDaemon
from torchx_tpu.federation import (
    DRAINED,
    DRAINING,
    HEALTHY,
    UNCORDONED,
    CellHandle,
    CellRegistry,
    CellSpec,
    FederationError,
    FederationPromoter,
    FederationRouter,
)
from torchx_tpu.resilience.breaker import BreakerState
from torchx_tpu.runner.api import get_runner


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestCellRegistry:
    def test_add_get_remove_rehydrate(self, tmp_path):
        root = str(tmp_path / "fed")
        reg = CellRegistry(root=root)
        reg.add("us-east1", "http://127.0.0.1:1001/", token="t1")
        reg.add("eu-west4", "http://127.0.0.1:1002", token="t2")
        # trailing slash normalized; journal is 0600 (it carries tokens)
        assert reg.get("us-east1").addr == "http://127.0.0.1:1001"
        assert os.stat(reg.path).st_mode & 0o777 == 0o600
        # a fresh registry over the same root replays the journal
        reg2 = CellRegistry(root=root)
        assert [s.name for s in reg2.cells()] == ["eu-west4", "us-east1"]
        assert reg2.get("eu-west4").token == "t2"
        # last writer wins: re-address then remove
        reg2.add("us-east1", "http://127.0.0.1:1003")
        assert reg2.remove("eu-west4")
        assert not reg2.remove("never-was")
        reg3 = CellRegistry(root=root)
        assert [s.name for s in reg3.cells()] == ["us-east1"]
        assert reg3.get("us-east1").addr == "http://127.0.0.1:1003"

    def test_add_requires_name_and_addr(self, tmp_path):
        reg = CellRegistry(root=str(tmp_path / "fed"))
        with pytest.raises(ValueError):
            reg.add("", "http://x")
        with pytest.raises(ValueError):
            reg.add("a", "")


# ---------------------------------------------------------------------------
# router scoring + dispatch (fake clients, no daemons)
# ---------------------------------------------------------------------------


class _FakeCellClient:
    """Scriptable stand-in for ControlClient's probe/dispatch surface."""

    def __init__(
        self,
        state=HEALTHY,
        rehydrated=True,
        draining=False,
        burn=0.0,
        dead=False,
    ):
        self.state = state
        self.rehydrated = rehydrated
        self.draining = draining
        self.burn = burn
        self.dead = dead
        self.calls = 0
        #: exception to raise from dispatched fns (None = succeed)
        self.dispatch_error = None

    def cell_status(self):
        if self.dead:
            raise ControlClientError(0, "unreachable")
        return {
            "cell": "x",
            "state": self.state,
            "draining": self.draining,
            "rehydrated": self.rehydrated,
        }

    def alerts(self):
        return {"enabled": True, "burns": {"ttft": {"long": self.burn}}}

    def do(self):
        self.calls += 1
        if self.dispatch_error is not None:
            raise self.dispatch_error
        return {"ok": True}


def _handle(name, client, clock=time.monotonic):
    return CellHandle(CellSpec(name=name, addr=f"http://{name}"), client=client, clock=clock)


def _router(handles, **kw):
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("probe_ttl_s", 0.0)  # re-probe every candidates() call
    return FederationRouter(handles, **kw)


class TestFederationRouter:
    def test_affinity_prefers_cache_warm_cell(self):
        a = _handle("aaa", _FakeCellClient())
        b = _handle("bbb", _FakeCellClient())
        b.update_prefix_digests(["d0", "d1", "d2"])
        r = _router([a, b])
        chain = ["d0", "d1", "d2", "d3"]
        assert [h.name for h in r.candidates(chain)] == ["bbb", "aaa"]
        # without a chain the name tie-break is deterministic
        assert [h.name for h in r.candidates()] == ["aaa", "bbb"]

    def test_overlap_is_a_prefix_match(self):
        b = _handle("bbb", _FakeCellClient())
        # holds a later block but NOT the chain head: no credit
        b.update_prefix_digests(["d2", "d3"])
        r = _router([b])
        assert r._overlap(b, ["d0", "d1", "d2", "d3"]) == 0.0
        b.update_prefix_digests(["d0", "d1"])
        assert r._overlap(b, ["d0", "d1", "d2", "d3"]) == 0.5

    def test_burn_over_budget_demotes_not_excludes(self):
        hot = _handle("aaa", _FakeCellClient(burn=3.0))
        cool = _handle("bbb", _FakeCellClient(burn=0.1))
        r = _router([hot, cool], burn_budget=1.0)
        assert [h.name for h in r.candidates()] == ["bbb", "aaa"]
        # the hot cell still serves when it is the only one left
        cool.client.dead = True
        name, _ = r.dispatch(lambda c: c.do())
        assert name == "aaa"

    def test_draining_unreachable_unrehydrated_excluded(self):
        ok = _handle("ok", _FakeCellClient())
        drn = _handle("drn", _FakeCellClient(state=DRAINING, draining=True))
        gone = _handle("gone", _FakeCellClient(dead=True))
        boot = _handle("boot", _FakeCellClient(rehydrated=False))
        r = _router([ok, drn, gone, boot])
        assert [h.name for h in r.candidates()] == ["ok"]

    def test_dispatch_spills_on_503_and_marks_draining(self):
        a = _handle("aaa", _FakeCellClient())
        b = _handle("bbb", _FakeCellClient())
        a.client.dispatch_error = ControlClientError(503, "cell draining")
        r = _router([a, b])
        name, result = r.dispatch(lambda c: c.do())
        assert name == "bbb" and result == {"ok": True}
        # the 503 verdict stuck: aaa drops out of the next candidate list
        # via its cached probe, before any TTL-driven re-probe
        assert a.last_probe["draining"] and a.last_probe["state"] == DRAINING

    def test_dispatch_reraises_non_spill_codes(self):
        a = _handle("aaa", _FakeCellClient())
        b = _handle("bbb", _FakeCellClient())
        a.client.dispatch_error = ControlClientError(400, "bad component")
        r = _router([a, b])
        with pytest.raises(ControlClientError) as ei:
            r.dispatch(lambda c: c.do())
        assert ei.value.code == 400
        assert b.client.calls == 0  # a malformed request is not replayed

    def test_transport_failures_trip_breaker_then_federation_error(self):
        clk = [0.0]
        a = _handle("aaa", _FakeCellClient(), clock=lambda: clk[0])
        a.client.dispatch_error = ControlClientError(0, "boom")
        slept = []
        # long probe TTL: the healthy-looking cached probe must not reset
        # the breaker's failure streak between dispatch rounds
        r = _router(
            [a], sleep=slept.append, clock=lambda: clk[0], probe_ttl_s=999.0
        )
        with pytest.raises(FederationError) as ei:
            r.dispatch(lambda c: c.do())
        assert "aaa" in ei.value.errors
        # trip_after transport failures opened the breaker
        assert a.breaker.state is BreakerState.OPEN
        assert a.client.calls == settings.FEDERATION_BREAKER_TRIP_AFTER
        # capped jittered backoff ran between rounds, never a hard spin
        assert len(slept) == r.max_rounds - 1
        assert all(0 < s <= r.policy.backoff_max_seconds * 1.5 for s in slept)

    def test_no_cells_is_federation_error(self):
        r = _router([])
        with pytest.raises(FederationError):
            r.dispatch(lambda c: c.do())

    def test_snapshot_reports_breaker_state(self):
        a = _handle("aaa", _FakeCellClient(burn=0.4))
        r = _router([a])
        snap = r.snapshot()
        assert snap["aaa"]["burn"] == 0.4
        assert snap["aaa"]["breaker"] == BreakerState.CLOSED.value


# ---------------------------------------------------------------------------
# satellite: 429 Retry-After handling in ControlClient
# ---------------------------------------------------------------------------


def _throttle_server(replies):
    """An HTTP server that pops one scripted reply per request:
    ("429", hint_header, hint_body) or ("200", body_dict)."""

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def do_GET(self):
            kind = replies.pop(0)
            if kind[0] == "429":
                _, header, body_hint = kind
                body = {"error": "throttled"}
                if body_hint is not None:
                    body["retry_after_seconds"] = body_hint
                data = json.dumps(body).encode()
                self.send_response(429)
                if header is not None:
                    self.send_header("Retry-After", str(header))
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
            else:
                data = json.dumps(kind[1]).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


class _Rng:
    def uniform(self, a, b):
        return 0.0  # no jitter: assert exact hints


class TestClient429Retry:
    def test_retry_after_header_honored_then_success(self):
        srv, addr = _throttle_server(
            [("429", 2, None), ("200", {"status": "ok"})]
        )
        try:
            slept = []
            client = ControlClient(
                addr, "t", sleep=slept.append, rng=_Rng(), retry_429=3
            )
            assert client.healthz() == {"status": "ok"}
            assert slept == [2.0]
        finally:
            srv.shutdown()

    def test_body_hint_used_when_header_missing_and_cap_applies(self):
        srv, addr = _throttle_server(
            [("429", None, 1.5), ("429", 10_000, None), ("200", {"status": "ok"})]
        )
        try:
            slept = []
            client = ControlClient(
                addr, "t", sleep=slept.append, rng=_Rng(), retry_429=3
            )
            assert client.healthz() == {"status": "ok"}
            assert slept == [1.5, settings.CONTROL_429_RETRY_CAP_SECONDS]
        finally:
            srv.shutdown()

    def test_attempts_are_bounded(self):
        srv, addr = _throttle_server([("429", 0, None)] * 4)
        try:
            slept = []
            client = ControlClient(
                addr, "t", sleep=slept.append, rng=_Rng(), retry_429=2
            )
            with pytest.raises(ControlClientError) as ei:
                client.healthz()
            assert ei.value.code == 429
            assert len(slept) == 2  # retry_429 sleeps, then surface
        finally:
            srv.shutdown()

    def test_retry_disabled_surfaces_immediately(self):
        srv, addr = _throttle_server([("429", 1, None)])
        try:
            slept = []
            client = ControlClient(
                addr, "t", sleep=slept.append, retry_429=0
            )
            with pytest.raises(ControlClientError) as ei:
                client.healthz()
            assert ei.value.code == 429 and slept == []
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# daemon: cell lifecycle + rehydration reporting
# ---------------------------------------------------------------------------


@pytest.fixture
def cell_daemon(tmp_path, monkeypatch):
    monkeypatch.setenv("TPX_WATCH_INTERVAL", "0.05")
    d = ControlDaemon(
        runner=get_runner("fed-test"),
        state_dir=str(tmp_path / "cell-a"),
        cell="us-east1",
    ).start()
    yield d
    d.close()
    d.runner.close()


class TestDaemonCellLifecycle:
    def test_healthz_reports_cell_and_rehydration(self, cell_daemon):
        client = ControlClient(cell_daemon.addr, cell_daemon.root_token)
        health = client.healthz()
        assert health["cell"] == "us-east1"
        assert health["rehydrated"] is True
        assert health["rehydration"]["journal_jobs"] == 0
        assert health["draining"] is False

    def test_drain_refuses_submits_and_uncordon_reopens(
        self, cell_daemon, tmp_path
    ):
        client = ControlClient(cell_daemon.addr, cell_daemon.root_token)
        assert client.cell_status()["state"] == HEALTHY
        drained = client.cell_drain()
        assert drained["draining"] and drained["state"] == DRAINED
        with pytest.raises(ControlClientError) as ei:
            client.submit(
                "utils.echo",
                ["--msg", "nope"],
                "local",
                cfg={"log_dir": str(tmp_path / "logs")},
            )
        assert ei.value.code == 503
        reopened = client.cell_uncordon()
        assert reopened["state"] == UNCORDONED
        assert client.cell_status()["state"] == HEALTHY
        handle = client.submit(
            "utils.echo",
            ["--msg", "back"],
            "local",
            cfg={"log_dir": str(tmp_path / "logs")},
        )
        assert client.wait(handle, timeout=60)["terminal"]

    def test_drain_survives_restart(self, cell_daemon):
        client = ControlClient(cell_daemon.addr, cell_daemon.root_token)
        client.cell_drain()
        state_dir = cell_daemon.state_dir
        cell_daemon.close()
        runner2 = get_runner("fed-test-2")
        d2 = ControlDaemon(runner=runner2, state_dir=state_dir, cell="us-east1")
        try:
            assert d2.cell_payload()["draining"] is True
            assert d2.cell_payload()["state"] == DRAINED
        finally:
            d2.close()
            runner2.close()

    def test_journal_records_carry_cell(self, cell_daemon, tmp_path):
        client = ControlClient(cell_daemon.addr, cell_daemon.root_token)
        handle = client.submit(
            "utils.echo",
            ["--msg", "stamped"],
            "local",
            cfg={"log_dir": str(tmp_path / "logs")},
        )
        client.wait(handle, timeout=60)
        from torchx_tpu.specs.api import parse_app_handle

        _, _, app_id = parse_app_handle(handle)
        event = cell_daemon.store.latest("local", app_id)
        assert event is not None and event.cell == "us-east1"

    def test_watch_stream_records_carry_cell(self, cell_daemon):
        """A watch adapter knows no cell: what it observes is stamped where
        it enters the daemon's journal, not only the daemon's own events."""
        from torchx_tpu.control.events import StateEvent
        from torchx_tpu.specs.api import AppState

        cell_daemon.reconciler.ingest(
            StateEvent("local", "seen-by-sidecar", AppState.RUNNING, source="sidecar")
        )
        event = cell_daemon.store.latest("local", "seen-by-sidecar")
        assert event.source == "sidecar" and event.cell == "us-east1"

    def test_router_treats_unrehydrated_cell_as_drained(self, cell_daemon):
        handle = CellHandle(
            CellSpec(name="us-east1", addr=cell_daemon.addr),
            client=ControlClient(cell_daemon.addr, cell_daemon.root_token),
        )
        router = _router([handle])
        assert [h.name for h in router.candidates()] == ["us-east1"]
        # a daemon mid-rehydration answers /v1/cell but is not routable
        cell_daemon.rehydrated = False
        try:
            snap = handle.probe()
            assert snap["reachable"] and not snap["rehydrated"]
            assert router.candidates() == []
        finally:
            cell_daemon.rehydrated = True

    def test_probe_of_dead_daemon_feeds_breaker(self):
        handle = CellHandle(
            CellSpec(name="ghost", addr="http://127.0.0.1:1"),
            client=ControlClient("http://127.0.0.1:1", "t", timeout=0.2),
        )
        for _ in range(settings.FEDERATION_BREAKER_TRIP_AFTER):
            assert handle.probe()["reachable"] is False
        assert handle.breaker.state is BreakerState.OPEN


# ---------------------------------------------------------------------------
# satellite: wait() survives a daemon restart mid-long-poll
# ---------------------------------------------------------------------------


class TestWaitAcrossRestart:
    def test_wait_reconnects_and_resolves_from_journal(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("TPX_WATCH_INTERVAL", "0.05")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        state_dir = str(tmp_path / "control")
        d1 = ControlDaemon(
            runner=get_runner("fed-wait"),
            state_dir=state_dir,
            host="127.0.0.1",
            port=port,
        ).start()
        client = ControlClient(
            d1.addr,
            d1.root_token,
            timeout=5.0,
            # compress the reconnect backoff so the test stays fast
            sleep=lambda s: time.sleep(min(s, 0.05)),
        )
        handle = client.submit(
            "utils.echo",
            ["--msg", "over-the-gap"],
            "local",
            cfg={"log_dir": str(tmp_path / "logs")},
        )
        # let the job reach its (journaled) terminal state, then take the
        # daemon down and start the wait against the dead address
        client.wait(handle, timeout=60)
        d1.close()
        d1.runner.close()
        result, errors = {}, []

        def _wait():
            try:
                result.update(client.wait(handle, timeout=30))
            except Exception as e:  # noqa: BLE001 - asserted below
                errors.append(e)

        t = threading.Thread(target=_wait)
        t.start()
        time.sleep(0.3)  # a few reconnect attempts fail against the gap
        runner2 = get_runner("fed-wait-2")
        d2 = ControlDaemon(
            runner=runner2,
            state_dir=state_dir,
            host="127.0.0.1",
            port=port,
        )
        # tokens die with the daemon: hand the waiting client the new
        # root token BEFORE the restarted daemon starts answering (real
        # callers re-read the 0600 discovery file the restart rewrites)
        client.token = d2.root_token
        d2.start()
        try:
            t.join(timeout=30)
            assert not t.is_alive()
            assert errors == []
            assert result["state"] == "SUCCEEDED" and result["terminal"]
        finally:
            d2.close()
            runner2.close()

    def test_wait_gives_up_after_reconnect_budget(self):
        slept = []
        client = ControlClient(
            "http://127.0.0.1:1",
            "t",
            timeout=0.2,
            sleep=slept.append,
        )
        with pytest.raises(ControlClientError) as ei:
            client.wait("local://fed/ghost", timeout=120)
        assert ei.value.code == 0
        # one capped, growing backoff per failed reconnect
        assert len(slept) == client.WAIT_RECONNECT_ATTEMPTS - 1
        assert all(s <= 5.0 * 1.1 for s in slept)


# ---------------------------------------------------------------------------
# promotion waves
# ---------------------------------------------------------------------------


class _FakePipelineClient(_FakeCellClient):
    def __init__(self, terminal="PROMOTED", submit_error=None, **kw):
        super().__init__(**kw)
        self.terminal = terminal
        self.submit_error = submit_error
        self.submitted = []

    def pipeline_submit(self, spec):
        if self.submit_error is not None:
            raise self.submit_error
        self.submitted.append(spec)
        return {"pipeline": f"p-{len(self.submitted)}"}

    def pipeline_status(self, pid):
        return {"pipeline": pid, "state": self.terminal, "reason": ""}


class TestFederationPromoter:
    def _promoter(self, handles, **kw):
        kw.setdefault("sleep", lambda s: None)
        kw.setdefault("poll_interval_s", 0.0)
        return FederationPromoter(_router(handles), **kw)

    def test_wave_halts_on_rollback_and_skips_rest(self):
        a = _handle("aaa", _FakePipelineClient(terminal="PROMOTED"))
        b = _handle("bbb", _FakePipelineClient(terminal="ROLLED_BACK"))
        c = _handle("ccc", _FakePipelineClient(terminal="PROMOTED"))
        wave = self._promoter([a, b, c]).run_wave(
            {"name": "cand"}, order=["aaa", "bbb", "ccc"]
        )
        assert wave.promoted == ["aaa"]
        assert wave.halted and "bbb" in wave.halt_reason
        assert wave.skipped == ["ccc"]
        assert c.client.submitted == []  # the candidate never reached ccc

    def test_wave_halts_on_burn_after_promote(self):
        a = _handle("aaa", _FakePipelineClient(terminal="PROMOTED", burn=5.0))
        b = _handle("bbb", _FakePipelineClient(terminal="PROMOTED"))
        wave = self._promoter([a, b], burn_threshold=1.0).run_wave(
            {"name": "cand"}, order=["aaa", "bbb"]
        )
        assert wave.promoted == []
        assert wave.halted and "burn" in wave.halt_reason
        assert wave.skipped == ["bbb"]

    def test_drained_cell_is_skipped_without_halting(self):
        a = _handle(
            "aaa",
            _FakePipelineClient(
                submit_error=ControlClientError(503, "cell draining")
            ),
        )
        b = _handle("bbb", _FakePipelineClient(terminal="PROMOTED"))
        wave = self._promoter([a, b]).run_wave(
            {"name": "cand"}, order=["aaa", "bbb"]
        )
        assert wave.cells["aaa"]["state"] == "UNREACHED"
        assert wave.promoted == ["bbb"] and not wave.halted

    def test_default_order_is_healthiest_first(self):
        hot = _handle("aaa", _FakePipelineClient(burn=2.0))
        cool = _handle("bbb", _FakePipelineClient(burn=0.1))
        promoter = self._promoter([hot, cool], burn_threshold=10.0)
        assert promoter._wave_order(None) == ["bbb", "aaa"]


# ---------------------------------------------------------------------------
# TPX605
# ---------------------------------------------------------------------------


class TestTPX605:
    def _codes(self, config):
        from torchx_tpu.analyze.rules import check_federation_config

        return [(d.code, d.field) for d in check_federation_config(config)]

    def test_single_cell_federation_warns(self):
        codes = self._codes({"cells": [{"name": "only", "addr": "http://x"}]})
        assert codes == [("TPX605", "cells")]

    def test_promote_without_rollback_warns(self):
        config = {
            "cells": [{"name": "a"}, {"name": "b"}],
            "promote": {"name": "ship", "rollback": False},
        }
        assert ("TPX605", "promote.ship") in self._codes(config)

    def test_non_positive_burn_threshold_warns(self):
        config = {
            "cells": [{"name": "a"}, {"name": "b"}],
            "pipelines": [
                {
                    "spec": {
                        "stages": [
                            {
                                "name": "promote",
                                "kind": "promote",
                                "burn_threshold": 0,
                            }
                        ]
                    }
                }
            ],
        }
        assert ("TPX605", "promote.promote") in self._codes(config)

    def test_clean_two_cell_config_is_silent(self):
        config = {
            "cells": [{"name": "a"}, {"name": "b"}],
            "promote": {"name": "ship", "burn_threshold": 1.0},
        }
        assert self._codes(config) == []


# ---------------------------------------------------------------------------
# serve-pool federation export
# ---------------------------------------------------------------------------


class TestServePoolFederation:
    def _pool(self, **kw):
        from torchx_tpu.serve.pool import ServePool

        app = SimpleNamespace(
            name="svc",
            roles=[SimpleNamespace(name="server", num_replicas=2)],
        )
        return ServePool(runner=None, app=app, **kw)

    def test_summary_unions_replica_prefix_digests(self):
        from torchx_tpu.serve.pool import ReplicaStatus

        pool = self._pool(cell="us-east1")
        pool.router.update(
            [
                ReplicaStatus(
                    replica_id=0,
                    url="http://r0",
                    healthy=True,
                    prefix_summary=("d0", "d1"),
                ),
                ReplicaStatus(
                    replica_id=1,
                    url="http://r1",
                    healthy=True,
                    prefix_summary=("d1", "d2"),
                ),
                # unhealthy replicas do not advertise their cache
                ReplicaStatus(
                    replica_id=2,
                    url="http://r2",
                    healthy=False,
                    prefix_summary=("dead",),
                ),
            ]
        )
        summary = pool.federation_summary()
        assert summary["cell"] == "us-east1"
        assert summary["prefix_digests"] == ["d0", "d1", "d2"]
        assert summary["replicas"] == 2
        # the summary feeds the router's affinity signal directly
        handle = CellHandle(CellSpec(name="us-east1", addr="http://x"))
        handle.update_prefix_digests(summary["prefix_digests"])
        assert handle.prefix_digests == {"d0", "d1", "d2"}

    def test_cell_defaults_from_environment(self, monkeypatch):
        monkeypatch.delenv(settings.ENV_TPX_CELL, raising=False)
        assert self._pool().cell == settings.DEFAULT_CELL_NAME
        monkeypatch.setenv(settings.ENV_TPX_CELL, "eu-west4")
        assert self._pool().cell == "eu-west4"


# ---------------------------------------------------------------------------
# the deterministic two-cell sim scenario
# ---------------------------------------------------------------------------


class TestFederationSim:
    def _run(self, tmp_path, tag, seed=11):
        from torchx_tpu.federation.sim import FederationSimHarness
        from torchx_tpu.sim.scenarios import get_scenario

        scenario = get_scenario("federation-two-cell")
        harness = FederationSimHarness(
            scenario, seed=seed, state_dir=str(tmp_path / tag)
        )
        return harness.run()

    def test_drain_mid_trace_zero_drops(self, tmp_path):
        report = self._run(tmp_path, "a")
        assert report.stats["requests"] > 0
        assert report.stats["dropped"] == 0
        assert report.stats["spillovers"] > 0
        # both cells served: the drained cell before/after, the survivor
        # throughout
        assert set(report.stats["per_cell"]) == {"eu-west4", "us-east1"}
        assert all(v > 0 for v in report.stats["per_cell"].values())
        # failover p99 is bounded: degraded, not collapsed
        assert report.stats["ttft_p99_during_s"] <= 1.0

    def test_same_seed_is_byte_identical(self, tmp_path):
        r1 = self._run(tmp_path, "a")
        r2 = self._run(tmp_path, "b")
        assert r1.journal_sha256 == r2.journal_sha256
        assert r1.stats == r2.stats

    def test_different_seed_diverges(self, tmp_path):
        r1 = self._run(tmp_path, "a", seed=11)
        r2 = self._run(tmp_path, "b", seed=12)
        assert r1.journal_sha256 != r2.journal_sha256
