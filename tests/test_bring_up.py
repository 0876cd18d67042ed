"""The refusals the chip bring-up added: paths that used to hide the device
(or a failure) behind a green run now fail, and say where a job ran.

CPU, tier-1. What only a chip can show is ``chip_smoke.py``'s job; its
``--cpu-tiny`` mode runs here so the script itself cannot rot.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import urllib.error
import urllib.request
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

ROOT = Path(__file__).resolve().parent.parent


class TestCompilationCache:
    """One knob, jax's own (parallel/xla_cache.py)."""

    @pytest.fixture
    def updates(self, monkeypatch):
        """Record jax.config.update calls and put the cache dir back."""
        seen = []
        real = jax.config.update
        before = jax.config.jax_compilation_cache_dir

        def spy(name, value):
            seen.append(name)
            real(name, value)

        monkeypatch.setattr(jax.config, "update", spy)
        yield seen
        real("jax_compilation_cache_dir", before)

    def test_env_var_is_left_to_jax(self, updates, monkeypatch, tmp_path):
        from torchx_tpu.parallel import xla_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "theirs"))
        assert xla_cache.setup_compilation_cache() == str(tmp_path / "theirs")
        assert "jax_compilation_cache_dir" not in updates
        # the thresholds are still lowered so every variant persists
        assert "jax_persistent_cache_min_entry_size_bytes" in updates

    def test_unset_means_one_fixed_dir_in_the_checkout(
        self, updates, monkeypatch, tmp_path
    ):
        from torchx_tpu.parallel import xla_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(xla_cache, "REPO_ROOT", str(tmp_path))
        want = str(tmp_path / ".jax_cache")
        assert xla_cache.setup_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert os.path.isdir(want)

    def test_real_root_is_the_checkout(self):
        from torchx_tpu.parallel import xla_cache

        assert Path(xla_cache.REPO_ROOT) == ROOT


class TestUnknownDevice:
    """A TPU the tables do not know is an error, not a nominal default."""

    def fake_device(self, monkeypatch, platform, kind, stats=None):
        dev = SimpleNamespace(
            platform=platform, device_kind=kind, memory_stats=lambda: stats
        )
        monkeypatch.setattr(jax, "devices", lambda *a: [dev])
        monkeypatch.setattr(jax, "local_devices", lambda *a: [dev])

    def test_peak_flops_resolves_what_the_v5e_reports(self, monkeypatch):
        from torchx_tpu.train.report import device_peak_flops

        self.fake_device(monkeypatch, "tpu", "TPU v5 lite")
        assert device_peak_flops() == 197e12

    def test_peak_flops_unknown_tpu_raises(self, monkeypatch):
        from torchx_tpu.train.report import device_peak_flops

        self.fake_device(monkeypatch, "tpu", "TPU v9 mega")
        with pytest.raises(ValueError, match="TPU v9 mega"):
            device_peak_flops()

    def test_peak_flops_cpu_keeps_its_nominal_value(self):
        from torchx_tpu.train.report import PEAK_FLOPS, device_peak_flops

        assert device_peak_flops() == PEAK_FLOPS["cpu"]

    def test_perf_for_resolves_what_the_v5e_reports(self):
        from torchx_tpu.tune.calibrate import generation_key
        from torchx_tpu.tune.rank import GENERATION_PERF, perf_for

        assert generation_key("TPU v5 lite") == "v5e"
        assert generation_key("v5litepod-4") == "v5e"
        assert generation_key("TPU v5p") == "v5p"
        assert perf_for("TPU v5 lite") is GENERATION_PERF["v5e"]

    def test_perf_for_unknown_tpu_raises_and_cpu_does_not(self):
        from torchx_tpu.tune.rank import perf_for

        with pytest.raises(ValueError, match="v9"):
            perf_for("TPU v9 mega")
        assert perf_for("").flops == perf_for("cpu").flops  # the sim default

    def test_hbm_budget_missing_on_a_tpu_raises(self, monkeypatch):
        from torchx_tpu.parallel.remat_auto import V5P_HBM_BYTES, device_hbm_bytes

        assert device_hbm_bytes() == V5P_HBM_BYTES  # the CPU reports none
        self.fake_device(monkeypatch, "tpu", "TPU v5 lite", stats={})
        with pytest.raises(RuntimeError, match="bytes_limit"):
            device_hbm_bytes()
        self.fake_device(
            monkeypatch, "tpu", "TPU v5 lite", stats={"bytes_limit": 16 << 30}
        )
        assert device_hbm_bytes() == 16 << 30


class TestTrainerSaysWhereItRan:
    def test_results_name_device_and_traced_ops(self):
        from torchx_tpu.train.run import train
        from torchx_tpu.models import llama
        from torchx_tpu.parallel.mesh import MeshConfig

        res = train(
            llama.llama_tiny(),
            MeshConfig(dp=1, fsdp=-1, tp=1, sp=1),
            batch=8,
            seq=32,
            steps=2,
        )
        assert res["platform"] == "cpu" and res["device_kind"] == "cpu"
        assert res["device_count"] == jax.device_count()
        assert "xla" in res["attention"].split("+")
        shards = res["largest_param_shards"]
        assert shards["devices"] == jax.device_count()
        assert shards["shard_frac"] == pytest.approx(1 / jax.device_count())


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _post(url, payload):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestDeadEngine:
    """An engine whose step raised used to leave /healthz at 200 "ok",
    queue new requests for ever, and exit 0 on SIGTERM."""

    def test_healthz_says_where_it_runs(self):
        from torchx_tpu.apps.generate_server import serve

        srv = serve("tiny", port=0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            code, body = _get(f"http://127.0.0.1:{srv.server_address[1]}/healthz")
            assert code == 200 and body["status"] == "ok"
            assert body["platform"] == "cpu" and body["device_kind"] == "cpu"
            assert body["device_count"] == jax.device_count()
            assert body["kernels"] == "reference" and body["failed"] is None
        finally:
            srv.shutdown()
            srv.service.close()

    def test_step_failure_turns_healthz_503_and_refuses_work(self, monkeypatch):
        from torchx_tpu.apps.generate_server import serve
        from torchx_tpu.serve.engine import ServeEngine

        def boom(self):
            if any(slot is not None for slot in self._slots):
                raise RuntimeError("device fell over")
            return False

        monkeypatch.setattr(ServeEngine, "_decode_once", boom)
        srv = serve("tiny", port=0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            code, body = _post(
                f"{base}/v1/generate", {"tokens": [[1, 2, 3]], "max_new_tokens": 4}
            )
            assert code == 500 and "device fell over" in body["error"]
            code, body = _get(f"{base}/healthz")
            assert code == 503 and body["status"] == "failed"
            assert "device fell over" in body["failed"]
            # a new request is refused at once, not queued for ever
            code, body = _post(
                f"{base}/v1/generate", {"tokens": [[1]], "max_new_tokens": 1}
            )
            assert code == 503 and "device fell over" in body["error"]
            assert srv.service.failed
        finally:
            srv.shutdown()
            srv.service.close()

    def test_a_failing_chunk_fails_the_request_whose_prompt_it_fed(self):
        """A request whose prompt is being fed sits in a slot and has no token
        yet. On the chip an 8192-wide prefill bucket once ran the compile out
        of HBM and the caller waited out its own timeout; the program that
        carries a prompt's chunk must fail its request the same way."""
        from torchx_tpu.models import llama
        from torchx_tpu.serve.engine import ServeEngine

        def no_hbm(*args):
            raise RuntimeError("RESOURCE_EXHAUSTED: ran out of hbm")

        cfg = llama.llama_tiny()
        engine = ServeEngine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg, max_slots=2)
        engine._decode_chunk = no_hbm
        engine.start()
        try:
            with pytest.raises(RuntimeError, match="ran out of hbm"):
                engine.generate([1, 2, 3], max_new_tokens=4, timeout=30)
            assert "ran out of hbm" in engine.failed
            assert engine.cache.alloc.used_blocks == 0  # the slot's blocks went back with it
        finally:
            engine.stop()

    def test_process_exits_nonzero_after_sigterm(self, tmp_path):
        """The whole process: engine dies, SIGTERM still drains, exit 1."""
        code = textwrap.dedent(
            """
            from torchx_tpu.apps import generate_server
            from torchx_tpu.serve.engine import ServeEngine

            def boom(self):
                if any(slot is not None for slot in self._slots):
                    raise RuntimeError("device fell over")
                return False

            ServeEngine._decode_once = boom
            generate_server.main(["--config", "tiny", "--port", "0"])
            """
        )
        env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
        proc = subprocess.Popen(
            [sys.executable, "-c", code],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        try:
            line = proc.stdout.readline()  # "generate_server: tiny ... on :PORT ..."
            assert "platform=cpu" in line, line + proc.stderr.read()
            port = int(line.split(" on :")[1].split()[0])
            code_, _ = _post(
                f"http://127.0.0.1:{port}/v1/generate",
                {"tokens": [[1, 2, 3]], "max_new_tokens": 4},
            )
            assert code_ == 500
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=60) == 1
            assert "device fell over" in proc.stderr.read()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


class TestOneProcessPerChip:
    def test_tune_driver_refuses_real_children_once_jax_is_imported(self):
        """This process has jax; the real probe/measure children need the
        chip it may hold. Stub commands (every other tune test) still run."""
        from torchx_tpu.tune import driver

        assert "jax" in sys.modules
        with pytest.raises(driver.TuneError, match="imported jax"):
            driver._assert_chip_free("measure")


@pytest.mark.integ
def test_chip_smoke_cpu_tiny_runs_green(tmp_path):
    """The smoke's whole control flow, tiny and on the CPU: it exits 0,
    says platform=cpu on every line it prints, and its own process never
    imports jax (it asserts that itself before printing the result)."""
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--cpu-tiny"],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    smoke_lines = [l for l in lines if l.startswith("chip_smoke:")]
    assert smoke_lines and all("platform=cpu" in l for l in smoke_lines)
    assert any("legs run: a b c d e1 e2" in l for l in smoke_lines)


def test_chip_smoke_fails_without_a_chip(tmp_path):
    """No --cpu-tiny, no TPU: non-zero exit and no result line — the
    launcher refuses the TPU role at dryrun (tpu_simulate=False)."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU chip" in proc.stdout
    assert '"ok"' not in proc.stdout
