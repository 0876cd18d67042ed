"""Generation server tests: real HTTP round-trips against a tiny model."""

import json
import threading
import time
import urllib.request

import pytest

from torchx_tpu.apps.generate_server import GenerateService, serve


@pytest.fixture(scope="module")
def server_url():
    srv = serve("tiny", port=0)  # OS-assigned port
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()


def post(url, payload):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestGenerateServer:
    def test_healthz(self, server_url):
        with urllib.request.urlopen(f"{server_url}/healthz", timeout=30) as r:
            body = json.loads(r.read())
        assert body["status"] == "ok"
        assert body["model"] == "tiny"

    def test_token_generation(self, server_url):
        code, body = post(
            f"{server_url}/v1/generate",
            {"tokens": [[1, 2, 3, 4]], "max_new_tokens": 4},
        )
        assert code == 200
        (seq,) = body["tokens"]
        assert len(seq) == 8 and seq[:4] == [1, 2, 3, 4]

    def test_mixed_lengths_batch(self, server_url):
        code, body = post(
            f"{server_url}/v1/generate",
            {"tokens": [[1, 2, 3], [4, 5, 6, 7, 8]], "max_new_tokens": 2},
        )
        assert code == 200
        a, b = body["tokens"]
        assert len(a) == 5 and a[:3] == [1, 2, 3]
        assert len(b) == 7 and b[:5] == [4, 5, 6, 7, 8]

    def test_text_mode_byte_codec(self, server_url):
        code, body = post(
            f"{server_url}/v1/generate",
            {"text": "hi", "max_new_tokens": 3},
        )
        assert code == 200
        (text,) = body["text"]
        assert text.startswith("hi")

    def test_errors_are_4xx(self, server_url):
        code, body = post(f"{server_url}/v1/generate", {"tokens": [[]]})
        assert code == 400 and "error" in body
        code, body = post(
            f"{server_url}/v1/generate",
            {"tokens": [[1]], "max_new_tokens": 10_000},
        )
        assert code == 400 and "max_seq" in body["error"]
        code, _ = post(f"{server_url}/nope", {})
        assert code == 404

    def test_unknown_config_rejected(self):
        with pytest.raises(ValueError, match="unknown config"):
            GenerateService("not-a-model")

    def test_component_materializes(self):
        from torchx_tpu.components.serve import generate_server

        app = generate_server(
            "llama3_1b", port=9000, int8=True, tpu="v5litepod-8"
        )
        (role,) = app.roles
        assert "--int8" in role.args
        assert role.port_map == {"http": 9000}
        assert role.resource.tpu is not None

    def test_disagg_component_materializes(self):
        from torchx_tpu.components.serve import generate_server_disagg
        from torchx_tpu.serve.kv_transfer import ROLE_METADATA_KEY

        app = generate_server_disagg(
            "llama3_1b", prefill_replicas=2, decode_replicas=2
        )
        pre, dec = app.roles
        assert pre.name == "prefill" and dec.name == "decode"
        assert pre.num_replicas == 2 and dec.num_replicas == 2
        i = list(pre.args).index("--serve-role")
        assert pre.args[i + 1] == "prefill"
        # default transfer spec spans the decode gang's port range and is
        # mirrored into both roles' metadata for the TPX213 submit rule
        spec = pre.metadata[ROLE_METADATA_KEY]
        assert spec == "http:http://127.0.0.1:8100,http://127.0.0.1:8101"
        assert dec.metadata[ROLE_METADATA_KEY] == spec
        assert spec in pre.args and spec in dec.args

    def test_disagg_component_rejects_bad_transfer_spec(self):
        from torchx_tpu.components.serve import generate_server_disagg

        with pytest.raises(ValueError, match="kv-transfer"):
            generate_server_disagg("llama3_1b", kv_transfer="smoke-signal:x")


class TestBatcher:
    """Cross-request coalescing: concurrent compatible requests merge into
    one device batch (JetStream-style); incompatible ones don't."""

    def test_concurrent_requests_coalesce(self):
        svc = GenerateService("tiny", batch_window_ms=200, max_batch=8, engine="coalesce")
        try:
            # warm the jit cache so the batch window isn't spent compiling
            svc.generate([[9, 9]], max_new_tokens=2)
            base_batches = svc.batches
            results = {}
            def hit(i):
                results[i] = svc.generate([[i, i + 1]], max_new_tokens=2)[0]
            threads = [
                threading.Thread(target=hit, args=(i,)) for i in range(1, 5)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert len(results) == 4
            for i, seq in results.items():
                assert seq[:2] == [i, i + 1] and len(seq) == 4
            # 4 compatible sequences arrived within one 200ms window ->
            # strictly fewer device dispatches than sequences
            assert svc.batches - base_batches < 4
        finally:
            svc.close()

    def test_incompatible_keys_do_not_merge(self):
        svc = GenerateService("tiny", batch_window_ms=50, max_batch=8, engine="coalesce")
        try:
            svc.generate([[1, 2]], max_new_tokens=2)
            svc.generate([[1, 2, 3]], max_new_tokens=2)  # different length
            base = svc.batches
            out = svc.generate(
                [[1, 2], [1, 2, 3]], max_new_tokens=2
            )  # mixed lengths in ONE request: two dispatches
            assert svc.batches - base == 2
            assert len(out[0]) == 4 and len(out[1]) == 5
        finally:
            svc.close()

    def test_decode_errors_surface_to_caller(self):
        svc = GenerateService("tiny", batch_window_ms=1, engine="coalesce")
        try:
            with pytest.raises(ValueError, match="max_seq"):
                svc.generate([[1] * 100], max_new_tokens=100)
        finally:
            svc.close()

    def test_close_is_idempotent(self):
        svc = GenerateService("tiny", batch_window_ms=1, engine="coalesce")
        svc.close()
        svc.close()

    def test_generate_after_close_raises(self):
        svc = GenerateService("tiny", batch_window_ms=1, engine="coalesce")
        svc.close()
        with pytest.raises(RuntimeError, match="closed"):
            svc.generate([[1, 2]], max_new_tokens=2)

    def test_close_drains_mixed_length_work(self):
        # a mixed-length request enqueues two incompatible pendings; a
        # close() racing the first dispatch must still let BOTH complete
        # (the shutdown sentinel re-arms after the incompatible re-queue)
        svc = GenerateService("tiny", batch_window_ms=100, max_batch=8, engine="coalesce")
        svc.generate([[5, 6]], max_new_tokens=2)  # warm compile
        svc.generate([[5, 6, 7]], max_new_tokens=2)
        results = []
        t = threading.Thread(
            target=lambda: results.append(
                svc.generate([[1, 2], [1, 2, 3]], max_new_tokens=2)
            )
        )
        t.start()
        time.sleep(0.01)  # let the pendings enqueue
        svc.close()
        t.join(timeout=60)
        assert not t.is_alive(), "caller stranded by shutdown"
        # either both sequences completed, or the race landed on the
        # closed error — never a hang
        if results:
            a, b = results[0]
            assert len(a) == 4 and len(b) == 5


class TestStreaming:
    def test_stream_matches_batch(self, server_url):
        # streaming yields the same tokens the batch path returns, in
        # incrementally delivered JSONL chunks
        code, body = post(
            f"{server_url}/v1/generate",
            {"tokens": [[1, 2, 3, 4]], "max_new_tokens": 6},
        )
        assert code == 200
        (expect,) = body["tokens"]
        req = urllib.request.Request(
            f"{server_url}/v1/generate",
            data=json.dumps(
                {
                    "tokens": [[1, 2, 3, 4]],
                    "max_new_tokens": 6,
                    "stream": True,
                    "stream_chunk": 2,
                }
            ).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        got = []
        lines = []
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.headers["Content-Type"] == "application/jsonl"
            for raw in resp:
                line = json.loads(raw)
                lines.append(line)
                got.extend(line.get("tokens", []))
        assert lines[-1] == {"done": True}
        assert got == expect[4:]
        # delivered in >1 chunk (chunk=2 over 6 tokens: 1 + 2 + 2 + 1)
        assert len(lines) >= 3

    def test_stream_rejects_multi_sequence(self, server_url):
        code, body = post(
            f"{server_url}/v1/generate",
            {"tokens": [[1, 2], [3, 4]], "max_new_tokens": 2, "stream": True},
        )
        assert code == 400
        assert "one sequence" in body["error"]

    def test_stream_text_mode(self, server_url):
        req = urllib.request.Request(
            f"{server_url}/v1/generate",
            data=json.dumps(
                {"text": "hi", "max_new_tokens": 3, "stream": True}
            ).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        deltas = []
        with urllib.request.urlopen(req, timeout=120) as resp:
            for raw in resp:
                line = json.loads(raw)
                if "text_delta" in line:
                    deltas.append(line["text_delta"])
        assert deltas  # decoded something, byte-codec round-trips


class TestStreamValidation:
    def test_stream_overflow_is_clean_400(self, server_url):
        # validation happens BEFORE the 200 goes out: the client sees a
        # clean 400 JSON error, not a half-started stream
        code, body = post(
            f"{server_url}/v1/generate",
            {
                "tokens": [[1, 2, 3]],
                "max_new_tokens": 10**6,
                "stream": True,
            },
        )
        assert code == 400
        assert "max_seq" in body["error"]

    def test_stream_chunk_zero_clamped(self, server_url):
        # stream_chunk=0 would loop forever if passed through; the handler
        # clamps it to >= 1
        req = urllib.request.Request(
            f"{server_url}/v1/generate",
            data=json.dumps(
                {
                    "tokens": [[1, 2]],
                    "max_new_tokens": 3,
                    "stream": True,
                    "stream_chunk": 0,
                }
            ).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        got = []
        with urllib.request.urlopen(req, timeout=120) as resp:
            for raw in resp:
                got.append(json.loads(raw))
        assert got[-1] == {"done": True}
        assert sum(len(x.get("tokens", [])) for x in got) == 3


class TestContinuousEngineServer:
    """The default engine is the continuous-batching ServeEngine; its
    stats surface on /healthz and its drain path returns 503s."""

    def test_healthz_reports_engine_stats(self, server_url):
        with urllib.request.urlopen(f"{server_url}/healthz", timeout=30) as r:
            body = json.loads(r.read())
        assert body["engine"] == "continuous"
        for k in ("occupancy", "queue_depth", "active_slots", "kv_blocks_free"):
            assert k in body, body

    def test_metricz_exports_serving_gauges(self, server_url):
        post(  # make sure at least one request has decoded
            f"{server_url}/v1/generate",
            {"tokens": [[2, 3]], "max_new_tokens": 2},
        )
        with urllib.request.urlopen(f"{server_url}/metricz", timeout=30) as r:
            text = r.read().decode()
        assert "tpx_serve_slot_occupancy" in text
        assert "tpx_serve_tokens_total" in text

    def test_engine_matches_coalesce_greedy(self):
        cont = GenerateService("tiny", engine="continuous", max_batch=4)
        coal = GenerateService(
            "tiny", engine="coalesce", batch_window_ms=1, max_batch=4
        )
        try:
            for prompt in ([1, 2, 3], [9, 8, 7, 6]):
                a = cont.generate([prompt], max_new_tokens=4)[0]
                b = coal.generate([prompt], max_new_tokens=4)[0]
                assert a == b, (prompt, a, b)
        finally:
            cont.close()
            coal.close()

    def test_seeded_sampling_is_deterministic_over_http(self, server_url):
        payload = {
            "tokens": [[4, 5]],
            "max_new_tokens": 4,
            "temperature": 0.8,
            "seed": 7,
        }
        _, a = post(f"{server_url}/v1/generate", payload)
        _, b = post(f"{server_url}/v1/generate", payload)
        assert a["tokens"] == b["tokens"]

    def test_eos_id_field_respected(self, server_url, early_stop_case):
        def generate(prompt, max_new, **fields):
            _, body = post(
                f"{server_url}/v1/generate",
                {"tokens": [prompt], "max_new_tokens": max_new, **fields},
            )
            (seq,) = body["tokens"]
            return seq

        prompt, seq, cut = early_stop_case(generate, 6)
        eos = seq[cut - 1]  # first emitted third or later
        short = generate(prompt, 6, eos_id=eos)
        assert short == seq[:cut] and short[-1] == eos

    def test_bad_engine_name_rejected(self):
        with pytest.raises(ValueError, match="engine"):
            GenerateService("tiny", engine="warp-drive")


class TestDrain:
    """SIGTERM drain: stop admission, finish in-flight work, fail
    /healthz so the pool's router stops sending traffic, exit cleanly."""

    def test_drain_finishes_inflight_then_rejects(self):
        svc = GenerateService("tiny", engine="continuous", max_batch=4)
        try:
            results = []
            t = threading.Thread(
                target=lambda: results.append(
                    svc.generate([[1, 2]], max_new_tokens=4)
                )
            )
            t.start()
            time.sleep(0.05)  # let it enter the engine
            assert svc.drain(grace_s=120) is True
            t.join(timeout=60)
            assert results and len(results[0][0]) == 6
            from torchx_tpu.apps.generate_server import ServiceDraining

            with pytest.raises(ServiceDraining):
                svc.generate([[1]], max_new_tokens=1)
        finally:
            svc.close()

    def test_draining_healthz_is_503(self):
        import urllib.error

        srv = serve("tiny", port=0, engine="continuous")
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            srv.service.drain(grace_s=60)
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(f"{base}/healthz", timeout=30)
            assert e.value.code == 503
            assert json.loads(e.value.read())["status"] == "draining"
            code, body = post(
                f"{base}/v1/generate", {"tokens": [[1]], "max_new_tokens": 1}
            )
            assert code == 503 and "drain" in body["error"]
        finally:
            srv.shutdown()
            srv.service.close()

    def test_make_drain_sequence(self):
        # the SIGTERM callable: drain the service, then stop serve_forever
        from torchx_tpu.apps.generate_server import make_drain

        calls = []

        class FakeServer:
            def shutdown(self):
                calls.append("shutdown")

        class FakeService:
            def drain(self, grace_s):
                calls.append(("drain", grace_s))
                return True

        make_drain(FakeServer(), FakeService(), grace_s=7.5)()
        assert calls == [("drain", 7.5), "shutdown"]

    def test_coalesce_drain_also_stops_admission(self):
        from torchx_tpu.apps.generate_server import ServiceDraining

        svc = GenerateService("tiny", engine="coalesce", batch_window_ms=1)
        try:
            svc.generate([[1, 2]], max_new_tokens=2)  # warm
            assert svc.drain(grace_s=60) is True
            with pytest.raises(ServiceDraining):
                svc.generate([[1]], max_new_tokens=1)
        finally:
            svc.close()


class TestDisaggHttp:
    """Prefill/decode split over real HTTP: the prefill service streams
    KV payloads to the decode replica's /v1/kv and returns the full
    sequence to the client, matching the unified engine exactly."""

    @pytest.fixture(scope="class")
    def decode_url(self):
        srv = serve("tiny", port=0, engine="continuous", serve_role="decode")
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        yield f"http://127.0.0.1:{srv.server_address[1]}"
        srv.shutdown()
        srv.service.close()

    def test_round_trip_matches_unified(self, decode_url):
        pre = GenerateService(
            "tiny",
            engine="continuous",
            serve_role="prefill",
            kv_transfer=f"http:{decode_url}",
        )
        uni = GenerateService("tiny", engine="continuous")
        try:
            prompts = [[1, 2, 3], list(range(4, 21))]
            for prompt in prompts:
                split = pre.generate([prompt], max_new_tokens=5)[0]
                whole = uni.generate([prompt], max_new_tokens=5)[0]
                assert split == whole, (prompt, split, whole)
        finally:
            pre.close()
            uni.close()

    def test_decode_healthz_publishes_role_and_block_size(self, decode_url):
        with urllib.request.urlopen(f"{decode_url}/healthz", timeout=30) as r:
            body = json.loads(r.read())
        assert body["serve_role"] == "decode"
        assert body["block_size"] > 0
        assert "prefix_summary" in body

    def test_kv_endpoint_rejects_garbage(self, decode_url):
        req = urllib.request.Request(
            f"{decode_url}/v1/kv",
            data=b"not an npz payload",
            headers={"Content-Type": "application/octet-stream"},
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 400

    def test_unified_role_rejects_kv_handoffs(self, server_url):
        # a valid payload at a non-decode replica is rejected (503) so
        # the sender requeues it to a real decode target
        import numpy as np

        from torchx_tpu.serve.kv_transfer import KvPayload, new_request_id

        payload = KvPayload(
            request_id=new_request_id(),
            tokens=[1, 2, 3, 4],
            generated=[5],
            cache_len=4,
            max_new_tokens=4,
            temperature=0.0,
            seed=0,
            eos_id=None,
            block_size=16,
            k=np.zeros((2, 1, 16, 2, 32), np.float32),
            v=np.zeros((2, 1, 16, 2, 32), np.float32),
        )
        req = urllib.request.Request(
            f"{server_url}/v1/kv",
            data=payload.to_bytes(),
            headers={"Content-Type": "application/octet-stream"},
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 503

    def test_role_validation(self):
        with pytest.raises(ValueError, match="serve role"):
            GenerateService("tiny", serve_role="sideways")
        with pytest.raises(ValueError, match="continuous"):
            GenerateService("tiny", engine="coalesce", serve_role="decode")
        with pytest.raises(ValueError, match="kv.transfer|transfer"):
            GenerateService(
                "tiny", engine="continuous", serve_role="prefill"
            )
