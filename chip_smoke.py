#!/usr/bin/env python3
"""Bring-up check: the llama3_1b trainer and server, on the chip, through
the entry points a user calls.

    python3 chip_smoke.py              # on a machine with a TPU
    python3 chip_smoke.py --cpu-tiny   # the same control flow in a sandbox

Legs, each a job launched through the launcher, one after another so that
one process owns the chip at a time:

  a  trainer, one chip, default kernels    ``tpx run -s local dist.spmd
     --tpu v5litepod-1 -m torchx_tpu.examples.train_llama -- --config
     llama3_1b --mesh fsdp=-1 --batch 2 --seq 2048 --steps 8``
  b  the same command with ``--steps 2``   (compile-cache hit)
  c  trainer with ``--kernels pallas``     (Mosaic; step-1 loss agrees with a)
  d  server ``serve.generate_server``      (requests, prefix hit, a slot
     joining and leaving mid-decode, SIGTERM drain, exit 0)
  e  with four or more chips: the trainer on ``fsdp=4`` in one process, and
     four one-chip server replicas

This process never imports jax. Every leg asserts where it ran; a failed
assertion, a non-zero child exit or a timeout fails the script. The last
line of stdout is the result, ``{"ok": true, "device": {...}}``, with the
device as the jobs' own jax reported it. ``--cpu-tiny`` (tiny config,
``JAX_PLATFORMS=cpu``, ``--kernels interpret``, simulated chips) is the only
place the platform assertion is relaxed, and says ``platform=cpu`` on every
line it prints.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(ROOT, ".chip_smoke")
MAX_NEW = 32
PORT = 18471


class Mode:
    """What differs between the chip run and ``--cpu-tiny``."""

    def __init__(self, cpu_tiny: bool) -> None:
        self.cpu_tiny = cpu_tiny
        self.platform = "cpu" if cpu_tiny else "tpu"
        self.config = "tiny" if cpu_tiny else "llama3_1b"
        self.vocab = 512 if cpu_tiny else 128256
        self.seq = 128 if cpu_tiny else 2048
        self.kernels = "interpret" if cpu_tiny else "pallas"
        # tiny's head_dim 16 / dim 64 fail the kernels' static shape gates,
        # so its legs trace the reference ops; llama3_1b passes every gate
        self.attention = {
            "default": "xla" if cpu_tiny else "splash",
            "kernels": "xla" if cpu_tiny else "fused_flash",
        }
        self.norm_residual = "reference" if cpu_tiny else "fused"
        # prefill walks the key blocks; decode takes the XLA function on both
        # configs (llama3_1b's head_dim 64 fails the decode kernel's gate too)
        self.serve_attention = "paged_walk+paged_xla"
        # prompt + MAX_NEW must fit tiny's max_seq of 128
        self.prompt_lens = (24, 50, 90) if cpu_tiny else (24, 100, 300)
        self.simulate = cpu_tiny
        self.tag = "platform=cpu " if cpu_tiny else ""


def say(mode: Mode, msg: str) -> None:
    print(f"chip_smoke: {mode.tag}{msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def child_env(mode: Mode) -> dict[str, str]:
    """What this process exports for the jobs it starts: the checkout on
    PYTHONPATH and every state directory under the scratch dir, so a run
    reads nothing another run left in ``~``."""
    env = {
        "PYTHONPATH": os.pathsep.join(
            p for p in (ROOT, os.environ.get("PYTHONPATH", "")) if p
        ),
        "HOME": os.path.join(SCRATCH, "home"),
    }
    for var, sub in (
        ("TPX_OBS_DIR", "obs"),
        ("TPX_SUPERVISOR_DIR", "supervisor"),
        ("TPX_TUNE_DIR", "tune"),
        ("TPX_CONTROL_DIR", "control"),
    ):
        env[var] = os.path.join(SCRATCH, sub)
    if mode.cpu_tiny:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def cache_dir() -> str:
    """Where the jobs keep their compile cache (parallel/xla_cache.py)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache"
    )


def cache_entries() -> int:
    try:
        return len(os.listdir(cache_dir()))
    except FileNotFoundError:
        return 0


def scheduler_cfg(mode: Mode, leg: str) -> dict[str, object]:
    return {
        "log_dir": os.path.join(SCRATCH, "logs", leg),
        "tpu_simulate": mode.simulate,
    }


def replica_file(cfg: dict, role: str, replica: int, name: str) -> str:
    """``<log_dir>/<app_id>/<role>/<replica>/<name>`` of the leg's one app."""
    (app_id,) = os.listdir(str(cfg["log_dir"]))
    return os.path.join(str(cfg["log_dir"]), app_id, role, str(replica), name)


def tail(path: str, n: int = 25) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<{e}>"


# -- trainer legs -----------------------------------------------------------


def run_trainer(
    mode: Mode, leg: str, tpu: str, mesh: str, batch: int, steps: int,
    kernels: str | None = None, timeout: float = 600.0,
) -> dict:
    """Launch the trainer the way the README does and return its ``final:``
    results plus the per-step losses it logged."""
    cfg = scheduler_cfg(mode, leg)
    cmd = [
        sys.executable, "-m", "torchx_tpu.cli.main", "run", "-s", "local",
        "-cfg", ",".join(f"{k}={v}" for k, v in cfg.items()),
        "dist.spmd", "--tpu", tpu, "-m", "torchx_tpu.examples.train_llama",
        "--", "--config", mode.config, "--mesh", mesh, "--batch", str(batch),
        "--seq", str(mode.seq), "--steps", str(steps),
    ]
    if kernels:
        cmd += ["--kernels", kernels]
    say(mode, f"leg {leg}: {' '.join(cmd[1:])}")
    # own session: on a timeout the CLI gets SIGTERM (its handler kills the
    # replicas it started), then the whole group is killed
    proc = subprocess.Popen(
        cmd, cwd=ROOT, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            proc.wait(15)
        except subprocess.TimeoutExpired:
            pass
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
        raise AssertionError(f"leg {leg}: timed out after {timeout:.0f}s")
    if proc.returncode != 0:
        print(out[-4000:], flush=True)
        try:
            print(tail(replica_file(cfg, "spmd", 0, "stderr.log")), flush=True)
        except (OSError, ValueError):
            pass
        raise AssertionError(f"leg {leg}: tpx run exited {proc.returncode}")
    stdout = open(replica_file(cfg, "spmd", 0, "stdout.log")).read()
    finals = [l for l in stdout.splitlines() if l.startswith("final: ")]
    check(len(finals) == 1, f"leg {leg}: no 'final:' line in replica stdout")
    res = ast.literal_eval(finals[0][len("final: "):])
    res["losses"] = {
        int(m.group(1)): float(m.group(2))
        for m in re.finditer(r"^step (\d+) loss=([0-9.eE+-]+|nan|inf)", stdout, re.M)
    }
    return res


def check_trainer(
    mode: Mode, leg: str, res: dict, devices: int, attention: str, kernels: str,
    falls: bool = True,
) -> None:
    say(
        mode,
        f"leg {leg}: platform={res['platform']} device_kind={res['device_kind']!r}"
        f" device_count={res['device_count']} attention={res['attention']}"
        f" kernels={res['kernels']} norm_residual={res['norm_residual'] or '-'}"
        f" loss {res['losses'].get(1)} -> {res['loss']:.4f}"
        f" step_time_s={res.get('step_time_s', float('nan')):.4f}"
        f" tokens_per_sec_per_chip={res['tokens_per_sec_per_chip']:.0f}"
        f" compile_s={res['launch_breakdown']['compile']:.1f}"
        f" init_state_s={res['launch_breakdown']['init_state']:.1f}"
        f" launch_to_first_step_s={res['launch_to_first_step_s']:.1f}",
    )
    check(res["platform"] == mode.platform, f"leg {leg}: ran on {res['platform']}")
    check(
        res["device_count"] == devices,
        f"leg {leg}: {res['device_count']} devices, expected {devices}",
    )
    check(res["attention"] == attention, f"leg {leg}: attention {res['attention']!r}")
    check(res["kernels"] == kernels, f"leg {leg}: kernels {res['kernels']!r}")
    losses = [res["losses"].get(1), res["loss"]]
    check(
        all(l is not None and l == l and abs(l) != float("inf") for l in losses),
        f"leg {leg}: loss not finite: {losses}",
    )
    if falls:  # not leg b: its second step still has a warm-up lr of ~0
        check(
            res["loss"] < res["losses"][1],
            f"leg {leg}: loss did not fall: {res['losses'][1]} -> {res['loss']}",
        )


# -- server legs ------------------------------------------------------------


def http_json(url: str, body: dict | None = None, timeout: float = 600.0):
    """-> (status, parsed JSON body); a 4xx/5xx is a status, not a raise."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def generate(base: str, prompt: list[int]) -> tuple[list[int], dict]:
    status, reply = http_json(
        f"{base}/v1/generate",
        {"tokens": [prompt], "max_new_tokens": MAX_NEW, "temperature": 0.0},
    )
    check(status == 200, f"/v1/generate -> {status} {reply}")
    return reply["tokens"][0][len(prompt):], reply["timing"]


def metric(base: str, name: str) -> float:
    with urllib.request.urlopen(f"{base}/metricz", timeout=30) as r:
        text = r.read().decode()
    return sum(
        float(l.rsplit(" ", 1)[1])
        for l in text.splitlines()
        if l.startswith(name) and not l.startswith("#")
    )


def wait_healthy(base: str, runner, handle: str, timeout: float) -> dict:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            status, health = http_json(f"{base}/healthz", timeout=5)
            if status == 200:
                return health
        except (urllib.error.URLError, OSError, ValueError):
            pass
        st = runner.status(handle, fresh=True)
        check(
            st is not None and not st.is_terminal(),
            f"server {handle} ended before it was healthy: {st}",
        )
        time.sleep(1.0)
    raise AssertionError(f"{base} not healthy after {timeout:.0f}s")


def server_pids(cfg: dict) -> list[int]:
    """The server processes: the child of each replica's ``sh`` wrapper,
    whose pid the scheduler's state file records."""
    (app_id,) = os.listdir(str(cfg["log_dir"]))
    with open(os.path.join(str(cfg["log_dir"]), app_id, ".tpx_state.json")) as f:
        state = json.load(f)
    wrappers = {replica["pid"] for replica in state["roles"]["server"]}
    pids = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                # "pid (comm) state ppid ...": comm may hold spaces
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue  # exited while we looked
        if ppid in wrappers:
            pids.append(int(entry))
    check(len(pids) == len(wrappers), f"server processes {pids} for {wrappers}")
    return pids


def check_tokens(mode: Mode, what: str, toks: list[int]) -> None:
    check(len(toks) == MAX_NEW, f"{what}: {len(toks)} new tokens, want {MAX_NEW}")
    check(
        all(isinstance(t, int) and 0 <= t < mode.vocab for t in toks),
        f"{what}: token outside the vocabulary: {toks}",
    )


def check_server_device(mode: Mode, leg: str, health: dict) -> None:
    say(
        mode,
        f"leg {leg}: platform={health['platform']}"
        f" device_kind={health['device_kind']!r}"
        f" device_count={health['device_count']}"
        f" visible_chips={health['visible_chips']}",
    )
    check(health["platform"] == mode.platform, f"leg {leg}: ran on {health['platform']}")
    check(health["device_count"] == 1, f"leg {leg}: {health['device_count']} devices")


def drain_and_wait(mode: Mode, leg: str, runner, handle: str, cfg: dict) -> None:
    """SIGTERM the server processes (what a preemption notice sends); each
    drains and exits 0, which the launcher reports as SUCCEEDED."""
    for pid in server_pids(cfg):
        os.kill(pid, signal.SIGTERM)
    st = runner.wait(handle, wait_interval=1, timeout=120)
    check(st is not None, f"leg {leg}: the scheduler lost {handle}")
    check(
        st.state.name == "SUCCEEDED",
        f"leg {leg}: server ended {st.state.name} after SIGTERM, want exit 0",
    )
    say(mode, f"leg {leg}: drained on SIGTERM, exit 0")


def stop_app(runner, handle: str) -> None:
    """Last resort on a failed leg: nothing this script started survives."""
    st = runner.status(handle, fresh=True)
    if st is not None and not st.is_terminal():
        runner.cancel(handle)


def leg_server(mode: Mode, runner) -> dict:
    leg = "d"
    cfg = scheduler_cfg(mode, leg)
    args = ["--config", mode.config, "--tpu", "v5litepod-1", "--port", str(PORT)]
    say(mode, f"leg {leg}: serve.generate_server {' '.join(args)}")
    t0 = time.monotonic()
    handle = runner.run_component("serve.generate_server", args, "local", cfg)
    base = f"http://127.0.0.1:{PORT}"
    try:
        health = wait_healthy(base, runner, handle, timeout=600)
        say(mode, f"leg {leg}: healthy after {time.monotonic() - t0:.1f}s")
        check_server_device(mode, leg, health)

        rng = random.Random(0)
        short, mid, long_ = (
            [rng.randrange(1, mode.vocab) for _ in range(n)]
            for n in mode.prompt_lens
        )

        first, timing = generate(base, short)
        check_tokens(mode, "first request", first)
        say(mode, f"leg {leg}: {len(short)}-token prompt cold ttft_ms={timing['ttft_ms']}")
        hits = metric(base, "tpx_serve_prefix_hits_total")
        again, timing = generate(base, short)
        check(again == first, f"repeated prompt answered differently:\n{first}\n{again}")
        check(
            metric(base, "tpx_serve_prefix_hits_total") > hits,
            "repeated prompt did not hit the prefix cache",
        )
        say(mode, f"leg {leg}: repeat identical, prefix hit, ttft_ms={timing['ttft_ms']}")

        # each longer prompt alone, cold and then again. The second time
        # its prefix comes from the cache and only the tail is prefilled, in
        # a narrower bucket — another XLA program, whose bf16 rounding need
        # not match the cold one's; with random weights the logits are near
        # ties, so the two may decode apart. That is reported, not failed.
        solo = {}
        for name, prompt in (("mid", mid), ("long", long_)):
            cold, timing = generate(base, prompt)
            check_tokens(mode, f"{len(prompt)}-token prompt", cold)
            say(
                mode,
                f"leg {leg}: {len(prompt)}-token prompt cold"
                f" ttft_ms={timing['ttft_ms']} total_ms={timing['total_ms']}",
            )
            solo[name], timing = generate(base, prompt)
            check_tokens(mode, f"{len(prompt)}-token prompt again", solo[name])
            same = sum(a == b for a, b in zip(cold, solo[name]))
            say(
                mode,
                f"leg {leg}: {len(prompt)}-token prompt from the prefix cache"
                f" ttft_ms={timing['ttft_ms']} total_ms={timing['total_ms']}"
                f" tokens equal to the cold run: {same}/{MAX_NEW}",
            )

        # two requests in flight at once: the second joins while the first
        # is mid-decode and the first leaves while the second still decodes.
        # Both take the programs their cached solo runs took, so beside
        # each other they must decode exactly what they decoded alone.
        _, before = http_json(f"{base}/healthz")
        results: dict[str, object] = {}

        def fire(name: str, prompt: list[int]) -> None:
            try:
                results[name] = generate(base, prompt)[0]
            except BaseException as e:  # noqa: BLE001 - re-raised by the caller
                results[name] = e

        t_mid = threading.Thread(target=fire, args=("mid", mid))
        t_long = threading.Thread(target=fire, args=("long", long_))
        t_mid.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            _, now = http_json(f"{base}/healthz")
            if now["tokens_out"] - before["tokens_out"] >= 4:
                break
            time.sleep(0.005)
        t_long.start()
        t_mid.join(300)
        t_long.join(300)
        check(not t_mid.is_alive() and not t_long.is_alive(), "concurrent requests hung")
        for name in ("mid", "long"):
            if isinstance(results[name], BaseException):
                raise results[name]
            check(
                results[name] == solo[name],
                f"{name} prompt decoded differently beside another request:"
                f"\n{solo[name]}\n{results[name]}",
            )
        _, after = http_json(f"{base}/healthz")
        steps = after["steps"] - before["steps"]
        # each request takes MAX_NEW - 1 decode steps after its prefill:
        # fully serial is 2x that, in lockstep 1x; in between, they overlapped
        check(
            MAX_NEW - 1 < steps < 2 * (MAX_NEW - 1),
            f"concurrent pair took {steps} decode steps: no slot joined"
            " and left mid-decode",
        )
        say(
            mode,
            f"leg {leg}: concurrent pair token-identical to solo,"
            f" {steps} shared decode steps, attention={after['attention']}"
            f" requests_done={after['requests_done']} failed={after['failed']}",
        )
        check(after["attention"] == mode.serve_attention, f"attention {after['attention']!r}")
        check(after["failed"] is None, f"engine died: {after['failed']}")
        drain_and_wait(mode, leg, runner, handle, cfg)
        return health
    except BaseException:
        print(tail(replica_file(cfg, "server", 0, "stderr.log")), flush=True)
        stop_app(runner, handle)
        raise


def leg_four_servers(mode: Mode, runner) -> None:
    leg = "e2"
    cfg = scheduler_cfg(mode, leg)
    port = PORT + 10
    args = [
        "--config", mode.config, "--tpu", "v5litepod-1", "--num_replicas", "4",
        "--port_stride", "1", "--port", str(port),
    ]
    say(mode, f"leg {leg}: serve.generate_server {' '.join(args)}")
    handle = runner.run_component("serve.generate_server", args, "local", cfg)
    try:
        rng = random.Random(0)
        prompt = [rng.randrange(1, mode.vocab) for _ in range(mode.prompt_lens[0])]
        answers, chips = [], []
        for i in range(4):
            base = f"http://127.0.0.1:{port + i}"
            health = wait_healthy(base, runner, handle, timeout=600)
            check_server_device(mode, f"{leg}[{i}]", health)
            chips.append(health["visible_chips"])
            toks, _ = generate(base, prompt)
            check_tokens(mode, f"replica {i}", toks)
            answers.append(toks)
        if not mode.cpu_tiny:  # simulated replicas have no chips to tell apart
            check(len(set(chips)) == 4, f"replicas share chips: {chips}")
        check(
            all(a == answers[0] for a in answers),
            f"replicas with the same weights answered differently: {answers}",
        )
        say(mode, f"leg {leg}: four replicas on chips {chips}, same answer from each")
        drain_and_wait(mode, leg, runner, handle, cfg)
    except BaseException:
        for i in range(4):
            print(tail(replica_file(cfg, "server", i, "stderr.log"), 12), flush=True)
        stop_app(runner, handle)
        raise


# -- main -------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--cpu-tiny", action="store_true",
        help="tiny config on the CPU with simulated chips: debugs this script,"
        " proves nothing about the device",
    )
    mode = Mode(parser.parse_args().cpu_tiny)

    # the program under test is the checkout this script sits in
    check(
        os.path.isfile(os.path.join(ROOT, "torchx_tpu", "__init__.py")),
        f"no torchx_tpu package beside {__file__}",
    )
    sys.path.insert(0, ROOT)
    from torchx_tpu.runner import get_runner
    from torchx_tpu.schedulers.local_scheduler import local_tpu_chip_count

    shutil.rmtree(SCRATCH, ignore_errors=True)
    os.makedirs(os.path.join(SCRATCH, "home"))
    os.environ.update(child_env(mode))
    chips = 4 if mode.cpu_tiny else local_tpu_chip_count()
    say(mode, f"{chips} chip(s) on this host; compile cache at {cache_dir()}")

    legs_run, legs_skipped = [], {}
    entries_before = cache_entries()
    a = run_trainer(mode, "a", "v5litepod-1", "fsdp=-1", batch=2, steps=8)
    check_trainer(mode, "a", a, 1, mode.attention["default"], "reference")
    legs_run.append("a")
    entries_after = cache_entries()
    # (tiny's sub-second compiles are under the persistence threshold)
    check(
        entries_after > 0 or mode.cpu_tiny,
        f"leg a left nothing in {cache_dir()}",
    )

    b = run_trainer(mode, "b", "v5litepod-1", "fsdp=-1", batch=2, steps=2)
    check_trainer(mode, "b", b, 1, mode.attention["default"], "reference", falls=False)
    cold, warm = (r["launch_breakdown"]["compile"] for r in (a, b))
    say(
        mode,
        f"leg b: compile {cold:.1f}s cold -> {warm:.1f}s warm; cache entries"
        f" {entries_before} before leg a, {entries_after} after",
    )
    # a cache that was warm before leg a (the machine came with one) has
    # nothing to show here, nor has tiny
    if entries_before == 0 and not mode.cpu_tiny:
        check(warm < 0.5 * cold, f"warm compile {warm:.1f}s vs cold {cold:.1f}s")
    legs_run.append("b")

    c = run_trainer(
        mode, "c", "v5litepod-1", "fsdp=-1", batch=2, steps=4, kernels=mode.kernels
    )
    check_trainer(mode, "c", c, 1, mode.attention["kernels"], mode.kernels)
    check(
        c["norm_residual"] == mode.norm_residual,
        f"leg c: norm_residual {c['norm_residual']!r}",
    )
    # same seed, same batch: two attention implementations, one loss
    check(
        abs(c["losses"][1] - a["losses"][1]) <= 2e-2,
        f"leg c: step-1 loss {c['losses'][1]} vs leg a {a['losses'][1]}",
    )
    legs_run.append("c")

    runner = get_runner()
    try:
        d = leg_server(mode, runner)
        legs_run.append("d")
        check(
            d["device_kind"] == a["device_kind"],
            f"trainer ran on {a['device_kind']!r}, server on {d['device_kind']!r}",
        )
        device = {"platform": a["platform"], "kind": a["device_kind"], "count": 1}

        if chips >= 4:
            e = run_trainer(mode, "e1", "v5litepod-4", "fsdp=4", batch=8, steps=8)
            check_trainer(mode, "e1", e, 4, mode.attention["default"], "reference")
            shards = e["largest_param_shards"]
            say(mode, f"leg e1: largest parameter {shards}")
            check(
                shards["devices"] == 4 and abs(shards["shard_frac"] - 0.25) < 1e-6,
                f"leg e1: parameter not spread over four devices: {shards}",
            )
            legs_run.append("e1")
            leg_four_servers(mode, runner)
            legs_run.append("e2")
            device["count"] = e["device_count"]
        else:
            legs_skipped["e"] = f"needs four chips, this host has {chips}"
    finally:
        runner.close()

    say(mode, f"legs run: {' '.join(legs_run)}; not run: {legs_skipped or 'none'}")
    check("jax" not in sys.modules, "the smoke's own process imported jax")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
