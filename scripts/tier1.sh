#!/usr/bin/env bash
# Tier-1 verify gate: the exact command from ROADMAP.md, wrapped so every
# contributor (and CI) runs the same thing. Excludes tests marked `slow`
# (registered in pyproject.toml); prints DOTS_PASSED and exits with
# pytest's status.
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)

# Observability smoke: run a local app under tracing and assert the JSONL
# trace is written, parseable, and renderable by `tpx trace`.
obs_dir=$(mktemp -d /tmp/tpx_obs_smoke.XXXXXX)
if timeout -k 10 120 env JAX_PLATFORMS=cpu TPX_OBS_DIR="$obs_dir" \
    python - <<'EOF'
import glob, json, os, sys
from torchx_tpu.cli.main import main
from torchx_tpu.obs import timeline

main(["run", "-s", "local", "--wait", "utils.echo", "--msg", "obs-smoke"])
paths = glob.glob(os.path.join(os.environ["TPX_OBS_DIR"], "*", "trace.jsonl"))
assert paths, "no trace.jsonl written"
records = [json.loads(l) for p in paths for l in open(p) if l.strip()]
spans = [r for r in records if timeline.is_span(r)]
assert any(s["name"] == "runner.run_component" for s in spans), spans
app_ids = {s["attrs"]["app_id"] for s in spans if "app_id" in s.get("attrs", {})}
assert app_ids, "no span carries an app_id"
main(["trace", app_ids.pop(), "--metrics"])
EOF
then echo "OBS_SMOKE=ok"; else echo "OBS_SMOKE=FAILED"; rc=1; fi
rm -rf "$obs_dir"

# Lint smoke: `tpx lint` must pass a known-good AppDef (exit 0), refuse a
# deliberately broken one (exit 1, >= 3 distinct codes), and emit stable
# machine-readable --json.
if timeout -k 10 120 env JAX_PLATFORMS=cpu python - <<'EOF'
import json, subprocess, sys, tempfile
from torchx_tpu.specs.api import AppDef, BindMount, Resource, Role, TpuSlice
from torchx_tpu.specs.serialize import appdef_to_dict

def dump(app):
    f = tempfile.NamedTemporaryFile("w", suffix=".json", delete=False)
    json.dump(appdef_to_dict(app), f)
    f.close()
    return f.name

good = dump(AppDef(name="good", roles=[Role(name="echo", image="/", entrypoint="echo", args=["hi"])]))
bad = dump(AppDef(name="bad", roles=[Role(
    name="trainer", image="img", entrypoint="python",
    env={"TPX_REPLICA_ID": "0"},
    mounts=[BindMount(src_path="/a", dst_path="/x"), BindMount(src_path="/b", dst_path="/x")],
    resource=Resource(tpu=TpuSlice("v5e", 16, "2x2x4")))]))

tpx = [sys.executable, "-m", "torchx_tpu.cli.main", "lint"]
r = subprocess.run(tpx + ["-s", "local", good], capture_output=True, text=True)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
r = subprocess.run(tpx + ["-s", "tpu_vm", bad], capture_output=True, text=True)
assert r.returncode == 1, (r.returncode, r.stdout, r.stderr)
r = subprocess.run(tpx + ["-s", "tpu_vm", "--json", bad], capture_output=True, text=True)
assert r.returncode == 1, (r.returncode, r.stdout, r.stderr)
doc = json.loads(r.stdout)
assert doc["version"] == 1 and doc["summary"]["error"] >= 3, doc
assert len({d["code"] for d in doc["diagnostics"]}) >= 3, doc
EOF
then echo "LINT_SMOKE=ok"; else echo "LINT_SMOKE=FAILED"; rc=1; fi

# Self-lint: the legacy entry point (now a shim over the selfcheck pass
# engine) keeps its contract — jax-free layers, scheduler subprocess
# seam, sim-hosted wall-clock discipline; "SELF_LINT: clean" + exit 0.
if timeout -k 10 60 python scripts/lint_internal.py
then echo "SELF_LINT=ok"; else echo "SELF_LINT=FAILED"; rc=1; fi

# Selfcheck: the whole-program invariant analyzer must run clean (zero
# unsuppressed TPX9xx findings against the checked-in triaged baseline),
# its --json report must be stable/parseable, and `tpx selfcheck --help`
# must never import jax (the analyzer rides the CLI fast path).
if timeout -k 10 120 env JAX_PLATFORMS=cpu python - <<'EOF'
import json, subprocess, sys

tpx = [sys.executable, "-m", "torchx_tpu.cli.main", "selfcheck"]
r = subprocess.run(tpx, capture_output=True, text=True, timeout=90)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
r = subprocess.run(tpx + ["--json"], capture_output=True, text=True, timeout=90)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
doc = json.loads(r.stdout)
assert doc["version"] == 1 and doc["diagnostics"] == [], doc
assert doc["suppressed"] >= 0, doc

# the selfcheck verb rides the lazy dispatcher: help never imports jax
probe = (
    "import sys\n"
    "from torchx_tpu.cli.main import main\n"
    "try: main(['selfcheck', '--help'])\n"
    "except SystemExit: pass\n"
    "assert 'jax' not in sys.modules, 'tpx selfcheck --help imported jax'\n"
)
r = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                   text=True, timeout=60)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
EOF
then echo "SELFCHECK=ok"; else echo "SELFCHECK=FAILED"; rc=1; fi

# Explain smoke: `tpx explain` on a builtin component must statically
# report the MoE-mesh resharding boundary (the involuntary-full-remat
# shape behind the MULTICHIP r03/r04 warning -> TPX700 ERROR, exit 1) and
# an HBM fit verdict — without the analyzer importing jax.
if timeout -k 10 120 env JAX_PLATFORMS=cpu python - <<'EOF'
import json, subprocess, sys

tpx = [sys.executable, "-m", "torchx_tpu.cli.main", "explain"]
argv = ["dist.spmd", "-j", "1x8", "-m", "my.custom_trainer", "--",
        "--config", "moe_tiny", "--mesh", "ep=2,fsdp=-1",
        "--batch", "8", "--seq", "128"]
r = subprocess.run(tpx + ["--json"] + argv, capture_output=True, text=True)
assert r.returncode == 1, (r.returncode, r.stdout, r.stderr)
doc = json.loads(r.stdout)
assert doc["version"] == 1, doc
role = doc["roles"][0]
kinds = {b["kind"] for b in role["sharding"]["boundaries"]}
assert "full_remat" in kinds, role["sharding"]
assert role["hbm"]["verdict"] in ("fits", "exceeds"), role["hbm"]
codes = {d["code"] for d in role["diagnostics"]}
assert "TPX700" in codes, codes

# same mesh, stock trainer: propagation proves it safe (exit 0)
r = subprocess.run(
    tpx + ["dist.spmd", "-j", "1x8", "-m", "torchx_tpu.examples.train_llama",
           "--", "--config", "moe_tiny", "--mesh", "ep=2,fsdp=-1"],
    capture_output=True, text=True)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
assert "FITS" in r.stdout or "EXCEEDS" in r.stdout, r.stdout

# the analyzer itself must never touch jax
probe = (
    "import sys\n"
    "from torchx_tpu.cli.main import main\n"
    "try: main(['explain', 'dist.spmd', '-j', '1x8', '-m', 'x.y', '--',\n"
    "           '--config', 'moe_tiny', '--mesh', 'ep=2,fsdp=-1'])\n"
    "except SystemExit: pass\n"
    "assert 'jax' not in sys.modules, 'tpx explain imported jax'\n"
)
r = subprocess.run([sys.executable, "-c", probe], capture_output=True, text=True)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
EOF
then echo "EXPLAIN_SMOKE=ok"; else echo "EXPLAIN_SMOKE=FAILED"; rc=1; fi

# Resilience smoke: a fault-injected local run must succeed anyway —
# the injected transient describe failures are absorbed by in-seam
# retries (retry metric non-zero), never surfacing to the user.
res_dir=$(mktemp -d /tmp/tpx_res_smoke.XXXXXX)
if timeout -k 10 120 env JAX_PLATFORMS=cpu TPX_OBS_DIR="$res_dir" \
    TPX_FAULT_PLAN='[{"backend": "local", "op": "describe", "nth": 1, "times": 2, "mode": "transient", "message": "injected 503"}]' \
    python - <<'EOF'
from torchx_tpu.cli.main import main
from torchx_tpu.obs import metrics as obs_metrics

main(["run", "-s", "local", "--wait", "utils.echo", "--msg", "res-smoke"])
retries = obs_metrics.CONTROL_PLANE_RETRIES.value(
    backend="local", op="describe", kind="UNAVAILABLE"
)
assert retries >= 2, f"expected >= 2 in-seam retries, saw {retries}"
EOF
then echo "RESILIENCE_SMOKE=ok"; else echo "RESILIENCE_SMOKE=FAILED"; rc=1; fi
rm -rf "$res_dir"

# Remat smoke: the MoE/expert-parallel dryrun leg (the r03 gather shape
# that used to trip GSPMD's replicate+reslice fallback) must compile with
# zero involuntary-full-rematerialization warnings and without the GSPMD
# sharding-propagation deprecation warning (Shardy is the partitioner).
remat_log=$(mktemp /tmp/tpx_remat_smoke.XXXXXX)
if timeout -k 10 420 env _TPX_DRYRUN_LEGS=moe \
    python -c 'import __graft_entry__ as g; g.dryrun_multichip(8)' \
    >"$remat_log" 2>&1 \
  && ! grep -q "Involuntary full rematerialization" "$remat_log" \
  && ! grep -q "GSPMD sharding propagation is going to be deprecated" "$remat_log"
then echo "REMAT_SMOKE=ok"; else echo "REMAT_SMOKE=FAILED"; rc=1; cat "$remat_log"; fi
rm -f "$remat_log"

# CLI fast-path smoke: the lazy dispatcher must keep `tpx --help` and
# `tpx list` off the heavy import path — jax (and the run-path command
# modules) must never enter sys.modules, and help must render inside a
# tight wall budget (the whole point of the warm-launch fast path).
if timeout -k 10 20 env JAX_PLATFORMS=cpu python - <<'EOF'
import sys
from torchx_tpu.cli.main import main

try:
    main(["--help"])
except SystemExit:
    pass
forbidden = ["jax", "numpy", "torchx_tpu.cli.cmd_run", "torchx_tpu.cli.cmd_lint"]
leaked = [m for m in forbidden if m in sys.modules]
assert not leaked, f"tpx --help imported {leaked}"

try:
    main(["list", "-s", "local"])
except SystemExit:
    pass
leaked = [m for m in ("jax", "torchx_tpu.cli.cmd_run") if m in sys.modules]
assert not leaked, f"tpx list imported {leaked}"
EOF
then echo "CLI_SMOKE=ok"; else echo "CLI_SMOKE=FAILED"; rc=1; fi

# Gang smoke: a local-scheduler preemption drill supervised with elastic
# reshape — the first attempt is "preempted" (drill exit code), and the
# resubmitted attempt must land on a shrunken-mesh dryrun ($TPX_MESH),
# asserted from the durable attempt ledger.
gang_dir=$(mktemp -d /tmp/tpx_gang_smoke.XXXXXX)
if timeout -k 10 120 env JAX_PLATFORMS=cpu \
    TPX_OBS_DIR="$gang_dir/obs" TPX_SUPERVISOR_DIR="$gang_dir/sup" \
    python - <<'EOF'
import os
from torchx_tpu.runner.api import Runner
from torchx_tpu.schedulers.local_scheduler import LocalScheduler
from torchx_tpu.specs.api import AppDef, Role
from torchx_tpu.supervisor import SupervisorPolicy
from torchx_tpu.supervisor.ledger import AttemptLedger

# exits with the drill code until the supervisor resubmits with a
# degraded $TPX_MESH; the reshaped attempt then succeeds
script = 'if [ -n "$TPX_MESH" ]; then exit 0; fi; exit 67'
app = AppDef(name="gang-drill", roles=[Role(
    name="w", image="", entrypoint="sh", args=["-c", script],
    env={"TPX_SIMULATE_PREEMPTION_EXIT": "67"},
)])
sched = LocalScheduler(session_name="gang-smoke", cache_size=10)
runner = Runner("gang-smoke", {"local": lambda session_name, **kw: sched})
with runner:
    info = runner.dryrun(
        app, "local", cfg={"log_dir": os.environ["TPX_OBS_DIR"] + "/logs"}
    )
    result = runner.supervise(info, SupervisorPolicy(
        max_preemptions=2, backoff_seconds=0.01, jitter=0.0,
        poll_interval=0.05, elastic_reshape=True, mesh="fsdp=-1",
        devices_per_replica=8,
    ), session="gang-smoke")
assert result.succeeded, result.status
assert result.attempts == 2, result.attempts
submitted = [
    e for e in AttemptLedger("gang-smoke").entries()
    if e.get("transition") == "submitted"
]
assert len(submitted) == 2, submitted
assert submitted[0].get("mesh") is None, submitted[0]
assert submitted[1]["mesh"] == "pp=1,dp=1,fsdp=4,ep=1,tp=1,sp=1", submitted[1]
EOF
then echo "GANG_SMOKE=ok"; else echo "GANG_SMOKE=FAILED"; rc=1; fi
rm -rf "$gang_dir"

# Control smoke: boot the `tpx control` daemon, submit + wait through the
# proxying CLI (TPX_CONTROL_ADDR), assert the journaled job reached
# terminal and the daemon's /metricz exports control-plane ops, and keep
# `tpx --help` jax-free with the control command registered.
ctl_dir=$(mktemp -d /tmp/tpx_ctl_smoke.XXXXXX)
if timeout -k 10 180 env JAX_PLATFORMS=cpu TPX_OBS_DIR="$ctl_dir/obs" \
    TPX_CONTROL_DIR="$ctl_dir/control" TPX_WATCH_INTERVAL=0.1 \
    python - <<'EOF'
import json, os, subprocess, sys, time, urllib.request

ctl = os.environ["TPX_CONTROL_DIR"]
daemon = subprocess.Popen(
    [sys.executable, "-m", "torchx_tpu.cli.main", "control"],
    stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
)
try:
    discovery = os.path.join(ctl, "control.json")
    deadline = time.monotonic() + 60
    while not os.path.exists(discovery):
        assert daemon.poll() is None, daemon.stdout.read()
        assert time.monotonic() < deadline, "daemon never wrote discovery"
        time.sleep(0.1)
    doc = json.load(open(discovery))
    addr = doc["addr"]

    env = dict(os.environ, TPX_CONTROL_ADDR=addr)
    tpx = [sys.executable, "-m", "torchx_tpu.cli.main"]
    r = subprocess.run(
        tpx + ["run", "-s", "local", "--wait", "utils.echo", "--msg", "ctl-smoke"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    handle = r.stdout.splitlines()[0].strip()
    assert handle.startswith("local://"), r.stdout

    r = subprocess.run(
        tpx + ["status", handle], capture_output=True, text=True, env=env,
        timeout=60,
    )
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    assert "SUCCEEDED" in r.stdout, r.stdout

    with urllib.request.urlopen(f"{addr}/metricz", timeout=10) as resp:
        metrics = resp.read().decode()
    assert "tpx_control_requests_total" in metrics, metrics[:2000]
    assert 'op="submit"' in metrics and 'op="status"' in metrics, metrics[:2000]
    assert "tpx_watch_events_total" in metrics, metrics[:2000]
finally:
    daemon.terminate()
    daemon.wait(timeout=10)

# the proxying layer must not drag the control (or jax) modules into the
# help fast path — only the lazy dispatcher's table may know about them
r = subprocess.run(
    [sys.executable, "-c", (
        "import sys\n"
        "from torchx_tpu.cli.main import main\n"
        "try: main(['--help'])\n"
        "except SystemExit: pass\n"
        "leaked = [m for m in ('jax', 'numpy', 'torchx_tpu.control',"
        " 'torchx_tpu.cli.cmd_control') if m in sys.modules]\n"
        "assert not leaked, f'tpx --help imported {leaked}'\n"
    )],
    capture_output=True, text=True, timeout=60,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
assert "control" in r.stdout, r.stdout
EOF
then echo "CONTROL_SMOKE=ok"; else echo "CONTROL_SMOKE=FAILED"; rc=1; fi
rm -rf "$ctl_dir"

# Serving smoke: boot generate_server on the tiny config (CPU, continuous
# engine, ephemeral port), answer /healthz, decode one /v1/generate, assert
# the continuous-batching occupancy gauge is exported on /metricz, repeat
# the same prompt and assert it hit the radix prefix cache, and check the
# serve-pool CLI's disaggregation flags stay jax-free.
serve_dir=$(mktemp -d /tmp/tpx_serve_smoke.XXXXXX)
if timeout -k 10 300 env JAX_PLATFORMS=cpu TPX_OBS_DIR="$serve_dir" \
    python - <<'EOF'
import json, subprocess, sys, threading, urllib.request
from torchx_tpu.apps.generate_server import serve

ready = threading.Event()
server = serve("tiny", port=0, ready_event=ready, engine="continuous", max_batch=4)
assert ready.wait(120), "server never became ready"
threading.Thread(target=server.serve_forever, daemon=True).start()
base = f"http://127.0.0.1:{server.server_address[1]}"
try:
    with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
        health = json.loads(r.read())
    assert health["status"] == "ok" and health["engine"] == "continuous", health
    assert "occupancy" in health and "queue_depth" in health, health
    assert health["serve_role"] == "unified", health
    req = urllib.request.Request(
        f"{base}/v1/generate",
        data=json.dumps({"tokens": [[1, 2, 3]], "max_new_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        body = json.loads(r.read())
    (seq,) = body["tokens"]
    assert seq[:3] == [1, 2, 3] and len(seq) == 7, body
    # repeated prompt long enough to span a full cache block (> block_size
    # tokens at the default block_size=16): the second pass must hit the
    # radix prefix cache and both must decode identical tokens
    prompt = list(range(1, 21))
    req = urllib.request.Request(
        f"{base}/v1/generate",
        data=json.dumps({"tokens": [prompt], "max_new_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"},
    )
    outs = []
    for _ in range(2):
        with urllib.request.urlopen(req, timeout=120) as r:
            outs.append(json.loads(r.read())["tokens"][0])
    assert outs[0] == outs[1], outs
    with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
        health = json.loads(r.read())
    assert health["prefix_summary"], health
    with urllib.request.urlopen(f"{base}/metricz", timeout=10) as r:
        metrics = r.read().decode()
    assert "tpx_serve_slot_occupancy" in metrics, metrics[:2000]
    assert "tpx_serve_tokens_total" in metrics, metrics[:2000]
    hits = [
        line for line in metrics.splitlines()
        if line.startswith("tpx_serve_prefix_hits_total")
    ]
    assert hits and float(hits[0].split()[-1]) > 0, metrics[:2000]
finally:
    server.shutdown()
    server.service.close()

# the disaggregation flags ride the help fast path: `tpx serve-pool
# --help` must show them without importing jax
r = subprocess.run(
    [sys.executable, "-c", (
        "import sys\n"
        "from torchx_tpu.cli.main import main\n"
        "try: main(['serve-pool', '--help'])\n"
        "except SystemExit: pass\n"
        "assert 'jax' not in sys.modules, 'tpx serve-pool --help imported jax'\n"
    )],
    capture_output=True, text=True, timeout=60,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
for flag in ("--disaggregate", "--kv-transfer", "--prefix-cache-reserve"):
    assert flag in r.stdout, (flag, r.stdout)
EOF
then echo "SERVE_SMOKE=ok"; else echo "SERVE_SMOKE=FAILED"; rc=1; fi
rm -rf "$serve_dir"

# Fleet smoke: boot `tpx control --fleet`, fill the modeled fleet with a
# serve gang, queue a batch then an interactive gang, and assert `tpx
# queue` orders interactive first, /metricz exports the tpx_fleet_*
# gauges, and `tpx --help` stays jax- AND fleet-free.
fleet_dir=$(mktemp -d /tmp/tpx_fleet_smoke.XXXXXX)
if timeout -k 10 180 env JAX_PLATFORMS=cpu TPX_OBS_DIR="$fleet_dir/obs" \
    TPX_CONTROL_DIR="$fleet_dir/control" TPX_WATCH_INTERVAL=0.1 \
    python - <<'EOF'
import json, os, subprocess, sys, time, urllib.request

ctl = os.environ["TPX_CONTROL_DIR"]
daemon = subprocess.Popen(
    [sys.executable, "-m", "torchx_tpu.cli.main", "control",
     "--fleet", "sim:v5e-1x4"],
    stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
)
try:
    discovery = os.path.join(ctl, "control.json")
    deadline = time.monotonic() + 60
    while not os.path.exists(discovery):
        assert daemon.poll() is None, daemon.stdout.read()
        assert time.monotonic() < deadline, "daemon never wrote discovery"
        time.sleep(0.1)
    doc = json.load(open(discovery))
    addr, token = doc["addr"], doc["token"]

    from torchx_tpu.control.client import ControlClient
    client = ControlClient(addr, token)
    log = os.path.join(os.environ["TPX_OBS_DIR"], "logs")
    filler = client.submit_job(
        "utils.sh", ["sleep", "30"], "local", cfg={"log_dir": log},
        priority="serve", replicas=4,
    )
    assert filler.get("handle", "").startswith("local://"), filler
    batch = client.submit_job(
        "utils.sh", ["sleep", "1"], "local", cfg={"log_dir": log},
        priority="batch",
    )
    inter = client.submit_job(
        "utils.sh", ["sleep", "1"], "local", cfg={"log_dir": log},
        priority="interactive",
    )
    assert batch.get("queued") and inter.get("queued"), (batch, inter)

    env = dict(os.environ, TPX_CONTROL_ADDR=addr)
    r = subprocess.run(
        [sys.executable, "-m", "torchx_tpu.cli.main", "queue"],
        capture_output=True, text=True, env=env, timeout=60,
    )
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    assert "queued (2):" in r.stdout, r.stdout
    lines = [l for l in r.stdout.splitlines() if l.strip().startswith("#")]
    assert "interactive" in lines[0] and "batch" in lines[1], r.stdout

    with urllib.request.urlopen(f"{addr}/metricz", timeout=10) as resp:
        metrics = resp.read().decode()
    assert 'tpx_fleet_queue_depth{klass="interactive"} 1' in metrics, metrics[:2000]
    assert 'tpx_fleet_chips{state="free"} 0' in metrics, metrics[:2000]
    assert 'tpx_fleet_placements_total{klass="serve"} 1' in metrics, metrics[:2000]
finally:
    daemon.terminate()
    daemon.wait(timeout=10)

# the queue verb must ride the same lazy dispatcher: no fleet (or jax)
# modules on the help fast path
r = subprocess.run(
    [sys.executable, "-c", (
        "import sys\n"
        "from torchx_tpu.cli.main import main\n"
        "try: main(['--help'])\n"
        "except SystemExit: pass\n"
        "leaked = [m for m in ('jax', 'numpy', 'torchx_tpu.fleet',"
        " 'torchx_tpu.control', 'torchx_tpu.cli.cmd_queue') if m in sys.modules]\n"
        "assert not leaked, f'tpx --help imported {leaked}'\n"
    )],
    capture_output=True, text=True, timeout=60,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
assert "queue" in r.stdout, r.stdout
EOF
then echo "FLEET_SMOKE=ok"; else echo "FLEET_SMOKE=FAILED"; rc=1; fi
rm -rf "$fleet_dir"

# Tune smoke: `tpx tune` over the tiny builtin space on CPU — static
# pruning must kill candidates with a journaled TPX7xx verdict at zero
# device seconds, the winner's plan artifact must be emitted and then
# ACCEPTED by the submit gate (and a drifted config refused, TPX706),
# and `tpx tune --help` must stay jax-free.
tune_dir=$(mktemp -d /tmp/tpx_tune_smoke.XXXXXX)
if timeout -k 10 300 env JAX_PLATFORMS=cpu TPX_TUNE_DIR="$tune_dir" \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'EOF'
import json, os, subprocess, sys

tpx = [sys.executable, "-m", "torchx_tpu.cli.main", "tune"]
r = subprocess.run(
    tpx + ["--space", "tiny-smoke", "--devices", "8", "--top-k", "1",
           "--no-aot", "--json"],
    capture_output=True, text=True, timeout=240,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
doc = json.loads(r.stdout)
report = doc["report"]
assert report["pruned_static"] >= 1, report
assert any(c.startswith("TPX7") for c in report["pruned_by_code"]), report
assert report["device_seconds_pruning"] == 0.0, report
assert report["measured"] >= 1, report
art = doc["artifact"]
assert art and os.path.exists(art), art
assert json.load(open(art))["digest"], art

# the emitted artifact pins the submit gate: the tuned config passes...
from torchx_tpu.analyze import analyze
from torchx_tpu.components import dist

win = doc["winner"]["candidate"]
def app_for(batch, policy):
    return dist.spmd(
        "--config", win["config"], "--mesh", win["mesh_spec"],
        "--batch", str(batch), "--seq", str(win["seq"]),
        "--remat-policy", policy,
        m="torchx_tpu.examples.train_llama", j="1x8",
    )
os.environ["TPX_PLAN_ARTIFACT"] = art
codes = {d.code for d in analyze(app_for(win["batch"], win["remat_policy"])).diagnostics}
assert "TPX706" not in codes and "TPX707" not in codes, codes
# ... and a config that drifted from the tuned plan is refused
codes = {d.code for d in analyze(app_for(win["batch"] * 2, win["remat_policy"])).diagnostics}
assert "TPX706" in codes, codes

# the tune verb rides the lazy dispatcher: help never imports jax
probe = (
    "import sys\n"
    "from torchx_tpu.cli.main import main\n"
    "try: main(['tune', '--help'])\n"
    "except SystemExit: pass\n"
    "assert 'jax' not in sys.modules, 'tpx tune --help imported jax'\n"
)
r = subprocess.run([sys.executable, "-c", probe], capture_output=True, text=True)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
EOF
then echo "TUNE_SMOKE=ok"; else echo "TUNE_SMOKE=FAILED"; rc=1; fi
rm -rf "$tune_dir"

# Top smoke: boot `tpx control` with an SLO spec, render one `tpx top
# --once` frame against it (header + slo line + metrics section), check
# the --json snapshot parses, and keep the verb off the help fast path.
top_dir=$(mktemp -d /tmp/tpx_top_smoke.XXXXXX)
if timeout -k 10 120 env JAX_PLATFORMS=cpu TPX_OBS_DIR="$top_dir/obs" \
    TPX_CONTROL_DIR="$top_dir/control" \
    python - <<'EOF'
import json, os, subprocess, sys, time

ctl = os.environ["TPX_CONTROL_DIR"]
daemon = subprocess.Popen(
    [sys.executable, "-m", "torchx_tpu.cli.main", "control",
     "--slo", "p99-ttft", "--scrape-interval", "0.2"],
    stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
)
try:
    discovery = os.path.join(ctl, "control.json")
    deadline = time.monotonic() + 60
    while not os.path.exists(discovery):
        assert daemon.poll() is None, daemon.stdout.read()
        assert time.monotonic() < deadline, "daemon never wrote discovery"
        time.sleep(0.1)
    addr = json.load(open(discovery))["addr"]
    env = dict(os.environ, TPX_CONTROL_ADDR=addr)
    tpx = [sys.executable, "-m", "torchx_tpu.cli.main", "top"]
    r = subprocess.run(tpx + ["--once"], capture_output=True, text=True,
                       env=env, timeout=60)
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    assert r.stdout.startswith("tpx top —"), r.stdout
    assert "slo:" in r.stdout, r.stdout
    r = subprocess.run(tpx + ["--json"], capture_output=True, text=True,
                       env=env, timeout=60)
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    snap = json.loads(r.stdout)
    assert snap["alerts"]["enabled"] and "p99-ttft" in snap["alerts"]["slos"], snap
finally:
    daemon.terminate()
    daemon.wait(timeout=10)

# the top verb rides the lazy dispatcher: help never imports it (or jax)
r = subprocess.run(
    [sys.executable, "-c", (
        "import sys\n"
        "from torchx_tpu.cli.main import main\n"
        "try: main(['--help'])\n"
        "except SystemExit: pass\n"
        "leaked = [m for m in ('jax', 'torchx_tpu.cli.cmd_top')"
        " if m in sys.modules]\n"
        "assert not leaked, f'tpx --help imported {leaked}'\n"
    )],
    capture_output=True, text=True, timeout=60,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
assert "top" in r.stdout, r.stdout
EOF
then echo "TOP_SMOKE=ok"; else echo "TOP_SMOKE=FAILED"; rc=1; fi
rm -rf "$top_dir"

# Pipeline smoke: a tiny train→eval→promote DAG through `tpx control` on
# the real local scheduler must reach PROMOTED, its journaled stages must
# be visible via `tpx pipeline status`, and the verb rides the lazy
# dispatcher (`tpx pipeline --help` never imports jax).
pl_dir=$(mktemp -d /tmp/tpx_pipeline_smoke.XXXXXX)
if timeout -k 10 180 env JAX_PLATFORMS=cpu TPX_OBS_DIR="$pl_dir/obs" \
    TPX_CONTROL_DIR="$pl_dir/control" TPX_WATCH_INTERVAL=0.1 \
    PL_DIR="$pl_dir" \
    python - <<'EOF'
import json, os, subprocess, sys, time

base = os.environ["PL_DIR"]
ckpt = os.path.join(base, "ckpt")
score = os.path.join(base, "score.json")
logs = os.path.join(base, "logs")
# the train stage writes a checkpoint payload + MANIFEST.json with the
# same sha256 relpath+bytes digest recipe the checkpoint writer uses
train_code = (
    "import hashlib,json,os\n"
    f"ckpt={ckpt!r}\n"
    "p=os.path.join(ckpt,'1'); os.makedirs(p,exist_ok=True)\n"
    "open(os.path.join(p,'w.bin'),'wb').write(b'weights-v1')\n"
    "h=hashlib.sha256()\n"
    "fp=os.path.join(p,'w.bin')\n"
    "h.update(os.path.relpath(fp,p).encode()); h.update(open(fp,'rb').read())\n"
    "json.dump({'latest_step':1,'steps':{'1':{'digest':h.hexdigest()}}},"
    "open(os.path.join(ckpt,'MANIFEST.json'),'w'))\n"
)
spec = {
    "name": "smoke",
    "stages": [
        {"name": "train", "kind": "train", "component": "utils.python",
         "args": ["-c", train_code], "ckpt_dir": ckpt,
         "cfg": {"log_dir": logs}},
        {"name": "eval", "kind": "eval", "component": "utils.python",
         "args": ["-m", "torchx_tpu.apps.eval_main", "--",
                  "--ckpt", "{train.path}", "--out", score,
                  "--score", "0.9"],
         "depends_on": ["train"], "score_file": score, "threshold": 0.5,
         "cfg": {"log_dir": logs}},
        {"name": "promote", "kind": "promote", "depends_on": ["eval"],
         "observe_s": 0.1},
    ],
}
spec_file = os.path.join(base, "spec.json")
json.dump(spec, open(spec_file, "w"))

ctl = os.environ["TPX_CONTROL_DIR"]
daemon = subprocess.Popen(
    [sys.executable, "-m", "torchx_tpu.cli.main", "control"],
    stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
)
try:
    discovery = os.path.join(ctl, "control.json")
    deadline = time.monotonic() + 60
    while not os.path.exists(discovery):
        assert daemon.poll() is None, daemon.stdout.read()
        assert time.monotonic() < deadline, "daemon never wrote discovery"
        time.sleep(0.1)
    addr = json.load(open(discovery))["addr"]
    env = dict(os.environ, TPX_CONTROL_ADDR=addr)
    tpx = [sys.executable, "-m", "torchx_tpu.cli.main", "pipeline"]
    r = subprocess.run(tpx + ["submit", "--file", spec_file],
                       capture_output=True, text=True, env=env, timeout=60)
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    pid = r.stdout.strip()
    assert pid.startswith("pl_"), r.stdout
    deadline = time.monotonic() + 120
    doc = {}
    while time.monotonic() < deadline:
        r = subprocess.run(tpx + ["status", pid, "--json"],
                           capture_output=True, text=True, env=env,
                           timeout=60)
        assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
        doc = json.loads(r.stdout)
        if doc["state"] in ("PROMOTED", "SUCCEEDED", "FAILED",
                            "ROLLED_BACK", "CANCELLED"):
            break
        time.sleep(0.2)
    assert doc.get("state") == "PROMOTED", doc
    states = {s["name"]: s["state"] for s in doc["stages"]}
    assert states == {"train": "SUCCEEDED", "eval": "SUCCEEDED",
                      "promote": "SUCCEEDED"}, states
    assert doc["incumbent"]["ckpt"] == ckpt, doc["incumbent"]
    # the journal backs the status view: every stage decision is on disk
    kinds = set()
    with open(os.path.join(ctl, "pipelines.jsonl")) as f:
        for line in f:
            kinds.add(json.loads(line).get("kind"))
    assert {"submit", "stage_submit", "stage_done", "gate",
            "promote_step", "incumbent"} <= kinds, kinds
finally:
    daemon.terminate()
    daemon.wait(timeout=10)

# the pipeline verb rides the lazy dispatcher: its help never imports jax
r = subprocess.run(
    [sys.executable, "-c", (
        "import sys\n"
        "from torchx_tpu.cli.main import main\n"
        "try: main(['pipeline', '--help'])\n"
        "except SystemExit: pass\n"
        "assert 'jax' not in sys.modules, 'tpx pipeline --help imported jax'\n"
    )],
    capture_output=True, text=True, timeout=60,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
EOF
then echo "PIPELINE_SMOKE=ok"; else echo "PIPELINE_SMOKE=FAILED"; rc=1; fi
rm -rf "$pl_dir"

# Sim smoke: two same-seed `tpx sim run` invocations of the bundled
# smoke scenario must produce byte-identical journals (the determinism
# contract), the journal must land on disk, and `tpx sim --help` must
# stay jax-free (the whole sim subsystem rides the CLI fast path).
sim_dir=$(mktemp -d /tmp/tpx_sim_smoke.XXXXXX)
if timeout -k 10 180 env JAX_PLATFORMS=cpu SIM_DIR="$sim_dir" \
    python - <<'EOF'
import hashlib, json, os, subprocess, sys

base = os.environ["SIM_DIR"]
tpx = [sys.executable, "-m", "torchx_tpu.cli.main", "sim"]
reports = []
for i in (1, 2):
    out = os.path.join(base, f"run{i}")
    r = subprocess.run(
        tpx + ["run", "--scenario", "smoke-tiny", "--seed", "7",
               "--out", out, "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    reports.append(json.loads(r.stdout))
a, b = reports
assert os.path.exists(a["journal"]), a
raw = open(a["journal"], "rb").read()
assert raw and hashlib.sha256(raw).hexdigest() == a["journal_sha256"], a
assert a["journal_sha256"] == b["journal_sha256"], (a, b)
assert a["stats"]["submitted"] > 0, a
assert a["stats"]["faults"] == 2, a

# the sim verb rides the lazy dispatcher: its help never imports jax
r = subprocess.run(
    [sys.executable, "-c", (
        "import sys\n"
        "from torchx_tpu.cli.main import main\n"
        "try: main(['sim', '--help'])\n"
        "except SystemExit: pass\n"
        "assert 'jax' not in sys.modules, 'tpx sim --help imported jax'\n"
    )],
    capture_output=True, text=True, timeout=60,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
EOF
then echo "SIM_SMOKE=ok"; else echo "SIM_SMOKE=FAILED"; rc=1; fi
rm -rf "$sim_dir"

# Profile smoke: a tiny profiled CPU train run (TPX_PROFILE=1) must leave
# one profile.jsonl whose `tpx profile --json` summary has every core
# phase nonzero, MFU in (0, 1], phases summing to the measured wall time
# (the 5% attribution acceptance bound), and a calibration table whose
# collective_scale moved off 1.0 (the measured-overlap feedback loop).
# `tpx profile --help` must stay jax-free (lint JAX_FREE covers the
# module; this covers the CLI dispatch path).
prof_dir=$(mktemp -d /tmp/tpx_profile_smoke.XXXXXX)
if timeout -k 10 300 env JAX_PLATFORMS=cpu PROF_DIR="$prof_dir" \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'EOF'
import glob, json, os, subprocess, sys

base = os.environ["PROF_DIR"]
os.environ["TPX_OBS_DIR"] = os.path.join(base, "obs")
os.environ["TPX_TUNE_DIR"] = os.path.join(base, "tune")
os.environ["TPX_PROFILE"] = "1"  # the env switch, not the --profile flag

from torchx_tpu.examples.train_llama import main as train_main

train_main(["--config", "tiny", "--mesh", "fsdp=-1", "--batch", "8",
            "--seq", "128", "--steps", "8"])

journals = glob.glob(os.path.join(base, "obs", "*", "profile.jsonl"))
assert len(journals) == 1, journals
r = subprocess.run(
    [sys.executable, "-m", "torchx_tpu.cli.main", "profile",
     journals[0], "--json"],
    capture_output=True, text=True, timeout=120,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
s = json.loads(r.stdout)
assert s["v"] == 1 and s["steps"] > 0, s
for ph in ("data_wait", "forward_backward", "optimizer", "host"):
    assert s["phase_seconds"].get(ph, 0) > 0, (ph, s["phase_seconds"])
assert 0 < s["mfu"] <= 1, s["mfu"]
total = sum(s["phase_seconds"].values()) + sum(s["grad_sync_seconds"].values())
assert abs(total - s["wall_s"]) / s["wall_s"] < 0.05, (total, s["wall_s"])

# the measured-residual loop closed: one profiled run moved the scale
from torchx_tpu.tune.calibrate import CalibrationTable

scale = CalibrationTable.load_default().scales_for("cpu-sim").collective_scale
assert scale != 1.0, scale

# the profile verb rides the lazy dispatcher: its help never imports jax
r = subprocess.run(
    [sys.executable, "-c", (
        "import sys\n"
        "from torchx_tpu.cli.main import main\n"
        "try: main(['profile', '--help'])\n"
        "except SystemExit: pass\n"
        "assert 'jax' not in sys.modules, 'tpx profile --help imported jax'\n"
    )],
    capture_output=True, text=True, timeout=60,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
EOF
then echo "PROFILE_SMOKE=ok"; else echo "PROFILE_SMOKE=FAILED"; rc=1; fi
rm -rf "$prof_dir"

# Overlap smoke: the step-time knobs end to end on the CPU sim. A
# profiled train through the CLI flags (--grad-bucket-mb auto +
# reference kernels) must surface a measured overlap_frac in
# `tpx profile --json`; an unprofiled bucketed run must produce a loss
# BITWISE identical to the single-sync run (bucket boundaries are value
# identities); and `tpx --help` must stay jax-free with the new knobs
# in the tree.
ov_dir=$(mktemp -d /tmp/tpx_overlap_smoke.XXXXXX)
if timeout -k 10 420 env JAX_PLATFORMS=cpu OV_DIR="$ov_dir" \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'EOF'
import glob, json, os, subprocess, sys, time

base = os.environ["OV_DIR"]
os.environ["TPX_OBS_DIR"] = os.path.join(base, "obs")
os.environ["TPX_TUNE_DIR"] = os.path.join(base, "tune")
os.environ["TPX_PROFILE"] = "1"

from torchx_tpu.examples.train_llama import main as train_main
from torchx_tpu.examples.train_llama import parse_mesh_arg, train
from torchx_tpu.models import llama

train_main(["--config", "tiny", "--mesh", "fsdp=-1", "--batch", "8",
            "--seq", "128", "--steps", "8",
            "--grad-bucket-mb", "auto", "--kernels", "reference"])

journals = glob.glob(os.path.join(base, "obs", "*", "profile.jsonl"))
assert len(journals) == 1, journals
r = subprocess.run(
    [sys.executable, "-m", "torchx_tpu.cli.main", "profile",
     journals[0], "--json"],
    capture_output=True, text=True, timeout=120,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
s = json.loads(r.stdout)
assert s["overlap_frac"] is not None, s
assert 0.0 <= s["overlap_frac"] <= 1.0, s["overlap_frac"]

# bitwise loss parity: bucketed vs single-sync, unprofiled
del os.environ["TPX_PROFILE"]
cfg = llama.llama_tiny()
mesh = parse_mesh_arg("fsdp=-1")
a = train(cfg, mesh, batch=8, seq=128, steps=8,
          launch_anchor=time.monotonic())
b = train(cfg, mesh, batch=8, seq=128, steps=8, grad_bucket_mb="auto",
          launch_anchor=time.monotonic())
assert b["grad_buckets"] >= 1 and b["grad_bucket_mb"] > 0, b
assert a["loss"] == b["loss"], (a["loss"], b["loss"])

# the launcher CLI stays jax-free with the step-time knobs in the tree
r = subprocess.run(
    [sys.executable, "-c", (
        "import sys\n"
        "from torchx_tpu.cli.main import main\n"
        "try: main(['--help'])\n"
        "except SystemExit: pass\n"
        "assert 'jax' not in sys.modules, 'tpx --help imported jax'\n"
    )],
    capture_output=True, text=True, timeout=60,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
EOF
then echo "OVERLAP_SMOKE=ok"; else echo "OVERLAP_SMOKE=FAILED"; rc=1; fi
rm -rf "$ov_dir"

# Federation smoke: boot two `tpx control` daemons as cells, register
# them with `tpx cell add`, submit through the federation router, drain
# one cell mid-stream with `tpx cell drain`, and assert every subsequent
# request lands on the survivor with ZERO request errors. `tpx cell list
# --json` must report the drained lifecycle state, and `tpx cell --help`
# must stay jax-free on the lazy dispatch path.
fed_dir=$(mktemp -d /tmp/tpx_fed_smoke.XXXXXX)
if timeout -k 10 300 env JAX_PLATFORMS=cpu FED_DIR="$fed_dir" \
    TPX_OBS_DIR="$fed_dir/obs" TPX_FEDERATION_DIR="$fed_dir/fed" \
    TPX_WATCH_INTERVAL=0.1 \
    python - <<'EOF'
import json, os, subprocess, sys, time

base = os.environ["FED_DIR"]
tpx = [sys.executable, "-m", "torchx_tpu.cli.main"]
cells = {"us-east1": None, "eu-west4": None}
daemons = []
try:
    for name in cells:
        state = os.path.join(base, name)
        p = subprocess.Popen(
            tpx + ["control", "--cell", name, "--state-dir", state],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        daemons.append(p)
        discovery = os.path.join(state, "control.json")
        deadline = time.monotonic() + 60
        while not os.path.exists(discovery):
            assert p.poll() is None, p.stdout.read()
            assert time.monotonic() < deadline, f"{name} never wrote discovery"
            time.sleep(0.1)
        cells[name] = json.load(open(discovery))

    for name, doc in cells.items():
        r = subprocess.run(
            tpx + ["cell", "add", name, "--addr", doc["addr"],
                   "--token", doc["token"]],
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)

    from torchx_tpu.federation import CellHandle, CellRegistry, FederationRouter

    registry = CellRegistry()
    assert len(registry) == 2, registry.cells()
    router = FederationRouter(
        [CellHandle(spec) for spec in registry.cells()], probe_ttl_s=0.0
    )
    log_dir = os.path.join(base, "logs")

    def submit(i):
        return router.submit(
            "utils.echo", ["--msg", f"fed-{i}"], "local",
            cfg={"log_dir": os.path.join(log_dir, str(i))},
        )

    pre = [submit(i) for i in range(4)]
    assert all(reply.get("handle") for _, reply in pre), pre

    # drain one cell through the CLI; the router must route away from it
    r = subprocess.run(
        tpx + ["cell", "drain", "us-east1", "--json"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    assert json.loads(r.stdout)["draining"] is True, r.stdout

    post = [submit(i) for i in range(4, 10)]  # zero errors: all spill over
    assert all(cell == "eu-west4" for cell, _ in post), post
    assert all(reply.get("handle") for _, reply in post), post

    r = subprocess.run(
        tpx + ["cell", "list", "--json"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    listed = json.loads(r.stdout)["cells"]
    assert listed["us-east1"]["state"] in ("DRAINING", "DRAINED"), listed
    assert listed["eu-west4"]["state"] == "HEALTHY", listed
finally:
    for p in daemons:
        p.terminate()
    for p in daemons:
        p.wait(timeout=10)

# the cell verb rides the lazy dispatcher: its help never imports jax
r = subprocess.run(
    [sys.executable, "-c", (
        "import sys\n"
        "from torchx_tpu.cli.main import main\n"
        "try: main(['cell', '--help'])\n"
        "except SystemExit: pass\n"
        "assert 'jax' not in sys.modules, 'tpx cell --help imported jax'\n"
    )],
    capture_output=True, text=True, timeout=60,
)
assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
EOF
then echo "FED_SMOKE=ok"; else echo "FED_SMOKE=FAILED"; rc=1; fi
rm -rf "$fed_dir"
exit $rc
