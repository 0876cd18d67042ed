"""Deep-preflight tests: the jax-free plan IR, sharding propagation per
parallelism leg, the HBM/collective cost model, TPX7xx gating in the
submit gate, the ``tpx explain`` CLI (golden-filed ``--json`` schema) and
the ``--aot`` cross-check against the XLA compiler's memory analysis."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from torchx_tpu.analyze import analyze
from torchx_tpu.analyze.costmodel import (
    collective_traffic,
    hbm_fit,
)
from torchx_tpu.analyze.explain import ExplainReport, deep_preflight, explain
from torchx_tpu.analyze.plan import (
    MODEL_SHAPES,
    ParallelPlan,
    PlanError,
    plan_from_role,
)
from torchx_tpu.analyze.propagation import propagate
from torchx_tpu.cli.main import main
from torchx_tpu.components import dist
from torchx_tpu.parallel.mesh_config import axis_networks
from torchx_tpu.specs.api import AppDef, Role

REPO = Path(__file__).resolve().parent.parent
GOLDEN = Path(__file__).resolve().parent / "fixtures" / "explain_golden.json"

GIB = 1024**3


def spmd_app(*trainer_args: str, m: str = "my.custom_trainer", j: str = "1x8", **kw) -> AppDef:
    """A dist.spmd AppDef shaped exactly like the CLI would build it."""
    return dist.spmd(*trainer_args, m=m, j=j, **kw)


def plan_of(app: AppDef) -> ParallelPlan:
    plan = plan_from_role(app.roles[0])
    assert plan is not None
    return plan


def kinds(flow) -> dict[str, str]:
    return {b.op: b.kind for b in flow.boundaries}


# ---------------------------------------------------------------------------
# plan IR
# ---------------------------------------------------------------------------


def test_model_shapes_match_jax_configs():
    """The honesty contract from plan.py's docstring: the arithmetic-only
    ModelShape mirror must agree exactly with the real (jax-importing)
    model configs on parameter counts."""
    from torchx_tpu.models import all_configs

    cfgs = all_configs()
    for name, shape in MODEL_SHAPES.items():
        cfg = cfgs[name]()
        assert shape.param_count() == cfg.param_count(), name
        if shape.is_moe:
            assert shape.active_param_count() == cfg.active_param_count(), name
        # the step profiler's MFU denominator reuses this mirror: the
        # FLOP arithmetic must agree exactly too
        assert shape.flops_per_token() == cfg.flops_per_token(), name


def test_plan_from_spmd_role():
    plan = plan_of(
        spmd_app("--config", "tiny", "--mesh", "fsdp=-1", "--batch", "16")
    )
    assert plan.model.name == "tiny"
    assert plan.axis("fsdp") == 8 and plan.devices == 8
    assert plan.batch == 16 and plan.seq == 128
    assert plan.mesh_spec == "fsdp=-1"
    assert not plan.serve and not plan.remat_safe
    assert plan.hbm_source == "assumed"  # CPU-sim role


def test_plan_flags_int8_ring_remat():
    plan = plan_of(
        spmd_app(
            "--config", "tiny", "--mesh", "fsdp=1,sp=-1",
            "--int8", "--ring-attention", "--remat-policy", "dots",
        )
    )
    assert plan.int8 and plan.ring_attention
    assert plan.remat_policy == "dots"
    # "auto" maps to the trainer's push floor
    plan = plan_of(
        spmd_app("--config", "tiny", "--remat-policy", "auto")
    )
    assert plan.remat_policy == "dots"


def test_plan_stock_trainer_is_remat_safe():
    plan = plan_of(
        spmd_app("--config", "moe_tiny", m="torchx_tpu.examples.train_llama")
    )
    assert plan.remat_safe


def test_plan_none_without_config():
    assert plan_from_role(spmd_app("--lr", "3e-4").roles[0]) is None
    assert plan_from_role(spmd_app("--config", "nonesuch").roles[0]) is None
    assert (
        plan_from_role(Role(name="r", image="img", entrypoint="bash")) is None
    )


def test_plan_error_on_unresolvable_mesh():
    with pytest.raises(PlanError):
        plan_of(spmd_app("--config", "tiny", "--mesh", "tp=3"))
    with pytest.raises(PlanError):
        plan_of(spmd_app("--config", "tiny", "--mesh", "bogus=2"))


def test_plan_tpu_topology_and_hbm_table():
    app = spmd_app(
        "--config", "llama3_8b", "--mesh", "fsdp=-1", tpu="v5p-32", j="1"
    )
    plan = plan_of(app)
    assert plan.hbm_source == "tpu_slice"
    assert plan.accelerator.startswith("v5p")
    assert plan.hbm_bytes_per_chip == 95 * GIB
    assert plan.devices == plan.slices * plan.chips_per_slice


def test_plan_tpx_mesh_env_overrides_flag():
    app = spmd_app("--config", "tiny", "--mesh", "fsdp=-1")
    role = dataclasses.replace(
        app.roles[0], env={**app.roles[0].env, "TPX_MESH": "fsdp=1,tp=-1"}
    )
    plan = plan_from_role(role)
    assert plan is not None and plan.axis("tp") == 8 and plan.axis("fsdp") == 1


# ---------------------------------------------------------------------------
# sharding propagation, one test per parallelism leg
# ---------------------------------------------------------------------------


def test_propagate_fsdp_leg():
    flow = propagate(plan_of(spmd_app("--config", "tiny", "--mesh", "fsdp=-1")))
    k = kinds(flow)
    assert k["embed.gather"] == "allgather"
    assert k["layer.qkv"] == "allgather"
    assert k["grad.sync"] == "allreduce"
    assert not flow.full_remat
    assert flow.activation_spec == "P('fsdp', None, None)"


def test_propagate_tp_leg():
    flow = propagate(
        plan_of(spmd_app("--config", "tiny", "--mesh", "fsdp=1,tp=-1"))
    )
    k = kinds(flow)
    assert k["layer.attn_out"] == "allreduce"
    assert k["layer.mlp_out"] == "allreduce"
    assert k["loss.ce"] == "allreduce"
    assert "embed.gather" not in k  # table not dim-sharded without fsdp


def test_propagate_pp_leg():
    flow = propagate(
        plan_of(spmd_app("--config", "tiny", "--mesh", "pp=2,fsdp=-1"))
    )
    assert kinds(flow)["pp.stage"] == "permute"


def test_propagate_ring_vs_allgather_sp_leg():
    ring = propagate(
        plan_of(
            spmd_app(
                "--config", "tiny", "--mesh", "fsdp=1,sp=-1", "--ring-attention"
            )
        )
    )
    assert kinds(ring)["attn.ring"] == "permute"
    full = propagate(
        plan_of(spmd_app("--config", "tiny", "--mesh", "fsdp=1,sp=-1"))
    )
    assert kinds(full)["attn.kv_allgather"] == "allgather"


def test_propagate_moe_full_remat_gated_by_remat_safety():
    """The tentpole boundary: ep x fsdp on a custom trainer makes both the
    embed gather and the MoE dispatch involuntary-full-remat; the stock
    trainer (with_sharding_constraint pins) keeps them benign."""
    custom = propagate(
        plan_of(spmd_app("--config", "moe_tiny", "--mesh", "ep=2,fsdp=-1"))
    )
    k = kinds(custom)
    assert custom.full_remat
    assert k["embed.gather"] == "full_remat"
    assert k["moe.dispatch"] == "full_remat"
    assert k["moe.combine"] == "alltoall"
    # axes reported in canonical mesh order
    dispatch = next(b for b in custom.boundaries if b.op == "moe.dispatch")
    assert dispatch.axes == ("fsdp", "ep")

    stock = propagate(
        plan_of(
            spmd_app(
                "--config", "moe_tiny", "--mesh", "ep=2,fsdp=-1",
                m="torchx_tpu.examples.train_llama",
            )
        )
    )
    assert not stock.full_remat
    assert kinds(stock)["moe.dispatch"] == "alltoall"


def test_propagate_moe_ep_alone_is_benign():
    flow = propagate(
        plan_of(spmd_app("--config", "moe_tiny", "--mesh", "ep=2,fsdp=1,dp=-1"))
    )
    assert not flow.full_remat
    assert kinds(flow)["moe.dispatch"] == "alltoall"


def test_propagate_serve_has_no_loss_or_grad():
    plan = dataclasses.replace(
        plan_of(spmd_app("--config", "tiny", "--mesh", "fsdp=-1")), serve=True
    )
    k = kinds(propagate(plan))
    assert "loss.ce" not in k and "grad.sync" not in k


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_hbm_fit_components_and_verdict():
    plan = plan_of(spmd_app("--config", "tiny", "--mesh", "fsdp=-1"))
    fit = hbm_fit(plan)
    assert set(fit.components) == {
        "params", "optimizer", "gradients", "activations", "logits", "batch",
    }
    assert fit.components["optimizer"] == 2 * fit.components["params"]
    assert fit.total_bytes == sum(fit.components.values())
    assert fit.fits and fit.verdict == "fits"
    d = fit.to_dict()
    assert d["usable_bytes"] == int(fit.budget_bytes * fit.headroom)


def test_hbm_fit_shards_params_over_fsdp_tp():
    flat = plan_of(spmd_app("--config", "tiny", "--mesh", "fsdp=1,dp=-1"))
    sharded = plan_of(spmd_app("--config", "tiny", "--mesh", "fsdp=4,tp=2"))
    assert (
        hbm_fit(sharded).components["params"] * 8
        <= hbm_fit(flat).components["params"] + 8
    )


def test_hbm_fit_serve_kv_pool():
    plan = dataclasses.replace(
        plan_of(spmd_app("--config", "tiny", "--mesh", "fsdp=1,tp=-1")),
        serve=True,
        max_batch=4,
    )
    fit = hbm_fit(plan)
    assert set(fit.components) == {"params", "kv_pool", "decode_state"}
    m = plan.model
    dense = 4 * m.n_layers * 2 * m.max_seq * m.n_kv_heads * m.head_dim
    assert fit.components["kv_pool"] == dense * m.dtype_bytes // 8  # /tp


def test_plan_parses_serve_role_and_prefix_reserve():
    app = spmd_app(
        "--config",
        "tiny",
        "--serve-role",
        "prefill",
        "--prefix-cache-reserve",
        "0.25",
    )
    plan = plan_of(app)
    assert plan.serve_role == "prefill" and plan.prefix_reserve == 0.25
    d = plan.to_dict()
    assert d["serve_role"] == "prefill" and d["prefix_reserve"] == 0.25
    # defaults: unified, no reserve
    default = plan_of(spmd_app("--config", "tiny"))
    assert default.serve_role == "unified" and default.prefix_reserve == 0.0


def test_hbm_fit_charges_prefix_cache_reserve():
    base = dataclasses.replace(
        plan_of(spmd_app("--config", "tiny", "--mesh", "fsdp=1,tp=-1")),
        serve=True,
        max_batch=4,
    )
    reserved = dataclasses.replace(base, prefix_reserve=0.25)
    fit0, fit1 = hbm_fit(base), hbm_fit(reserved)
    assert "prefix_cache" not in fit0.components
    # the reserve holds cached prefixes ON TOP of the live-sequence pool
    assert fit1.components["prefix_cache"] == -(
        -fit1.components["kv_pool"] // 4
    )
    assert fit1.total_bytes == fit0.total_bytes + fit1.components["prefix_cache"]


def test_collective_traffic_axes_and_network():
    plan = plan_of(
        spmd_app("--config", "moe_tiny", "--mesh", "ep=2,fsdp=4", j="1x8")
    )
    traffic = {t.axis: t for t in collective_traffic(plan)}
    assert set(traffic) == {"fsdp", "ep"}
    # single slice of 8: everything is ICI
    assert all(t.network == "ici" for t in traffic.values())
    assert traffic["fsdp"].bytes_per_step > 0
    assert "alltoall_dispatch" in traffic["ep"].ops


def test_axis_networks_classification():
    # 2 slices x 4 chips: innermost fsdp stays on ICI, outer dp is DCN
    nets = axis_networks({"dp": 2, "fsdp": 4}, chips_per_slice=4)
    assert nets["fsdp"] == "ici" and nets["dp"] == "dcn"
    assert nets["tp"] == "none"  # size-1 axis
    # an axis straddling the slice edge is mixed
    nets = axis_networks({"fsdp": 8}, chips_per_slice=4)
    assert nets["fsdp"] == "mixed"


# ---------------------------------------------------------------------------
# TPX7xx diagnostics: deep_preflight + the submit gate
# ---------------------------------------------------------------------------


def dcodes(diags) -> list[str]:
    return [d.code for d in diags]


def test_tpx700_moe_boundary_error():
    """The MULTICHIP r03/r04 dryrun scenario, caught statically: custom
    trainer + moe mesh -> TPX700 ERROR naming the exact boundary."""
    app = spmd_app("--config", "moe_tiny", "--mesh", "ep=2,fsdp=-1")
    plan, diags = deep_preflight(app.roles[0])
    assert plan is not None
    assert dcodes(diags).count("TPX700") == 2  # embed.gather + moe.dispatch
    fields = {d.field for d in diags if d.code == "TPX700"}
    assert fields == {"sharding.embed.gather", "sharding.moe.dispatch"}
    assert all(d.severity.value == "error" for d in diags)


def test_tpx701_hbm_exceeded():
    app = spmd_app("--config", "llama3_8b", "--mesh", "fsdp=-1")
    _plan, diags = deep_preflight(app.roles[0], hbm_bytes=1 * GIB)
    assert "TPX701" in dcodes(diags)
    d = next(d for d in diags if d.code == "TPX701")
    assert "params" in d.message and d.severity.value == "error"


def test_tpx702_dcn_axis_warning():
    # 2 slices x 8 chips, fsdp spanning all 16 devices -> mixed network
    app = spmd_app(
        "--config", "llama3_1b", "--mesh", "fsdp=-1", tpu="v5e-8", j="2"
    )
    _plan, diags = deep_preflight(app.roles[0])
    assert "TPX702" in dcodes(diags)
    d = next(d for d in diags if d.code == "TPX702")
    assert d.severity.value == "warning" and "fsdp" in d.message


def test_tpx703_broken_mesh():
    app = spmd_app("--config", "tiny", "--mesh", "tp=3")
    plan, diags = deep_preflight(app.roles[0])
    assert plan is None and dcodes(diags) == ["TPX703"]


def test_tpx704_serve_kv_overflow():
    role = Role(
        name="server",
        image="img",
        entrypoint="python",
        args=[
            "-m", "torchx_tpu.apps.generate_server",
            "--config", "llama3_8b", "--max-batch", "64",
        ],
    )
    _plan, diags = deep_preflight(role, hbm_bytes=8 * GIB)
    assert "TPX704" in dcodes(diags)
    assert next(d for d in diags if d.code == "TPX704").severity.value == "warning"


def test_tpx705_no_plan_info():
    _plan, diags = deep_preflight(spmd_app("--steps", "5").roles[0])
    assert dcodes(diags) == ["TPX705"]
    assert diags[0].severity.value == "info"


def test_gate_runs_deep_preflight_and_supersedes_tpx110():
    """The submit gate on a plan-shaped role reports propagation's TPX700
    and stands the TPX110 heuristic down; TPX705 never reaches the gate."""
    report = analyze(spmd_app("--config", "moe_tiny", "--mesh", "ep=2,fsdp=-1"))
    got = [d.code for d in report.diagnostics]
    assert "TPX700" in got and "TPX110" not in got and "TPX705" not in got


def test_gate_tpx110_heuristic_still_fires_without_plan():
    """Regression for the pre-propagation behavior: a custom trainer with
    no recognizable --config keeps the TPX110 pattern-match warning."""
    report = analyze(spmd_app("--mesh", "ep=2,fsdp=-1"))
    got = [d.code for d in report.diagnostics]
    assert "TPX110" in got and "TPX700" not in got and "TPX705" not in got


def test_gate_tpx110_silent_for_stock_trainer():
    report = analyze(
        spmd_app("--mesh", "ep=2,fsdp=-1", m="torchx_tpu.examples.train_llama")
    )
    assert "TPX110" not in [d.code for d in report.diagnostics]


def test_gate_tpx111_unknown_axis_still_errors():
    report = analyze(spmd_app("--config", "tiny", "--mesh", "fsd=2"))
    assert "TPX111" in [d.code for d in report.diagnostics]


# ---------------------------------------------------------------------------
# the explain report + CLI
# ---------------------------------------------------------------------------


def test_explain_report_schema_golden():
    """``tpx explain --json`` is schema version 1 and byte-stable: the
    full report for a fixed plan must match the committed golden file.
    Regenerate deliberately with scripts/gen_explain_golden.py when the
    schema (or the cost model) changes on purpose."""
    app = spmd_app(
        "--config", "moe_tiny", "--mesh", "ep=2,fsdp=-1",
        "--batch", "8", "--seq", "128",
    )
    report = explain(app, gate="test")
    got = report.to_dict()
    golden = json.loads(GOLDEN.read_text())
    assert got == golden


def test_explain_report_render_and_summary():
    app = spmd_app("--config", "moe_tiny", "--mesh", "ep=2,fsdp=-1")
    report = explain(app, gate="test")
    assert report.has_errors
    assert report.summary()["error"] == 2
    text = report.render()
    assert "INVOLUNTARY FULL REMAT" in text
    assert "FITS" in text and "TPX700" in text


def test_explain_metrics_and_span(tmp_path, monkeypatch):
    monkeypatch.setenv("TPX_OBS_DIR", str(tmp_path / "obs"))
    from torchx_tpu.obs import metrics as obs_metrics

    explain(spmd_app("--config", "tiny"), gate="test", session="s1")
    text = obs_metrics.REGISTRY.render()
    assert "tpx_explain_runs_total" in text
    assert "tpx_explain_hbm_total_bytes" in text


def test_explain_mixed_app_keeps_non_plan_roles():
    app = AppDef(
        name="mixed",
        roles=[
            spmd_app("--config", "tiny", "--mesh", "fsdp=-1").roles[0],
            Role(name="sidecar", image="img", entrypoint="bash"),
        ],
    )
    report = explain(app, gate="test")
    assert len(report.roles) == 2
    assert report.roles[1]["plan"] is None
    assert dcodes(report.roles[1]["_diags"]) == ["TPX705"]
    assert not report.has_errors  # TPX705 is info


def test_cli_explain_json_and_exit_codes(capsys):
    argv = [
        "explain", "--json", "dist.spmd",
        "-j", "1x8", "-m", "my.custom_trainer",
        "--", "--config", "moe_tiny", "--mesh", "ep=2,fsdp=-1",
    ]
    with pytest.raises(SystemExit) as e:
        main(argv)
    assert e.value.code == 1  # TPX700 errors
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == 1
    got = [d["code"] for r in doc["roles"] for d in r["diagnostics"]]
    assert "TPX700" in got
    boundary_kinds = {
        b["kind"] for r in doc["roles"] for b in r["sharding"]["boundaries"]
    }
    assert "full_remat" in boundary_kinds


def test_cli_explain_clean_stock_trainer(capsys):
    argv = [
        "explain", "dist.spmd",
        "-j", "1x8", "-m", "torchx_tpu.examples.train_llama",
        "--", "--config", "moe_tiny", "--mesh", "ep=2,fsdp=-1",
    ]
    with pytest.raises(SystemExit) as e:
        main(argv)
    assert e.value.code == 0
    out = capsys.readouterr().out
    assert "FITS" in out and "full_remat" not in out


def test_cli_explain_usage_errors(capsys):
    with pytest.raises(SystemExit) as e:
        main(["explain", "--json"])
    assert e.value.code == 2
    with pytest.raises(SystemExit) as e:
        main(["explain", "-s", "nonesuch", "dist.spmd", "-m", "x"])
    assert e.value.code == 2
    assert "unknown scheduler" in capsys.readouterr().err


def test_cli_explain_hbm_override(capsys):
    argv = [
        "explain", "--hbm-gb", "0.001", "dist.spmd",
        "-j", "1x8", "-m", "torchx_tpu.examples.train_llama",
        "--", "--config", "tiny", "--mesh", "fsdp=-1",
    ]
    with pytest.raises(SystemExit) as e:
        main(argv)
    assert e.value.code == 1
    assert "TPX701" in capsys.readouterr().out


@pytest.mark.integ
def test_explain_path_never_imports_jax():
    """The acceptance bar SELF_LINT enforces statically, proven
    dynamically: a full non---aot explain run leaves jax unimported."""
    code = (
        "import sys\n"
        "from torchx_tpu.cli.main import main\n"
        "try:\n"
        "    main(['explain', '--json', 'dist.spmd', '-j', '1x8',"
        " '-m', 'my.t', '--', '--config', 'moe_tiny',"
        " '--mesh', 'ep=2,fsdp=-1'])\n"
        "except SystemExit:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'explain imported jax'\n"
    )
    env = {**os.environ, "TPX_EVENT_DESTINATION": "null"}
    subprocess.run(
        [sys.executable, "-c", code], check=True, cwd=str(REPO), env=env,
        stdout=subprocess.DEVNULL,
    )


# ---------------------------------------------------------------------------
# --aot cross-check (imports jax)
# ---------------------------------------------------------------------------


def test_aot_cross_check_tiny_agrees():
    import jax

    if jax.device_count() < 8:
        pytest.skip("needs the 8-virtual-device CPU mesh")
    app = spmd_app(
        "--config", "tiny", "--mesh", "fsdp=-1", "--batch", "8",
        m="torchx_tpu.examples.train_llama",
    )
    report = explain(app, aot=True, gate="test")
    aot = report.roles[0]["aot"]
    assert "error" not in aot, aot
    assert aot["fits"] is True
    assert abs(aot["state_agreement_pct"]) <= 15.0


@pytest.mark.slow
def test_aot_cross_check_1b_within_15pct():
    """The acceptance criterion: on the 1B config the static state
    prediction agrees with compile_fit's argument bytes within 15%."""
    import jax

    if jax.device_count() < 8:
        pytest.skip("needs the 8-virtual-device CPU mesh")
    app = spmd_app(
        "--config", "llama3_1b", "--mesh", "fsdp=-1",
        "--batch", "8", "--seq", "512",
        m="torchx_tpu.examples.train_llama",
    )
    report = explain(app, aot=True, gate="test")
    aot = report.roles[0]["aot"]
    assert "error" not in aot, aot
    assert abs(aot["state_agreement_pct"]) <= 15.0


def test_aot_cross_check_device_mismatch_reports_error():
    app = spmd_app("--config", "tiny", "--mesh", "fsdp=-1", j="1x4")
    report = explain(app, aot=True, gate="test")
    aot = report.roles[0]["aot"]
    assert "error" in aot and "4 device" in aot["error"]
