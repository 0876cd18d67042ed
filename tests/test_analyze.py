"""Preflight analyzer tests: diagnostics model, rule families, the Runner
lint gate, `tpx lint` CLI, builtin self-lint, and TpuSlice edge cases."""

import json
from typing import Mapping, Optional

import pytest

from torchx_tpu.analyze import (
    Diagnostic,
    LintError,
    LintReport,
    RuleContext,
    Severity,
    all_rules,
    analyze,
    analyze_component,
    capabilities_for,
    register_rule,
)
from torchx_tpu.cli.main import main
from torchx_tpu.runner.api import Runner
from torchx_tpu.schedulers.api import (
    DescribeAppResponse,
    ListAppResponse,
    Scheduler,
    SchedulerCapabilities,
)
from torchx_tpu.specs.api import (
    AppDef,
    AppDryRunInfo,
    AppState,
    BindMount,
    CfgVal,
    Resource,
    RetryPolicy,
    Role,
    TpuSlice,
    parse_mounts,
    runopts,
)
from torchx_tpu.specs.file_linter import validate_source
from torchx_tpu.specs.finder import get_components
from torchx_tpu.specs.serialize import appdef_to_dict
from torchx_tpu.supervisor.policy import SupervisorPolicy


def app_with(**role_kwargs) -> AppDef:
    defaults = dict(name="worker", image="img", entrypoint="python")
    defaults.update(role_kwargs)
    return AppDef(name="app", roles=[Role(**defaults)])


def broken_app() -> AppDef:
    """The canonical deliberately-broken AppDef from the acceptance criteria:
    bad topology dims + launcher-owned env + duplicate mounts; on tpu_vm the
    mounts also hit the capability rule."""
    return AppDef(
        name="bad",
        roles=[
            Role(
                name="trainer",
                image="img",
                entrypoint="python",
                env={"TPX_REPLICA_ID": "0"},
                mounts=[
                    BindMount(src_path="/a", dst_path="/x"),
                    BindMount(src_path="/b", dst_path="/x"),
                ],
                resource=Resource(tpu=TpuSlice("v5e", 16, "2x2x4")),
            )
        ],
    )


def codes(report: LintReport) -> list[str]:
    return [d.code for d in report.diagnostics]


# ---------------------------------------------------------------------------
# Diagnostics model
# ---------------------------------------------------------------------------


class TestDiagnosticsModel:
    def test_location(self):
        assert Diagnostic("X", Severity.ERROR, "m", role="r", field="f").location == "r.f"
        assert Diagnostic("X", Severity.ERROR, "m", role="r").location == "r"
        assert Diagnostic("X", Severity.ERROR, "m", field="f").location == "f"
        assert Diagnostic("X", Severity.ERROR, "m").location == "app"

    def test_report_sorts_errors_first(self):
        r = LintReport(target="t")
        r.extend(
            [
                Diagnostic("TPX203", Severity.INFO, "i"),
                Diagnostic("TPX202", Severity.WARNING, "w"),
                Diagnostic("TPX201", Severity.ERROR, "e"),
            ]
        )
        assert [d.severity for d in r.diagnostics] == [
            Severity.ERROR,
            Severity.WARNING,
            Severity.INFO,
        ]
        assert r.has_errors
        assert len(r.errors) == 1 and len(r.warnings) == 1
        assert r.summary() == {"error": 1, "warning": 1, "info": 1}

    def test_to_dict_is_stable(self):
        r = LintReport(target="t", scheduler="local")
        r.extend([Diagnostic("TPX010", Severity.ERROR, "no roles", field="roles")])
        d = r.to_dict()
        assert d["version"] == 1
        assert d["target"] == "t"
        assert d["scheduler"] == "local"
        assert d["summary"] == {"error": 1, "warning": 0, "info": 0}
        assert d["diagnostics"][0]["code"] == "TPX010"
        # keys must stay stable: external tooling parses this
        assert list(d) == ["version", "target", "scheduler", "diagnostics", "summary"]

    def test_render_clean_and_dirty(self):
        r = LintReport(target="t")
        assert "clean" in r.render()
        r.extend([Diagnostic("TPX011", Severity.ERROR, "no entrypoint", role="r", hint="set it")])
        out = r.render()
        assert "TPX011" in out and "[r]" in out and "fix: set it" in out

    def test_lint_error_mentions_escape_hatch(self):
        r = LintReport(target="t")
        r.extend([Diagnostic("TPX010", Severity.ERROR, "no roles")])
        msg = str(LintError(r))
        assert "--no-lint" in msg and "TPX_NO_LINT" in msg and "TPX010" in msg


# ---------------------------------------------------------------------------
# Rule registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_builtin_rules_registered(self):
        names = set(all_rules())
        assert {
            "structure",
            "topology",
            "env",
            "macros",
            "ports",
            "mounts",
            "capabilities",
            "retries",
        } <= names

    def test_custom_rule_runs_and_is_replaceable(self):
        def my_rule(ctx: RuleContext):
            yield Diagnostic("TPX999", Severity.WARNING, "custom")

        register_rule("test-custom", my_rule)
        try:
            report = analyze(app_with())
            assert "TPX999" in codes(report)
        finally:
            from torchx_tpu.analyze import rules as rules_mod

            rules_mod._RULES.pop("test-custom", None)


# ---------------------------------------------------------------------------
# TPX01x structure
# ---------------------------------------------------------------------------


class TestStructureRules:
    def test_clean_app_has_no_findings(self):
        assert analyze(app_with(), scheduler="local").diagnostics == []

    def test_no_roles(self):
        assert codes(analyze(AppDef(name="empty"))) == ["TPX010"]

    def test_missing_entrypoint_and_image(self):
        report = analyze(app_with(entrypoint="", image=""))
        assert "TPX011" in codes(report)
        assert "TPX015" in codes(report)

    def test_bad_replica_counts(self):
        assert "TPX012" in codes(analyze(app_with(num_replicas=0)))
        assert "TPX013" in codes(analyze(app_with(num_replicas=2, min_replicas=3)))

    def test_duplicate_role_names(self):
        app = AppDef(
            name="app",
            roles=[
                Role(name="r", image="i", entrypoint="e"),
                Role(name="r", image="i", entrypoint="e"),
            ],
        )
        assert "TPX014" in codes(analyze(app))


# ---------------------------------------------------------------------------
# TPX1xx topology + TpuSlice edge cases
# ---------------------------------------------------------------------------


class TestTopologyRules:
    def test_impossible_v5e_chip_count(self):
        # 10 > 8 single-host chips and not a multiple of the 4-chip host VM
        report = analyze(app_with(resource=Resource(tpu=TpuSlice("v5e", 10))))
        assert codes(report) == ["TPX101"]

    def test_v5e_pod_cap(self):
        report = analyze(app_with(resource=Resource(tpu=TpuSlice("v5e", 512))))
        assert "TPX101" in codes(report)

    def test_dims_mismatch_both_ways(self):
        r2 = analyze(app_with(resource=Resource(tpu=TpuSlice("v5e", 16, "2x2x4"))))
        assert codes(r2) == ["TPX102"]
        r3 = analyze(app_with(resource=Resource(tpu=TpuSlice("v4", 16, "4x4"))))
        assert codes(r3) == ["TPX102"]

    def test_valid_slices_are_clean(self):
        for tpu in (
            TpuSlice("v5e", 16, "4x4"),
            TpuSlice("v4", 16, "2x2x4"),
            TpuSlice("v5p", 8),
            TpuSlice("v5e", 256),
        ):
            assert analyze(app_with(resource=Resource(tpu=tpu))).diagnostics == []

    def test_tpu_in_devices(self):
        report = analyze(app_with(resource=Resource(devices={"google.com/tpu": 4})))
        assert "TPX103" in codes(report)


class TestMeshRules:
    """TPX110/TPX111 regression: the heuristic mesh rule keeps firing for
    roles deep preflight cannot plan, and stands down when TPX700
    propagation owns the role (tests/test_explain.py covers the TPX7xx
    side)."""

    def heuristic_role(self, *extra, entrypoint="python"):
        return app_with(
            entrypoint=entrypoint,
            args=["-m", "my.custom_trainer", "--mesh", "ep=2,fsdp=-1", *extra],
            env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        )

    def test_tpx110_fires_without_a_plan(self):
        # no --config: plan_from_role returns None, the heuristic owns it
        report = analyze(self.heuristic_role())
        assert "TPX110" in codes(report)
        assert "TPX700" not in codes(report)

    def test_tpx110_stock_trainer_stays_clean(self):
        report = analyze(
            app_with(
                entrypoint="python",
                args=[
                    "-m", "torchx_tpu.examples.train_llama",
                    "--mesh", "ep=2,fsdp=-1",
                ],
            )
        )
        assert "TPX110" not in codes(report)

    def test_tpx110_superseded_by_propagation(self):
        # a recognizable --config resolves into a ParallelPlan: TPX700
        # carries the exact boundary and the pattern-match stands down
        report = analyze(self.heuristic_role("--config", "moe_tiny"))
        assert "TPX110" not in codes(report)
        assert "TPX700" in codes(report)

    def test_tpx110_stands_down_on_broken_plans(self):
        # plan-shaped but inconsistent: TPX703 owns the role
        report = analyze(
            app_with(
                entrypoint="python",
                args=[
                    "-m", "my.custom_trainer",
                    "--config", "moe_tiny", "--mesh", "ep=3,fsdp=7",
                ],
                env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
            )
        )
        assert "TPX703" in codes(report)
        assert "TPX110" not in codes(report)

    def test_tpx111_unknown_axis_always_errors(self):
        report = analyze(self.heuristic_role("--mesh", "fsd=2"))
        assert "TPX111" in codes(report)
        # ...including on plan-shaped roles (spec hygiene never stands down)
        report = analyze(
            app_with(
                entrypoint="python",
                args=["-m", "t", "--config", "tiny", "--mesh=fsd=2"],
            )
        )
        assert "TPX111" in codes(report)


class TestKernelsRule:
    """TPX112: ``--kernels pallas`` that will silently fall back."""

    def test_pallas_without_tpu_resource_warns(self):
        report = analyze(
            app_with(
                entrypoint="python",
                args=["-m", "t", "--config", "llama3_8b", "--kernels", "pallas"],
            )
        )
        diags = [d for d in report.diagnostics if d.code == "TPX112"]
        assert len(diags) == 1
        assert "non-TPU" in diags[0].message
        assert "fall back" in diags[0].message

    def test_pallas_on_tpu_with_tileable_shapes_is_clean(self):
        # llama3_8b: head_dim 128, dim 4096, seq 256 — all tileable
        report = analyze(
            app_with(
                entrypoint="python",
                args=[
                    "-m", "t", "--config", "llama3_8b",
                    "--kernels", "pallas", "--seq", "256",
                ],
                resource=Resource(tpu=TpuSlice("v5e", 8)),
            )
        )
        assert "TPX112" not in codes(report)

    def test_pallas_untileable_shapes_warn_even_on_tpu(self):
        # tiny: head_dim 16, dim 64 — neither kernel can tile
        report = analyze(
            app_with(
                entrypoint="python",
                args=["-m", "t", "--config", "tiny", "--kernels=pallas"],
                resource=Resource(tpu=TpuSlice("v5e", 8)),
            )
        )
        diags = [d for d in report.diagnostics if d.code == "TPX112"]
        assert len(diags) == 1
        assert "head_dim 16" in diags[0].message
        assert "reference" in diags[0].message

    def test_pallas_ragged_seq_warns(self):
        report = analyze(
            app_with(
                entrypoint="python",
                args=[
                    "-m", "t", "--config", "llama3_8b",
                    "--kernels", "pallas", "--seq", "100",
                ],
                resource=Resource(tpu=TpuSlice("v5e", 8)),
            )
        )
        diags = [d for d in report.diagnostics if d.code == "TPX112"]
        assert len(diags) == 1 and "seq 100" in diags[0].message

    def test_reference_and_interpret_never_fire(self):
        for kernels in ("reference", "interpret"):
            report = analyze(
                app_with(
                    entrypoint="python",
                    args=["-m", "t", "--config", "tiny", "--kernels", kernels],
                )
            )
            assert "TPX112" not in codes(report)


class TestTpuSliceEdgeCases:
    """Satellite: TpuSlice naming/shape edge cases backing the TPX1xx rules."""

    def test_invalid_accelerator_type_strings(self):
        for bad in ("v5litepod", "v5litepod-0", "v9-8", "potato-4"):
            with pytest.raises(ValueError):
                TpuSlice.from_type(bad)

    def test_topology_must_factor_chip_count(self):
        with pytest.raises(ValueError, match="topology"):
            TpuSlice("v5e", 8, "2x3")

    def test_cores_vs_chips_naming(self):
        # v2..v5p count TensorCores in the type suffix; v5e/v6e count chips
        assert TpuSlice.from_type("v5p-32").chips == 16
        assert TpuSlice.from_type("v4-16").chips == 8
        assert TpuSlice.from_type("v5litepod-16").chips == 16
        assert TpuSlice.from_type("v6e-8").chips == 8

    def test_accelerator_type_round_trip(self):
        assert TpuSlice("v5p", 16).accelerator_type == "v5p-32"
        assert TpuSlice("v5e", 8).accelerator_type == "v5litepod-8"
        # aliases normalize on construction
        assert TpuSlice("v5litepod", 8).accelerator == "v5e"
        assert TpuSlice("v5lite", 4).accelerator == "v5e"

    def test_host_layout(self):
        # single-host v5e slice uses the full 8-chip host ...
        assert TpuSlice("v5e", 8).hosts == 1
        # ... but multi-host slices are built from 4-chip VMs
        assert TpuSlice("v5e", 16).hosts == 4
        assert TpuSlice("v5p", 16).hosts == 4


# ---------------------------------------------------------------------------
# TPX2xx env / macros / ports / mounts
# ---------------------------------------------------------------------------


class TestEnvRules:
    def test_launcher_owned_env_is_error(self):
        report = analyze(app_with(env={"TPX_REPLICA_ID": "0"}))
        assert codes(report) == ["TPX201"]

    def test_reserved_prefix_is_warning(self):
        report = analyze(app_with(env={"TPX_MY_THING": "x"}))
        assert codes(report) == ["TPX202"]

    def test_documented_knobs_are_silent(self):
        report = analyze(
            app_with(env={"TPX_RESUME_STEP": "5", "TPU_SKIP_MDS_QUERY": "1"})
        )
        assert report.diagnostics == []

    def test_jax_env_is_info(self):
        report = analyze(app_with(env={"JAX_PLATFORMS": "cpu"}))
        assert codes(report) == ["TPX203"]
        assert not report.has_errors


class TestMacroRules:
    def test_unknown_macro_warns(self):
        report = analyze(app_with(args=["--out", "${output_dir}"]))
        assert codes(report) == ["TPX204"]

    def test_known_macros_and_escapes_are_silent(self):
        report = analyze(
            app_with(args=["--id", "${app_id}", "--replica", "${replica_id}", "$${HOME}"])
        )
        assert report.diagnostics == []


class TestPortAndMountRules:
    def test_duplicate_port(self):
        report = analyze(app_with(port_map={"http": 8080, "grpc": 8080}))
        assert codes(report) == ["TPX210"]

    def test_port_out_of_range(self):
        report = analyze(app_with(port_map={"http": 70000}))
        assert codes(report) == ["TPX211"]

    def test_serve_port_without_port_map_warns(self):
        report = analyze(app_with(args=["--config", "tiny", "--port", "8000"]))
        assert codes(report) == ["TPX212"]
        (d,) = report.diagnostics
        assert d.severity == Severity.WARNING
        assert "port_map" in d.hint

    def test_serve_port_equals_form_detected(self):
        report = analyze(app_with(args=["--port=9000"]))
        assert codes(report) == ["TPX212"]

    def test_mapped_serve_port_is_silent(self):
        report = analyze(
            app_with(args=["--port", "8000"], port_map={"http": 8000})
        )
        assert report.diagnostics == []

    def test_ephemeral_and_non_numeric_ports_are_silent(self):
        # port 0 means "OS picks"; a macro value is not statically checkable
        report = analyze(
            app_with(args=["--port", "0", "--port", "${replica_id}"])
        )
        assert report.diagnostics == []

    def test_disagg_role_without_transfer_path_errors(self):
        report = analyze(app_with(args=["--serve-role", "prefill"]))
        assert codes(report) == ["TPX213"]
        (d,) = report.diagnostics
        assert d.severity == Severity.ERROR
        assert "--kv-transfer" in d.hint

    def test_disagg_decode_equals_form_detected(self):
        report = analyze(app_with(args=["--serve-role=decode"]))
        assert codes(report) == ["TPX213"]

    def test_disagg_role_with_transfer_arg_is_silent(self):
        report = analyze(
            app_with(
                args=[
                    "--serve-role",
                    "prefill",
                    "--kv-transfer",
                    "http:http://127.0.0.1:8100",
                ]
            )
        )
        assert report.diagnostics == []

    def test_disagg_role_with_metadata_is_silent(self):
        report = analyze(
            app_with(
                args=["--serve-role", "decode"],
                metadata={"tpx/kv_transfer": "file:/var/spool/tpx-kv"},
            )
        )
        assert report.diagnostics == []

    def test_unified_serve_role_is_silent(self):
        report = analyze(app_with(args=["--serve-role", "unified"]))
        assert report.diagnostics == []

    def test_disagg_component_wires_both_roles_clean(self):
        from torchx_tpu.components.serve import generate_server_disagg

        report = analyze(generate_server_disagg("llama3_1b"))
        assert "TPX213" not in codes(report)

    def test_slo_on_unscrapable_backend_warns(self):
        report = analyze(
            app_with(args=["--slo", "p99-ttft"]), scheduler="tpu_vm"
        )
        assert "TPX214" in codes(report)
        d = next(d for d in report.diagnostics if d.code == "TPX214")
        assert d.severity == Severity.WARNING
        assert "metricz_scrape" in d.message
        assert "textfile" in d.hint

    def test_slo_equals_form_and_metadata_detected(self):
        report = analyze(app_with(args=["--slo=goodput"]), scheduler="tpu_vm")
        assert "TPX214" in codes(report)
        report = analyze(
            app_with(metadata={"tpx/slo": "p99-ttft"}), scheduler="tpu_vm"
        )
        assert "TPX214" in codes(report)

    def test_slo_on_scrapable_backend_is_silent(self):
        for backend in ("local", "local_docker", "gke", "slurm"):
            report = analyze(
                app_with(args=["--slo", "p99-ttft"]), scheduler=backend
            )
            assert "TPX214" not in codes(report), backend

    def test_no_slo_declared_is_silent(self):
        report = analyze(app_with(), scheduler="tpu_vm")
        assert "TPX214" not in codes(report)

    def test_profile_on_unscrapable_backend_warns(self):
        report = analyze(app_with(args=["--profile"]), scheduler="tpu_vm")
        assert "TPX215" in codes(report)
        d = next(d for d in report.diagnostics if d.code == "TPX215")
        assert d.severity == Severity.WARNING
        assert "metricz_scrape" in d.message
        assert "tpx profile" in d.hint

    def test_profile_env_switch_detected(self):
        report = analyze(
            app_with(env={"TPX_PROFILE": "1"}), scheduler="tpu_vm"
        )
        assert "TPX215" in codes(report)
        # a disabled switch is silent
        report = analyze(
            app_with(env={"TPX_PROFILE": "0"}), scheduler="tpu_vm"
        )
        assert "TPX215" not in codes(report)

    def test_profile_dir_flag_does_not_trigger(self):
        # --profile-dir is the xprof trace flag, a different feature
        report = analyze(
            app_with(args=["--profile-dir", "/tmp/x"]), scheduler="tpu_vm"
        )
        assert "TPX215" not in codes(report)

    def test_profile_on_scrapable_backend_is_silent(self):
        for backend in ("local", "local_docker", "gke", "slurm"):
            report = analyze(
                app_with(args=["--profile"]), scheduler=backend
            )
            assert "TPX215" not in codes(report), backend

    def test_duplicate_mount_dst(self):
        report = analyze(
            app_with(
                mounts=[
                    BindMount(src_path="/a", dst_path="/x"),
                    BindMount(src_path="/b", dst_path="/x"),
                ]
            )
        )
        assert codes(report) == ["TPX220"]

    def test_relative_mount_dst_warns(self):
        report = analyze(
            app_with(mounts=[BindMount(src_path="/a", dst_path="data")])
        )
        assert codes(report) == ["TPX221"]

    def test_parse_mounts_rejects_duplicate_destinations(self):
        with pytest.raises(ValueError, match="duplicate mount destination"):
            parse_mounts(
                ["type=bind", "src=/a", "dst=/x", "type=bind", "src=/b", "dst=/x"]
            )
        # distinct destinations still parse
        mounts = parse_mounts(
            ["type=bind", "src=/a", "dst=/x", "type=bind", "src=/b", "dst=/y"]
        )
        assert [m.dst_path for m in mounts] == ["/x", "/y"]


# ---------------------------------------------------------------------------
# TPX3xx scheduler capabilities
# ---------------------------------------------------------------------------


class TestCapabilityRules:
    def test_capabilities_for_builtin_backends(self):
        local = capabilities_for("local")
        assert local is not None and local.multislice and local.classifies_preemption
        tpu_vm = capabilities_for("tpu_vm")
        assert tpu_vm is not None and tpu_vm.requires_tpu and not tpu_vm.mounts
        gke = capabilities_for("gke")
        assert gke is not None and gke.mounts and gke.multislice
        assert capabilities_for("no_such_backend") is None

    def test_unknown_scheduler_reports_info_only(self):
        report = analyze(app_with(), scheduler="no_such_backend")
        assert codes(report) == ["TPX300"]
        assert not report.has_errors

    def test_mounts_on_backend_without_mounts(self):
        report = analyze(
            app_with(mounts=[BindMount(src_path="/a", dst_path="/x")]),
            scheduler="tpu_vm",
        )
        assert "TPX301" in codes(report)

    def test_multi_role_on_single_role_backend(self):
        app = AppDef(
            name="app",
            roles=[
                Role(name="a", image="i", entrypoint="e"),
                Role(name="b", image="i", entrypoint="e"),
            ],
        )
        report = analyze(app, scheduler="tpu_vm")
        assert "TPX303" in codes(report)

    def test_multislice_on_single_slice_backend(self):
        report = analyze(
            app_with(num_replicas=2, resource=Resource(tpu=TpuSlice("v5e", 4))),
            scheduler="slurm",
        )
        assert "TPX304" in codes(report)

    def test_tpu_only_backend_needs_tpu(self):
        report = analyze(app_with(), scheduler="tpu_vm")
        assert "TPX305" in codes(report)

    def test_retries_without_native_restarts(self):
        report = analyze(app_with(max_retries=3), scheduler="tpu_vm")
        assert "TPX306" in codes(report)
        # docker restarts natively: no warning
        report = analyze(app_with(max_retries=3), scheduler="local_docker")
        assert "TPX306" not in codes(report)

    def test_concrete_resources_unset(self):
        report = analyze(app_with(), scheduler="vertex")
        assert "TPX307" in codes(report)
        report = analyze(
            app_with(resource=Resource(cpu=8, memMB=1024)), scheduler="vertex"
        )
        assert "TPX307" not in codes(report)

    def test_explicit_capabilities_override_registry(self):
        caps = SchedulerCapabilities(mounts=True, delete=True)
        report = analyze(
            app_with(mounts=[BindMount(src_path="/a", dst_path="/x")]),
            scheduler="tpu_vm",
            capabilities=caps,
        )
        assert "TPX301" not in codes(report)


# ---------------------------------------------------------------------------
# TPX4xx supervisor / retry coherence
# ---------------------------------------------------------------------------


class TestRetryRules:
    def test_negative_retries(self):
        assert "TPX402" in codes(analyze(app_with(max_retries=-1)))

    def test_replica_retry_on_tpu_role(self):
        def replica_role_on(chips):
            return analyze(
                app_with(
                    retry_policy=RetryPolicy.REPLICA,
                    resource=Resource(tpu=TpuSlice("v5e", chips)),
                )
            )

        # v5e-16 is four hosts: one cannot rejoin the others' collective
        assert "TPX401" in codes(replica_role_on(16))
        # a one-host slice restarts whole (N one-chip servers are N worlds)
        assert "TPX401" not in codes(replica_role_on(4))
        # REPLICA on a CPU role is fine
        assert "TPX401" not in codes(analyze(app_with(retry_policy=RetryPolicy.REPLICA)))

    def test_preemption_budget_on_blind_backend(self):
        policy = SupervisorPolicy(max_preemptions=5)
        report = analyze(app_with(), scheduler="vertex", policy=policy)
        assert "TPX403" in codes(report)
        report = analyze(app_with(), scheduler="local", policy=policy)
        assert "TPX403" not in codes(report)

    def test_resume_env_collision(self):
        policy = SupervisorPolicy()
        report = analyze(
            app_with(env={policy.resume_env: "7"}), policy=policy
        )
        assert "TPX404" in codes(report)


# ---------------------------------------------------------------------------
# The acceptance-criteria broken AppDef
# ---------------------------------------------------------------------------


class TestBrokenAppAcceptance:
    def test_reports_at_least_three_distinct_codes(self):
        report = analyze(broken_app(), scheduler="tpu_vm")
        distinct = set(codes(report))
        assert {"TPX102", "TPX201", "TPX220", "TPX301"} <= distinct
        assert len({c for c in distinct if c}) >= 3
        assert report.has_errors


# ---------------------------------------------------------------------------
# Runner gate
# ---------------------------------------------------------------------------


class _StubScheduler(Scheduler[dict]):
    def __init__(self, session_name: str, **kwargs):
        super().__init__("stub", session_name)
        self._counter = 0
        self.apps: dict[str, AppState] = {}

    def run_opts(self) -> runopts:
        return runopts()

    def _submit_dryrun(self, app: AppDef, cfg: Mapping[str, CfgVal]):
        return AppDryRunInfo({"app": app})

    def schedule(self, dryrun_info) -> str:
        self._counter += 1
        app_id = f"stub_app_{self._counter}"
        self.apps[app_id] = AppState.RUNNING
        return app_id

    def describe(self, app_id: str) -> Optional[DescribeAppResponse]:
        if app_id not in self.apps:
            return None
        return DescribeAppResponse(app_id=app_id, state=self.apps[app_id])

    def _cancel_existing(self, app_id: str) -> None:
        self.apps[app_id] = AppState.CANCELLED

    def list(self):
        return [ListAppResponse(app_id=a, state=s) for a, s in self.apps.items()]


@pytest.fixture
def runner():
    stub = _StubScheduler("test")
    r = Runner("test", {"stub": lambda session_name, **kw: stub})
    yield r
    r.close()


class TestRunnerGate:
    def test_submit_refuses_broken_app(self, runner):
        with pytest.raises(LintError) as ei:
            runner.run(broken_app(), "stub")
        report = ei.value.report
        # stub has no capability profile, so TPX301 drops out, but the
        # AppDef-intrinsic errors survive
        assert {"TPX102", "TPX201", "TPX220"} <= set(codes(report))

    def test_dryrun_refuses_broken_app(self, runner):
        with pytest.raises(LintError):
            runner.dryrun(broken_app(), "stub")

    def test_no_lint_flag_bypasses(self, runner):
        handle = runner.run(broken_app(), "stub", no_lint=True)
        assert handle.startswith("stub://")

    def test_env_escape_hatch(self, runner, monkeypatch):
        monkeypatch.setenv("TPX_NO_LINT", "1")
        handle = runner.run(broken_app(), "stub")
        assert handle.startswith("stub://")

    def test_clean_app_passes_gate(self, runner):
        handle = runner.run(app_with(), "stub")
        assert handle.startswith("stub://")

    def test_warnings_do_not_gate(self, runner):
        # reserved-prefix env is only a warning
        handle = runner.run(app_with(env={"TPX_MY_KNOB": "x"}), "stub")
        assert handle.startswith("stub://")


# ---------------------------------------------------------------------------
# Builtin components pass their own linter (satellite)
# ---------------------------------------------------------------------------


class TestBuiltinSelfLint:
    @pytest.mark.parametrize("name", sorted(get_components()))
    def test_builtin_component_is_clean(self, name):
        report = analyze_component(name)
        assert not report.errors, report.render()
        assert not report.warnings, report.render()


# ---------------------------------------------------------------------------
# file_linter: codes, string annotations, PEP 604 unions (satellite)
# ---------------------------------------------------------------------------


class TestFileLinter:
    def test_string_annotations_accepted(self):
        src = (
            "def c(x: 'str', n: \"int\" = 1) -> 'AppDef':\n"
            '    """A component.\n\n    Args:\n        x: x.\n        n: n.\n    """\n'
        )
        assert validate_source(src, "c") == []

    def test_pep604_unions_accepted(self):
        src = (
            "def c(x: str | None = None, ns: list[str] | None = None) -> AppDef:\n"
            '    """A component.\n\n    Args:\n        x: x.\n        ns: ns.\n    """\n'
        )
        assert validate_source(src, "c") == []

    def test_missing_annotation_code(self):
        msgs = validate_source('def c(x) -> AppDef:\n    """D."""\n', "c")
        assert [m.code for m in msgs] == ["TPX002"]

    def test_kwargs_code(self):
        msgs = validate_source('def c(**kw: str) -> AppDef:\n    """D."""\n', "c")
        assert "TPX004" in [m.code for m in msgs]

    def test_bad_return_code(self):
        msgs = validate_source('def c() -> int:\n    """D."""\n', "c")
        assert "TPX005" in [m.code for m in msgs]

    def test_docstring_warning_only_with_include_warnings(self):
        src = "def c() -> AppDef:\n    pass\n"
        assert validate_source(src, "c") == []
        warnings = validate_source(src, "c", include_warnings=True)
        assert [m.code for m in warnings] == ["TPX006"]

    def test_syntax_error_code(self):
        msgs = validate_source("def c(:\n", "c")
        assert [m.code for m in msgs] == ["TPX001"]


# ---------------------------------------------------------------------------
# CLI: tpx lint (flags before the target — REMAINDER swallows the rest)
# ---------------------------------------------------------------------------


class TestCmdLint:
    def _run(self, argv, capsys):
        with pytest.raises(SystemExit) as ei:
            main(argv)
        out = capsys.readouterr()
        return ei.value.code or 0, out.out, out.err

    def test_lint_clean_component(self, capsys):
        rc, out, _ = self._run(["lint", "utils.echo"], capsys)
        assert rc == 0
        assert "clean" in out

    def test_lint_bad_appdef_json_text(self, tmp_path, capsys):
        path = tmp_path / "bad.json"
        path.write_text(json.dumps(appdef_to_dict(broken_app())))
        rc, out, _ = self._run(["lint", "-s", "tpu_vm", str(path)], capsys)
        assert rc == 1
        for code in ("TPX102", "TPX201", "TPX220", "TPX301"):
            assert code in out

    def test_lint_bad_appdef_json_json(self, tmp_path, capsys):
        path = tmp_path / "bad.json"
        path.write_text(json.dumps(appdef_to_dict(broken_app())))
        rc, out, _ = self._run(
            ["lint", "-s", "tpu_vm", "--json", str(path)], capsys
        )
        assert rc == 1
        doc = json.loads(out)
        assert doc["version"] == 1
        assert doc["scheduler"] == "tpu_vm"
        assert doc["summary"]["error"] >= 3
        assert len({d["code"] for d in doc["diagnostics"]}) >= 3

    def test_lint_good_appdef_json(self, tmp_path, capsys):
        path = tmp_path / "good.json"
        path.write_text(json.dumps(appdef_to_dict(app_with())))
        rc, out, _ = self._run(["lint", "-s", "local", str(path)], capsys)
        assert rc == 0
        assert "clean" in out

    def test_lint_unknown_scheduler_is_usage_error(self, capsys):
        rc, _, err = self._run(["lint", "-s", "nope", "utils.echo"], capsys)
        assert rc == 2
        assert "unknown scheduler" in err

    def test_lint_no_target_is_usage_error(self, capsys):
        rc, _, err = self._run(["lint"], capsys)
        assert rc == 2
        assert "target" in err

    def test_lint_unreadable_json_is_usage_error(self, tmp_path, capsys):
        rc, _, err = self._run(["lint", str(tmp_path / "missing.json")], capsys)
        assert rc == 2

    def test_lint_component_with_args_lints_appdef(self, capsys):
        rc, out, _ = self._run(
            ["lint", "-s", "local", "--", "utils.echo", "--msg", "hi"], capsys
        )
        assert rc == 0

    def test_lint_component_without_required_args_is_info(self, capsys):
        # dist.ddp needs --script; materialization fails -> TPX007 info, rc 0
        rc, out, _ = self._run(["lint", "dist.ddp"], capsys)
        assert rc == 0
        assert "TPX007" in out


class TestRunNoLintFlag:
    def test_run_dryrun_refuses_broken_stdin_spec(self, tmp_path, capsys, monkeypatch):
        import io
        import sys as _sys

        spec = json.dumps(appdef_to_dict(broken_app()))
        monkeypatch.setattr(_sys, "stdin", io.StringIO(spec))
        with pytest.raises(SystemExit) as ei:
            main(["run", "-s", "local", "--dryrun", "--stdin"])
        assert ei.value.code == 1
        assert "preflight lint" in capsys.readouterr().err

    def test_run_dryrun_no_lint_bypasses(self, tmp_path, capsys, monkeypatch):
        import io
        import sys as _sys

        spec = json.dumps(appdef_to_dict(broken_app()))
        monkeypatch.setattr(_sys, "stdin", io.StringIO(spec))
        main(["run", "-s", "local", "--dryrun", "--no-lint", "--stdin"])
        assert "=== APPLICATION ===" in capsys.readouterr().out


class TestRecoveryRules:
    def test_checkpoint_resume_without_ckpt_flag_warns(self):
        policy = SupervisorPolicy(checkpoint_dir="/ckpt", max_preemptions=2)
        report = analyze(app_with(), policy=policy)
        assert "TPX503" in codes(report)
        d = next(d for d in report.diagnostics if d.code == "TPX503")
        assert d.severity is Severity.WARNING
        assert "step 0" in d.message
        assert "--ckpt-dir /ckpt" in d.hint

    def test_role_passing_a_ckpt_flag_is_coherent(self):
        policy = SupervisorPolicy(checkpoint_dir="/ckpt", max_preemptions=2)
        report = analyze(
            app_with(args=["--ckpt-dir", "/ckpt"]), policy=policy
        )
        assert "TPX503" not in codes(report)
        # = -joined and snake_case spellings count too
        report = analyze(
            app_with(args=["--checkpoint-dir=/ckpt"]), policy=policy
        )
        assert "TPX503" not in codes(report)
        report = analyze(app_with(args=["--ckpt_dir", "/c"]), policy=policy)
        assert "TPX503" not in codes(report)

    def test_silent_without_checkpoint_dir_or_resume_budgets(self):
        # no checkpoint_dir: nothing to resume from — not this rule's beat
        report = analyze(app_with(), policy=SupervisorPolicy(max_preemptions=5))
        assert "TPX503" not in codes(report)
        # checkpoint_dir but zero resume-relevant budgets: never resubmits
        quiet = SupervisorPolicy(
            checkpoint_dir="/ckpt",
            max_preemptions=0,
            max_infra_retries=0,
            max_hang_retries=0,
        )
        assert "TPX503" not in codes(analyze(app_with(), policy=quiet))
        # no policy at all
        assert "TPX503" not in codes(analyze(app_with()))

    def test_hang_budget_alone_arms_the_rule(self):
        policy = SupervisorPolicy(
            checkpoint_dir="/ckpt",
            max_preemptions=0,
            max_infra_retries=0,
            max_hang_retries=2,
        )
        assert "TPX503" in codes(analyze(app_with(), policy=policy))
