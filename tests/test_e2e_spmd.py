"""Flagship end-to-end: dist.spmd forms a real multi-process JAX mesh.

The TPU analog of the reference's compute_world_size e2e
(torchx/examples/apps/compute_world_size, driven by DistributedTestCase at
test/fixtures.py:253-305): 2 processes x 2 simulated devices rendezvous via
jax.distributed and psum across the global mesh.
"""

import os

import pytest

import torchx_tpu
from torchx_tpu.runner.api import get_runner
from torchx_tpu.specs.api import AppState

EXAMPLE = os.path.join(
    os.path.dirname(torchx_tpu.__file__), "examples", "compute_mesh_size.py"
)


@pytest.mark.e2e
def test_spmd_mesh_formation(tmp_path, one_local_gang):
    with get_runner("spmd-e2e") as runner:
        handle = runner.run_component(
            "dist.spmd",
            ["-j", "2x2", "--script", EXAMPLE],
            "local",
            {"log_dir": str(tmp_path)},
        )
        status = runner.wait(handle, wait_interval=0.5)
        assert status is not None and status.state == AppState.SUCCEEDED, (
            status and status.format()
        )
        for replica in (0, 1):
            lines = list(runner.log_lines(handle, "spmd", replica))
            assert any("computed_mesh_size=4" in ln for ln in lines), lines


@pytest.mark.e2e
def test_ddp_torchrun_world_size(tmp_path):
    """The compat dist.ddp path: torchrun + c10d rendezvous + gloo
    allreduce (the reference's canonical e2e, compute_world_size)."""
    script = os.path.join(
        os.path.dirname(torchx_tpu.__file__),
        "examples",
        "compute_world_size_torch.py",
    )
    with get_runner("ddp-e2e") as runner:
        handle = runner.run_component(
            "dist.ddp",
            ["-j", "1x2", "--script", script],
            "local",
            {"log_dir": str(tmp_path)},
        )
        status = runner.wait(handle, wait_interval=0.5)
        assert status is not None and status.state == AppState.SUCCEEDED, (
            status and status.format()
        )
        lines = list(runner.log_lines(handle, "ddp", 0))
        assert any("computed_world_size=2" in ln for ln in lines), lines


@pytest.mark.e2e
def test_ddp_multinode_deferred_endpoint(tmp_path):
    """2 separate torchrun agents rendezvous through the shell-deferred
    ${TPX_COORDINATOR_HOST:=localhost} endpoint (SURVEY hard-part (a))."""
    script = os.path.join(
        os.path.dirname(torchx_tpu.__file__),
        "examples",
        "compute_world_size_torch.py",
    )
    with get_runner("ddp-mn") as runner:
        handle = runner.run_component(
            "dist.ddp",
            ["-j", "2x1", "--script", script],
            "local",
            {"log_dir": str(tmp_path)},
        )
        status = runner.wait(handle, wait_interval=0.5)
        assert status.state == AppState.SUCCEEDED, status.format()
        lines = list(runner.log_lines(handle, "ddp", 0))
        assert any("computed_world_size=2" in ln for ln in lines), lines


@pytest.mark.e2e
def test_spmd_failure_surfaces_structured_error(tmp_path):
    with get_runner("spmd-e2e-fail") as runner:
        handle = runner.run_component(
            "dist.spmd",
            [
                "-j",
                "1x1",
                "--script",
                EXAMPLE,
                "--env",
                "TPX_EXAMPLE_THROWS=1",
            ],
            "local",
            {"log_dir": str(tmp_path)},
        )
        status = runner.wait(handle, wait_interval=0.5)
        assert status.state == AppState.FAILED
        assert "injected failure" in status.structured_error_msg


@pytest.mark.e2e
def test_spmd_retry_restarts_failed_gang(tmp_path, one_local_gang):
    """Fault-injected replica death + max_retries: the gang restarts and
    the SECOND attempt forms the full mesh (BASELINE: retry policies
    actually restart a failed gang, proven end-to-end)."""
    marker = tmp_path / "fault-fired"
    with get_runner("spmd-e2e-retry") as runner:
        handle = runner.run_component(
            "dist.spmd",
            [
                "-j",
                "2x2",
                "--script",
                EXAMPLE,
                "--max_retries",
                "1",
                "--env",
                f"TPX_EXAMPLE_THROWS=once:{marker},TPX_EXAMPLE_THROWS_REPLICA=1",
            ],
            "local",
            {"log_dir": str(tmp_path)},
        )
        status = runner.wait(handle, wait_interval=0.5)
        assert status is not None and status.state == AppState.SUCCEEDED, (
            status and status.format()
        )
        assert marker.exists()  # the fault really fired on attempt 0
        for replica in (0, 1):
            lines = list(runner.log_lines(handle, "spmd", replica))
            assert any("computed_mesh_size=4" in ln for ln in lines), lines


@pytest.mark.e2e
def test_resize_resumes_training_from_checkpoint(tmp_path, one_local_gang):
    """BASELINE config 4, operator-driven: `resize` a live 2-process SPMD
    training gang down to 1; the restarted world re-forms jax.distributed,
    resumes from the checkpoint, and finishes."""
    import time

    ckpt = tmp_path / "ckpt"
    with get_runner("resize-e2e") as runner:
        handle = runner.run_component(
            "dist.spmd",
            [
                "-j", "2x1",
                "-m", "torchx_tpu.examples.train_llama",
                "--",
                "--config", "tiny",
                "--mesh", "dp=-1,fsdp=1",
                "--batch", "4",
                "--seq", "32",
                "--steps", "300",
                "--ckpt-dir", str(ckpt),
                "--ckpt-every", "20",
            ],
            "local",
            {"log_dir": str(tmp_path)},
        )
        def finalized_step() -> bool:
            # orbax writes async saves into *.orbax-checkpoint-tmp-* staging
            # dirs first; only a committed digit-named step dir (or pickle
            # step file) counts as a durable checkpoint
            if not ckpt.exists():
                return False
            return any(
                p.name.isdigit() or p.name.startswith("step_")
                for p in ckpt.iterdir()
            )

        # wait until training is underway and a checkpoint landed
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if finalized_step():
                break
            status = runner.status(handle)
            assert status is not None and not status.is_terminal(), (
                status and status.format()
            )
            time.sleep(0.5)
        else:
            raise TimeoutError("no checkpoint appeared")
        runner.resize(handle, "spmd", 1)
        status = runner.wait(handle, wait_interval=0.5)
        assert status is not None and status.state == AppState.SUCCEEDED, (
            status and status.format()
        )
        lines = list(runner.log_lines(handle, "spmd", 0))
        assert any("resumed from checkpoint step" in ln for ln in lines), lines
        # exactly one replica in the resized terminal gang
        (rs,) = status.roles
        assert len(rs.replicas) == 1
