"""Test configuration: force CPU JAX with 8 virtual devices.

Mirrors the reference's distributed-without-a-cluster strategy
(torchx/test/fixtures.py:253-305) using XLA's host-platform device-count
flag so mesh/sharding tests run anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("TPX_EVENT_DESTINATION", "null")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_registries(tmp_path, monkeypatch):
    """Keep per-user registry files (~/.tpx_local_apps, ~/.tpxslurmjobdirs),
    supervisor ledgers, and the obs trace/metrics sinks out of the real
    home during tests. Control-plane breakers are process-global state and
    must not leak trips between tests."""
    monkeypatch.setenv("TPX_OBS_DIR", str(tmp_path / "obs"))
    monkeypatch.setenv("TPX_SUPERVISOR_DIR", str(tmp_path / "supervisor"))
    from torchx_tpu.resilience import call as resilience_call
    from torchx_tpu.resilience import faults as resilience_faults

    resilience_call.reset_breakers()
    resilience_faults.reset()
    monkeypatch.setattr(
        "torchx_tpu.schedulers.local_scheduler._registry_path",
        lambda: str(tmp_path / "tpx_local_apps"),
        raising=False,
    )
    monkeypatch.setattr(
        "torchx_tpu.schedulers.slurm_scheduler._registry_path",
        lambda: str(tmp_path / "tpx_slurm_dirs"),
        raising=False,
    )


@pytest.fixture
def one_local_gang():
    """One gang at a time on the launcher's coordinator port. The local scheduler gives every
    app's ``jax.distributed`` coordinator the one port ``settings.TPX_COORDINATOR_PORT``, and two
    coordinators listen on it side by side: a process of one gang then reaches the other's
    ("task 1 unexpectedly tried to connect with a different incarnation"), which failed
    ``test_docs.py::test_quickstart_local_path_executes`` beside ``test_e2e_spmd.py`` on another
    worker (two gangs at once by hand: two of six failed). Tests that start a gang of two or
    more processes take this lock, which holds across the workers' processes."""
    import fcntl
    import tempfile

    from torchx_tpu import settings

    path = os.path.join(tempfile.gettempdir(), f"tpx-test-coordinator-{settings.TPX_COORDINATOR_PORT}.lock")
    with open(path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        yield


def _early_stop_case(generate, max_new, prompt_len=3):
    """A prompt on which "stop at this id" can be told from both "stop at
    the first token" and "never stop": ``(prompt, full, cut)``, where
    ``full = generate(prompt, max_new)`` is the prompt and its greedy
    continuation, and ``full[cut - 1]`` is a token the continuation emits
    for the first time at its third position or later and before its last.
    A run given that id as EOS must return ``full[:cut]``. A continuation
    that repeats one token (``406, 406, 406``) has no such position: every
    id it holds stops it at its first token, so the next prompt is tried."""
    for start in range(1, 64):
        prompt = list(range(start, start + prompt_len))
        full = generate(prompt, max_new)
        for cut in range(prompt_len + 3, len(full)):
            if full[cut - 1] not in full[: cut - 1]:
                return prompt, full, cut
    raise AssertionError("no prompt's continuation emits a new token third or later")


@pytest.fixture(scope="session")
def early_stop_case():
    """:func:`_early_stop_case`, for the EOS tests of the three servers."""
    return _early_stop_case
