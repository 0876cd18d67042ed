"""Checkpoint/resume: sharded save/restore + preemption-recovery loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchx_tpu.models import llama
from torchx_tpu.parallel.checkpoint import Checkpointer
from torchx_tpu.parallel.mesh import MeshConfig, make_mesh
from torchx_tpu.train.run import train
from torchx_tpu.train.step import init_state, make_optimizer


class TestCheckpointer:
    def test_save_restore_sharded_state(self, tmp_path):
        cfg = llama.llama_tiny()
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
        opt = make_optimizer(warmup=1)
        state = init_state(cfg, mesh, opt)
        ckpt = Checkpointer(str(tmp_path))
        assert ckpt.save(5, state)
        assert ckpt.latest_step() == 5
        restored = ckpt.restore(5, state)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored arrays carry the same shardings
        assert (
            jax.tree.leaves(restored)[1].sharding.spec
            == jax.tree.leaves(state)[1].sharding.spec
        )
        ckpt.close()

    def test_restore_latest_empty(self, tmp_path):
        ckpt = Checkpointer(str(tmp_path))
        step, state = ckpt.restore_latest({"x": jnp.zeros(3)})
        assert step is None and state is None
        ckpt.close()

    def test_max_to_keep(self, tmp_path):
        ckpt = Checkpointer(str(tmp_path), max_to_keep=2)
        state = {"x": jnp.arange(4.0)}
        for s in (1, 2, 3):
            ckpt.save(s, state)
        assert ckpt.latest_step() == 3
        ckpt.close()


class TestPreemptionRecovery:
    def test_train_resumes_from_checkpoint(self, tmp_path):
        """The BASELINE config-4 loop: run, 'die', relaunch, resume."""
        cfg = llama.llama_tiny()
        mc = MeshConfig(dp=1, fsdp=-1, tp=1, sp=1)
        # first run: 6 steps, checkpoint every 2
        m1 = train(
            cfg, mc, batch=8, seq=32, steps=6,
            ckpt_dir=str(tmp_path), ckpt_every=2, warmup=2, lr=1e-2,
        )
        assert m1["final_step"] == 6
        assert m1["resumed_from_step"] == 0
        # "preempted" relaunch: must resume from the saved step, not 0
        m2 = train(
            cfg, mc, batch=8, seq=32, steps=4,
            ckpt_dir=str(tmp_path), ckpt_every=2, warmup=2, lr=1e-2,
        )
        assert m2["resumed_from_step"] == 6
        assert m2["final_step"] > 6
        # training continued descending from where it left off
        assert m2["loss"] <= m1["loss"] + 0.1


def test_async_save_overlaps_and_restores(tmp_path):
    """Async checkpointing (default): save() returns immediately, wait()
    makes the checkpoint durable, restore round-trips the state."""
    import jax.numpy as jnp

    from torchx_tpu.parallel.checkpoint import Checkpointer

    state = {"w": jnp.arange(16.0).reshape(4, 4), "step": jnp.int32(7)}
    ckpt = Checkpointer(str(tmp_path), async_save=True)
    try:
        assert ckpt.save(1, state)
        # a second save while the first may still be in flight must not
        # corrupt anything (orbax serializes internally)
        state2 = {"w": state["w"] * 2, "step": jnp.int32(8)}
        ckpt.save(2, state2, force=True)
        ckpt.wait()
        assert ckpt.latest_step() == 2
        step, restored = ckpt.restore_latest(state2)
        assert step == 2
        assert float(restored["w"][0, 1]) == 2.0
    finally:
        ckpt.close()


def test_sync_mode_still_supported(tmp_path):
    import jax.numpy as jnp

    from torchx_tpu.parallel.checkpoint import Checkpointer

    ckpt = Checkpointer(str(tmp_path), async_save=False)
    try:
        ckpt.save(1, {"x": jnp.ones(3)})
        assert ckpt.latest_step() == 1
    finally:
        ckpt.close()


class TestRobustness:
    """Edge cases a real preemption leaves behind: partial/corrupt
    checkpoint dirs must not take down the resume path."""

    def _state(self):
        import jax.numpy as jnp

        return {"w": jnp.arange(8, dtype=jnp.float32), "step": jnp.int32(0)}

    def test_restore_falls_back_past_corrupt_latest(self, tmp_path):
        """A preemption mid-write leaves the newest step corrupt; resume
        must fall back to the previous intact step, not die."""
        import jax.numpy as jnp

        from torchx_tpu.parallel.checkpoint import Checkpointer

        ckpt = Checkpointer(str(tmp_path), async_save=False)
        ckpt.save(1, {"w": jnp.full(8, 1.0), "step": jnp.int32(1)})
        ckpt.save(2, {"w": jnp.full(8, 2.0), "step": jnp.int32(2)})
        ckpt.wait()
        ckpt.close()
        # gut step 2's payload (orbax dir "2" or pickle "step_2.pkl")
        corrupted = 0
        for p in tmp_path.iterdir():
            if p.name == "2" or p.name.startswith("step_2"):
                if p.is_file():
                    p.write_bytes(b"truncated")
                    corrupted += 1
                else:
                    for child in p.rglob("*"):
                        if child.is_file():
                            child.write_bytes(b"truncated")
                            corrupted += 1
        assert corrupted, "corruption target not found: layout changed?"
        ckpt2 = Checkpointer(str(tmp_path), async_save=False)
        step, restored = ckpt2.restore_latest(self._state())
        # fell back to the intact step 1 with its REAL data
        assert step == 1
        assert (jax.device_get(restored["w"]) == 1.0).all()
        # the corrupt step was quarantined, so training that resumes from
        # step 1 can SAVE step 2 again (no StepAlreadyExistsError crash
        # loop under gang-restart retries)
        assert ckpt2.save(2, {"w": jnp.full(8, 2.5), "step": jnp.int32(2)})
        ckpt2.wait()
        ckpt2.close()
        ckpt3 = Checkpointer(str(tmp_path))
        step3, restored3 = ckpt3.restore_latest(self._state())
        ckpt3.close()
        assert step3 == 2
        assert (jax.device_get(restored3["w"]) == 2.5).all()
        # the quarantined dir is kept aside as evidence
        assert any(".corrupt" in p.name for p in tmp_path.iterdir())

    def test_all_corrupt_raises_instead_of_reinit(self, tmp_path):
        import pytest as _pytest

        from torchx_tpu.parallel.checkpoint import Checkpointer

        ckpt = Checkpointer(str(tmp_path), async_save=False)
        ckpt.save(1, self._state())
        ckpt.wait()
        ckpt.close()
        for p in tmp_path.rglob("*"):
            if p.is_file():
                p.write_bytes(b"junk")
        ckpt2 = Checkpointer(str(tmp_path), async_save=False)
        with _pytest.raises(RuntimeError, match="failed to restore"):
            ckpt2.restore_latest(self._state())
        ckpt2.close()

    def test_empty_directory_roundtrip(self, tmp_path):
        from torchx_tpu.parallel.checkpoint import Checkpointer

        ckpt = Checkpointer(str(tmp_path / "fresh"))
        step, restored = ckpt.restore_latest(self._state())
        assert restored is None and not step
        ckpt.close()

    def test_save_interval_respected(self, tmp_path):
        from torchx_tpu.parallel.checkpoint import Checkpointer

        state = self._state()
        ckpt = Checkpointer(str(tmp_path), save_interval_steps=5, async_save=False)
        for s in range(1, 12):
            ckpt.save(s, state)
        ckpt.wait()
        ckpt.close()
        ckpt2 = Checkpointer(str(tmp_path))
        step, restored = ckpt2.restore_latest(self._state())
        ckpt2.close()
        # only interval steps persisted; latest is the last multiple of 5
        assert step == 10


def _pickle_ckpt(path, **kw):
    """A Checkpointer forced onto the pickle fallback (the backend that
    owns the snapshot-then-write machinery) even when orbax is present."""
    from torchx_tpu.parallel.checkpoint import Checkpointer

    ckpt = Checkpointer(str(path), **kw)
    if ckpt._mgr is not None:
        ckpt._mgr.close()
        ckpt._mgr = None
        ckpt._ocp = None
    return ckpt


class TestSnapshotThenWrite:
    """Async pickle checkpointing: device→host snapshot fenced in save(),
    serialization/digest/manifest on a background thread."""

    def _state(self, v=1.0):
        import jax.numpy as jnp

        return {"w": jnp.full(8, v), "step": jnp.int32(int(v))}

    def test_background_write_completes_at_wait(self, tmp_path, monkeypatch):
        import threading

        from torchx_tpu.parallel import checkpoint as ckpt_mod

        gate = threading.Event()
        real_write = ckpt_mod.Checkpointer._pickle_write

        def gated_write(self, step, host_state):
            gate.wait(timeout=30)
            real_write(self, step, host_state)

        monkeypatch.setattr(ckpt_mod.Checkpointer, "_pickle_write", gated_write)
        ckpt = _pickle_ckpt(tmp_path, async_save=True)
        assert ckpt.save(1, self._state())
        # save() returned while the writer is gated: nothing on disk yet,
        # which is the point — the step loop is not stalled by the write
        assert not any(p.name.startswith("step_") for p in tmp_path.iterdir())
        gate.set()
        ckpt.wait()
        assert (tmp_path / "step_1.pkl").exists()
        # digest + manifest were finalized by the background thread
        assert ckpt.verify_step(1) is True
        step, restored = ckpt.restore_latest(self._state())
        assert step == 1
        assert (jax.device_get(restored["w"]) == 1.0).all()
        ckpt.close()

    def test_snapshot_is_fenced_before_mutation(self, tmp_path, monkeypatch):
        """The state captured is the state AT save() time, even if the
        caller overwrites its buffers while the write is in flight."""
        import threading

        import numpy as _np

        from torchx_tpu.parallel import checkpoint as ckpt_mod

        gate = threading.Event()
        real_write = ckpt_mod.Checkpointer._pickle_write

        def gated_write(self, step, host_state):
            gate.wait(timeout=30)
            real_write(self, step, host_state)

        monkeypatch.setattr(ckpt_mod.Checkpointer, "_pickle_write", gated_write)
        ckpt = _pickle_ckpt(tmp_path, async_save=True)
        state = {"w": _np.full(8, 3.0)}  # host buffer: mutable in place
        ckpt.save(1, state)
        state["w"][:] = -1.0  # trainer reuses the buffer mid-write
        gate.set()
        ckpt.wait()
        _, restored = ckpt.restore_latest({"w": _np.zeros(8)})
        assert (restored["w"] == 3.0).all()
        ckpt.close()

    def test_crash_mid_background_write_falls_back(self, tmp_path, monkeypatch):
        """Kill mid-background-write: restore_latest falls back to the
        previous verified step and the MANIFEST is never torn."""
        import json as _json

        from torchx_tpu import settings
        from torchx_tpu.parallel import checkpoint as ckpt_mod

        ckpt = _pickle_ckpt(tmp_path, async_save=True)
        ckpt.save(1, self._state(1.0))
        ckpt.wait()

        real_dump = ckpt_mod.pickle.dump

        def dying_dump(obj, f, *a, **kw):
            f.write(b"\x80\x04partial")  # torn bytes land in the .tmp file
            raise OSError("simulated kill mid-write")

        monkeypatch.setattr(ckpt_mod.pickle, "dump", dying_dump)
        ckpt.save(2, self._state(2.0))
        with pytest.raises(RuntimeError, match="background checkpoint write"):
            ckpt.wait()
        monkeypatch.setattr(ckpt_mod.pickle, "dump", real_dump)
        # no torn step file escaped the tmp+rename protocol
        assert not (tmp_path / "step_2.pkl").exists()
        # the manifest is intact JSON and still points at the verified step
        doc = _json.loads(
            (tmp_path / settings.CHECKPOINT_MANIFEST).read_text()
        )
        assert doc["latest_step"] == 1
        ckpt2 = _pickle_ckpt(tmp_path)
        step, restored = ckpt2.restore_latest(self._state())
        assert step == 1
        assert (jax.device_get(restored["w"]) == 1.0).all()
        ckpt2.close()
        ckpt.close()

    def test_writer_error_also_surfaces_at_next_save(self, tmp_path, monkeypatch):
        from torchx_tpu.parallel import checkpoint as ckpt_mod

        ckpt = _pickle_ckpt(tmp_path, async_save=True)

        def dying_dump(obj, f, *a, **kw):
            raise OSError("disk full")

        monkeypatch.setattr(ckpt_mod.pickle, "dump", dying_dump)
        ckpt.save(1, self._state())
        ckpt._writer.join()  # let the failure land before unpatching
        monkeypatch.undo()
        with pytest.raises(RuntimeError, match="background checkpoint write"):
            ckpt.save(2, self._state())
        # latched error cleared: subsequent saves work again
        assert ckpt.save(3, self._state(3.0))
        ckpt.wait()
        assert ckpt.latest_step() == 3
        ckpt.close()

    def test_back_to_back_saves_serialize(self, tmp_path):
        ckpt = _pickle_ckpt(tmp_path, async_save=True, max_to_keep=10)
        for s in range(1, 6):
            assert ckpt.save(s, self._state(float(s)))
        ckpt.wait()
        assert ckpt.latest_step() == 5
        for s in range(1, 6):
            assert ckpt.verify_step(s) is True
        ckpt.close()
