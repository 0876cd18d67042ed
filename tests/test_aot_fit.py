"""CI gate for the north-star memory fit.

Compiles the real llama3_8b training step — the exact config the 45%-MFU
v5p-32 claim uses, modulo the attention kernel — on the virtual-device CPU
backend and asserts the compiler's per-device memory fits v5p HBM. The CPU
backend's xla-attention fallback materializes [b, h, s, s] logits that the
TPU splash kernel never does, so a fit HERE is a conservative upper bound
of the fit on the real slice. scripts/aot_memory_fit.py runs the same
machinery against the true v5p topology when a TPU PJRT plugin is present;
its measured table lives in docs/performance.md.
"""

from __future__ import annotations

import dataclasses

import jax
import pytest

from torchx_tpu.parallel.aot_fit import (
    DEFAULT_HEADROOM,
    V5P_HBM_BYTES,
    compile_fit,
    model_state_bytes_per_device,
    north_star_cfg,
)
from torchx_tpu.parallel.mesh import MeshConfig, make_mesh

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-virtual-device CPU mesh"
)


def _mesh():
    return make_mesh(MeshConfig(fsdp=4, tp=2), devices=jax.devices()[:8])


class TestAbstractState:
    def test_state_shardings_cover_every_leaf(self):
        from torchx_tpu.train.step import abstract_train_state, make_optimizer
        from torchx_tpu.models import llama

        cfg = llama.llama_tiny()
        mesh = _mesh()
        state = abstract_train_state(cfg, mesh, make_optimizer())
        leaves = jax.tree.leaves(state)
        assert len(leaves) > 10  # params + mu + nu + counters
        for leaf in leaves:
            assert leaf.sharding.mesh is mesh
        # Adam's mu/nu mirror the params specs: spot-check one layer leaf
        import jax.tree_util as jtu

        flat = dict(jtu.tree_flatten_with_path(state)[0])

        def spec_of(path_substr):
            for path, leaf in jtu.tree_flatten_with_path(state)[0]:
                if path_substr in jtu.keystr(path):
                    return leaf.sharding.spec
            raise AssertionError(path_substr)

        assert flat is not None
        wq_spec = spec_of("params['layers']['wq']")
        mu_wq_spec = spec_of("mu['layers']['wq']")
        assert wq_spec == mu_wq_spec

    def test_model_state_analytic_matches_sharded_args(self):
        """The per-device argument bytes the compiler reports must match
        the analytic params+moments accounting (within the replicated
        scalars + token buffer)."""
        from torchx_tpu.models import llama

        cfg = llama.llama_tiny()
        mesh = _mesh()
        r = compile_fit(cfg, mesh, batch=8, seq=128)
        analytic = model_state_bytes_per_device(
            dataclasses.replace(cfg), mesh.devices.size
        )
        # tiny model: norms replicate (not fsdp-sharded), so allow 2x slack
        assert r.args_bytes < analytic * 4 + 1 * 1024 * 1024
        assert r.args_bytes > analytic // 4
        assert r.peak_bytes > 0
        assert r.fits


class TestMoEFit:
    def test_moe_family_dispatch(self):
        """compile_fit must route MoE configs through moe.init_params /
        moe.param_specs (the dense specs lack w_router — regression from
        the Mixtral v5p fit run)."""
        from torchx_tpu.models import moe

        cfg = moe.moe_tiny()
        r = compile_fit(cfg, _mesh(), batch=8, seq=128)
        assert r.peak_bytes > 0
        assert r.fits


@pytest.mark.integ
class TestNorthStarFit:
    """llama3_8b on the intended v5p-32 sharding (fsdp x tp), CPU upper
    bound. Marked integ: one 8B AOT compile (~1-2 min on CI CPUs)."""

    def test_llama3_8b_fits_v5p(self):
        cfg = north_star_cfg(attn_impl="auto")  # auto -> xla off-TPU
        mesh = _mesh()
        # 8 virtual devices model half the v5p-32 slice; per-device model
        # state is therefore 2x the real slice's -> still an upper bound
        r = compile_fit(cfg, mesh, batch=8, seq=4096)
        assert r.fits, (
            f"north-star config does not fit v5p HBM: peak "
            f"{r.peak_bytes / 2**30:.1f} GiB/dev vs "
            f"{V5P_HBM_BYTES * DEFAULT_HEADROOM / 2**30:.0f} GiB budget"
        )
        # model state alone (params + Adam moments over 8 devices) is
        # ~6 GiB/dev; the compiler's argument accounting must see it
        analytic = model_state_bytes_per_device(cfg, mesh.devices.size)
        assert r.args_bytes > analytic * 0.8
