"""The trainer is a library under ``torchx_tpu/train/``: which way its
imports point, that the launched entry defines nothing of its own, and
that ``train()`` is its stages called in order."""

from __future__ import annotations

import inspect
import os
import runpy
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# train()'s result at a full run and at a single-step smoke, as PR 28 had
# them (bench.py, tune/measure.py, __graft_entry__.py, test_bring_up and
# benchmark/train_call.py read them); "grad_bucket_trials" and "profile"
# join only when --grad-bucket-mb auto / --profile ask for them
SMOKE_KEYS = {
    "platform", "device_kind", "device_count", "attention", "norm_residual",
    "largest_param_shards", "loss", "tokens_per_sec", "tokens_per_sec_per_chip",
    "mfu", "launch_to_first_step_s", "launch_breakdown", "remat_policy",
    "kernels", "grad_bucket_mb", "grad_buckets",
}
RESULT_KEYS = SMOKE_KEYS | {
    "final_step", "resumed_from_step", "step_time_s", "data_wait_s",
    "data_wait_frac", "prefetch_depth", "preempted",
}


@pytest.fixture(scope="module")
def graph():
    from torchx_tpu.analyze.selfcheck.graph import build_graph

    return build_graph(os.path.join(REPO, "torchx_tpu"), "torchx_tpu", REPO)


def _targets(graph, mod, edges=("eager", "lazy")):
    return {e.target for kind in edges for e in getattr(graph, kind).get(mod, ())}


def test_nothing_outside_examples_imports_examples(graph):
    importers = {
        mod
        for mod in graph.modules
        if not mod.startswith("torchx_tpu.examples")
        and any(t.startswith("torchx_tpu.examples") for t in _targets(graph, mod))
    }
    assert importers == set()


def test_the_step_knows_the_model_the_mesh_and_the_scope_names_only(graph):
    def within(target, allowed):
        # an allowed module, something inside it, or a package on the way to it
        return any(
            target == a or target.startswith(a + ".") or a.startswith(target + ".")
            for a in allowed
        )

    allowed = ("torchx_tpu.models", "torchx_tpu.obs.hot", "torchx_tpu.parallel.mesh")
    step = "torchx_tpu.train.step"
    assert [t for t in _targets(graph, step, ("eager",)) if not within(t, allowed)] == []
    # the one import inside a function: the bucketed gradient sync, taken
    # only by a step that was given a bucket plan
    lazy = allowed + ("torchx_tpu.parallel.overlap",)
    assert [t for t in _targets(graph, step, ("lazy",)) if not within(t, lazy)] == []
    # and what imports the step is not imported by it, however far down
    closure = graph.eager_closure(step)
    assert not closure & {"torchx_tpu.train.run", "torchx_tpu.train.report",
                          "torchx_tpu.parallel.aot_fit"}


def test_the_entry_run_as_main_defines_no_class_and_no_step(monkeypatch, capsys):
    """The launcher runs the entry with ``runpy`` under the name
    ``__main__``, which executes the file a second time: whatever it defines
    then exists twice, and a checkpoint's restore target built from one
    ``TrainState`` does not fit a step compiled for the other."""
    from torchx_tpu.train import step

    entry = "torchx_tpu.examples.train_llama"
    monkeypatch.setattr(sys, "argv", [entry, "--help"])
    with pytest.raises(SystemExit) as stop:
        runpy.run_module(entry, run_name="__main__", alter_sys=True)
    assert stop.value.code == 0 and "--ckpt-dir" in capsys.readouterr().out

    ns = runpy.run_module(entry, run_name="__twin__")  # same file, main() not called
    assert ns["TrainState"] is step.TrainState
    assert ns["make_train_step"] is step.make_train_step
    own = [
        name
        for name, v in ns.items()
        if getattr(v, "__module__", None) == "__twin__"
        and (inspect.isclass(v) or hasattr(v, "lower"))  # a class, a jitted function
    ]
    assert own == []


def _stages(cfg, mesh_config, batch, seq, steps, **kw):
    from torchx_tpu.train import run as tr

    run = tr.resolve(cfg, mesh_config, batch, seq)
    pending = tr.open_io(run, **kw)
    tr.compile_step(run, pending.lower_state)
    tr.join(run, pending)
    loss, first_step_s = tr.first_step(run)
    timed = tr.loop(run, steps)
    return run, tr.summarize(run, loss, first_step_s, timed)


def test_train_is_its_stages_in_order():
    from torchx_tpu.models import llama
    from torchx_tpu.parallel.mesh import MeshConfig
    from torchx_tpu.train.run import train

    cfg, mesh_config = llama.llama_tiny(), MeshConfig(dp=1, fsdp=-1, tp=1, sp=1)
    whole = train(cfg, mesh_config, batch=8, seq=32, steps=5)
    run, staged = _stages(cfg, mesh_config, 8, 32, 5)
    assert set(whole) == set(staged) == RESULT_KEYS
    assert staged["loss"] == whole["loss"]
    assert staged["final_step"] == whole["final_step"] == 5
    # what a caller holds between the stages: the state and the compiled step
    assert int(run.state.step) == 5 and callable(run.step_fn)
    assert set(whole["launch_breakdown"]) == {
        "import", "backend_init", "init_state", "compile", "first_step",
    }
    smoke = train(cfg, mesh_config, batch=8, seq=32, steps=1)
    assert set(smoke) == SMOKE_KEYS


def test_resume_with_a_corpus_restores_and_streams_together(tmp_path):
    """A resumed run with ``--data`` has the restore thread and the corpus
    thread in flight at once, each under its own copy of the context; one
    copy entered twice fails the second thread before it starts."""
    from torchx_tpu.models import llama
    from torchx_tpu.parallel.mesh import MeshConfig
    from torchx_tpu.train.run import train

    cfg, mesh_config = llama.llama_tiny(), MeshConfig(dp=1, fsdp=-1, tp=1, sp=1)
    corpus = tmp_path / "tokens.bin"
    np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=1 << 16, dtype=np.uint32
    ).tofile(corpus)
    kw = dict(ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2, data_path=str(corpus))
    first = train(cfg, mesh_config, batch=8, seq=32, steps=3, **kw)
    assert first["final_step"] == 3 and first["resumed_from_step"] == 0
    again = train(cfg, mesh_config, batch=8, seq=32, steps=3, **kw)
    assert again["resumed_from_step"] == 3 and again["final_step"] == 6
    assert {"restore", "data_setup"} <= set(again["launch_breakdown"])
    assert "init_state" not in again["launch_breakdown"]
