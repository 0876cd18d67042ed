"""The check's comparison reduced where the logits are made (PR 39):
``serve_cell.served_gaps`` gives the head the served positions alone, a slice
at a time, and must return the numbers the whole ``[padded, vocab]`` logits
give (``served_gaps_full`` below: the comparison as it was until then), in
memory that no request's length moves."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import kinds, models, serve_cell

from .conftest import FIXTURES

KINDS = ["tiny-dense", "tiny-moe", "tiny-mla-moe", "tiny-exaone-moe", "tiny-mla-moe-hc"]


def config(name):
    with open(os.path.join(FIXTURES, "configs", f"{name}.json")) as f:
        return dict(json.load(f), bench_dir=FIXTURES)


def served_gaps_full(params, c, sample, quant=None):
    """``serve_cell.served_gaps`` as it was until PR 39, over the reference's whole ``[1, padded, vocab]``
    logits: ``(padded + 2 x answer) x vocab x 4`` bytes at once, which is why no run makes them any more."""
    ref = kinds.reference(c)
    gaps = []
    for s in sample:
        toks, n_p, n_g = serve_cell._padded_tokens(s), len(s["prompt"]), len(s["generated"])
        lg = ref.logits(params, toks, c)[0, n_p - 1 : n_p - 1 + n_g]
        if quant is None:
            served = jnp.asarray(s["generated"], jnp.int32)
        else:
            served = jnp.argmax(ref.logits(params, toks, c, quant)[0, n_p - 1 : n_p - 1 + n_g], axis=-1)
        gaps.extend(np.asarray(jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]).tolist())
    return gaps


def sample_of(c, lengths, seed=3):
    """Requests of ``(prompt, answer)`` lengths, every token drawn from the
    seed: the gaps are large and all differ, which is what a comparison of two
    ways to read them wants."""
    rng = np.random.default_rng(seed)
    out = []
    for n_p, n_g in lengths:
        out.append({"prompt": rng.integers(0, c["vocab_size"], n_p).tolist(),
                    "generated": rng.integers(0, c["vocab_size"], n_g).tolist()})
    return out


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("name", KINDS)
def test_the_reduced_comparison_gives_the_numbers_of_the_full_logits(name, quant, monkeypatch, capsys):
    monkeypatch.setattr(serve_cell, "SLICE", 16)  # several slices and a short last one at a test's lengths
    c = config(name)
    params = models.make_weights(c, 11)
    # a one-token answer, one of exactly a slice, one of two slices and a part
    sample = sample_of(c, [(9, 1), (5, 16), (20, 37)])
    new = serve_cell.served_gaps(params, c, sample, quant)
    old = served_gaps_full(params, c, sample, quant)
    assert len(new) == len(old) == 1 + 16 + 37
    np.testing.assert_allclose(new, old, atol=1e-5, rtol=0)
    assert sum(g > 0 for g in new) == sum(g > 0 for g in old)
    for tolerance in (0.01, 1.0):
        a, b = serve_cell._numbers(new, tolerance), serve_cell._numbers(old, tolerance)
        assert a == pytest.approx(b, abs=1e-5)
    assert "check: memory in use" in capsys.readouterr().out


def test_the_served_token_of_the_reference_reads_a_gap_of_nought():
    c = config("tiny-dense")
    params = models.make_weights(c, 11)
    prompt = list(range(7))
    lg = kinds.reference(c).logits(params, jnp.asarray([prompt + [0] * 3], jnp.int32), c)
    first = int(jnp.argmax(lg[0, len(prompt) - 1]))
    assert serve_cell.served_gaps(params, c, [{"prompt": prompt, "generated": [first]}]) == [0.0]
    other = (first + 1) % c["vocab_size"]
    assert serve_cell.served_gaps(params, c, [{"prompt": prompt, "generated": [other]}])[0] > 0.0


def _compiled_sizes(c, params, sample, quant, monkeypatch):
    """Every program ``served_gaps`` compiled for its slices over ``sample``:
    the shapes of its arguments and its temporary bytes."""
    seen = []
    real = jax.stages.Lowered.compile

    def spy(self, *a, **kw):
        compiled = real(self, *a, **kw)
        seen.append((tuple(str(v.shape) for v in jax.tree.leaves(self.args_info)),
                     compiled.memory_analysis().temp_size_in_bytes))
        return compiled

    monkeypatch.setattr(jax.stages.Lowered, "compile", spy)
    serve_cell.served_gaps(params, c, sample, quant)
    monkeypatch.undo()
    return seen


@pytest.mark.parametrize("quant", [None, "fp8"])
def test_the_comparisons_memory_does_not_grow_with_the_answer(quant, monkeypatch):
    """A 512-position answer and a 3,072-position one go through one program of
    ``SLICE`` positions: the same shapes, the same temporary bytes, and those
    within a few ``[SLICE, vocab]`` float32 arrays (the full logits of the
    longer request alone would be seven times that)."""
    c = config("tiny-mla-moe-hc")
    params = models.make_weights(c, 11)
    assert serve_cell.SLICE == 512
    short = _compiled_sizes(c, params, sample_of(c, [(16, 512)]), quant, monkeypatch)
    long = _compiled_sizes(c, params, sample_of(c, [(16, 3072), (16, 512)]), quant, monkeypatch)
    assert len(short) == len(long) == 1  # one compile a call, not one a length
    assert short == long
    logits_of_a_slice = serve_cell.SLICE * c["vocab_size"] * 4
    assert 0 < long[0][1] <= (4 if quant else 3) * logits_of_a_slice + (1 << 20)
