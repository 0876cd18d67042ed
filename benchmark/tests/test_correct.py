"""``correct`` comes out false when it should: for the control (the reference
in fp8 in the program's place, at a size a test can hold) and for a timed path
broken underneath. These drive a whole run, without the look for a chip."""

import jax.numpy as jnp

from benchmark.lib import serve_cell, spec, train_cell

from .conftest import FIXTURES


def run(name, control=None):
    cell = spec.load_cell(name, FIXTURES)
    runner = train_cell if cell.kind == "train" else serve_cell
    return runner.run(cell, 13, 1.0, False, 0.0, allow_cpu=True, control=control)


def test_training_control_fails_a_limit_and_the_program_does_not():
    rec = run("tiny-train", control="fp8")
    limits = spec.load_cell("tiny-train", FIXTURES).check
    assert rec["verdict"].correct
    for numbers in rec["controls"].values():  # the reference in fp8, the program's int8 path
        assert any(numbers[k] > limits[f"{k}_limit"] for k in numbers)


def test_serving_control_fails_a_limit_and_the_program_does_not():
    rec = run("tiny-chat", control="fp8")
    limits = spec.load_cell("tiny-chat", FIXTURES).check
    assert rec["verdict"].correct
    assert any(v > limits.get(f"{k}_limit", float("inf")) for k, v in rec["control"].items())


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax

    from torchx_tpu.examples import train_llama as tl

    real = tl.make_train_step

    def broken(cfg, mesh, optimizer, **kw):
        inner = real(cfg, mesh, optimizer, **kw)

        def step(state, batch):
            _, loss, aux = inner.__wrapped__(state, batch)
            return state, loss, aux

        return jax.jit(step)

    monkeypatch.setattr(tl, "make_train_step", broken)
    rec = run("tiny-train")
    assert not rec["verdict"].correct
    over = {name for name, v, lim in rec["verdict"].rows if v > lim}
    assert "param_change_norm_gap" in over


def test_a_batch_half_left_out_is_not_correct(monkeypatch):
    from torchx_tpu.models import llama

    real = llama.loss_and_aux
    monkeypatch.setattr(
        llama, "loss_and_aux",
        lambda p, batch, cfg, mesh=None: real(p, {"tokens": batch["tokens"][:1]}, cfg, mesh))
    rec = run("tiny-train")
    assert not rec["verdict"].correct


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from torchx_tpu.models import generate as gen

    real = gen.paged_decode_step

    def altered(params, tokens, positions, tables, pools, cfg, keys, temps):
        nxt, pools = real(params, tokens, positions, tables, pools, cfg, keys, temps)
        return (nxt + 1) % cfg.vocab_size, pools

    monkeypatch.setattr(gen, "paged_decode_step", altered)
    rec = run("tiny-chat")
    assert not rec["verdict"].correct


def test_tokens_altered_at_a_few_positions_are_over_the_share_limit(monkeypatch):
    from torchx_tpu.models import generate as gen

    real = gen.paged_decode_step

    def altered(params, tokens, positions, tables, pools, cfg, keys, temps):
        nxt, pools = real(params, tokens, positions, tables, pools, cfg, keys, temps)
        return jnp.where(positions % 4 == 0, (nxt + 1) % cfg.vocab_size, nxt), pools

    monkeypatch.setattr(gen, "paged_decode_step", altered)
    rec = run("tiny-backlog")
    assert not rec["verdict"].correct
    assert "served_gap_over_share" in {name for name, v, lim in rec["verdict"].rows if v > lim}


def test_the_sample_holds_the_longest_and_the_requests_decoding_together():
    def req(i, t_first, t_done, n):
        return {"done": True, "error": None, "prompt": [i] * 4, "generated": [i] * n,
                "t_first": t_first, "t_done": t_done}

    # 20 requests, four at a time, one group after another; a longer one later on
    snap = [req(i, 10.0 * (i // 4), 10.0 * (i // 4) + 10.0, 3) for i in range(20)]
    snap.append(req(50, 100.0, 110.0, 9))
    snap.append(dict(req(99, 0.0, 50.0, 5), done=False))  # unfinished: never sampled
    for seed in range(8):
        sample, together = serve_cell.sample_finished(snap, 5, seed, 0.0, 50.0)
        ids = [s["prompt"][0] for s in sample]
        assert ids[0] == 50 and len(set(ids)) == 5 and together == 4
        assert len({i // 4 for i in ids[1:]}) == 1  # four slots at one instant
    sample, together = serve_cell.sample_finished(snap, 7, 3, 0.0, 50.0)
    assert len(sample) == 7 and together == 4  # filled up with others, drawn from the seed
    assert sample == serve_cell.sample_finished(snap, 7, 3, 0.0, 50.0)[0]
