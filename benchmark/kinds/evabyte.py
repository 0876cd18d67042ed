"""Model kind ``evabyte``: EvaByte's block as its ``config.json`` (``model_type``
``evabyte``, ``attention_class`` ``eva``) publishes it: a byte-level dense decoder
whose attention reads, in one softmax, the exact keys of the byte's own aligned
window of ``window_size`` and one pooled row of every ``chunk_size`` bytes of every
window before it, pooled with two learned vectors a head; RMSNorm gains stored
about zero (``norm_add_unit_offset``), residual adds in float32 (``fp32_skip_add``),
float32 logits, and ``num_pred_heads`` output heads side by side of which serving
reads the first. ``reference/evabyte.py`` writes the equations out. The program's
``LlamaConfig`` runs it (``torchx_tpu/models/eva.py``, ``llama.py``, ``generate.py``,
``serve/kv_pool.py::EvaTables``): what a sequence holds in the paged pool is not
its tokens but its window's rows and the pooled rows behind it.

The config's keys give ``W``, ``C``, the class name and the head count and not the
forms: those are under the configuration's ``assumed`` and held alike by program
and reference (aligned and not sliding windows; pooling weights ``softmax(phi . k)``
over a chunk's roped keys; ``mu`` added to the pooled key alone; visibility from
the next window on).

**How the weights are drawn** (:func:`weight_shapes`; the reason is the check). Every
matrix at ``fan_in^-0.5`` as in the other cells: the stream is normed to unit size
ahead of each projection, so ``q`` and ``k`` have entries of order 1, scores ``q . k
/ sqrt(hd)`` a deviation of 1, and logits a deviation of 1. ``phi`` and ``mu`` are
published as a clamped normal times ``hd^-0.5`` and drawn as a normal of that
deviation: ``phi . k_j`` then has a deviation of 1 over a chunk's 16 keys, so the
pooling weights differ by factors of ``e`` (a ``phi`` of zeros would make every
pooling a flat mean, and a program that pooled with the wrong weights would pass).
A pooled key is a weighted mean of 16 keys and about a third of a key's length:
the pooled rows' scores have a deviation of ~0.35 against the window's 1, and with
256 to 640 pooled rows beside ~1,024 exact ones they take 15 to 30% of a query's
softmax: a program that lost them (``scripts/calibrate_evabyte.py --drop-pooled``)
moves every logit behind the first window. ``mu`` is a twelfth of a key's length
and moves a pooled score by ~0.09: the CPU tests hold it at float32's tolerance,
the chip's check cannot see it. The norms' gains are drawn about zero with
``assumed_norm_gain_std`` (zeros, the published initial value, would hide a gain
read as ``g`` and not ``1 + g`` only where nothing else did: the stream would be
zero).

The counts are the least a step must read or multiply. The harness hands a kind
the slots' summed tokens and no more, and a slot's rows, ``(W / C) (t // W) + t % W
+ 1``, are not a function of that sum: :func:`rows_attended` takes the rows a slot
of the mean context holds at a uniform phase of its window.
"""

from __future__ import annotations

from typing import Any

from benchmark.lib import counts, models

REFERENCE = "evabyte"  # reference/evabyte.py

#: keys whose other value the program does not build: refused, not ignored
_ONLY = {
    "attention_bias": False, "attention_class": "eva", "hidden_act": "silu", "rope_scaling": None,
    "tie_word_embeddings": False, "fp32_logits": True, "num_chunks": None,
}  # fmt: skip


def program_config(config: dict, **overrides: Any):
    """The program's ``LlamaConfig`` from the published keys."""
    from torchx_tpu.models import llama

    for key, only in _ONLY.items():
        if config.get(key, only) != only:
            raise ValueError(f"the program builds {key} = {only!r} only, not {config[key]!r}")
    d, h, kvh, hd, f, L, v = counts.gqa_dims(config)
    return llama.LlamaConfig(**{**dict(
        vocab_size=v,
        dim=d,
        n_layers=L,
        n_heads=h,
        n_kv_heads=kvh,
        attn_head_dim=hd,
        ffn_dim=f,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=False,
        dtype=models._dtype(config),
        eva_window=int(config["window_size"]),
        eva_chunk=int(config["chunk_size"]),
        norm_unit_offset=bool(config["norm_add_unit_offset"]),
        fp32_skip_add=bool(config["fp32_skip_add"]),
        pred_heads=int(config["num_pred_heads"]),
    ), **overrides})  # fmt: skip


def weight_shapes(config: dict) -> dict:
    """The parameter tree as the program lays it out (``llama.init_params`` with
    EVA attention), each leaf ``(shape, init)``; the module's docstring says how
    they are drawn and why."""
    d, h, kvh, hd, f, L, v = counts.gqa_dims(config)
    gain = ("normal", float(config["assumed_norm_gain_std"]))  # stored about 0, applied as 1 + g
    vector = ("normal", hd**-0.5)
    layers = {
        "attn_norm": ((L, d), gain),
        "wq": ((L, d, h * hd), d),
        "wk": ((L, d, kvh * hd), d),
        "wv": ((L, d, kvh * hd), d),
        "wo": ((L, h * hd, d), h * hd),
        "eva_phi": ((L, kvh, hd), vector),
        "eva_mu_k": ((L, kvh, hd), vector),
        "mlp_norm": ((L, d), gain),
        "w_gate": ((L, d, f), d),
        "w_up": ((L, d, f), d),
        "w_down": ((L, f, d), f),
    }
    return {
        "embed": ((v, d), d),
        "layers": layers,
        "final_norm": ((d,), gain),
        "lm_head": ((d, int(config["num_pred_heads"]) * v), d),
    }


# -- counts --------------------------------------------------------------------


def layer_matmul_params(c: dict) -> int:
    """One layer's weights but its two norms: attention's projections, ``phi``
    and ``mu`` of every cache head, the SwiGLU."""
    d, _, kvh, hd, f, _, _ = counts.gqa_dims(c)
    return counts.gqa_attention_params(c) + 2 * kvh * hd + 3 * d * f


def param_count(c: dict) -> int:
    """All parameters held: the layers, the embedding, the final norm and every
    output head."""
    d, _, _, _, _, L, v = counts.gqa_dims(c)
    return L * (layer_matmul_params(c) + 2 * d) + v * d + d + d * v * int(c["num_pred_heads"])


def rows_attended(c: dict, context: float) -> float:
    """Rows a position at ``context`` reads in a layer: itself and what is before
    it inside one window; behind the first, ``W / C`` pooled rows a finished
    window and its own window's rows up to itself. ``context`` is a mean over
    slots, and the rows are not linear in it: this takes a slot at the mean at a
    uniform phase of its window, ``context / C + (1 - 1 / C) (W - 1) / 2 + 1``.
    ``benchmark/tests/test_evabyte_kind.py`` holds it to the exact mean over drawn
    sets of contexts."""
    window, chunk = c["window_size"], c["chunk_size"]
    if context < window:
        return context + 1.0
    return context / chunk + (1.0 - 1.0 / chunk) * (window - 1) / 2.0 + 1.0


def forward_flops_per_token(c: dict, keys: float, head: bool = True) -> float:
    """2 per matmul weight of the layers and of head 0 (the other heads'
    columns are not multiplied in serving), scores and values over the rows a
    position at ``keys`` really attends (:func:`rows_attended`), and its share
    of the pooling: a sixteenth of a chunk's weights and two weighted sums."""
    d, h, kvh, hd, _, L, v = counts.gqa_dims(c)
    matmul = L * layer_matmul_params(c) + (d * v if head else 0)
    pooling = L * 3 * 2 * kvh * hd  # phi . k, a k, a v: a multiply-add an element a position
    return 2.0 * matmul + L * 2 * 2 * (h * hd) * rows_attended(c, keys) + pooling


def train_flops_per_token(c: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(c, seq / 2)


def row_bytes(c: dict, dtype_bytes: int = 2) -> int:
    """One row of the cache over all layers: K and V of every cache head. A
    pooled row has a key's shape."""
    return counts.gqa_kv_bytes_per_token(c, dtype_bytes)


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What a byte of context costs for ever: its share of its chunk's pooled
    row. (While it is in the window it costs a whole row: :func:`window_bytes_per_slot`.)"""
    return row_bytes(c, dtype_bytes) // c["chunk_size"]


def window_bytes_per_slot(c: dict, dtype_bytes: int = 2) -> int:
    """A whole window's rows and the staging of its pooled rows: the most a slot
    holds that does not grow with its context."""
    return (c["window_size"] + c["window_size"] // c["chunk_size"]) * row_bytes(c, dtype_bytes)


def pool_op_bytes(c: dict, slots_active: float, dtype_bytes: int = 2) -> float:
    """Least bytes the pooling moves in one decode step: a slot fills a chunk
    every ``C`` steps, and then reads its ``C`` rows and writes one."""
    chunk = c["chunk_size"]
    return slots_active / chunk * (chunk + 1) * row_bytes(c, dtype_bytes)


def decode_step_bytes(c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must move: every layer weight and head 0
    once, one embedding row a slot, the rows each slot attends
    (:func:`rows_attended` at the slots' mean context, from the cache
    coordinate: not ``tokens_held`` rows) and the pooling's."""
    d, _, _, _, _, L, v = counts.gqa_dims(c)
    weights = L * (layer_matmul_params(c) + 2 * d) + d + d * v
    rows = slots_active * rows_attended(c, tokens_held / slots_active) if slots_active else 0.0
    return (weights + slots_active * d) * dtype_bytes + rows * row_bytes(c, dtype_bytes) + pool_op_bytes(c, slots_active, dtype_bytes)


def aux_must_be_zero(aux) -> dict:  # noqa: ANN001
    """A dense layer has nothing a training step's ``aux`` must hold at 0."""
    return {}
