"""Model kind ``tied``, as a later PR would bring one: a dense decoder whose
output head is its embedding, under GPT-2's key names. The program's
``LlamaConfig`` already runs it (``tie_embeddings``); nothing the benchmark
had reads these keys. ``tests/test_extend.py`` copies this file in as new.
"""

from __future__ import annotations

from typing import Any

from benchmark.lib import models

REFERENCE = "tied"


def _dims(c: dict) -> tuple[int, int, int, int, int, int, int]:
    d, h = c["n_embd"], c["n_head"]
    return d, h, c["n_head_kv"], d // h, c["n_inner"], c["n_layer"], c["vocab_size"]


def program_config(config: dict, **overrides: Any):
    from torchx_tpu.models import llama

    d, h, kvh, _, f, L, v = _dims(config)
    kw = dict(
        vocab_size=v, dim=d, n_layers=L, n_heads=h, n_kv_heads=kvh, ffn_dim=f,
        rope_theta=float(config["rotary_emb_base"]), norm_eps=float(config["layer_norm_epsilon"]),
        tie_embeddings=True, dtype=models._dtype(config),
    )
    return llama.LlamaConfig(**dict(kw, **overrides))


def weight_shapes(config: dict) -> dict:
    d, h, kvh, hd, f, L, v = _dims(config)
    layers = {
        "attn_norm": ((L, d), 0), "mlp_norm": ((L, d), 0),
        "wq": ((L, d, h * hd), d), "wk": ((L, d, kvh * hd), d), "wv": ((L, d, kvh * hd), d),
        "wo": ((L, h * hd, d), h * hd),
        "w_gate": ((L, d, f), d), "w_up": ((L, d, f), d), "w_down": ((L, f, d), f),
    }
    # the table doubles as the head, at the deviation the model states
    embed = ((v, d), ("normal", float(config["initializer_range"])))
    return {"embed": embed, "layers": layers, "final_norm": ((d,), 0)}


def _layer_matmul_params(c: dict) -> int:
    d, h, kvh, hd, f, _, _ = _dims(c)
    return d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * f


def param_count(c: dict) -> int:
    d, _, _, _, _, L, v = _dims(c)
    return L * (_layer_matmul_params(c) + 2 * d) + v * d + d


def train_flops_per_token(c: dict, seq: int) -> float:
    d, _, _, _, _, L, v = _dims(c)
    return 6.0 * (L * _layer_matmul_params(c) + d * v) + 3.0 * L * 2 * 2 * d * (seq / 2)


def forward_flops_per_token(c: dict, keys: float, head: bool = True) -> float:
    d, _, _, _, _, L, v = _dims(c)
    return 2.0 * (L * _layer_matmul_params(c) + (d * v if head else 0)) + L * 2 * 2 * d * keys


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    _, _, kvh, hd, _, L, _ = _dims(c)
    return L * 2 * kvh * hd * dtype_bytes


def decode_step_bytes(c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2) -> float:
    """The table is read whole: it is the head."""
    d, _, _, _, _, L, v = _dims(c)
    weights = L * (_layer_matmul_params(c) + 2 * d) + d + d * v
    return weights * dtype_bytes + tokens_held * kv_bytes_per_token(c, dtype_bytes)


def aux_must_be_zero(aux) -> dict:  # noqa: ANN001
    return {}
