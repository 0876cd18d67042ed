"""Model kind ``llama``: a dense decoder of equal layers, grouped-query
attention and a SwiGLU, under the keys of a published Llama- or Mistral-style
``config.json``. The program's ``LlamaConfig`` runs it.
"""

from __future__ import annotations

from typing import Any

from benchmark.lib import counts, models

REFERENCE = "model"  # reference/model.py: logits, mean_nll


def program_kwargs(config: dict) -> dict:
    """The fields of ``LlamaConfig`` from the published keys."""
    if config.get("sliding_window"):
        raise ValueError("the program has no sliding-window attention")
    return dict(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=models._dtype(config),
    )


def program_config(config: dict, **overrides: Any):
    from torchx_tpu.models import llama

    return llama.LlamaConfig(**dict(program_kwargs(config), **overrides))


def weight_shapes(config: dict) -> dict:
    """The parameter tree as the program lays it out: layers stacked on a
    leading axis. Each leaf is ``(shape, fan_in)``, fan-in 0 for a norm gain."""
    d, h, kvh, hd, f, L, v = counts.gqa_dims(config)
    layers = {
        "attn_norm": ((L, d), 0),
        "wq": ((L, d, h * hd), d),
        "wk": ((L, d, kvh * hd), d),
        "wv": ((L, d, kvh * hd), d),
        "wo": ((L, h * hd, d), h * hd),
        "mlp_norm": ((L, d), 0),
        "w_gate": ((L, d, f), d),
        "w_up": ((L, d, f), d),
        "w_down": ((L, f, d), f),
    }
    tree = {"embed": ((v, d), d), "layers": layers, "final_norm": ((d,), 0)}
    if not config.get("tie_word_embeddings", False):
        tree["lm_head"] = ((d, v), d)
    return tree


def layer_matmul_params(c: dict) -> int:
    """Matmul weights of one layer: attention projections and the FFN."""
    d, _, _, _, f, _, _ = counts.gqa_dims(c)
    return counts.gqa_attention_params(c) + 3 * d * f


def param_count(c: dict) -> int:
    return counts.decoder_param_count(c, layer_matmul_params(c))


def train_flops_per_token(c: dict, seq: int) -> float:
    return counts.decoder_train_flops_per_token(c, seq, layer_matmul_params(c))


def forward_flops_per_token(c: dict, keys: float, head: bool = True) -> float:
    return counts.decoder_forward_flops_per_token(c, keys, layer_matmul_params(c), head)


kv_bytes_per_token = counts.gqa_kv_bytes_per_token


def decode_step_bytes(c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2) -> float:
    return counts.decoder_decode_step_bytes(c, layer_matmul_params(c), slots_active, tokens_held, dtype_bytes)


def aux_must_be_zero(aux) -> dict:  # noqa: ANN001
    """Readings of a training step's ``aux`` that make a run not correct
    unless they are 0: a dense layer has none."""
    return {}
