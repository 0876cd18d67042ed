"""Model kind ``moe``: Mixtral's block, the dense kind's layer with its SwiGLU
replaced by ``num_local_experts`` of them behind a softmax top-k router. The
program's ``MoEConfig`` runs it.
"""

from __future__ import annotations

from typing import Any

from benchmark.lib import counts, kinds

_dense = kinds.load("llama")  # everything around the experts is the dense kind's

REFERENCE = "model"  # reference/model.py takes the expert branch where a layer has a router


def program_config(config: dict, **overrides: Any):
    from torchx_tpu.models import moe

    kw = _dense.program_kwargs(config)
    kw.update(
        n_experts=config["num_local_experts"],
        top_k=config["num_experts_per_tok"],
        capacity_factor=float(config["deployment"]["capacity_factor"]),
    )
    kw.update(overrides)
    return moe.MoEConfig(**kw)


def weight_shapes(config: dict) -> dict:
    """The dense tree with the experts on the axis after the layers', and the router."""
    d, _, _, _, _, L, _ = counts.gqa_dims(config)
    E = config["num_local_experts"]
    tree = _dense.weight_shapes(config)
    layers = tree["layers"]
    for name in ("w_gate", "w_up", "w_down"):
        (_, *rest), fan_in = layers[name]
        layers[name] = ((L, E, *rest), fan_in)
    layers["w_router"] = ((L, d, E), d)
    return tree


def layer_matmul_params(c: dict, active_only: bool) -> int:
    """Matmul weights of one layer: attention projections, the router, and
    the active or all experts."""
    d, _, _, _, f, _, _ = counts.gqa_dims(c)
    experts = c["num_local_experts"]
    n = c["num_experts_per_tok"] if active_only else experts
    return counts.gqa_attention_params(c) + n * 3 * d * f + d * experts


def param_count(c: dict) -> int:
    return counts.decoder_param_count(c, layer_matmul_params(c, active_only=False))


def train_flops_per_token(c: dict, seq: int) -> float:
    return counts.decoder_train_flops_per_token(c, seq, layer_matmul_params(c, active_only=True))


def forward_flops_per_token(c: dict, keys: float, head: bool = True) -> float:
    return counts.decoder_forward_flops_per_token(c, keys, layer_matmul_params(c, active_only=True), head)


kv_bytes_per_token = counts.gqa_kv_bytes_per_token


def decode_step_bytes(c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2) -> float:
    """With 16 slots of 2 experts each all 8 experts are needed in all but a
    few steps, so all experts count."""
    return counts.decoder_decode_step_bytes(
        c, layer_matmul_params(c, active_only=False), slots_active, tokens_held, dtype_bytes)


def aux_must_be_zero(aux) -> dict:  # noqa: ANN001
    """The router's overflow: a routing dropped for want of capacity is a
    different model, so a run that drops one is not correct."""
    from torchx_tpu.models import llama

    return {"router_overflow": float(aux[llama.AUX_OVERFLOW])}
