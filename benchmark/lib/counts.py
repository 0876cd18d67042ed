"""Operations and bytes the algorithm needs, worked out from shapes.

The benchmark's own count, not the program's: causal attention is counted at
``s/2`` keys per query, the output head counts and the embedding lookup does
not (an untied table does no matmul), a mixture-of-experts layer counts its
``experts_per_token`` experts and its router, and nothing that is recomputed
(remat) is counted.

The four public counts ask the configuration's model kind
(``kinds/<kind>.py``), which knows what a layer of that kind holds. The
arithmetic that kinds share is below them: a decoder of equal layers with
grouped-query attention, given one layer's matmul weights.
"""

from __future__ import annotations

from . import kinds


def param_count(c: dict) -> int:
    """All parameters: layers with norms, embedding, final norm, head."""
    return kinds.of(c).param_count(c)


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward plus backward FLOPs per trained token at sequence length ``seq``."""
    return kinds.of(c).train_flops_per_token(c, seq)


def forward_flops_per_token(c: dict, keys: float, head: bool = True) -> float:
    """Forward FLOPs of one token whose query attends ``keys`` positions: 2 per
    active matmul weight, scores and values over ``keys``; ``head`` False leaves
    the output head out (a prompt's positions but the last need none). A third
    of :func:`train_flops_per_token` at a sequence of ``2 x keys``, where every
    layer attends all that came before."""
    return kinds.of(c).forward_flops_per_token(c, keys, head)


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """Bytes of attention state one cached token holds, over all layers."""
    return kinds.of(c).kv_bytes_per_token(c, dtype_bytes)


def decode_step_bytes(
    c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2
) -> float:
    """Least bytes one decode step must read."""
    return kinds.of(c).decode_step_bytes(c, slots_active, tokens_held, dtype_bytes)


# -- shared arithmetic: equal layers, grouped-query attention ----------------


def gqa_dims(c: dict) -> tuple[int, int, int, int, int, int, int]:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    kvh = c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return d, h, kvh, hd, c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"]


def gqa_attention_params(c: dict) -> int:
    """Matmul weights of one layer's attention projections."""
    d, h, kvh, hd, _, _, _ = gqa_dims(c)
    return d * h * hd + 2 * d * kvh * hd + h * hd * d


def decoder_param_count(c: dict, layer_matmul_params: int) -> int:
    """Layers of ``layer_matmul_params`` matmul weights and two norms each,
    embedding, final norm, head."""
    d, _, _, _, _, L, v = gqa_dims(c)
    total = L * (layer_matmul_params + 2 * d) + v * d + d
    if not c.get("tie_word_embeddings", False):
        total += d * v
    return total


def decoder_forward_flops_per_token(c: dict, keys: float, active_layer_matmul_params: int, head: bool = True) -> float:
    """2 per active matmul weight (layers and, with ``head``, the head), plus
    scores and values over ``keys`` positions, ``2 * 2 * d`` each."""
    d, h, _, hd, _, L, v = gqa_dims(c)
    matmul = L * active_layer_matmul_params + (d * v if head else 0)
    return 2.0 * matmul + L * 2 * 2 * (h * hd) * keys


def decoder_train_flops_per_token(c: dict, seq: int, active_layer_matmul_params: int) -> float:
    """Forward and twice that backward: 6 per active matmul weight (layers and
    head), plus causal attention, whose query sees ``seq/2`` keys on average."""
    return 3.0 * decoder_forward_flops_per_token(c, seq / 2, active_layer_matmul_params)


def gqa_kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    _, _, kvh, hd, _, L, _ = gqa_dims(c)
    return L * 2 * kvh * hd * dtype_bytes


def decoder_decode_step_bytes(
    c: dict, layer_matmul_params: int, slots_active: float, tokens_held: float, dtype_bytes: int = 2
) -> float:
    """Every layer weight and the head once, one embedding row per active
    slot, and the K/V of every token the active slots hold."""
    d, _, _, _, _, L, v = gqa_dims(c)
    weights = L * (layer_matmul_params + 2 * d) + d + d * v
    return (
        weights * dtype_bytes
        + slots_active * d * dtype_bytes
        + tokens_held * gqa_kv_bytes_per_token(c, dtype_bytes)
    )
