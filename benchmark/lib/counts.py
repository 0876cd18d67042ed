"""Operations and bytes the algorithm needs, worked out from shapes.

The benchmark's own count, not the program's: causal attention is counted at
``s/2`` keys per query, the output head counts and the embedding lookup does
not (an untied table does no matmul), a mixture-of-experts layer counts its
``experts_per_token`` experts and its router, and nothing that is recomputed
(remat) is counted.
"""

from __future__ import annotations


def _dims(c: dict) -> tuple[int, int, int, int, int, int, int]:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    kvh = c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return d, h, kvh, hd, c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"]


def layer_matmul_params(c: dict, active_only: bool) -> int:
    """Matmul weights of one layer: attention projections, the FFN (for a
    mixture of experts: router plus the active or all experts)."""
    d, h, kvh, hd, f, _, _ = _dims(c)
    attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
    experts = c.get("num_local_experts", 0)
    if experts:
        n = c["num_experts_per_tok"] if active_only else experts
        return attn + n * 3 * d * f + d * experts
    return attn + 3 * d * f


def param_count(c: dict) -> int:
    """All parameters: layers with norms, embedding, final norm, head."""
    d, _, _, _, _, L, v = _dims(c)
    total = L * (layer_matmul_params(c, active_only=False) + 2 * d) + v * d + d
    if not c.get("tie_word_embeddings", False):
        total += d * v
    return total


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward plus backward FLOPs per trained token at sequence length
    ``seq``: 6 per active matmul weight (layers and head), plus causal
    attention, whose scores and values cost ``2 * 2 * d * seq/2`` forward."""
    d, h, _, hd, _, L, v = _dims(c)
    matmul = L * layer_matmul_params(c, active_only=True) + d * v
    attention_fwd = L * 2 * 2 * (h * hd) * (seq / 2)
    return 6.0 * matmul + 3.0 * attention_fwd


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    _, _, kvh, hd, _, L, _ = _dims(c)
    return L * 2 * kvh * hd * dtype_bytes


def decode_step_bytes(
    c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2
) -> float:
    """Least bytes one decode step must read: every layer weight and the head
    once (with 16 slots of 2 experts each all 8 experts are needed in all but
    a few steps, so all experts count), one embedding row per active slot,
    and the K/V of every token the active slots hold."""
    d, _, _, _, _, L, v = _dims(c)
    weights = L * (layer_matmul_params(c, active_only=False) + 2 * d) + d + d * v
    return (
        weights * dtype_bytes
        + slots_active * d * dtype_bytes
        + tokens_held * kv_bytes_per_token(c, dtype_bytes)
    )
