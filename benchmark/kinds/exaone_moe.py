"""Model kind ``exaone_moe``: K-EXAONE-236B-A23B's block as its ``config.json``
publishes it, served as **one chip of eight**. Grouped-query attention (64 query
/ 8 K/V heads of 128 over a hidden size of 6,144) whose layers alternate by
``layer_types``: three ``sliding_attention`` layers (a window of
``sliding_window`` keys) then one ``full_attention`` layer; layer 0 a dense
SwiGLU, every later layer ``num_experts`` routed experts of
``moe_intermediate_size`` (top ``num_experts_per_tok`` of a sigmoid router,
renormalised, times ``routed_scaling_factor``) beside one shared expert. The
program's ``MoEConfig`` runs it (``torchx_tpu/models/llama.py``, ``moe.py``,
``generate.py``).

**The share.** ``num_experts`` in the configuration's file is what this chip
holds (16, ids ``experts_held_from`` onward); ``published_num_experts`` (128) is
the router's width. Routing is over all 128 as published; the experts that live
on the other seven chips are not computed and nothing stands in for them or for
the exchange: what they would add to a layer's output is left out, here and in
``reference/exaone_moe.py`` alike, and that partial sum goes on to the next
layer. ``vocab_size`` is one of eight slices of ``published_vocab_size``.

What the keys do not fix, taken from the family's convention (``assumed`` in
the file) and held alike by program and reference: RMSNorm over each head of
``q`` and ``k`` with a learned gain; rotary embedding on sliding layers only,
pairs ``(i, i + 64)``; RMSNorm ahead of attention and ahead of the FFN; a
selection bias that chooses and never weighs, a seeded normal of deviation
``assumed_router_bias_std``.

**Not built**: the multi-token-prediction module (``num_nextn_predict_layers``
1, ``mtp_layer_types``: one full-attention block used for self-speculation). It
is no part of the model's own forward pass, and the engine yields one token a
slot a step. Grouped routing (``n_group`` 1) is the identity here.

The counts below are the least a step must read or multiply: window rows, not
context rows, on sliding layers; of the held experts those the active slots
are expected to reach, not all held.
"""

from __future__ import annotations

from typing import Any

from benchmark.lib import models

REFERENCE = "exaone_moe"  # reference/exaone_moe.py: logits, mean_nll

_LAYER_TYPES = {"sliding_attention": "sliding", "full_attention": "full"}


def _dims(c: dict) -> dict:
    kinds = [_LAYER_TYPES[t] for t in c["layer_types"]]
    return dict(
        d=c["hidden_size"], h=c["num_attention_heads"], kvh=c["num_key_value_heads"], hd=c["head_dim"],
        L=c["num_hidden_layers"], v=c["vocab_size"], f=c["intermediate_size"], fe=c["moe_intermediate_size"],
        held=c["num_experts"], E=c.get("published_num_experts", c["num_experts"]), k=c["num_experts_per_tok"],
        shared=c["num_shared_experts"], nd=c["first_k_dense_replace"], window=c["sliding_window"],
        n_sliding=kinds.count("sliding"), n_full=kinds.count("full"),
    )  # fmt: skip


def program_config(config: dict, **overrides: Any):
    """The program's ``MoEConfig`` from the published keys; what the program
    does not build is refused here, not ignored (the MTP block excepted, which
    the docstring above says is left out)."""
    from torchx_tpu.models import moe

    for key, only in (("n_group", 1), ("topk_group", 1), ("norm_topk_prob", True), ("scoring_func", "sigmoid"),
                      ("hidden_act", "silu"), ("tie_word_embeddings", False)):  # fmt: skip
        if config.get(key, only) != only:
            raise ValueError(f"the program runs {key} = {only!r} only, not {config[key]!r}")
    m = _dims(config)
    if config["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("the program runs rope_type = 'default' only")
    if len(config["layer_types"]) != m["L"] or config["mlp_layer_types"] != ["dense"] * m["nd"] + ["sparse"] * (m["L"] - m["nd"]):
        raise ValueError("layer_types and mlp_layer_types must name num_hidden_layers layers, the dense ones first")
    if any((w != 0) != (t == "sliding_attention") or w not in (0, m["window"])
           for w, t in zip(config["sliding_windows"], config["layer_types"])):  # fmt: skip
        raise ValueError("sliding_windows must give sliding_window on sliding layers and 0 on full ones")
    kw = dict(
        vocab_size=m["v"], dim=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=m["kvh"], attn_head_dim=m["hd"],
        ffn_dim=m["f"], rope_theta=float(config["rope_parameters"]["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), tie_embeddings=False, dtype=models._dtype(config),
        layer_types=tuple(_LAYER_TYPES[t] for t in config["layer_types"]), sliding_window=m["window"],
        qk_norm=True, rope_full_layers=False,
        n_experts=m["E"], experts_held=m["held"] if m["held"] != m["E"] else 0,
        experts_held_from=int(config.get("experts_held_from", 0)), top_k=m["k"], expert_ffn_dim=m["fe"],
        n_shared_experts=m["shared"], router_score="sigmoid", router_bias=True,
        routed_scale=float(config["routed_scaling_factor"]), n_dense_layers=m["nd"],
        capacity_factor=0.0,  # as published no routing is dropped
    )  # fmt: skip
    kw.update(overrides)
    return moe.MoEConfig(**kw)


def weight_shapes(config: dict) -> dict:
    """The parameter tree as the program lays it out: two groups of equal
    layers, each stacked on a leading axis; the routed experts are the held
    ones, the router and its bias as wide as published. A leaf is ``(shape, init)``."""
    m = _dims(config)
    d, h, kvh, hd = m["d"], m["h"], m["kvh"], m["hd"]

    def attention(L: int) -> dict:
        return {
            "attn_norm": ((L, d), 0),
            "wq": ((L, d, h * hd), d), "wk": ((L, d, kvh * hd), d), "wv": ((L, d, kvh * hd), d),
            "wo": ((L, h * hd, d), h * hd),
            "q_norm": ((L, hd), 0), "k_norm": ((L, hd), 0),
            "mlp_norm": ((L, d), 0),
        }  # fmt: skip

    nd, Le, E, held, fe, fs = m["nd"], m["L"] - m["nd"], m["E"], m["held"], m["fe"], m["shared"] * m["fe"]
    dense = dict(attention(nd), w_gate=((nd, d, m["f"]), d), w_up=((nd, d, m["f"]), d),
                 w_down=((nd, m["f"], d), m["f"]))  # fmt: skip
    expert = dict(
        attention(Le),
        w_router=((Le, d, E), d),
        router_bias=((Le, E), ("normal", float(config["assumed_router_bias_std"]))),
        w_gate=((Le, held, d, fe), d), w_up=((Le, held, d, fe), d), w_down=((Le, held, fe, d), fe),
        ws_gate=((Le, d, fs), d), ws_up=((Le, d, fs), d), ws_down=((Le, fs, d), fs),
    )  # fmt: skip
    tree = {"embed": ((m["v"], d), d), "layers": expert, "final_norm": ((d,), 0), "lm_head": ((d, m["v"]), d)}
    if nd:
        tree["dense_layers"] = dense
    return tree


# -- counts --------------------------------------------------------------------


def attention_params(c: dict) -> int:
    """Matmul weights of one layer's attention: W_q, W_k, W_v, W_o."""
    m = _dims(c)
    return m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kvh"] * m["hd"] + m["h"] * m["hd"] * m["d"]


def expert_params(c: dict) -> int:
    """One routed expert's three matrices."""
    m = _dims(c)
    return 3 * m["d"] * m["fe"]


def expert_layer_params(c: dict, experts: float) -> float:
    """Matmul weights of a sparse layer's FFN with ``experts`` routed experts
    counted: those, the shared expert, the router (as wide as published) and its bias."""
    m = _dims(c)
    return (experts + m["shared"]) * expert_params(c) + m["d"] * m["E"] + m["E"]


def held_experts_reached(c: dict, rows: float) -> float:
    """Expected number of a layer's **held** experts that ``rows`` tokens reach,
    each choosing ``k`` of the ``E`` published evenly: ``held (1 - (1 - k/E)^rows)``."""
    m = _dims(c)
    return m["held"] * (1.0 - (1.0 - m["k"] / m["E"]) ** rows)


def _norms(c: dict) -> int:
    m = _dims(c)
    return 2 * m["d"] + 2 * m["hd"]  # attn_norm, mlp_norm, q_norm, k_norm


def _stack_params(c: dict, experts: float) -> float:
    """Every layer's weights with ``experts`` routed experts counted a sparse layer."""
    m = _dims(c)
    each = attention_params(c) + _norms(c)
    return m["L"] * each + m["nd"] * 3 * m["d"] * m["f"] + (m["L"] - m["nd"]) * expert_layer_params(c, experts)


def param_count(c: dict) -> int:
    """What this chip holds: the held experts, the vocabulary's slice."""
    m = _dims(c)
    return int(_stack_params(c, m["held"])) + 2 * m["v"] * m["d"] + m["d"]


def _attention_keys(c: dict, seq: float) -> float:
    """Keys a query attends on average over a sequence of ``seq``, summed over
    the layers: ``seq / 2`` on a full layer, at most the window on a sliding one."""
    m = _dims(c)
    w = min(m["window"], seq)
    sliding = (w * (w + 1) / 2 + (seq - w) * w) / seq  # positions below the window see fewer
    return m["n_full"] * seq / 2 + m["n_sliding"] * sliding


def _active_matmul_params(c: dict, head: bool = True) -> float:
    """Matmul weights one token multiplies on this chip: of its ``k`` routings
    the ``held / E`` expected to land here, the shared expert, everything
    outside the experts, with ``head`` the head's slice; no norm, no bias."""
    m = _dims(c)
    active = m["k"] * m["held"] / m["E"]
    return (_stack_params(c, active) - m["L"] * _norms(c) - (m["L"] - m["nd"]) * m["E"]
            + (m["d"] * m["v"] if head else 0))  # fmt: skip


def train_flops_per_token(c: dict, seq: int) -> float:
    """6 per active matmul weight of this chip's share (of a token's ``k``
    routings the ``held / E`` that land here, the shared expert, the head's
    slice), plus causal and windowed attention, forward and twice that backward."""
    m = _dims(c)
    return 6.0 * _active_matmul_params(c) + 3.0 * 2 * 2 * m["h"] * m["hd"] * _attention_keys(c, seq)


def forward_flops_per_token(c: dict, keys: float, head: bool = True) -> float:
    """The forward FLOPs this chip spends on a token that has ``keys``
    positions to attend: all of them on a full layer, at most the window on a
    sliding one. The experts a token's routings reach **here** count (``k held
    / E`` = 1 expected), not the ``k`` it picks over the eight chips: the other
    seven's work is done on no chip of this cell."""
    m = _dims(c)
    attended = m["n_full"] * keys + m["n_sliding"] * min(m["window"], keys)
    return 2.0 * _active_matmul_params(c, head) + 2 * 2 * m["h"] * m["hd"] * attended


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What a further token of context costs: K and V on the full layers. A
    sliding layer keeps its window whatever the context (:func:`window_bytes_per_slot`)."""
    m = _dims(c)
    return m["n_full"] * 2 * m["kvh"] * m["hd"] * dtype_bytes


def window_bytes_per_slot(c: dict, rows: float, dtype_bytes: int = 2) -> float:
    """K and V a slot of ``rows`` tokens must read on the sliding layers: its
    last ``sliding_window`` rows, or all it has."""
    m = _dims(c)
    return m["n_sliding"] * min(m["window"], rows) * 2 * m["kvh"] * m["hd"] * dtype_bytes


def decode_attention_bytes(c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2) -> float:
    """K/V rows one decode step must read: every held token on the full layers,
    the window's rows of every slot on the sliding ones."""
    rows = tokens_held / max(slots_active, 1e-9)
    return tokens_held * kv_bytes_per_token(c, dtype_bytes) + slots_active * window_bytes_per_slot(c, rows, dtype_bytes)


def decode_held_expert_bytes(c: dict, slots_active: float, dtype_bytes: int = 2) -> float:
    """Bytes of held experts that ``slots_active`` tokens are expected to reach, all sparse layers."""
    m = _dims(c)
    return (m["L"] - m["nd"]) * held_experts_reached(c, slots_active) * expert_params(c) * dtype_bytes


def decode_step_bytes(c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must read: every weight outside the routed
    experts once, of each sparse layer the held experts that ``slots_active``
    tokens are expected to reach, the head's slice, one embedding row a slot,
    and the K/V rows of :func:`decode_attention_bytes`."""
    m = _dims(c)
    weights = _stack_params(c, held_experts_reached(c, slots_active)) + m["d"] + m["d"] * m["v"]
    return (weights + slots_active * m["d"]) * dtype_bytes + decode_attention_bytes(c, slots_active, tokens_held, dtype_bytes)


def prefill_expert_flops_per_token(c: dict) -> float:
    """Forward FLOPs the expert FFNs of all sparse layers spend here on one
    token: of its ``k`` routings the ``held / E`` expected to land on this
    chip, and the shared expert, 2 a multiply-add."""
    m = _dims(c)
    return (m["L"] - m["nd"]) * 2.0 * (m["k"] * m["held"] / m["E"] + m["shared"]) * expert_params(c)


def prefill_attention_flops(c: dict, cached: float, tokens: float) -> float:
    """Forward FLOPs of scores and weighted values for a row that prefills
    ``tokens`` positions behind ``cached`` ones: the query at position ``p``
    multiplies ``p + 1`` keys on a full layer and ``min(p + 1, window)`` on a
    sliding one, 2 x 2 x heads x head width each."""
    m = _dims(c)
    full = tokens * cached + tokens * (tokens + 1) / 2
    ramp = max(0.0, min(cached + tokens, m["window"]) - cached)  # positions that still see fewer than a window
    sliding = ramp * cached + ramp * (ramp + 1) / 2 + (tokens - ramp) * m["window"]
    return 2 * 2 * m["h"] * m["hd"] * (m["n_full"] * full + m["n_sliding"] * sliding)


def aux_must_be_zero(aux) -> dict:  # noqa: ANN001
    """The router's overflow: the dropless dispatch reports 0 by construction,
    and a run in which it does not is not this model."""
    from torchx_tpu.models import llama

    return {"router_overflow": float(aux[llama.AUX_OVERFLOW])}
