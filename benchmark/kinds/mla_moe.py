"""Model kind ``mla_moe``: DeepSeek-V3's block as Kimi-VL-A3B's language model
publishes it: multi-head latent attention (one normed latent and one rotary
key a token, shared by the heads), ``first_k_dense_replace`` dense layers, then
expert layers of ``n_routed_experts`` small experts behind a sigmoid router
with a selection bias (``noaux_tc``) beside ``n_shared_experts`` shared ones.
The program's ``MoEConfig`` runs it (``torchx_tpu/models/mla.py``, ``moe.py``).

Departures of the program from the published layer, none in the equations:

* rotary pairing: the program rotates dimension ``i`` with ``i + rope/2``, the
  checkpoint pairs ``(2i, 2i+1)``; the weights therefore hold the rotary
  columns of ``W_q`` (each head's) and ``W_kva`` evens first, then odds, and
  ``reference/mla_moe.py`` puts them back before rotating as published;
* the selection bias is a seeded normal of deviation 0.02 where the published
  initial value is 0, so that "the bias chooses, never weighs" is exercised;
* a cached row is padded from 576 to 640 values (whole lanes); the counts
  below charge the 576 the algorithm needs;
* decode attends absorbed (``W_kvb`` folded into query and result) and rounds
  in another order than the expanded form;
* not built: the vision tower and projector (no keys in the catalog row), query
  compression (``q_lora_rank`` null here), grouped routing (``n_group`` 1),
  expert parallelism (``ep_size`` 1), rotary scaling (null).
"""

from __future__ import annotations

from typing import Any

from benchmark.lib import models

REFERENCE = "mla_moe"  # reference/mla_moe.py: logits, mean_nll


def _dims(c: dict) -> dict:
    return dict(
        d=c["hidden_size"], h=c["num_attention_heads"], L=c["num_hidden_layers"], v=c["vocab_size"],
        f=c["intermediate_size"], fe=c["moe_intermediate_size"], E=c["n_routed_experts"],
        k=c["num_experts_per_tok"], shared=c["n_shared_experts"], nd=c["first_k_dense_replace"],
        r=c["kv_lora_rank"], dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"], dv=c["v_head_dim"],
    )  # fmt: skip


def program_config(config: dict, **overrides: Any):
    """The program's ``MoEConfig`` from the published keys; what the program
    does not build is refused here, not ignored."""
    from torchx_tpu.models import moe

    for key, only in (("q_lora_rank", None), ("rope_scaling", None), ("n_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1), ("norm_topk_prob", True), ("attention_bias", False),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"), ("hidden_act", "silu"),
                      ("ep_size", 1)):  # fmt: skip
        if config.get(key, only) != only:
            raise ValueError(f"the program runs {key} = {only!r} only, not {config[key]!r}")
    m = _dims(config)
    kw = dict(
        vocab_size=m["v"], dim=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=config["num_key_value_heads"],
        ffn_dim=m["f"], rope_theta=float(config["rope_theta"]), norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]), dtype=models._dtype(config),
        kv_lora_rank=m["r"], qk_nope_dim=m["dn"], qk_rope_dim=m["dr"], v_head_dim=m["dv"],
        n_experts=m["E"], top_k=m["k"], expert_ffn_dim=m["fe"], n_shared_experts=m["shared"],
        router_score="sigmoid", router_bias=True, routed_scale=float(config["routed_scaling_factor"]),
        n_dense_layers=m["nd"], capacity_factor=0.0,  # as published no routing is dropped
    )  # fmt: skip
    kw.update(overrides)
    return moe.MoEConfig(**kw)


def weight_shapes(config: dict) -> dict:
    """The parameter tree as the program lays it out: two groups of equal
    layers, each stacked on a leading axis. A leaf is ``(shape, init)``."""
    m = _dims(config)
    d, h, r = m["d"], m["h"], m["r"]

    def attention(L: int) -> dict:
        return {
            "attn_norm": ((L, d), 0),
            "wq": ((L, d, h * (m["dn"] + m["dr"])), d),
            "w_kva": ((L, d, r + m["dr"]), d),
            "kv_norm": ((L, r), 0),
            "w_kvb": ((L, r, h * (m["dn"] + m["dv"])), r),
            "wo": ((L, h * m["dv"], d), h * m["dv"]),
            "mlp_norm": ((L, d), 0),
        }

    nd, Le, E, fe, fs = m["nd"], m["L"] - m["nd"], m["E"], m["fe"], m["shared"] * m["fe"]
    dense = dict(attention(nd), w_gate=((nd, d, m["f"]), d), w_up=((nd, d, m["f"]), d),
                 w_down=((nd, m["f"], d), m["f"]))  # fmt: skip
    expert = dict(
        attention(Le),
        w_router=((Le, d, E), d),
        router_bias=((Le, E), ("normal", float(config["assumed_router_bias_std"]))),
        w_gate=((Le, E, d, fe), d), w_up=((Le, E, d, fe), d), w_down=((Le, E, fe, d), fe),
        ws_gate=((Le, d, fs), d), ws_up=((Le, d, fs), d), ws_down=((Le, fs, d), fs),
    )  # fmt: skip
    tree = {"embed": ((m["v"], d), d), "layers": expert, "final_norm": ((d,), 0)}
    if nd:
        tree["dense_layers"] = dense
    if not config.get("tie_word_embeddings", False):
        tree["lm_head"] = ((d, m["v"]), d)
    return tree


# -- counts --------------------------------------------------------------------


def attention_params(c: dict) -> int:
    """Matmul weights of one layer's attention: W_q, W_kva, W_kvb, W_o."""
    m = _dims(c)
    return (m["d"] * m["h"] * (m["dn"] + m["dr"]) + m["d"] * (m["r"] + m["dr"])
            + m["r"] * m["h"] * (m["dn"] + m["dv"]) + m["h"] * m["dv"] * m["d"])  # fmt: skip


def expert_params(c: dict) -> int:
    """One routed expert's three matrices."""
    m = _dims(c)
    return 3 * m["d"] * m["fe"]


def expert_layer_params(c: dict, experts: float) -> float:
    """Matmul weights of an expert layer's FFN with ``experts`` routed experts
    counted: those, the shared expert, the router and its bias."""
    m = _dims(c)
    return (experts + m["shared"]) * expert_params(c) + m["d"] * m["E"] + m["E"]


def distinct_experts(c: dict, rows: float) -> float:
    """Expected number of a layer's experts that ``rows`` tokens reach, each
    choosing ``k`` of ``E`` evenly: ``E (1 - (1 - k/E)^rows)``."""
    m = _dims(c)
    return m["E"] * (1.0 - (1.0 - m["k"] / m["E"]) ** rows)


def _norms(c: dict) -> int:
    m = _dims(c)
    return 2 * m["d"] + m["r"]  # attn_norm, mlp_norm, kv_norm


def _stack_params(c: dict, experts: float) -> float:
    """Every layer's weights with ``experts`` routed experts counted a layer."""
    m = _dims(c)
    each = attention_params(c) + _norms(c)
    return m["L"] * each + m["nd"] * 3 * m["d"] * m["f"] + (m["L"] - m["nd"]) * expert_layer_params(c, experts)


def param_count(c: dict) -> int:
    m = _dims(c)
    head = 0 if c.get("tie_word_embeddings", False) else m["d"] * m["v"]
    return int(_stack_params(c, m["E"])) + m["v"] * m["d"] + m["d"] + head


def _active_matmul_params(c: dict, head: bool = True) -> float:
    """Matmul weights one token multiplies: the chosen experts, the shared one,
    everything outside the experts, with ``head`` the head; no norm, no bias."""
    m = _dims(c)
    norms = m["L"] * _norms(c)
    return _stack_params(c, m["k"]) - norms - (m["L"] - m["nd"]) * m["E"] + (m["d"] * m["v"] if head else 0)


def _attention_flops_per_key(c: dict) -> float:
    """Scores over ``nope + rope`` and values over ``v``, every head of every layer, 2 a multiply-add."""
    m = _dims(c)
    return m["L"] * 2 * m["h"] * (m["dn"] + m["dr"] + m["dv"])


def forward_flops_per_token(c: dict, keys: float, head: bool = True) -> float:
    """2 per active matmul weight (the chosen experts, the shared one, with
    ``head`` the head), plus scores over ``keys`` keys of ``nope + rope`` and as
    many values of ``v``: the published (expanded) form; the program's absorbed
    decode multiplies more a key and reads less."""
    return 2.0 * _active_matmul_params(c, head) + _attention_flops_per_key(c) * keys


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and twice that backward, causal attention over ``seq/2`` keys."""
    return 3.0 * forward_flops_per_token(c, seq / 2)


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What the algorithm keeps a token: the latent and the rotary key, every
    layer (the program pads a row to 640 values: 1,280 B where this says 1,152)."""
    m = _dims(c)
    return m["L"] * (m["r"] + m["dr"]) * dtype_bytes


def decode_step_bytes(c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must read: every weight outside the routed
    experts once, of each expert layer the experts that ``slots_active`` tokens
    are expected to reach, the head, one embedding row a slot, and the latent
    rows of every token the slots hold."""
    m = _dims(c)
    weights = _stack_params(c, distinct_experts(c, slots_active)) + m["d"] + m["d"] * m["v"]
    return (weights + slots_active * m["d"]) * dtype_bytes + tokens_held * kv_bytes_per_token(c, dtype_bytes)


def prefill_expert_flops_per_token(c: dict) -> float:
    """Forward FLOPs the expert FFNs of all expert layers spend on one token:
    its ``k`` chosen experts and the shared one, 2 a multiply-add."""
    m = _dims(c)
    return (m["L"] - m["nd"]) * 2.0 * (m["k"] + m["shared"]) * expert_params(c)


def aux_must_be_zero(aux) -> dict:  # noqa: ANN001
    """The router's overflow: the dropless dispatch reports 0 by construction,
    and a run in which it does not is not this model."""
    from torchx_tpu.models import llama

    return {"router_overflow": float(aux[llama.AUX_OVERFLOW])}
