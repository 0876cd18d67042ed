"""Model kind ``qwen3_next``: Qwen3-Next-80B-A3B's block as its ``config.json`` (``model_type``
``qwen3_next``) publishes it, served as **one chip of four that share each layer**. Layer ``i``
attends where ``(i + 1) % full_attention_interval == 0`` (grouped-query attention, 16 query / 2
K/V heads of 256, whose query projection also makes an output gate; zero-centred norms over
each head of ``q`` and ``k``; rotary embedding over the first quarter of a head) and is else a
**Gated DeltaNet** layer (16 key / 32 value heads of 128, a causal convolution of 4 taps, a
state ``[32, 128, 128]`` float32 moved on by the delta rule): three of those for every attention
layer, so six layers of eight keep a slot's state and **no K/V**. Every layer's feed-forward is
``num_experts`` routed experts of ``moe_intermediate_size`` (top ``num_experts_per_tok`` of a
softmax router, renormalised) beside one shared expert weighed by ``sigmoid(x . w_s)``. Every
RMSNorm gain but the linear mixer's is stored about zero and applied as ``1 + w``.
``reference/qwen3_next.py`` writes the equations out. The program's ``MoEConfig`` runs it
(``torchx_tpu/models/llama.py``, ``gdn.py``, ``moe.py``, ``generate.py``).

**The share.** ``num_experts`` in the configuration's file is what this chip holds (128, ids
``experts_held_from`` onward); ``published_num_experts`` (512) is the router's width. Routing is
over all 512 as published; the experts that live on the other three chips are not computed and
nothing stands in for them or for the exchange: what they would add to a layer's output is left
out, here and in the reference alike, and that partial sum goes on to the next layer. The
mixers, the router and the shared expert are whole on each chip. ``vocab_size`` is one of four
slices of ``published_vocab_size``.

**The parameter tree**: what every layer has (the two norms, the router, the experts, the
shared expert and its gate) is one stack under ``layers``; the layers' mixers differ in their
leaves, so they are a stack a kind under ``mixers``: ``full`` (the attending layers' ``wq`` with
the gate's columns, ``wk``, ``wv``, ``wo``, ``q_norm``, ``k_norm``) and ``state`` (the linear layers'
``gdn_*``), each over the layers of its kind in the order they run.

What the keys do not fix is under the configuration's ``assumed`` and held alike by program
and reference. **How the weights are drawn** (:func:`weight_shapes`; the reason is the check):
every matrix at ``fan_in^-0.5``; every zero-centred gain a normal of deviation
``assumed_norm_gain_std`` about its 0 and the gated norm's plain gain a normal of deviation 1,
so that a dropped ``1 +`` (or one too many) moves every logit; ``A_log`` and ``dt_bias`` with
``assumed_A_log_std`` and ``assumed_dt_bias_std`` as ``falcon-h1``'s are, so that heads forget at
every rate from at once to over hundreds of positions and a state lost between a prompt's
last chunk and its slot's first decode step shows for as long.

**Not built**: the multi-token-prediction block (``described_as``: MTP 1). It is no part of the
model's own forward pass, and the engine yields one token a slot a step.

The counts below are the least a step must read or multiply: of the held experts those the
active slots are expected to reach, not all held; K/V on the attending layers alone;
:func:`decode_state_bytes` is the term that grows with slots and not with tokens.
"""

from __future__ import annotations

from typing import Any

from benchmark.lib import models

REFERENCE = "qwen3_next"  # reference/qwen3_next.py

#: keys whose other value the program does not build: refused, not ignored
_ONLY = {
    "decoder_sparse_step": 1, "mlp_only_layers": [], "hidden_act": "silu", "rope_scaling": None,
    "use_sliding_window": False, "tie_word_embeddings": False, "norm_topk_prob": True,
}  # fmt: skip


def _dims(c: dict) -> dict:
    L, every = c["num_hidden_layers"], c["full_attention_interval"]
    n_full = sum((i + 1) % every == 0 for i in range(L))
    hk, H, D = c["linear_num_key_heads"], c["linear_num_value_heads"], c["linear_key_head_dim"]
    return dict(
        d=c["hidden_size"], h=c["num_attention_heads"], kvh=c["num_key_value_heads"], hd=c["head_dim"], L=L,
        v=c["vocab_size"], fe=c["moe_intermediate_size"], fs=c["shared_expert_intermediate_size"],
        held=c["num_experts"], E=c.get("published_num_experts", c["num_experts"]), k=c["num_experts_per_tok"],
        n_full=n_full, n_linear=L - n_full, hk=hk, H=H, D=D, K=c["linear_conv_kernel_dim"], width=(2 * hk + H) * D,
    )  # fmt: skip


def layer_types(c: dict) -> tuple[str, ...]:
    """The program's name of each layer's mixer, in the order the layers run."""
    every = c["full_attention_interval"]
    return tuple("full" if (i + 1) % every == 0 else "linear" for i in range(c["num_hidden_layers"]))


def program_config(config: dict, **overrides: Any):
    """The program's ``MoEConfig`` from the published keys; what the program does not build is refused
    here, not ignored (the MTP block excepted, which the docstring above says is left out)."""
    from torchx_tpu.models import moe

    for key, only in _ONLY.items():
        if config.get(key, only) != only:
            raise ValueError(f"the program builds {key} = {only!r} only, not {config[key]!r}")
    m = _dims(config)
    if config["linear_value_head_dim"] != m["D"]:
        raise ValueError("the program keeps a square state: linear_value_head_dim must be linear_key_head_dim")
    if m["fs"] % m["fe"]:
        raise ValueError("shared_expert_intermediate_size must be whole experts of moe_intermediate_size")
    kw = dict(
        vocab_size=m["v"], dim=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=m["kvh"], attn_head_dim=m["hd"],
        ffn_dim=config["intermediate_size"],  # a dense layer's width: no layer of this stack is dense
        rope_theta=float(config["rope_theta"]), norm_eps=float(config["rms_norm_eps"]), tie_embeddings=False,
        dtype=models._dtype(config), layer_types=layer_types(config), qk_norm=True, norm_unit_offset=True,
        rotary_dim=int(m["hd"] * config["partial_rotary_factor"]), attn_output_gate=True,
        gdn_heads=m["H"], gdn_key_heads=m["hk"], gdn_head_dim=m["D"], gdn_conv=m["K"],
        n_experts=m["E"], experts_held=m["held"] if m["held"] != m["E"] else 0,
        experts_held_from=int(config.get("experts_held_from", 0)), top_k=m["k"], expert_ffn_dim=m["fe"],
        n_shared_experts=m["fs"] // m["fe"], shared_expert_gate=True, router_score="softmax",
        capacity_factor=0.0,  # as published no routing is dropped
    )  # fmt: skip
    kw.update(overrides)
    return moe.MoEConfig(**kw)


def weight_shapes(config: dict) -> dict:
    """The parameter tree as the program lays it out (``moe.init_params`` over a stack with linear
    layers), each leaf ``(shape, init)``; the module's docstring says why some are drawn with a deviation."""
    m = _dims(config)
    d, L, E, held, fe, fs = m["d"], m["L"], m["E"], m["held"], m["fe"], m["fs"]
    h, kvh, hd, nf, nl = m["h"], m["kvh"], m["hd"], m["n_full"], m["n_linear"]
    about_zero = ("normal", float(config["assumed_norm_gain_std"]))  # a gain stored about zero, applied as 1 + w
    layers = {
        "attn_norm": ((L, d), about_zero),
        "mlp_norm": ((L, d), about_zero),
        "w_router": ((L, d, E), d),
        "w_gate": ((L, held, d, fe), d), "w_up": ((L, held, d, fe), d), "w_down": ((L, held, fe, d), fe),
        "ws_gate": ((L, d, fs), d), "ws_up": ((L, d, fs), d), "ws_down": ((L, fs, d), fs),
        "w_shared_gate": ((L, d), d),
    }  # fmt: skip
    full = {
        "wq": ((nf, d, 2 * h * hd), d), "wk": ((nf, d, kvh * hd), d), "wv": ((nf, d, kvh * hd), d),
        "wo": ((nf, h * hd, d), h * hd),
        "q_norm": ((nf, hd), about_zero), "k_norm": ((nf, hd), about_zero),
    }  # fmt: skip
    inner = m["H"] * m["D"]
    state = {
        "gdn_in": ((nl, d, m["width"] + inner), d),
        "gdn_ba": ((nl, d, 2 * m["H"]), d),
        "gdn_conv_w": ((nl, m["K"], m["width"]), m["K"]),
        "gdn_dt_bias": ((nl, m["H"]), ("normal", float(config["assumed_dt_bias_std"]))),
        "gdn_A_log": ((nl, m["H"]), ("normal", float(config["assumed_A_log_std"]))),
        "gdn_norm": ((nl, m["D"]), ("normal", 1.0)),  # a plain gain: of either sign, of size one
        "gdn_out": ((nl, inner, d), inner),
    }
    return {
        "embed": ((m["v"], d), d),
        "layers": layers,
        "mixers": {"full": full, "state": state},
        "final_norm": ((d,), about_zero),
        "lm_head": ((d, m["v"]), d),
    }


# -- counts --------------------------------------------------------------------


def attention_params(c: dict) -> int:
    """Matmul weights of one attending layer's mixer: W_q with the gate's columns, W_k, W_v, W_o."""
    m = _dims(c)
    return 2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kvh"] * m["hd"] + m["h"] * m["hd"] * m["d"]


def delta_net_params(c: dict) -> int:
    """Matmul weights of one linear layer's mixer: its two input projections and its output projection."""
    m = _dims(c)
    inner = m["H"] * m["D"]
    return m["d"] * (m["width"] + inner) + m["d"] * 2 * m["H"] + inner * m["d"]


def _mixer_extras(c: dict) -> tuple[int, int]:
    """What is no matmul weight in (an attending, a linear) layer's mixer: the two head norms; the
    convolution, ``A_log``, ``dt_bias`` and the gated norm's gain."""
    m = _dims(c)
    return 2 * m["hd"], m["K"] * m["width"] + 2 * m["H"] + m["D"]


def expert_params(c: dict) -> int:
    """One routed expert's three matrices."""
    m = _dims(c)
    return 3 * m["d"] * m["fe"]


def held_experts_reached(c: dict, rows: float) -> float:
    """Expected number of a layer's **held** experts that ``rows`` tokens reach, each choosing ``k`` of the
    ``E`` published evenly: ``held (1 - (1 - k/E)^rows)``."""
    m = _dims(c)
    return m["held"] * (1.0 - (1.0 - m["k"] / m["E"]) ** rows)


def _ffn_params(c: dict, experts: float) -> float:
    """One layer's feed-forward with ``experts`` routed experts counted: those, the shared expert and
    its gate, the router as wide as published."""
    m = _dims(c)
    return experts * expert_params(c) + 3 * m["d"] * m["fs"] + m["d"] + m["d"] * m["E"]


def _stack_params(c: dict, experts: float) -> float:
    """Every layer's weights, norms and all, with ``experts`` routed experts counted a layer."""
    m = _dims(c)
    attn_extra, linear_extra = _mixer_extras(c)
    return (
        m["L"] * (2 * m["d"] + _ffn_params(c, experts))
        + m["n_full"] * (attention_params(c) + attn_extra)
        + m["n_linear"] * (delta_net_params(c) + linear_extra)
    )


def param_count(c: dict) -> int:
    """What this chip holds: the held experts, the vocabulary's slice."""
    m = _dims(c)
    return int(_stack_params(c, m["held"])) + 2 * m["v"] * m["d"] + m["d"]


def gdn_step_flops(c: dict) -> float:
    """The delta rule's own work for one token in one linear layer, whatever computes it: a multiply-add
    an element of ``S [H, D, D]`` to read ``S^T k``, one to write ``k u^T``, one to read ``S^T q``."""
    m = _dims(c)
    return 6.0 * m["H"] * m["D"] * m["D"]


def gdn_chunk_flops(c: dict, tokens: float) -> float:
    """The same for ``tokens`` positions of a prompt, over all the linear layers: what the chunked form
    computes at the least, however it multiplies."""
    return tokens * _dims(c)["n_linear"] * gdn_step_flops(c)


def _active_matmul_params(c: dict, head: bool = True) -> float:
    """Matmul weights one token multiplies on this chip: of its ``k`` routings the ``held / E`` expected
    to land here, the shared expert, the router, its layer's mixer, with ``head`` the head's slice."""
    m = _dims(c)
    routed_here = m["k"] * m["held"] / m["E"]
    per_layer = routed_here * expert_params(c) + 3 * m["d"] * m["fs"] + m["d"] + m["d"] * m["E"]
    return (m["L"] * per_layer + m["n_full"] * attention_params(c) + m["n_linear"] * delta_net_params(c)
            + (m["d"] * m["v"] if head else 0))  # fmt: skip


def forward_flops_per_token(c: dict, keys: float, head: bool = True) -> float:
    """The forward FLOPs this chip spends on a token that has ``keys`` positions to attend: 2 a matmul
    weight, scores and values over ``keys`` on the attending layers, the delta rule on the linear ones.
    The experts a token's routings reach **here** count (``k held / E`` = 2.5 expected), not the ``k`` it
    picks over the four chips: the other three's work is done on no chip of this cell."""
    m = _dims(c)
    return (2.0 * _active_matmul_params(c, head) + m["n_full"] * 2 * 2 * m["h"] * m["hd"] * keys
            + m["n_linear"] * gdn_step_flops(c))  # fmt: skip


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and twice that backward, a query seeing ``seq / 2`` keys on average."""
    return 3.0 * forward_flops_per_token(c, seq / 2)


def kv_bytes_per_token(c: dict, dtype_bytes: int = 2) -> int:
    """What a further token of context costs: K and V on the attending layers. A linear layer keeps
    its state whatever the context (:func:`state_bytes_per_slot`)."""
    m = _dims(c)
    return m["n_full"] * 2 * m["kvh"] * m["hd"] * dtype_bytes


def state_bytes_per_slot(c: dict, dtype_bytes: int = 2) -> int:
    """What a slot holds whatever its length, over the linear layers: ``S [H, D, D]`` in float32 and the
    convolution's last ``K - 1`` inputs in the model's type."""
    m = _dims(c)
    return m["n_linear"] * (m["H"] * m["D"] * m["D"] * 4 + (m["K"] - 1) * m["width"] * dtype_bytes)


def decode_state_bytes(c: dict, slots_active: float, dtype_bytes: int = 2) -> float:
    """Least bytes one decode step moves of recurrent state: each active slot's read once and written once."""
    return 2.0 * slots_active * state_bytes_per_slot(c, dtype_bytes)


def decode_held_expert_bytes(c: dict, slots_active: float, dtype_bytes: int = 2) -> float:
    """Bytes of held experts that ``slots_active`` tokens are expected to reach, all layers."""
    return _dims(c)["L"] * held_experts_reached(c, slots_active) * expert_params(c) * dtype_bytes


def decode_step_bytes(c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must move: every weight outside the routed experts once, of each layer
    the held experts that ``slots_active`` tokens are expected to reach, the head's slice, one embedding
    row a slot, the K/V of every token the slots hold on the attending layers, and the slots' recurrent
    state read and written on the linear ones: a term that grows with slots, not tokens."""
    m = _dims(c)
    weights = _stack_params(c, held_experts_reached(c, slots_active)) + m["d"] + m["d"] * m["v"]
    return (
        (weights + slots_active * m["d"]) * dtype_bytes
        + tokens_held * kv_bytes_per_token(c, dtype_bytes)
        + decode_state_bytes(c, slots_active, dtype_bytes)
    )


def aux_must_be_zero(aux) -> dict:  # noqa: ANN001
    """The router's overflow: the dropless dispatch reports 0 by construction, and a run in which it
    does not is not this model."""
    from torchx_tpu.models import llama

    return {"router_overflow": float(aux[llama.AUX_OVERFLOW])}
