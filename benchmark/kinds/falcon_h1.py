"""Model kind ``falcon_h1``: Falcon-H1's block as its ``config.json``
(``model_type`` ``falcon_h1``) publishes it: in **every** layer a Mamba-2 mixer
beside grouped-query attention, both reading one norm and adding into one
residual, then a SwiGLU; fixed multipliers (maximal-update parametrisation) on
the embedding, the logits, attention's input, keys and output, the mixer's
input, the five segments of its projection and its output, and the
feed-forward's gate and output. ``reference/falcon_h1.py`` writes the equations
out. The program's ``LlamaConfig`` runs it (``torchx_tpu/models/ssm.py``,
``llama.py``, ``generate.py``): what a sequence carries is K/V rows by position
**and** a state ``[H, P, N]`` float32 plus the convolution's last ``K - 1`` inputs
a layer, by slot.

What the keys do not fix is under the configuration's ``assumed`` and held alike
by program and reference: the gated norm's grouping (``mamba_n_groups`` groups of
``d_ssm / G``, one gain), the rotary pairing ``(i, i + 64)``, the state in float32,
the convolution's tail in the model's type. ``mamba_expand``, ``mlp_expansion_factor``,
``mamba_chunk_size`` and ``num_logits_to_keep`` fix nothing of the equations
(``mamba_d_ssm`` and ``intermediate_size`` are given; a chunk length changes how the
scan is multiplied, not what it gives) and are read by nobody.

**How the weights are drawn** (:func:`weight_shapes`; the reason is the check, not
speed). With every matrix at ``fan_in^-0.5`` the published multipliers leave the
keys at 0.011 (a softmax that is flat: attention would be a running mean, and a
stale row or a wrong mask would barely show), the feed-forward at a hundredth of
the stream, the logits at 0.008, and ``B . C`` at 0.02, so that the state's
read-out is 2% of the skip ``D x`` beside it: a mixer whose state was lost would
move no served logit. The multipliers are what a maximal-update checkpoint is
trained *with*; its matrices are larger by about their inverse. So the matrices
whose product a multiplier makes small are drawn larger by that multiplier
(``wk`` by ``1 / key_multiplier``, ``w_gate`` by ``1 / mlp_multipliers[0]``,
``lm_head`` by ``1 / lm_head_multiplier``, ``ssm_in`` and ``w_down`` by the
configuration's ``assumed_ssm_in_gain`` and ``assumed_w_down_gain``), and ``A_log``,
``dt_bias`` and the convolution's bias are drawn with the configuration's
``assumed_*_std`` in place of their initial 0, so that heads forget at every rate
from at once to over hundreds of positions. The multipliers themselves stay as
published, in program and reference alike.

The counts are the least a step must read or multiply. :func:`decode_state_bytes`
is the term that grows with slots and not with tokens: each active slot's state
and tail read once and written once a layer.
"""

from __future__ import annotations

from typing import Any

from benchmark.lib import counts, models

REFERENCE = "falcon_h1"  # reference/falcon_h1.py

#: keys whose other value the program does not build: refused, not ignored
_ONLY = {
    "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False, "projectors_bias": False,
    "mamba_conv_bias": True, "mamba_rms_norm": True, "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "hidden_act": "silu", "rope_scaling": None, "attn_layer_indices": None, "tie_word_embeddings": False,
}  # fmt: skip


def _dims(c: dict) -> dict:
    d, h, kvh, hd, f, L, v = counts.gqa_dims(c)
    H, P, N, G, K = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"], c["mamba_n_groups"], c["mamba_d_conv"]
    return dict(d=d, h=h, kvh=kvh, hd=hd, f=f, L=L, v=v, H=H, P=P, N=N, G=G, K=K,
                d_ssm=H * P, width=H * P + 2 * G * N, proj=2 * H * P + 2 * G * N + H)  # fmt: skip


def program_config(config: dict, **overrides: Any):
    """The program's ``LlamaConfig`` from the published keys."""
    from torchx_tpu.models import llama

    for key, only in _ONLY.items():
        if config.get(key, only) != only:
            raise ValueError(f"the program builds {key} = {only!r} only, not {config[key]!r}")
    m = _dims(config)
    if config["mamba_d_ssm"] != m["d_ssm"]:
        raise ValueError("mamba_d_ssm must be mamba_n_heads x mamba_d_head")
    return llama.LlamaConfig(**{**dict(
        vocab_size=config["vocab_size"],
        dim=m["d"],
        n_layers=m["L"],
        n_heads=m["h"],
        n_kv_heads=m["kvh"],
        attn_head_dim=m["hd"],
        ffn_dim=m["f"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=False,
        dtype=models._dtype(config),
        ssm_heads=m["H"],
        ssm_head_dim=m["P"],
        ssm_state=m["N"],
        ssm_groups=m["G"],
        ssm_conv=m["K"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        lm_head_multiplier=float(config["lm_head_multiplier"]),
        attention_in_multiplier=float(config["attention_in_multiplier"]),
        attention_out_multiplier=float(config["attention_out_multiplier"]),
        key_multiplier=float(config["key_multiplier"]),
        ssm_in_multiplier=float(config["ssm_in_multiplier"]),
        ssm_out_multiplier=float(config["ssm_out_multiplier"]),
        ssm_multipliers=tuple(config["ssm_multipliers"]),
        mlp_multipliers=tuple(config["mlp_multipliers"]),
    ), **overrides})  # fmt: skip


def weight_shapes(config: dict) -> dict:
    """The parameter tree as the program lays it out (``llama.init_params`` with
    a mixer), each leaf ``(shape, init)``; the module's docstring says why some
    are drawn wider than their fan-in."""
    m = _dims(config)
    d, L, f = m["d"], m["L"], m["f"]
    wide = lambda fan_in, gain: ("normal", float(gain) * fan_in**-0.5)  # noqa: E731
    layers = {
        "attn_norm": ((L, d), 0),
        "wq": ((L, d, m["h"] * m["hd"]), d),
        "wk": ((L, d, m["kvh"] * m["hd"]), wide(d, 1.0 / config["key_multiplier"])),
        "wv": ((L, d, m["kvh"] * m["hd"]), d),
        "wo": ((L, m["h"] * m["hd"], d), m["h"] * m["hd"]),
        "mlp_norm": ((L, d), 0),
        "w_gate": ((L, d, f), wide(d, 1.0 / config["mlp_multipliers"][0])),
        "w_up": ((L, d, f), d),
        "w_down": ((L, f, d), wide(f, config["assumed_w_down_gain"])),
        "ssm_in": ((L, d, m["proj"]), wide(d, config["assumed_ssm_in_gain"])),
        "ssm_conv_w": ((L, m["K"], m["width"]), m["K"]),
        "ssm_conv_b": ((L, m["width"]), ("normal", float(config["assumed_conv_bias_std"]))),
        "ssm_dt_bias": ((L, m["H"]), ("normal", float(config["assumed_dt_bias_std"]))),
        "ssm_A_log": ((L, m["H"]), ("normal", float(config["assumed_A_log_std"]))),
        "ssm_D": ((L, m["H"]), 0),
        "ssm_norm": ((L, m["d_ssm"]), 0),
        "ssm_out": ((L, m["d_ssm"], d), m["d_ssm"]),
    }
    return {
        "embed": ((m["v"], d), d),
        "layers": layers,
        "final_norm": ((d,), 0),
        "lm_head": ((d, m["v"]), wide(d, 1.0 / config["lm_head_multiplier"])),
    }


# -- counts --------------------------------------------------------------------


def mixer_params(c: dict) -> int:
    """One layer's mixer: ``W_in``, ``W_out``, the convolution, ``A_log``, ``D``,
    ``dt_bias`` and the gated norm's gain."""
    m = _dims(c)
    return m["d"] * m["proj"] + m["d_ssm"] * m["d"] + (m["K"] + 1) * m["width"] + 3 * m["H"] + m["d_ssm"]


def layer_matmul_params(c: dict) -> int:
    """One layer's weights but its two norms: attention's projections, the mixer, the SwiGLU."""
    m = _dims(c)
    return counts.gqa_attention_params(c) + mixer_params(c) + 3 * m["d"] * m["f"]


def param_count(c: dict) -> int:
    return counts.decoder_param_count(c, layer_matmul_params(c))


def ssm_scan_flops(c: dict) -> float:
    """The recurrence's own work for one token in one layer, whatever computes
    it: a multiply-add an element of ``S [H, P, N]`` to move it on, and one to
    read it out."""
    m = _dims(c)
    return 4.0 * m["P"] * m["N"] * m["H"]


def forward_flops_per_token(c: dict, keys: float, head: bool = True) -> float:
    """2 per matmul weight, scores and values over ``keys`` positions, and the recurrence."""
    return counts.decoder_forward_flops_per_token(c, keys, layer_matmul_params(c), head) + _dims(c)["L"] * ssm_scan_flops(c)


def train_flops_per_token(c: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(c, seq / 2)


kv_bytes_per_token = counts.gqa_kv_bytes_per_token  # attention's rows alone: the state does not grow with tokens


def state_bytes_per_slot(c: dict, dtype_bytes: int = 2) -> int:
    """What a slot holds whatever its length, over all layers: ``S [H, P, N]``
    in float32 and the convolution's last ``K - 1`` inputs in the model's type."""
    m = _dims(c)
    return m["L"] * (m["H"] * m["P"] * m["N"] * 4 + (m["K"] - 1) * m["width"] * dtype_bytes)


def decode_state_bytes(c: dict, slots_active: float, dtype_bytes: int = 2) -> float:
    """Least bytes one decode step moves of recurrent state: each active slot's read once and written once."""
    return 2.0 * slots_active * state_bytes_per_slot(c, dtype_bytes)


def decode_step_bytes(c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must move: every weight and the head once, one
    embedding row a slot, the K/V of every token the slots hold, and the slots'
    recurrent state read and written: a term that grows with slots, not tokens."""
    return (
        counts.decoder_decode_step_bytes(c, layer_matmul_params(c), slots_active, tokens_held, dtype_bytes)
        + decode_state_bytes(c, slots_active, dtype_bytes)
    )


def aux_must_be_zero(aux) -> dict:  # noqa: ANN001
    """A dense layer has nothing a training step's ``aux`` must hold at 0."""
    return {}
