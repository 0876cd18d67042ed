"""Model kind ``mla_moe_hc``: Xing4.0-29B-A4B's block as its ``config.json``
(``model_type`` ``xing4_0``) publishes it: kind ``mla_moe``'s layer (latent
attention, ``first_k_dense_replace`` dense layers, then ``n_routed_experts``
small experts behind a sigmoid router with a selection bias beside
``n_shared_experts`` shared ones) with three mechanisms that kind refuses:

1. **The residual path** (``hc_mult`` n = 4: manifold-constrained hyper-connections).
   The stream is ``X [n, d]`` a token, the embedding copied into all n rows.
   Around each sublayer ``F`` (attention with its ``attn_norm``, the FFN with
   its ``mlp_norm``), with its own ``phi [n d, 2n + n^2]``, ``b [2n + n^2]`` and
   three scalars ``a = (a_pre, a_post, a_res)``::

       z = flatten(X) / sqrt(mean(flatten(X)^2) + hc_eps);   m = z phi
       H_pre  = sigmoid(a_pre m[:n] + b[:n]);   H_post = 2 sigmoid(a_post m[n:2n] + b[n:2n])
       M = exp(clip(a_res m[2n:] + b[2n:], mhc_h_res_clamp_min, mhc_h_res_clamp_max)) as [n, n]
       hc_sinkhorn_iters times:  M = M / (rowsum(M) + hc_eps);  M = M / (colsum(M) + hc_eps)
       y = F(H_pre X);   X'[i] = sum_j M[i, j] X[j] + H_post[i] y

   After the last layer the n rows are summed ahead of ``final_norm``.
2. **The query through its own latent** (``q_lora_rank``): ``c_q = RMSNorm_q(u
   W_qa)``, ``q = c_q W_qb`` as ``[h, nope + rope]``.
3. **YaRN** over the rotary columns (``rope_scaling``): ``f_i = theta^(-2i/rope)``;
   ``low = floor(rope ln(L0 / (beta_fast 2 pi)) / (2 ln theta))``, ``high = ceil(rope
   ln(L0 / (beta_slow 2 pi)) / (2 ln theta))``, clipped to ``[0, rope - 1]``;
   ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = (f_i / factor)
   ramp_i + f_i (1 - ramp_i)``; cos and sin times ``mscale(factor, mscale) /
   mscale(factor, mscale_all_dim)`` with ``mscale(s, m) = 0.1 m ln s + 1``; the softmax
   scale is ``(nope + rope)^-0.5 mscale(factor, mscale_all_dim)^2``.

The program's ``MoEConfig`` runs it (``torchx_tpu/models/hyper.py``, ``mla.py``,
``moe.py``, ``ops/rope.py``). What the keys do not fix is under the
configuration's ``assumed`` and held alike by program and reference: the stream
copied in and summed out, no learned gain in ``z``'s norm, ``hc_eps`` standing in
both places, ``M``'s orientation (row i of ``X'`` from row i of ``M``), rows then
columns. The departures kind ``mla_moe``'s docstring lists (rotary columns stored
evens first, a seeded selection bias, a cached row padded to whole lanes, decode
absorbed) hold here too, and one more in how a weight is stored: the tree holds
``W_kvb``'s key and value columns apart, each transposed (``w_uk [h, nope, rank]``,
``w_uv [h, v, rank]``), the heads outermost as the absorbed decode multiplies them;
the reference transposes them back.

**Not built**: the multi-token-prediction layer (``num_nextn_predict_layers`` 1).
It is no part of the model's own forward pass, and the engine yields one token a
slot a step. Grouped routing (``n_group`` 1) is the identity; ``ep_size`` 1.

The counts are the least a step must read or multiply. :func:`hc_bytes` is the
residual path's: the stream read once and written once a layer. ISSUE 33 asked for
three passes a sublayer (read for the norm and the read-in, read and written for
the write-back); the chip's compiler already does with less (it never writes the
stream between a layer's two sublayers: the second's readers compute the first's
write-back again from what that one read), and a share of that count read 134-147%
in decode (my chip runs, PR 33): counted too high, so it is not the roofline.
"""

from __future__ import annotations

from typing import Any

from benchmark.lib import kinds, models

_mla_moe = kinds.load("mla_moe")  # the layer round which this kind's three mechanisms go

REFERENCE = "mla_moe_hc"  # reference/mla_moe_hc.py: logits, mean_nll

distinct_experts = _mla_moe.distinct_experts
expert_params = _mla_moe.expert_params
expert_layer_params = _mla_moe.expert_layer_params
kv_bytes_per_token = _mla_moe.kv_bytes_per_token
prefill_expert_flops_per_token = _mla_moe.prefill_expert_flops_per_token
aux_must_be_zero = _mla_moe.aux_must_be_zero


def _dims(c: dict) -> dict:
    n = c["hc_mult"]
    return dict(_mla_moe._dims(c), rq=c["q_lora_rank"], n=n, hc_width=2 * n + n * n)


def program_config(config: dict, **overrides: Any):
    """The program's ``MoEConfig`` from the published keys; what the program
    does not build is refused here, not ignored (the MTP layer excepted, which
    the docstring above says is left out)."""
    from torchx_tpu.ops.rope import YarnScaling

    scaling = config["rope_scaling"]
    if scaling.get("type") != "yarn":
        raise ValueError(f"the program runs rope_scaling type 'yarn' only, not {scaling.get('type')!r}")
    if not config["q_lora_rank"] or not config["hc_mult"]:
        raise ValueError("kind mla_moe_hc compresses the query and carries several streams: mla_moe runs the others")
    published = dict(config, q_lora_rank=None, rope_scaling=None)  # what kind mla_moe refuses, built here
    return _mla_moe.program_config(
        published,
        **{
            "q_lora_rank": int(config["q_lora_rank"]),
            "rope_scaling": YarnScaling(
                factor=float(scaling["factor"]),
                original_max_seq=int(scaling["original_max_position_embeddings"]),
                beta_fast=float(scaling["beta_fast"]),
                beta_slow=float(scaling["beta_slow"]),
                mscale=float(scaling["mscale"]),
                mscale_all_dim=float(scaling["mscale_all_dim"]),
            ),
            "hc_mult": int(config["hc_mult"]),
            "hc_sinkhorn_iters": int(config["hc_sinkhorn_iters"]),
            "hc_eps": float(config["hc_eps"]),
            "hc_res_clamp": (float(config["mhc_h_res_clamp_min"]), float(config["mhc_h_res_clamp_max"])),
            **overrides,
        },
    )


def _layer_leaves(config: dict, layers: int) -> dict:
    """What a layer of this kind holds beyond kind ``mla_moe``'s, ``layers`` deep."""
    m = _dims(config)
    d, h, rq = m["d"], m["h"], m["rq"]
    std = config["assumed_hc_b_std"]
    leaves = {
        "w_qa": ((layers, d, rq), d),
        "q_latent_norm": ((layers, rq), 0),
        "w_qb": ((layers, rq, h * (m["dn"] + m["dr"])), rq),
        "w_uk": ((layers, h, m["dn"], m["r"]), m["r"]),  # W_kvb's key columns, transposed: the heads outermost
        "w_uv": ((layers, h, m["dv"], m["r"]), m["r"]),  # ... and its value columns
    }
    for sub in ("attn", "mlp"):
        leaves[f"hc_{sub}_phi"] = ((layers, m["n"] * d, m["hc_width"]), m["n"] * d)
        leaves[f"hc_{sub}_b"] = ((layers, m["hc_width"]), ("normal", float(std)))
        leaves[f"hc_{sub}_a"] = ((layers, 3), 0)
    return leaves


def weight_shapes(config: dict) -> dict:
    """Kind ``mla_moe``'s tree with ``wq`` replaced by the query's latent pair
    and its norm, ``w_kvb`` by its key and value parts transposed, and each
    sublayer's ``phi``, ``b`` and ``a`` added."""
    tree = _mla_moe.weight_shapes(config)
    for group in ("dense_layers", "layers"):
        if group in tree:
            layers = tree[group]
            n_layers = layers.pop("wq")[0][0]
            del layers["w_kvb"]
            layers.update(_layer_leaves(config, n_layers))
    return tree


# -- counts --------------------------------------------------------------------


def attention_params(c: dict) -> int:
    """Matmul weights of one layer's attention: W_qa, W_qb, W_kva, W_kvb, W_o."""
    m = _dims(c)
    q_width = m["h"] * (m["dn"] + m["dr"])
    return _mla_moe.attention_params(c) - m["d"] * q_width + m["d"] * m["rq"] + m["rq"] * q_width


def hc_params(c: dict) -> int:
    """One layer's hyper-connections: ``phi``, ``b`` and ``a`` of its two sublayers."""
    m = _dims(c)
    return 2 * ((m["n"] * m["d"] + 1) * m["hc_width"] + 3)


def _stack_params(c: dict, experts: float) -> float:
    """Every layer's weights with ``experts`` routed experts counted a layer."""
    m = _dims(c)
    more = attention_params(c) - _mla_moe.attention_params(c) + m["rq"] + hc_params(c)  # + q_latent_norm
    return _mla_moe._stack_params(c, experts) + m["L"] * more


def param_count(c: dict) -> int:
    m = _dims(c)
    head = 0 if c.get("tie_word_embeddings", False) else m["d"] * m["v"]
    return int(_stack_params(c, m["E"])) + m["v"] * m["d"] + m["d"] + head


def _more_than_mla_moe(c: dict) -> tuple[float, float]:
    """-> (matmul weights a token multiplies beyond kind ``mla_moe``'s, all
    layers: the query's two matmuls in ``W_q``'s place and each sublayer's
    ``phi``; forward FLOPs of the mixes themselves, ``4 n^2 d`` a layer)."""
    m = _dims(c)
    q_width = m["h"] * (m["dn"] + m["dr"])
    more = m["d"] * m["rq"] + m["rq"] * q_width - m["d"] * q_width + 2 * m["n"] * m["d"] * m["hc_width"]
    mixes = 2 * 2.0 * (m["n"] * m["n"] + 2 * m["n"]) * m["d"]
    return m["L"] * more, m["L"] * mixes


def forward_flops_per_token(c: dict, keys: float, head: bool = True) -> float:
    """Kind ``mla_moe``'s count with :func:`_more_than_mla_moe`'s beside it."""
    more, mixes = _more_than_mla_moe(c)
    return _mla_moe.forward_flops_per_token(c, keys, head) + 2.0 * more + mixes


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and twice that backward, causal attention over ``seq/2`` keys."""
    return 3.0 * forward_flops_per_token(c, seq / 2)


def hc_bytes(c: dict, rows: float, dtype_bytes: int = 2) -> float:
    """Least bytes the residual path moves for ``rows`` tokens, over all
    layers: the stream (``rows x n x d``) read once and written once a layer (it
    is the layer scan's carry; between a layer's two sublayers it need not be
    written), each sublayer's input written and its output read (``rows x d``
    each), each sublayer's ``phi`` once."""
    m = _dims(c)
    layer = 2 * rows * m["n"] * m["d"] + 2 * (2 * rows * m["d"] + m["n"] * m["d"] * m["hc_width"])
    return m["L"] * layer * dtype_bytes


def decode_step_bytes(c: dict, slots_active: float, tokens_held: float, dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must read: every weight outside the routed
    experts once (each sublayer's ``phi`` among them), of each expert layer the
    experts that ``slots_active`` tokens are expected to reach, the head, one
    embedding row a slot, and the latent rows of every token the slots hold."""
    m = _dims(c)
    weights = _stack_params(c, distinct_experts(c, slots_active)) + m["d"] + m["d"] * m["v"]
    return (weights + slots_active * m["d"]) * dtype_bytes + tokens_held * kv_bytes_per_token(c, dtype_bytes)
