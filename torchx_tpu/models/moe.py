"""Sparse Mixture-of-Experts Llama variant with expert parallelism.

Extends the dense Llama family (models/llama.py) with a Mixtral-style MoE
FFN using the canonical GShard/Switch **einsum dispatch** formulation —
top-k routing materialized as one-hot dispatch/combine tensors with a
fixed per-expert capacity, so every shape is static and XLA lays the whole
thing on the MXU (no dynamic gathers, the TPU-idiomatic MoE).

Expert parallelism (EP): the expert axis of the expert weights shards over
the combined ``("ep", "tp")`` mesh axes (see :func:`param_specs`); the
dispatch einsum then becomes the token all-to-all over ICI, placed by XLA.
A dedicated ``ep`` axis means ep and tp size independently — tp=1, ep=8
runs a small MoE expert-parallel without tensor parallelism; at ep=1 the
layout degenerates to experts-over-tp. Capacity overflow tokens are
dropped (standard GShard semantics) — size capacity_factor accordingly.

A model of many small experts (64 of width 1,408, six a token) cannot pay
for capacity buffers: to drop nothing every expert would compute every
token. ``capacity_factor = 0`` says the model drops no routing, and
:func:`moe_ffn` then sorts the (token, choice) rows by expert and runs one
grouped matmul over the experts held (:mod:`torchx_tpu.ops.grouped_matmul`:
on a TPU the Pallas call ``grouped_matmul_walk``, a walk over the experts that
have rows in which an expert's weights cross the wire once a call, traced once
a shape in a process whatever the call sites; elsewhere
``jax.lax.ragged_dot``, which also differentiates it), whatever the
imbalance. The router's scoring (softmax, or sigmoid with a
selection bias that chooses and never weighs), the scale on the routed sum,
a shared expert beside the routed ones and leading dense layers are fields
of the config, not functions of their own.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchx_tpu.models import llama
from torchx_tpu.obs import hot


@dataclasses.dataclass(frozen=True)
class MoEConfig(llama.LlamaConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 2.0
    # Switch/GShard load-balancing auxiliary loss coefficient: pushes the
    # router toward uniform expert utilization (0 disables)
    router_aux_coef: float = 0.01
    # width of one routed expert; 0 = ffn_dim (Mixtral: every expert is a
    # whole FFN). ffn_dim stays the width of a dense layer's SwiGLU
    expert_ffn_dim: int = 0
    # shared experts: one SwiGLU of n_shared_experts * expert width that
    # every token takes, added to the routed sum
    n_shared_experts: int = 0
    # the shared expert's output times sigmoid(x . w_shared_gate), a scalar a token
    shared_expert_gate: bool = False
    # "softmax": probabilities over the experts, the top_k renormalised.
    # "sigmoid": a score an expert, the top_k of score + router_bias chosen,
    # weighed by their scores alone over their sum, times routed_scale
    router_score: str = "softmax"
    router_bias: bool = False
    routed_scale: float = 1.0
    # leading dense layers ahead of the expert layers (of n_layers in all):
    # the tree then has two groups, "dense_layers" and "layers"
    n_dense_layers: int = 0
    # a chip's share of a layer's experts: of the n_experts the router scores
    # and chooses over, the experts_held from experts_held_from on live here
    # (0: all of them). The expert weights are [layers, held, ...]; a routing to
    # an expert that lives elsewhere is weighed as published and not computed:
    # what it would add is the other chips' to add (dropless dispatch only)
    experts_held: int = 0
    experts_held_from: int = 0

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.experts_held and not (
            self.capacity_factor <= 0 and 0 <= self.experts_held_from <= self.n_experts - self.experts_held
        ):
            raise ValueError("a share of the experts needs the dropless dispatch and a range inside n_experts")
        if self.shared_expert_gate and not self.n_shared_experts:
            raise ValueError("shared_expert_gate weighs the shared expert: it needs n_shared_experts")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score must be 'softmax' or 'sigmoid', got {self.router_score!r}")
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("n_dense_layers must leave at least one expert layer")

    @property
    def expert_width(self) -> int:
        """Intermediate width of one routed expert."""
        return self.expert_ffn_dim or self.ffn_dim

    @property
    def n_experts_held(self) -> int:
        """Routed experts whose weights live here."""
        return self.experts_held or self.n_experts

    def _expert_layer_extra(self, experts: int) -> int:
        """What an expert layer holds beyond a dense layer's count, with
        ``experts`` routed experts counted."""
        d = self.dim
        return (
            3 * d * self.expert_width * (experts + self.n_shared_experts)
            + d * self.n_experts  # router
            + (self.n_experts if self.router_bias else 0)
            + (d if self.shared_expert_gate else 0)
            - 3 * d * self.ffn_dim  # in place of the dense SwiGLU
        )

    def param_count(self) -> int:
        """Exact parameter count (dense shapes + the FFNs of the experts held)."""
        n_expert_layers = self.n_layers - self.n_dense_layers
        return super().param_count() + n_expert_layers * self._expert_layer_extra(self.n_experts_held)

    def flops_per_token(self) -> float:
        """MoE FLOPs count only the top_k ACTIVE experts per token."""
        attn = 12 * self.n_layers * self.dim * self.max_seq
        return 6 * self.active_param_count() + attn

    def active_param_count(self) -> int:
        """Params touched per token (top_k experts) — the MFU-relevant N."""
        n_expert_layers = self.n_layers - self.n_dense_layers
        return super().param_count() + n_expert_layers * self._expert_layer_extra(self.top_k)


def moe_tiny(**overrides: Any) -> MoEConfig:
    """Test/debug MoE config: runs anywhere in milliseconds."""
    defaults = dict(
        vocab_size=512,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_dim=128,
        max_seq=128,
        dtype=jnp.float32,
        remat=False,
        n_experts=4,
        top_k=2,
    )
    defaults.update(overrides)
    return MoEConfig(**defaults)


def mixtral_8x7b_shape(**overrides: Any) -> MoEConfig:
    """Mixtral-8x7B architecture shape (for parity/scaling experiments)."""
    defaults = dict(
        vocab_size=32000,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=14336,
        n_experts=8,
        top_k=2,
        rope_theta=1e6,
    )
    defaults.update(overrides)
    return MoEConfig(**defaults)


CONFIGS = {"moe_tiny": moe_tiny, "mixtral_8x7b": mixtral_8x7b_shape}


# -- parameters ------------------------------------------------------------


def init_params(cfg: MoEConfig, key: jax.Array) -> llama.Params:
    """Dense-llama params with the FFN weights of the expert layers expanded
    to [L, E, ...], a router (and its selection bias) and the shared expert
    added. With leading dense layers the stack splits into two groups:
    ``dense_layers`` keeps the first ``n_dense_layers`` as they are."""
    params = llama.init_params(cfg, key)
    nd = cfg.n_dense_layers
    L, E, d, f = cfg.n_layers - nd, cfg.n_experts, cfg.dim, cfg.expert_width
    held = cfg.n_experts_held
    k_router, k_g, k_u, k_d, k_s = jax.random.split(jax.random.fold_in(key, 17), 5)

    def init(key, shape, in_dim):  # noqa: ANN001
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * (in_dim**-0.5)
        ).astype(cfg.dtype)

    if nd:
        params["dense_layers"] = jax.tree.map(lambda w: w[:nd], params["layers"])
        params["layers"] = jax.tree.map(lambda w: w[nd:], params["layers"])
    layers = params["layers"]
    layers["w_router"] = init(k_router, (L, d, E), d)
    if cfg.router_bias:
        layers["router_bias"] = jnp.zeros((L, E), dtype=cfg.dtype)
    layers["w_gate"] = init(k_g, (L, held, d, f), d)
    layers["w_up"] = init(k_u, (L, held, d, f), d)
    layers["w_down"] = init(k_d, (L, held, f, d), f)
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        ks = jax.random.split(k_s, 3)
        layers["ws_gate"] = init(ks[0], (L, d, fs), d)
        layers["ws_up"] = init(ks[1], (L, d, fs), d)
        layers["ws_down"] = init(ks[2], (L, fs, d), fs)
        if cfg.shared_expert_gate:
            layers["w_shared_gate"] = init(jax.random.fold_in(k_s, 3), (L, d), d)
    return params


def param_specs(cfg: MoEConfig, pp: bool = False) -> llama.Params:
    """Expert axis shards over ``("ep", "tp")`` combined (expert
    parallelism, independent of tensor-parallel size); within-expert dims
    shard over ``fsdp`` like the dense model; the shared expert shards like
    a dense FFN; the stacked layer axis shards over ``pp`` when pipeline
    parallelism is on."""
    layer_axis = "pp" if pp else None
    expert_axes = ("ep", "tp")
    specs = llama.param_specs(cfg, pp=pp)
    if cfg.n_dense_layers:
        specs["dense_layers"] = dict(specs["layers"])
    layers = specs["layers"]
    layers["w_router"] = P(layer_axis, "fsdp", None)
    if cfg.router_bias:
        layers["router_bias"] = P(layer_axis, None)
    layers["w_gate"] = P(layer_axis, expert_axes, "fsdp", None)
    layers["w_up"] = P(layer_axis, expert_axes, "fsdp", None)
    layers["w_down"] = P(layer_axis, expert_axes, None, "fsdp")
    if cfg.n_shared_experts:
        layers["ws_gate"] = P(layer_axis, "fsdp", "tp")
        layers["ws_up"] = P(layer_axis, "fsdp", "tp")
        layers["ws_down"] = P(layer_axis, "tp", "fsdp")
        if cfg.shared_expert_gate:
            layers["w_shared_gate"] = P(layer_axis, None)
    return specs


def shard_params(params: llama.Params, cfg: MoEConfig, mesh) -> llama.Params:  # noqa: ANN001
    """Device-put params onto the mesh per :func:`param_specs` (experts
    over the ep axis)."""
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        param_specs(cfg, pp=mesh.shape.get("pp", 1) > 1),
    )


# -- MoE FFN ----------------------------------------------------------------


def _route(cfg: MoEConfig, layer: llama.Params, x: jnp.ndarray):
    """-> (scores [b, s, E] f32 that sum to one or lie in (0, 1), the
    ``top_k`` experts chosen [b, s, k], their weights [b, s, k] f32)."""
    k = cfg.top_k
    logits = jnp.einsum("bsd,de->bse", x, layer["w_router"], preferred_element_type=jnp.float32)
    if cfg.router_score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(scores, k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(axis=-1, keepdims=True), 1e-9)
        return scores, gate_idx, gate_vals * cfg.routed_scale
    scores = jax.nn.sigmoid(logits)
    # the bias chooses and never weighs: an expert's weight is its own score
    choose = scores + layer["router_bias"].astype(jnp.float32) if cfg.router_bias else scores
    _, gate_idx = jax.lax.top_k(choose, k)
    gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
    gate_vals = gate_vals / (gate_vals.sum(axis=-1, keepdims=True) + 1e-20) * cfg.routed_scale
    return scores, gate_idx, gate_vals


def _capacity_experts(cfg: MoEConfig, layer: llama.Params, x, gate_idx, gate_vals):  # noqa: ANN001
    """GShard einsum dispatch over fixed per-expert capacity buffers: a
    routing past its expert's capacity is dropped. -> (out, overflow)."""
    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    capacity = max(1, int(cfg.capacity_factor * s * k / E))
    with jax.named_scope(hot.MOE_DISPATCH):
        # expert one-hot per choice: [b, s, k, E]
        choice_oh = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
        # position of each (token, choice) in its expert's capacity buffer:
        # cumsum over the flattened (s, k) token-choice axis, per (b, E)
        flat = choice_oh.reshape(b, s * k, E)
        pos = jnp.cumsum(flat, axis=1) - flat  # [b, s*k, E]
        pos = (pos * flat).sum(-1).reshape(b, s, k).astype(jnp.int32)  # [b, s, k]
        within = pos < capacity
        pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32) * within[..., None]

        # dispatch [b, s, E, C] (0/1) and combine (gate-weighted)
        dispatch = jnp.einsum("bske,bskc->bsec", choice_oh, pos_oh)
        combine = jnp.einsum("bske,bskc,bsk->bsec", choice_oh, pos_oh, gate_vals)

        # tokens -> expert capacity slots: [b, E, C, d]
        expert_in = jnp.einsum("bsec,bsd->becd", dispatch.astype(x.dtype), x)
    with jax.named_scope(hot.MOE_EXPERTS):
        # per-expert SwiGLU, expert axis stays leading (sharded over tp)
        gate = jax.nn.silu(jnp.einsum("becd,edf->becf", expert_in, layer["w_gate"]))
        up = jnp.einsum("becd,edf->becf", expert_in, layer["w_up"])
        expert_out = jnp.einsum("becf,efd->becd", gate * up, layer["w_down"])
    # back to tokens, gate-weighted
    with jax.named_scope(hot.MOE_COMBINE):
        out = jnp.einsum("bsec,becd->bsd", combine.astype(x.dtype), expert_out)
    return out, jax.lax.stop_gradient(1.0 - within.astype(jnp.float32).mean())


def _dropless_experts(cfg: MoEConfig, layer: llama.Params, x, gate_idx, gate_vals):  # noqa: ANN001
    """Sorted dispatch: the (token, choice) rows in expert order, one
    grouped matmul a projection over the experts held, every row computed
    by its expert whatever the imbalance. Where this chip holds a share of
    the experts (``cfg.experts_held``) the rows of experts that live elsewhere
    sort behind every held expert's, belong to no group, are not multiplied
    and add nothing: the sum is this chip's part of the routed sum. -> out."""
    from torchx_tpu.ops.grouped_matmul import grouped_matmul

    b, s, d = x.shape
    held, k = cfg.n_experts_held, cfg.top_k
    rows = b * s * k
    with jax.named_scope(hot.MOE_DISPATCH), jax.named_scope(hot.MOE_SORT):
        expert_of = gate_idx.reshape(rows)
        if cfg.experts_held:
            expert_of = expert_of - cfg.experts_held_from
            here = (expert_of >= 0) & (expert_of < held)
            expert_of = jnp.where(here, expert_of, held)  # elsewhere: behind the last held group, counted in none
        order = jnp.argsort(expert_of, stable=True)  # row r of the sorted batch is choice order[r]
        group_sizes = jnp.zeros((held,), jnp.int32).at[expert_of].add(1)
        sorted_in = x.reshape(b * s, d)[order // k]  # [rows, d]
    with jax.named_scope(hot.MOE_EXPERTS):
        # under a layer scan the experts come as the whole stack and the layer's number
        # (llama.scan_layers): the grouped matmul reads its layer where it lies
        at = layer.get("layer_index")
        gmm = functools.partial(grouped_matmul, group_sizes=group_sizes, layer=at, spread_over=cfg.n_experts)
        gate = jax.nn.silu(gmm(sorted_in, layer["w_gate"]))
        up = gmm(sorted_in, layer["w_up"])
        sorted_out = gmm(gate * up, layer["w_down"])  # [rows, d]
    with jax.named_scope(hot.MOE_COMBINE):
        # back to (token, choice) order by gather, then the weighted sum in float32
        unsorted = sorted_out[jnp.argsort(order)].reshape(b, s, k, d)
        if cfg.experts_held:  # a row of no group is whatever the kernel's output buffer held
            unsorted = jnp.where(here.reshape(b, s, k, 1), unsorted, 0)
        out = jnp.einsum("bskd,bsk->bsd", unsorted.astype(jnp.float32), gate_vals)
    return out.astype(x.dtype)


def moe_ffn(
    cfg: MoEConfig,
    layer: llama.Params,  # one layer's slice (with w_router/w_gate/w_up/w_down)
    x: jnp.ndarray,  # [b, s, d]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Route -> dispatch -> per-expert SwiGLU -> combine, plus the shared
    expert where the config has one. ``capacity_factor > 0`` takes the
    GShard einsum dispatch over capacity buffers (static shapes, routings
    past capacity dropped); ``capacity_factor = 0`` the dropless sorted
    dispatch.

    Returns (output, aux): aux is the router-health vector
    ``[balance, entropy, overflow]`` —

    * balance: the Switch-style load-balancing loss
      ``E * Σ_e fraction_routed_e * mean_router_prob_e`` (≈1 when
      balanced; this component, and only this, is scaled into the loss
      by cfg.router_aux_coef),
    * entropy: mean router-distribution entropy normalized by log(E)
      (1 = uniform routing, →0 as the router collapses onto experts),
    * overflow: fraction of (token, choice) routings dropped because
      their expert's capacity buffer was full (0 by construction on the
      dropless path).

    The trainer surfaces all three at log points."""
    E = cfg.n_experts
    with jax.named_scope(hot.MOE_ROUTER):
        scores, gate_idx, gate_vals = _route(cfg, layer, x)
    if cfg.capacity_factor > 0:
        out, overflow = _capacity_experts(cfg, layer, x, gate_idx, gate_vals)
    else:
        out, overflow = _dropless_experts(cfg, layer, x, gate_idx, gate_vals), jnp.float32(0.0)
    if cfg.n_shared_experts:
        with jax.named_scope(hot.MOE_SHARED):
            gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, layer["ws_gate"]))
            up = jnp.einsum("bsd,df->bsf", x, layer["ws_up"])
            shared = jnp.einsum("bsf,fd->bsd", gate * up, layer["ws_down"])
            if cfg.shared_expert_gate:
                weight = jax.nn.sigmoid(jnp.einsum("bsd,d->bs", x, layer["w_shared_gate"], preferred_element_type=jnp.float32))
                shared = (shared.astype(jnp.float32) * weight[..., None]).astype(shared.dtype)
            out = out + shared
    with jax.named_scope(hot.MOE_ROUTER):  # router health, beside the routing itself
        # load-balancing aux: fraction of top-1 routings per expert x mean
        # router probability per expert (Switch Transformer eq. 4-6)
        probs = scores if cfg.router_score == "softmax" else scores / scores.sum(-1, keepdims=True)
        top1_oh = jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32)  # [b, s, E]
        frac_routed = top1_oh.mean(axis=(0, 1))  # [E]
        mean_prob = probs.mean(axis=(0, 1))  # [E]
        balance = E * jnp.sum(frac_routed * mean_prob)
        # router health metrics (monitoring only; stop_gradient keeps them
        # out of the backward pass)
        p_safe = jnp.maximum(probs, 1e-9)
        entropy = jax.lax.stop_gradient(
            (-(p_safe * jnp.log(p_safe)).sum(-1).mean()) / jnp.log(float(E))
        )
        # order fixed by llama.AUX_BALANCE / AUX_ENTROPY / AUX_OVERFLOW
        aux = jnp.stack([balance, entropy, overflow])
    return out, aux


# -- model glue -------------------------------------------------------------
# llama._layer dispatches to moe_ffn when the config carries n_experts
# (duck-typed on the config, imported lazily there); forward/loss_fn are
# re-exported so MoE callers depend only on this module.


def forward(
    params: llama.Params,
    tokens: jnp.ndarray,
    cfg: MoEConfig,
    mesh=None,  # noqa: ANN001
) -> jnp.ndarray:
    """Logits for a MoE config (the shared llama forward dispatches to
    the expert FFN when the config carries experts)."""
    return llama.forward(params, tokens, cfg, mesh)


def loss_fn(
    params: llama.Params,
    batch: dict[str, jnp.ndarray],
    cfg: MoEConfig,
    mesh=None,  # noqa: ANN001
) -> jnp.ndarray:
    """Next-token CE + router balancing aux term."""
    return llama.loss_fn(params, batch, cfg, mesh)
