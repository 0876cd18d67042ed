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
"""

from __future__ import annotations

import dataclasses
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

    def param_count(self) -> int:
        """Exact parameter count (dense shapes + per-expert FFNs)."""
        dense = super().param_count()
        # replace the dense FFN with E experts + router
        ffn = 3 * self.dim * self.ffn_dim
        return dense + self.n_layers * (
            (self.n_experts - 1) * ffn + self.dim * self.n_experts
        )

    def flops_per_token(self) -> float:
        """MoE FLOPs count only the top_k ACTIVE experts per token."""
        attn = 12 * self.n_layers * self.dim * self.max_seq
        return 6 * self.active_param_count() + attn

    def active_param_count(self) -> int:
        """Params touched per token (top_k experts) — the MFU-relevant N."""
        ffn = 3 * self.dim * self.ffn_dim
        dense = super().param_count()
        return dense + self.n_layers * (
            (self.top_k - 1) * ffn + self.dim * self.n_experts
        )


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
    """Dense-llama params with the FFN weights expanded to [L, E, ...] and a
    router added."""
    params = llama.init_params(cfg, key)
    L, E, d, f = cfg.n_layers, cfg.n_experts, cfg.dim, cfg.ffn_dim
    k_router, k_g, k_u, k_d = jax.random.split(jax.random.fold_in(key, 17), 4)

    def init(key, shape, in_dim):  # noqa: ANN001
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * (in_dim**-0.5)
        ).astype(cfg.dtype)

    layers = params["layers"]
    layers["w_router"] = init(k_router, (L, d, E), d)
    layers["w_gate"] = init(k_g, (L, E, d, f), d)
    layers["w_up"] = init(k_u, (L, E, d, f), d)
    layers["w_down"] = init(k_d, (L, E, f, d), f)
    return params


def param_specs(cfg: MoEConfig, pp: bool = False) -> llama.Params:
    """Expert axis shards over ``("ep", "tp")`` combined (expert
    parallelism, independent of tensor-parallel size); within-expert dims
    shard over ``fsdp`` like the dense model; the stacked layer axis shards
    over ``pp`` when pipeline parallelism is on."""
    layer_axis = "pp" if pp else None
    expert_axes = ("ep", "tp")
    specs = llama.param_specs(cfg, pp=pp)
    specs["layers"]["w_router"] = P(layer_axis, "fsdp", None)
    specs["layers"]["w_gate"] = P(layer_axis, expert_axes, "fsdp", None)
    specs["layers"]["w_up"] = P(layer_axis, expert_axes, "fsdp", None)
    specs["layers"]["w_down"] = P(layer_axis, expert_axes, None, "fsdp")
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


def moe_ffn(
    cfg: MoEConfig,
    layer: llama.Params,  # one layer's slice (with w_router/w_gate/w_up/w_down)
    x: jnp.ndarray,  # [b, s, d]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """GShard einsum dispatch: route -> dispatch to capacity slots ->
    per-expert SwiGLU -> combine. Static shapes throughout.

    Returns (output, aux): aux is the router-health vector
    ``[balance, entropy, overflow]`` —

    * balance: the Switch-style load-balancing loss
      ``E * Σ_e fraction_routed_e * mean_router_prob_e`` (≈1 when
      balanced; this component, and only this, is scaled into the loss
      by cfg.router_aux_coef),
    * entropy: mean router-distribution entropy normalized by log(E)
      (1 = uniform routing, →0 as the router collapses onto experts),
    * overflow: fraction of (token, choice) routings dropped because
      their expert's capacity buffer was full.

    The trainer surfaces all three at log points."""
    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    capacity = max(1, int(cfg.capacity_factor * s * k / E))

    with jax.named_scope(hot.MOE_ROUTER):
        router_logits = jnp.einsum(
            "bsd,de->bse", x, layer["w_router"], preferred_element_type=jnp.float32
        )
        probs = jax.nn.softmax(router_logits, axis=-1)  # [b, s, E] f32
        gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [b, s, k]
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(axis=-1, keepdims=True), 1e-9
        )

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
    with jax.named_scope(hot.MOE_ROUTER):  # router health, beside the routing itself
        # load-balancing aux: fraction of top-1 routings per expert x mean
        # router probability per expert (Switch Transformer eq. 4-6)
        top1_oh = choice_oh[:, :, 0, :]  # [b, s, E]
        frac_routed = top1_oh.mean(axis=(0, 1))  # [E]
        mean_prob = probs.mean(axis=(0, 1))  # [E]
        balance = E * jnp.sum(frac_routed * mean_prob)
        # router health metrics (monitoring only; stop_gradient keeps them
        # out of the backward pass)
        p_safe = jnp.maximum(probs, 1e-9)
        entropy = jax.lax.stop_gradient(
            (-(p_safe * jnp.log(p_safe)).sum(-1).mean()) / jnp.log(float(E))
        )
        overflow = jax.lax.stop_gradient(1.0 - within.astype(jnp.float32).mean())
        # order fixed by llama.AUX_BALANCE / AUX_ENTROPY / AUX_OVERFLOW
        aux = jnp.stack([balance, entropy, overflow])

    # back to tokens, gate-weighted
    with jax.named_scope(hot.MOE_COMBINE):
        out = jnp.einsum("bsec,becd->bsd", combine.astype(x.dtype), expert_out)
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
