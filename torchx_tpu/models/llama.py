"""Llama-3-family decoder, pure-functional JAX, TPU-first.

Design choices for the TPU compilation model:

* **Stacked layer params + ``lax.scan``** over layers — one compiled layer
  body instead of n_layers unrolled copies: seconds-not-minutes compiles at
  8B scale, and XLA pipelines the scan cleanly.
* **``jax.checkpoint`` on the scan body** (``remat=True``) — recompute
  activations in backward, trading MXU FLOPs (abundant) for HBM (scarce).
* **bfloat16 params/activations, float32 softmax/norms/logits** — the
  standard TPU numerics recipe.
* **GSPMD sharding via PartitionSpec trees** — :func:`param_specs` maps
  every param to the canonical 5-axis mesh (pp/dp/fsdp/tp/sp);
  :func:`forward` drops ``with_sharding_constraint`` hints on the residual
  stream so XLA places the collectives (all-gather for fsdp params,
  all-reduce for tp partials) on ICI.
* **Ring attention** over the ``sp`` axis for long-context training
  (config.use_ring_attention), falling back to full (flash) attention when
  the sequence is unsharded.

The flagship model config matches Llama-3-8B (meta-llama/Meta-Llama-3-8B
architecture: 32 layers, 4096 dim, 32 heads / 8 KV heads, 14336 FFN,
128256 vocab, rope theta 500k).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchx_tpu.models import gdn, hyper, ssm
from torchx_tpu.obs import hot
from torchx_tpu.parallel import mesh as mesh_lib
from torchx_tpu.ops.attention import attention, note_traced
from torchx_tpu.ops.norms import rms_norm
from torchx_tpu.ops.quant import maybe_matmul
from torchx_tpu.ops.ring_attention import ring_attention
from torchx_tpu.ops.rope import YarnScaling, apply_rope_whole, rope_frequencies

Params = dict[str, Any]

# Layout of the router-health aux vector threaded through every forward:
# [Switch balance loss, normalized router entropy, capacity-overflow
# fraction]. Dense layers contribute zeros. Shared by moe.moe_ffn (the
# producer), the trainer's log line, and the dryrun gate — index through
# these names, never bare integers.
AUX_BALANCE, AUX_ENTROPY, AUX_OVERFLOW = 0, 1, 2
AUX_LEN = 3


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    # width of one attention head where the model states it apart from
    # dim / n_heads (64 heads of 128 over a hidden size of 6,144); 0: dim / n_heads
    attn_head_dim: int = 0
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    remat: bool = True
    # remat policy: "full" recomputes everything (min memory);
    # "dots" saves matmul outputs (fewer recomputes, more memory)
    remat_policy: str = "full"
    attn_impl: str = "auto"  # auto | xla | pallas | splash
    # flash-attention tile sizes (0 = kernel defaults); tune for head_dim
    # (profiling: defaults underfill the MXU at head_dim 64 — see
    # docs/performance.md)
    attn_block_q: int = 0
    attn_block_kv: int = 0
    use_ring_attention: bool = False
    # cross-entropy is computed in sequence chunks of this size so the
    # [batch, seq, vocab] float32 logits never materialize (the dominant
    # activation at 128k vocab); 0 disables chunking
    loss_chunk: int = 512
    # microbatches for pipeline parallelism (meshes with pp > 1);
    # 0 = auto (2x the pp degree — a 2(S-1)/(2S) bubble)
    pp_microbatches: int = 0
    # AQT int8 training matmuls for the layer projections (wq/wk/wv/wo +
    # FFN): int8 runs ~1.94x faster than bf16 on v5e MXUs (measured, see
    # docs/performance.md); master weights stay bf16, quantization is
    # dynamic per step with a straight-through estimator in the backward
    int8_matmuls: bool = False
    # which projections int8_matmuls quantizes: "all" (attention + FFN)
    # or "ffn" (gate/up/down only — the largest, most int8-friendly dots;
    # attention projections at head_dim granularity amortize the dynamic
    # quant/dequant overhead worst, so selective mode trims overhead at
    # small batch; measured crossover in docs/performance.md)
    int8_scope: str = "all"
    # store CE logits in f32 instead of bf16 (exact-f32 cross entropy at
    # 2x the logits HBM traffic; see _token_nll for the measured tradeoff)
    ce_f32_logits: bool = False
    # fused-kernel selection for the layer hot path (ops/fused.py):
    # "reference" keeps the stock ops; "pallas" swaps in the fused
    # flash-attention and residual+RMSNorm Mosaic kernels on TPU (each
    # call site falls back per-shape when gating fails — TPX112 warns at
    # launch time); "interpret" runs the same kernels in the Pallas
    # interpreter (CPU parity tests only — slow)
    kernels: str = "reference"
    # multi-head latent attention (models/mla.py) when kv_lora_rank > 0: a
    # token's keys and values of all heads are one normed latent of this
    # width plus one rotary key of qk_rope_dim shared by the heads; each
    # head's query is (qk_nope_dim, qk_rope_dim) wide and its value
    # v_head_dim. 0 keeps grouped-query attention over n_kv_heads.
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # the attention of each layer, in the order the layers run: "sliding" (query
    # i admits key j where i - sliding_window < j <= i), "full" (j <= i) or "linear"
    # (no attention: a Gated DeltaNet mixer, models/gdn.py, in attention's place).
    # Empty: every layer is full. The pattern is data: a stack of mixed kinds
    # runs in this order (scan_layers), a sliding layer's cache is a pool of
    # its own that holds a slot's window and no more (generate.init_kv_pools), and
    # a linear layer keeps no K/V at all: a row of the mixer's store a slot
    layer_types: tuple[str, ...] = ()
    sliding_window: int = 0
    # RMSNorm over each head's width on q and on k, a learned gain each
    # (q_norm, k_norm), ahead of the rotary embedding
    qk_norm: bool = False
    # False: full-attention layers take no rotary embedding, sliding layers do
    rope_full_layers: bool = True
    # latent attention's query through a latent of its own when > 0: q = RMSNorm(u W_qa) W_qb
    q_lora_rank: int = 0
    # YaRN's blend of two rotary frequency sets and its scale on the softmax
    # (ops/rope.py::YarnScaling); None: the one theta
    rope_scaling: Optional[YarnScaling] = None
    # hyper-connections (models/hyper.py) when hc_mult > 0: the residual stream is
    # hc_mult rows a token; each sublayer reads one mix of them and writes back
    # through a per-token matrix that hc_sinkhorn_iters row-then-column
    # normalisations make doubly stochastic, its logits clipped to hc_res_clamp
    # first. 0: x = x + f(x)
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 1e-6
    hc_res_clamp: tuple[float, float] = (-30.0, 30.0)
    # a Mamba-2 mixer (models/ssm.py) beside attention in every layer when ssm_heads > 0,
    # both off the layer's one norm and into its one residual add: ssm_heads heads of
    # ssm_head_dim channels over ssm_groups groups of ssm_state-wide input and output maps,
    # a depthwise causal convolution of ssm_conv taps ahead of them; the chunked form
    # multiplies inside chunks of ssm_chunk positions. What a sequence carries is a state
    # [heads, head_dim, state] in float32 and the convolution's last ssm_conv - 1 inputs:
    # a serving engine keeps them a slot, beside the paged K/V. 0: attention alone
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # fixed multipliers on the model's paths (maximal-update parametrisation): the
    # embedding's rows, the logits, attention's input, its keys ahead of the rotation and
    # its output, the mixer's input, the five segments of its projection (z, x, B, C, dt)
    # and its output, the feed-forward's gate ahead of its activation and its output.
    # 1.0 multiplies nothing: the program of a model without them is as it was
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)
    # EVA attention (models/eva.py) when eva_window > 0: a position attends, in one softmax, the
    # exact keys of its own aligned window of eva_window positions up to itself and one pooled
    # row of every chunk of eva_chunk positions of every window before it, pooled with two
    # learned vectors a cache head (eva_phi, eva_mu_k). A serving engine keeps the window's rows
    # and the pooled rows in one paged pool under one table: what a sequence holds there is not
    # its tokens. 0: every position attends every position before it
    eva_window: int = 0
    eva_chunk: int = 0
    # RMSNorm gains are stored as g and applied as 1 + g (the layer's two norms and the final norm)
    norm_unit_offset: bool = False
    # the residual adds are made in float32 and rounded once to the stream's type
    fp32_skip_add: bool = False
    # output heads side by side in lm_head [dim, pred_heads * vocab_size]: head i predicts the
    # token i + 1 ahead. Serving and the loss read head 0, the first vocab_size columns
    pred_heads: int = 1
    # the rotary embedding turns the first rotary_dim values of a head, pairs (i, i + rotary_dim / 2), and
    # leaves the rest as they are; 0: the whole head
    rotary_dim: int = 0
    # wq makes [heads, 2 head_dim]: a head's first half its query, its second a gate, and the heads'
    # output is multiplied by sigmoid(gate) ahead of wo
    attn_output_gate: bool = False
    # the Gated DeltaNet mixer of the "linear" layers (models/gdn.py): gdn_heads value heads and
    # gdn_key_heads key heads of gdn_head_dim each (a key head serves gdn_heads / gdn_key_heads value
    # heads), a depthwise causal convolution of gdn_conv taps over [q | k | v]; the chunked form solves
    # inside sub-chunks of gdn_chunk positions. What a sequence carries is a state [heads, head_dim,
    # head_dim] in float32 and the convolution's last gdn_conv - 1 inputs. 0: no such layers
    gdn_heads: int = 0
    gdn_key_heads: int = 0
    gdn_head_dim: int = 0
    gdn_conv: int = 4
    gdn_chunk: int = 64

    def __post_init__(self) -> None:
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "hc_res_clamp", tuple(float(v) for v in self.hc_res_clamp))
        object.__setattr__(self, "ssm_multipliers", tuple(float(v) for v in self.ssm_multipliers))
        object.__setattr__(self, "mlp_multipliers", tuple(float(v) for v in self.mlp_multipliers))
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has five entries (z, x, B, C, dt) and mlp_multipliers two (gate, down)")
        if self.ssm_heads:
            if not (self.ssm_head_dim and self.ssm_state) or self.ssm_heads % self.ssm_groups or self.ssm_conv < 2:
                raise ValueError("a mixer needs ssm_head_dim, ssm_state, ssm_conv >= 2 and ssm_heads a multiple of ssm_groups")
            if self.kv_lora_rank or self.layer_types or self.hc_mult or self.kernels != "reference" or self.use_ring_attention:
                raise ValueError(
                    "the mixer runs beside full grouped-query attention through the stock layer ops: not beside latent"
                    " attention, sliding layers, hyper-connections, the fused or the ring kernels"
                )
        if self.eva_window:
            if self.eva_chunk < 1 or self.eva_window % self.eva_chunk:
                raise ValueError("eva_window must be whole chunks of eva_chunk positions")
            if (
                self.kv_lora_rank or self.layer_types or self.ssm_heads or self.hc_mult or self.qk_norm
                or self.kernels != "reference" or self.use_ring_attention
            ):  # fmt: skip
                raise ValueError(
                    "EVA attention runs through the stock layer ops over grouped-query heads: not beside latent"
                    " attention, sliding layers, a mixer, hyper-connections, QK-norm, the fused or the ring kernels"
                )
        if self.norm_unit_offset and (self.kv_lora_rank or self.ssm_heads or self.hc_mult or self.kernels != "reference"):
            raise ValueError(
                "unit-offset gains are built for the layer's two norms, the final norm and QK-norm through the stock ops:"
                " not beside the norms of latent attention, a mixer, hyper-connections or the fused kernels"
            )
        if self.rotary_dim and (self.rotary_dim % 2 or self.rotary_dim > self.head_dim or self.kv_lora_rank):
            raise ValueError("rotary_dim is an even part of a grouped-query head")
        if self.attn_output_gate and (self.kv_lora_rank or self.eva_window or self.kernels != "reference" or self.use_ring_attention):
            raise ValueError("the output gate is built for grouped-query attention through the stock layer ops")
        linear = "linear" in self.layer_types
        if linear != bool(self.gdn_heads):
            raise ValueError("'linear' layers and gdn_heads go together: a linear layer is a Gated DeltaNet mixer")
        if linear:
            if not (self.gdn_key_heads and self.gdn_head_dim) or self.gdn_heads % self.gdn_key_heads or self.gdn_conv < 2:
                raise ValueError("a linear layer needs gdn_head_dim, gdn_conv >= 2 and gdn_heads a multiple of gdn_key_heads")
            if (
                "sliding" in self.layer_types or "full" not in self.layer_types or self.kv_lora_rank or self.ssm_heads
                or self.hc_mult or self.eva_window or self.kernels != "reference" or self.use_ring_attention
            ):  # fmt: skip
                raise ValueError(
                    "linear layers run between full grouped-query layers through the stock layer ops: not beside sliding"
                    " layers, latent attention, a Mamba mixer, hyper-connections, EVA attention, the fused or the ring kernels"
                )
        if self.pred_heads < 1:
            raise ValueError("pred_heads counts the output heads: at least one")
        if self.hc_mult and (self.kernels != "reference" or self.use_ring_attention):
            raise ValueError("hyper-connections run through the stock layer ops only, not the fused or ring kernels")
        if self.q_lora_rank and not self.kv_lora_rank:
            raise ValueError("q_lora_rank compresses latent attention's query: it needs kv_lora_rank")
        if self.layer_types:
            if len(self.layer_types) != self.n_layers or set(self.layer_types) - {"sliding", "full", "linear"}:
                raise ValueError(
                    f"layer_types must name {self.n_layers} layers 'sliding', 'full' or 'linear', got {self.layer_types!r}"
                )
            if "sliding" in self.layer_types and self.sliding_window < 1:
                raise ValueError("a sliding layer needs sliding_window >= 1")
            if self.kv_lora_rank:
                raise ValueError("latent attention has no sliding layers")
        if self.kv_lora_rank and not (self.qk_nope_dim and self.qk_rope_dim and self.v_head_dim):
            raise ValueError(
                "latent attention needs qk_nope_dim, qk_rope_dim and v_head_dim"
            )
        if self.int8_scope not in ("all", "ffn"):
            raise ValueError(
                f"int8_scope must be 'all' or 'ffn', got {self.int8_scope!r}"
            )
        if self.kernels not in ("reference", "pallas", "interpret"):
            raise ValueError(
                "kernels must be 'reference', 'pallas' or 'interpret',"
                f" got {self.kernels!r}"
            )

    @property
    def head_dim(self) -> int:
        """Per-head projection width (dim / n_heads unless stated)."""
        return self.attn_head_dim or self.dim // self.n_heads

    @property
    def ssm_inner(self) -> int:
        """Channels of the mixer: its heads times a head's width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """What the mixer's convolution runs over: x, B and C side by side."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def rope_dim(self) -> int:
        """Width of what the rotary embedding turns in a head."""
        return self.qk_rope_dim if self.kv_lora_rank else self.rotary_dim or self.head_dim

    @property
    def query_width(self) -> int:
        """Columns of ``wq`` a head: its query, and with an output gate the gate beside it."""
        return self.head_dim * (2 if self.attn_output_gate else 1)

    @property
    def gdn_conv_width(self) -> int:
        """What a linear layer's convolution runs over: q, k and v side by side."""
        return (2 * self.gdn_key_heads + self.gdn_heads) * self.gdn_head_dim

    @property
    def attn_scale(self) -> float:
        """What the scores are multiplied by ahead of the softmax: the width the
        query and key are dotted over to the power -1/2, times the square of
        YaRN's ``mscale`` where the model scales its rotary frequencies."""
        width = self.qk_nope_dim + self.qk_rope_dim if self.kv_lora_rank else self.head_dim
        scale = width**-0.5
        return scale * self.rope_scaling.attention_mscale**2 if self.rope_scaling else scale

    @property
    def cache_kinds(self) -> tuple[str, ...]:
        """The cache kind of each layer: ``"window"`` for a sliding layer (its
        pool holds the blocks that still touch a slot's window), ``"state"`` for a
        linear one (no blocks: a row of the mixer's store), else ``"full"``."""
        kinds = {"sliding": "window", "linear": "state"}
        return tuple(kinds.get(t, "full") for t in self.layer_types) or ("full",) * self.n_layers

    @property
    def layer_period(self) -> int:
        """The shortest period of the layers' kinds from layer 0 on (1: all alike)."""
        kinds = self.cache_kinds
        return next(p for p in range(1, len(kinds) + 1) if all(k == kinds[i % p] for i, k in enumerate(kinds)))

    def layers_of(self, kind: str) -> int:
        """How many layers keep a cache of ``kind``."""
        return self.cache_kinds.count(kind)

    @property
    def cache_row(self) -> tuple[int, int]:
        """``(rows, width)`` of what a position holds of K (and of V) in a layer's paged pool: its
        cache heads as they are, or, where they are fewer than fill a packed tile (the decode kernel
        takes 4 or a multiple: ``ops.paged_attention.kernel_eligible``) and a head is several lanes
        wide, each head as its 128-value parts one after another: 2 heads of 256 lie as 4 rows of
        128, which is how the chip lays ``[2, 256]`` out anyway, and the kernel then needs no
        re-laying of the pool in front of it (553 MB a layer at 128 slots: my rehearsal, PR 49)."""
        kvh, hd = self.n_kv_heads, self.head_dim
        if kvh % 4 and hd > 128 and hd % 128 == 0 and (kvh * hd // 128) % 4 == 0:
            return kvh * hd // 128, 128
        return kvh, hd

    @property
    def cache_width(self) -> int:
        """Values a token holds in one layer's cache: K and V of every cache
        head, or one row of the latent and its rotary key, padded with zeros
        to whole 128-value lanes (512 + 64 -> 640: the TPU's tiling pads the
        row so in HBM whatever the shape says, and a row of whole lanes is
        what a block copy and a matmul can take)."""
        if self.kv_lora_rank:
            return -(-(self.kv_lora_rank + self.qk_rope_dim) // 128) * 128
        return 2 * self.n_kv_heads * self.head_dim

    def attention_param_count(self) -> int:
        """Matmul weights of one layer's attention."""
        d, h = self.dim, self.n_heads
        if self.kv_lora_rank:
            r, rq, q_width = self.kv_lora_rank, self.q_lora_rank, h * (self.qk_nope_dim + self.qk_rope_dim)
            return (
                (d * rq + rq + rq * q_width if rq else d * q_width)  # w_qa, q_latent_norm, w_qb | wq
                + d * (r + self.qk_rope_dim)  # w_kva
                + r * h * (self.qk_nope_dim + self.v_head_dim)  # w_kvb
                + h * self.v_head_dim * d  # wo
                + r  # kv_norm
            )
        hd = self.head_dim
        learned = (2 * hd if self.qk_norm else 0) + (2 * self.n_kv_heads * hd if self.eva_window else 0)  # q_norm, k_norm | eva_phi, eva_mu_k
        return d * h * self.query_width + 2 * d * self.n_kv_heads * hd + h * hd * d + learned

    def flops_per_token(self) -> float:
        """Training FLOPs/token (fwd+bwd), 6N + attention quadratic term."""
        n_params = self.param_count()
        attn = (
            12
            * self.n_layers
            * self.dim
            * self.max_seq  # per-token causal avg is seq/2; 2*seq/2*... -> seq
        )
        return 6 * n_params + attn

    def param_count(self) -> int:
        """Exact parameter count for this shape (layers + embeddings)."""
        d, f, v = self.dim, self.ffn_dim, self.vocab_size
        n = self.hc_mult
        per_layer = (
            3 * d * f  # gate, up, down
            + 2 * d  # norms
            + (2 * ((n * d + 1) * (2 * n + n * n) + 3) if n else 0)  # hyper-connections: phi, b, a of two sublayers
            + ssm.param_count(self)
        )
        n_linear = self.layers_of("state")  # a linear layer has a Gated DeltaNet mixer where the others have attention
        mixers = (self.n_layers - n_linear) * self.attention_param_count() + n_linear * gdn.param_count(self)
        total = self.n_layers * per_layer + mixers + v * d + d  # embed + final norm
        if not self.tie_embeddings:
            total += d * v * self.pred_heads
        return total


# -- presets ---------------------------------------------------------------


def llama3_8b(**overrides: Any) -> LlamaConfig:
    """Llama-3-8B (the config defaults: 32L/4096d/32h/8kv/128k vocab)."""
    return LlamaConfig(**overrides)


def llama3_1b(**overrides: Any) -> LlamaConfig:
    """Llama-3.2-1B shape (tied embeddings).

    attn_block_q/kv defaults come from the hardware sweeps
    (``scripts/tune_attention_blocks.py`` on v5e-1, seq 2048): with the
    GQA-native splash kernel that ``attn_impl="auto"`` now picks on TPU,
    512/512 tiles measure 46.9% MFU (50.2% steady-state) vs 39.6% for the
    best flash tiling (256/512) and 23.9% at kernel-default 128 tiles —
    head_dim 64 underfills the MXU, larger tiles amortize it; full tables
    in docs/performance.md.
    """
    defaults = dict(
        dim=2048,
        n_layers=16,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=8192,
        tie_embeddings=True,
        attn_block_q=512,
        attn_block_kv=512,
    )
    defaults.update(overrides)
    return LlamaConfig(**defaults)


def llama_tiny(**overrides: Any) -> LlamaConfig:
    """Test/debug config: runs on anything in milliseconds."""
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
    )
    defaults.update(overrides)
    return LlamaConfig(**defaults)


CONFIGS = {
    "llama3_8b": llama3_8b,
    "llama3_1b": llama3_1b,
    "tiny": llama_tiny,
}


# -- parameters ------------------------------------------------------------


def init_params(cfg: LlamaConfig, key: jax.Array) -> Params:
    """Scaled-normal init; layer params stacked on a leading axis."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    d, f = cfg.dim, cfg.ffn_dim
    hd, h, kvh, L = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.n_layers

    def norm_init(key, shape, in_dim):  # noqa: ANN001
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * (in_dim**-0.5)
        ).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 7)
    n_linear = cfg.layers_of("state")  # layers with a Gated DeltaNet mixer where the others have attention
    if cfg.kv_lora_rank:
        r, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        rq = cfg.q_lora_rank
        if rq:
            k_qa, k_qb = jax.random.split(ks[0])
            query = {
                "w_qa": norm_init(k_qa, (L, d, rq), d),
                "q_latent_norm": jnp.ones((L, rq), dtype=cfg.dtype),
                "w_qb": norm_init(k_qb, (L, rq, h * (dn + dr)), rq),
            }
        else:
            query = {"wq": norm_init(ks[0], (L, d, h * (dn + dr)), d)}
        attn = {
            **query,
            "w_kva": norm_init(ks[1], (L, d, r + dr), d),
            "kv_norm": jnp.ones((L, r), dtype=cfg.dtype),
            # beside a compressed query the up-projection's key and value parts lie apart,
            # each transposed, the heads outermost (models/mla.py)
            **(
                {"w_uk": norm_init(ks[2], (L, h, dn, r), r), "w_uv": norm_init(jax.random.fold_in(ks[2], 1), (L, h, dv, r), r)}
                if rq
                else {"w_kvb": norm_init(ks[2], (L, r, h * (dn + dv)), r)}
            ),
            "wo": norm_init(ks[3], (L, h * dv, d), h * dv),
        }
    else:
        La = L - n_linear  # attention's leaves: of the layers that attend
        attn = {
            "wq": norm_init(ks[0], (La, d, h * cfg.query_width), d),
            "wk": norm_init(ks[1], (La, d, kvh * hd), d),
            "wv": norm_init(ks[2], (La, d, kvh * hd), d),
            "wo": norm_init(ks[3], (La, h * hd, d), h * hd),
        }
        gain = jnp.zeros if cfg.norm_unit_offset else jnp.ones  # applied as 1 + g: norm_gain
        if cfg.qk_norm:
            attn["q_norm"] = gain((La, hd), dtype=cfg.dtype)
            attn["k_norm"] = gain((La, hd), dtype=cfg.dtype)
        if cfg.eva_window:  # a clamped normal times hd^-0.5 as published; here a plain normal
            k_phi, k_mu = jax.random.split(jax.random.fold_in(k_layers, 9))
            attn["eva_phi"] = norm_init(k_phi, (La, kvh, hd), hd)
            attn["eva_mu_k"] = norm_init(k_mu, (La, kvh, hd), hd)
    gain = jnp.zeros if cfg.norm_unit_offset else jnp.ones  # applied as 1 + g: norm_gain
    mixers = {}
    if n_linear:  # the layers' mixers differ in their leaves: a stack a kind beside the stack of what every layer has
        mixers = {"mixers": {"full": attn, "state": gdn.init_leaves(cfg, jax.random.fold_in(k_layers, 10), n_linear)}}
        attn = {}
    params: Params = {
        "embed": norm_init(k_embed, (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": gain((L, d), dtype=cfg.dtype),
            **attn,
            "mlp_norm": gain((L, d), dtype=cfg.dtype),
            "w_gate": norm_init(ks[4], (L, d, f), d),
            "w_up": norm_init(ks[5], (L, d, f), d),
            "w_down": norm_init(ks[6], (L, f, d), f),
            **hyper.init_leaves(cfg, jax.random.fold_in(k_layers, 7), L),
            **ssm.init_leaves(cfg, jax.random.fold_in(k_layers, 8), L),
        },
        **mixers,
        "final_norm": gain((d,), dtype=cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(k_head, (d, cfg.pred_heads * cfg.vocab_size), d)
    return params


def param_specs(cfg: LlamaConfig, pp: bool = False) -> Params:
    """PartitionSpec tree matching init_params, on the pp/dp/fsdp/tp/sp mesh.

    2D sharding: the "fsdp" axis shards the model dimension (ZeRO-3-style
    weight gather per layer under the scan), "tp" shards heads/ffn
    (Megatron-style, all-reduce after wo/w_down). The stacked layer axis
    shards over "pp" when pipeline parallelism is on (each stage owns a
    contiguous run of layers), else stays unsharded.
    """
    layer_axis = "pp" if pp else None
    if cfg.kv_lora_rank:
        # the latent is every head's: its down-projection and norm stay
        # whole over tp, the per-head up-projection splits there
        query = (
            {"w_qa": P(layer_axis, "fsdp", None), "q_latent_norm": P(layer_axis, None), "w_qb": P(layer_axis, None, "tp")}
            if cfg.q_lora_rank
            else {"wq": P(layer_axis, "fsdp", "tp")}
        )
        attn = {
            **query,
            "w_kva": P(layer_axis, "fsdp", None),
            "kv_norm": P(layer_axis, None),
            **(
                {"w_uk": P(layer_axis, "tp", None, None), "w_uv": P(layer_axis, "tp", None, None)}
                if cfg.q_lora_rank
                else {"w_kvb": P(layer_axis, None, "tp")}
            ),
            "wo": P(layer_axis, "tp", "fsdp"),
        }
    else:
        attn = {
            "wq": P(layer_axis, "fsdp", "tp"),
            "wk": P(layer_axis, "fsdp", "tp"),
            "wv": P(layer_axis, "fsdp", "tp"),
            "wo": P(layer_axis, "tp", "fsdp"),
        }
        if cfg.qk_norm:
            attn["q_norm"] = P(layer_axis, None)
            attn["k_norm"] = P(layer_axis, None)
        if cfg.eva_window:  # two vectors a cache head: every chip's, read whole
            attn["eva_phi"] = P(layer_axis, None, None)
            attn["eva_mu_k"] = P(layer_axis, None, None)
    mixers = {}
    if cfg.layers_of("state"):  # the linear mixer's heads are not split: its matrices shard their model axis
        state = {
            name: P(layer_axis, *{"gdn_in": ("fsdp", None), "gdn_ba": ("fsdp", None), "gdn_out": (None, "fsdp")}.get(name, (None,) * len(shape)))
            for name, shape in gdn.leaf_shapes(cfg).items()
        }
        mixers, attn = {"mixers": {"full": attn, "state": state}}, {}
    specs: Params = {
        # vocab axis unsharded: a gather over a vocab-sharded table forces
        # the SPMD partitioner into full rematerialization; dim shards fine
        "embed": P(None, "fsdp"),
        "layers": {
            "attn_norm": P(layer_axis, None),
            **attn,
            "mlp_norm": P(layer_axis, None),
            "w_gate": P(layer_axis, "fsdp", "tp"),
            "w_up": P(layer_axis, "fsdp", "tp"),
            "w_down": P(layer_axis, "tp", "fsdp"),
            # a sublayer's mixing coefficients are every chip's: a few rows a token, read whole
            **{name: P(layer_axis, *(None,) * len(shape)) for name, shape in hyper.leaf_shapes(cfg).items()},
            # the mixer's heads and groups are not split: its two matrices shard their model axis, the rest is every chip's
            **{
                name: P(layer_axis, *{"ssm_in": ("fsdp", None), "ssm_out": (None, "fsdp")}.get(name, (None,) * len(shape)))
                for name, shape in ssm.leaf_shapes(cfg).items()
            },
        },
        **mixers,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("fsdp", "tp")
    return specs


def layer_groups(params: Params) -> tuple[str, ...]:
    """The parameter tree's groups of equal layers, in the order they run:
    each is one ``[L_group, ...]`` stack, scanned by itself."""
    return ("dense_layers", "layers") if "dense_layers" in params else ("layers",)


#: an expert layer's leaves that a dropless dispatch reads through its grouped matmul
_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


@jax.tree_util.register_static
class _Kind(str):
    """A layer's ``attn_kind`` as it rides in the layer's dict: a string to
    whoever reads it, and to jax a node without leaves, so that the dict still
    goes through ``jax.checkpoint`` and ``lax.scan`` as a tree of arrays."""


def scan_layers(cfg: LlamaConfig, step, x, stack: Params, first: int = 0, mixers: Optional[Params] = None):  # noqa: ANN001, ANN201
    """Run ``step(x, layer) -> (x, ys)`` over one group's stack of layers in
    the order they lie, ``x`` any carry; ``first`` is the group's first layer's
    number in the model. A layer is its slice of every leaf plus what says
    where it stands: ``layer_index`` (its number in the group),
    ``attn_kind`` (``cfg.cache_kinds``: ``"full"`` or ``"window"``, static) and
    ``kind_index`` (how many layers of that kind run before it in the model:
    its place in that kind's pool stack). What a kernel reads (the routed
    experts of a dropless expert layer, which go in whole beside it, for
    :mod:`torchx_tpu.ops.grouped_matmul`; the paged pools a serving step carries
    in ``x``) would be copied if sliced out of its stack first, and is read at
    those indices where it lies instead. Where the layers' mixers differ in their
    leaves (an attention's, a linear mixer's: ``params["mixers"]``, a stack a cache
    kind over the layers of that kind) a layer's own mixer is indexed out of its
    kind's stack at ``kind_index``, as its cache is out of its kind's pools.

    Layers of one kind are one ``lax.scan`` with the stack as ``xs``, as it
    always was. Where kinds alternate (``cfg.layer_types``) the scan runs over
    whole periods of the pattern with the period's layers, each of its own
    kind, in the body; the layers ahead of the group's first period boundary
    and behind its last run one by one. There a layer is indexed out of the
    stack where it lies, inside the loop: a slice or a reshape of the stack in
    front of the loop is a copy of the weights every step (a reshape of ``xs``
    to ``[periods, period, ...]`` made XLA re-lay and copy whole expert stacks,
    3.5 GB each at Mixtral's widths: my chip run, PR 31). -> (x, stacked ys)."""
    n = jax.tree.leaves(stack)[0].shape[0]
    dropless = "w_router" in stack and getattr(cfg, "capacity_factor", 1.0) <= 0
    whole = {k: stack[k] for k in _EXPERT_WEIGHTS} if dropless else {}
    sliced = {k: w for k, w in stack.items() if k not in whole}
    kinds, period = cfg.cache_kinds, cfg.layer_period

    def place(k: int, p=0):  # noqa: ANN001, ANN202 - layer k of the model, moved on by p periods
        kind = _Kind(kinds[k])
        return kind, p * kinds[:period].count(kind) + kinds[:k].count(kind)

    def run(x, layer, i, kind, kind_index):  # noqa: ANN001, ANN202
        own = {k: jax.lax.dynamic_index_in_dim(w, kind_index, keepdims=False) for k, w in mixers[kind].items()} if mixers else {}
        return step(x, dict(layer, **own, **whole, layer_index=i, attn_kind=kind, kind_index=kind_index))

    if period == 1:
        if mixers:
            raise NotImplementedError("a stack a kind of mixers runs under a period of mixed kinds")

        def body(x, xs):  # noqa: ANN001, ANN202
            i, layer = xs
            return run(x, layer, i, _Kind(kinds[first]), i + first if first else i)

        with jax.named_scope(hot.LAYERS):
            return jax.lax.scan(body, x, (jnp.arange(n, dtype=jnp.int32), sliced))

    head = min(n, -first % period)
    n_periods = (n - head) // period
    stacked = lambda ys: jax.tree.map(lambda *a: jnp.stack(a), *ys)  # noqa: E731

    def one_by_one(x, lo: int, hi: int):  # noqa: ANN001, ANN202
        ys = []
        for i in range(lo, hi):
            x, y = run(x, {k: w[i] for k, w in sliced.items()}, i, *place(first + i))
            ys.append(y)
        return x, ([stacked(ys)] if ys else [])

    def body(x, p):  # noqa: ANN001, ANN202
        ys = []
        for j in range(period):
            i = head + p * period + j
            layer = {k: jax.lax.dynamic_index_in_dim(w, i, keepdims=False) for k, w in sliced.items()}
            x, y = run(x, layer, i, *place(first + head + j, p))
            ys.append(y)
        return x, stacked(ys)

    with jax.named_scope(hot.LAYERS):
        x, parts = one_by_one(x, 0, head)
        if n_periods:
            x, ys = jax.lax.scan(body, x, jnp.arange(n_periods, dtype=jnp.int32))
            parts.append(jax.tree.map(lambda y: y.reshape(-1, *y.shape[2:]), ys))
        x, tail = one_by_one(x, head + n_periods * period, n)
    parts += tail
    return x, (parts[0] if len(parts) == 1 else jax.tree.map(lambda *a: jnp.concatenate(a), *parts))


def model_fns(cfg: LlamaConfig):
    """(init_params, param_specs) for the config's model family — dense,
    or MoE when the config carries experts. The single dispatch point the
    trainer and the AOT-fit machinery share."""
    if getattr(cfg, "n_experts", 0):
        from torchx_tpu.models import moe

        return moe.init_params, moe.param_specs
    return init_params, param_specs


def shard_params(params: Params, cfg: LlamaConfig, mesh: Mesh) -> Params:
    """Device-put params onto the mesh per param_specs."""
    specs = param_specs(cfg, pp=mesh.shape.get("pp", 1) > 1)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


# -- forward ---------------------------------------------------------------


def _constraint(x: jnp.ndarray, mesh: Optional[Mesh], *spec) -> jnp.ndarray:
    if mesh is None:
        return x
    manual = mesh_lib.manual_axes()
    if manual:
        # inside a shard_map manual region (pp stage, possibly with sp
        # manual too for in-stage ring attention): constraints may only
        # name the still-automatic axes — manual ones are per-shard here
        def strip(entry):  # noqa: ANN001
            if entry is None or isinstance(entry, str):
                return None if entry in manual else entry
            kept = tuple(a for a in entry if a not in manual)
            return kept if kept else None

        spec = tuple(strip(e) for e in spec)
        if all(e is None for e in spec):
            return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def rope_table(cfg: LlamaConfig, positions: int, start=0) -> tuple[jnp.ndarray, jnp.ndarray]:  # noqa: ANN001
    """(cos, sin) ``[positions, rope_dim / 2]`` of the model's rotary embedding
    from position ``start`` on: its theta, and its YaRN blend where it has one."""
    return rope_frequencies(cfg.rope_dim, positions, cfg.rope_theta, start=start, scaling=cfg.rope_scaling)


def ffn(
    cfg: LlamaConfig, layer: Params, mlp_in: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The FFN half of a layer: dense SwiGLU, or the expert layer when the
    layer carries a router (a stack may lead with dense layers before its
    expert layers: the layer's own tree says which it is). -> (down, aux).
    Shared by the training forward and the KV-cache decode path so the two
    can never diverge."""
    if "w_router" in layer:
        if cfg.int8_matmuls:
            import warnings

            scope = cfg.int8_scope
            warnings.warn(
                "int8_matmuls does not cover the MoE expert einsums"
                " (expert-stacked weights need a grouped AQT einsum); "
                + (
                    "only the attention projections quantize"
                    if scope == "all"
                    else "with int8_scope='ffn' NOTHING quantizes on a MoE"
                    " config — the flag is a no-op here"
                ),
                stacklevel=2,
            )
        from torchx_tpu.models.moe import moe_ffn

        return moe_ffn(cfg, layer, mlp_in)

    i8 = cfg.int8_matmuls
    gate_by, down_by = cfg.mlp_multipliers
    with jax.named_scope(hot.MLP):
        gate = jax.nn.silu(scaled(maybe_matmul(mlp_in, layer["w_gate"], int8_training=i8), gate_by))
        up = maybe_matmul(mlp_in, layer["w_up"], int8_training=i8)
        down = scaled(maybe_matmul(gate * up, layer["w_down"], int8_training=i8), down_by)
    return down, jnp.zeros((AUX_LEN,), jnp.float32)  # aux vector: dense = zeros


def scaled(x: jnp.ndarray, by: float) -> jnp.ndarray:
    """``x`` times one of the model's fixed multipliers; 1.0 multiplies nothing."""
    return x if by == 1.0 else x * by


def norm_gain(cfg: LlamaConfig, gain: jnp.ndarray) -> jnp.ndarray:
    """What an RMSNorm of the model multiplies by: the stored ``gain``, or ``1 +
    gain`` in float32 where the model stores its gains about zero
    (``norm_unit_offset``)."""
    return 1.0 + gain.astype(jnp.float32) if cfg.norm_unit_offset else gain


def window_of(cfg: LlamaConfig, layer: Params) -> int:
    """The window of ``layer``'s attention: ``cfg.sliding_window`` on a sliding
    layer (``scan_layers`` says which it is), 0 on a full one."""
    return cfg.sliding_window if layer.get("attn_kind") == "window" else 0


def norm_and_rotate(cfg: LlamaConfig, layer: Params, q, k, cos, sin, rope):  # noqa: ANN001, ANN201
    """One layer's q and k ``[..., heads, hd]`` as attention takes them: the keys
    times the model's ``key_multiplier`` where it has one, normed
    a head where the model has QK-norm, then rotated by ``rope(x, cos, sin)``
    where this layer takes the rotary embedding (every layer, or with
    ``rope_full_layers`` off the sliding ones alone). The uncached forward and
    both serving programs share it, each with the rotation of its own layout."""
    k = scaled(k, cfg.key_multiplier)
    if cfg.qk_norm:
        with jax.named_scope(hot.QK_NORM):
            q = rms_norm(q, norm_gain(cfg, layer["q_norm"]), cfg.norm_eps)
            k = rms_norm(k, norm_gain(cfg, layer["k_norm"]), cfg.norm_eps)
    if cfg.rope_full_layers or window_of(cfg, layer):
        if cfg.rotary_dim and cfg.rotary_dim < cfg.head_dim:  # the head's first values turn, the rest pass

            def rope(x, cos, sin, whole=rope, n=cfg.rotary_dim):  # noqa: ANN001, ANN202
                return jnp.concatenate((whole(x[..., :n], cos, sin), x[..., n:]), axis=-1)

        q, k = rope(q, cos, sin), rope(k, cos, sin)
    return q, k


def split_gate(cfg: LlamaConfig, q: jnp.ndarray):  # noqa: ANN201
    """``wq``'s product ``[..., heads, width]`` -> ``(q [..., heads, hd], gate)``: with an output gate a
    head's first ``hd`` values are its query and its last ``hd`` the gate, else there is none."""
    if not cfg.attn_output_gate:
        return q, None
    return q[..., : cfg.head_dim], q[..., cfg.head_dim :]


def gate_heads(out: jnp.ndarray, gate: Optional[jnp.ndarray]) -> jnp.ndarray:
    """The heads' output times ``sigmoid(gate)`` where attention has an output gate."""
    if gate is None:
        return out
    with jax.named_scope(hot.ATTN_GATE):
        return (out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)


def _gqa_attention(
    cfg: LlamaConfig,
    mesh: Optional[Mesh],
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    attn_in: jnp.ndarray,  # [b, s, d], normed
    layer: Params,
) -> jnp.ndarray:
    """Grouped-query attention of one layer, projections included: -> [b, s, d]."""
    b, s, _ = attn_in.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    i8_attn = cfg.int8_matmuls and cfg.int8_scope == "all"
    q, gate = split_gate(cfg, maybe_matmul(attn_in, layer["wq"], int8_training=i8_attn).reshape(b, s, h, -1))
    k = maybe_matmul(attn_in, layer["wk"], int8_training=i8_attn).reshape(b, s, kvh, hd)
    v = maybe_matmul(attn_in, layer["wv"], int8_training=i8_attn).reshape(b, s, kvh, hd)
    window = window_of(cfg, layer)
    q, k = norm_and_rotate(cfg, layer, q, k, cos, sin, apply_rope_whole)
    if window and (cfg.kernels != "reference" or cfg.use_ring_attention):
        raise NotImplementedError("a sliding layer runs through ops.attention only, not the fused or ring kernels")
    if cfg.eva_window:
        from torchx_tpu.models import eva

        attn_out = eva.attention_full(cfg, layer, q, k, v)
    elif cfg.use_ring_attention and mesh is not None and mesh.shape.get("sp", 1) > 1:
        with jax.named_scope(hot.ATTN_KERNEL):
            attn_out = ring_attention(q, k, v, mesh)
    else:
        attn_out = None
        if cfg.kernels != "reference":
            from torchx_tpu.ops.fused import flash_attention as fused_flash

            # None when gating fails (shape/platform/mesh): stock path below
            with jax.named_scope(hot.ATTN_KERNEL):
                attn_out = fused_flash(
                    q,
                    k,
                    v,
                    causal=True,
                    kernels=cfg.kernels,
                    block_q=cfg.attn_block_q,
                    block_kv=cfg.attn_block_kv,
                    mesh=mesh,
                )
        if attn_out is None:
            attn_out = attention(  # names itself attn_kernel
                q,
                k,
                v,
                causal=True,
                impl=cfg.attn_impl,
                block_q=cfg.attn_block_q,
                block_kv=cfg.attn_block_kv,
                mesh=mesh,
                window=window,
            )
    # named so remat policies can SAVE the kernel output: the attention
    # kernels are not dot_generals, so "dots" alone recomputes the whole
    # flash/splash forward in the backward pass (see "dots_attn")
    attn_out = gate_heads(checkpoint_name(attn_out, "attn_out"), gate)
    return maybe_matmul(
        attn_out.reshape(b, s, h * hd), layer["wo"], int8_training=i8_attn
    )


def _layer(
    cfg: LlamaConfig,
    mesh: Optional[Mesh],
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    x: jnp.ndarray,  # [b, s, d]
    layer: Params,  # one layer's slice
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """-> (x, aux): aux is the MoE load-balancing loss contribution of this
    layer (0 for dense layers).

    ``cos``/``sin`` of None means the sequence axis is manual here (ring
    attention inside a pipeline stage): x holds only this device's shard of
    positions, so the RoPE frequencies are computed locally from the
    shard's global offset."""
    if cos is None:
        start = jax.lax.axis_index("sp") * x.shape[1]
        cos, sin = rope_table(cfg, x.shape[1], start)

    def attend(stream_in):  # noqa: ANN001, ANN202 - the attention sublayer, its norm included
        with jax.named_scope(hot.NORM):
            attn_in = rms_norm(stream_in, norm_gain(cfg, layer["attn_norm"]), cfg.norm_eps, mesh=mesh)
        if layer.get("attn_kind") == "state":  # a linear layer: the Gated DeltaNet mixer in attention's place
            return gdn.forward(cfg, layer, attn_in), None
        with jax.named_scope(hot.ATTN), hot.attn_kind_scope(cfg, layer):
            if cfg.kv_lora_rank:
                from torchx_tpu.models import mla

                return mla.attention_full(cfg, layer, attn_in, cos, sin), None
            out = _gqa_attention(cfg, mesh, cos, sin, scaled(attn_in, cfg.attention_in_multiplier), layer)
            out = scaled(out, cfg.attention_out_multiplier)
        if cfg.ssm_heads:  # the mixer reads the same norm and adds into the same residual
            out = out + ssm.forward(cfg, layer, attn_in)
        return out, None

    def feed_forward(stream_in):  # noqa: ANN001, ANN202 - dense SwiGLU, or MoE when the config carries experts
        with jax.named_scope(hot.NORM):
            mlp_in = rms_norm(stream_in, norm_gain(cfg, layer["mlp_norm"]), cfg.norm_eps, mesh=mesh)
        return ffn(cfg, layer, mlp_in)

    if cfg.kernels != "reference":
        from torchx_tpu.ops.fused import rms_norm_residual

        attn_out, _ = attend(x)
        # fused residual-add + RMSNorm: one VMEM pass yields both the mlp
        # input and the continued stream (degrades internally to the
        # reference op sequence when gating fails — identical values)
        with jax.named_scope(hot.NORM):
            mlp_in, x = rms_norm_residual(
                x,
                attn_out,
                layer["mlp_norm"],
                cfg.norm_eps,
                kernels=cfg.kernels,
                mesh=mesh,
            )
        x = _constraint(x, mesh, ("dp", "fsdp"), "sp", None)
        down, aux = ffn(cfg, layer, mlp_in)
        x = x + down
        return _constraint(x, mesh, ("dp", "fsdp"), "sp", None), aux
    x, _ = hyper.residual(cfg, layer, "attn", x, attend)
    x = _constraint(x, mesh, ("dp", "fsdp"), "sp", None)
    x, aux = hyper.residual(cfg, layer, "mlp", x, feed_forward)
    return _constraint(x, mesh, ("dp", "fsdp"), "sp", None), aux


def _remat(body, cfg: LlamaConfig, looped: bool = False):  # noqa: ANN001
    """``body`` under the configuration's rematerialization. ``looped`` says that every call of it is the body
    of a ``lax.scan`` of two turns or more: differentiation then puts the forward and its recomputation into
    two loops, which no common-subexpression pass can merge, and ``jax.checkpoint``'s own guard against that
    merge (``prevent_cse``: an optimization barrier round everything the recomputation reads) only costs. On
    the chip's compiler the barrier makes a buffer of each of its operands, so a layer's seven weights were
    sliced out of their stacks and written again every backward turn, and nothing on either side of it fused
    with the other: 1,040 MiB of pure movement a layer in ``mistral7b-train-4k``'s backward loop, 320 without
    (PERF.md section 6, PR 51; ``obs.hlo.moves_by_loop``). A layer that runs outside a loop, or in a loop of
    one turn, which the compiler unrolls, keeps the guard."""
    if not cfg.remat:
        return body
    if cfg.remat_policy == "auto":
        # "auto" is a launch-time directive, not a policy: the trainer
        # resolves it to a concrete policy via memory analysis before the
        # forward ever traces (parallel/remat_auto.choose_remat_policy)
        raise ValueError(
            "remat_policy='auto' must be resolved before tracing — "
            "call torchx_tpu.parallel.remat_auto.choose_remat_policy"
            " (the trainer does this at launch)"
        )
    note_traced("remat", "in_loop" if looped else "guarded")
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif cfg.remat_policy == "dots_attn":
        # dots + the named attention-kernel outputs: flash/splash are pallas
        # calls, not dot_generals, so plain "dots" recomputes the whole
        # attention forward in the backward; saving [b, s, h, d] bf16 per
        # layer (~17 MB/layer at 1B shapes) skips that recompute
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("attn_out"),
        )
    else:
        policy = None
    return jax.checkpoint(body, policy=policy, prevent_cse=not looped)


def forward_features(
    params: Params,
    tokens: jnp.ndarray,  # [b, s] int32
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """-> (final-norm hidden states [b, s, dim], router-health aux).

    aux is the [AUX_LEN] vector [balance, entropy, overflow] (all zeros
    for dense models; see moe.moe_ffn). Under pipeline parallelism the
    per-layer aux threads through the pipeline (summed over stages,
    averaged over microbatches). The MoE balancing term is nonlinear in
    token statistics, so the microbatch-averaged value differs slightly
    from the full-batch pp=1 value when routing varies across microbatches
    — the standard group-wise aux (GShard computes it per dispatch group
    the same way); router balancing pressure is preserved, exact loss
    parity is not."""
    # The table lookup follows the ZeRO-3 pattern of every other fsdp
    # weight: all-gather the (dim-sharded) table at use and gather with
    # batch/seq-sharded indices, so the output is BORN in the activation
    # sharding. Replicating the operand alone is not enough: GSPMD's
    # gather heuristic may still pick operand-passthrough (output
    # dim-sharded, indices all-gathered) and then reshard to the
    # batch/seq layout — an axis-moving reshard it can only do by
    # involuntary full rematerialization (replicate + reslice), warned on
    # every compile. Constraining the gather OUTPUT pins the
    # index-passthrough partitioning, so the reshard (and the indices
    # all-gather feeding it) never exists.
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    seq_spec = "sp" if sp > 1 and tokens.shape[1] % sp == 0 else None
    tokens = _constraint(tokens, mesh, ("dp", "fsdp"), seq_spec)
    with jax.named_scope(hot.EMBED):
        table = _constraint(params["embed"], mesh, None, None)
        x = _constraint(table[tokens], mesh, ("dp", "fsdp"), seq_spec, None)
        x = scaled(x.astype(cfg.dtype), cfg.embedding_multiplier)
    return features_from_embeddings(params, x, cfg, mesh)


def features_from_embeddings(
    params: Params,
    x: jnp.ndarray,  # [b, s, d] input embeddings
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`forward_features` starting AFTER the embedding lookup — the
    continuous-input entry point interpretability needs (gradients w.r.t.
    embeddings, e.g. saliency / integrated gradients over tokens)."""
    s = x.shape[1]
    x = hyper.expand(cfg, x.astype(cfg.dtype))
    x = _constraint(x, mesh, ("dp", "fsdp"), "sp", None)

    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    # ring attention under pp runs inside the pipeline's manual region, so
    # the sequence axis manualizes at the pipeline shard_map (Shardy rejects
    # a nested shard_map rebinding pp) and RoPE is computed per-shard from
    # the sp position offset (cos/sin of None -> _layer computes locally)
    ring_in_pp = (
        pp > 1
        and cfg.use_ring_attention
        and mesh is not None
        and mesh.shape.get("sp", 1) > 1
    )
    if ring_in_pp:
        cos = sin = None
    else:
        cos, sin = rope_table(cfg, s)

    # every layer a turn of a scan of its group (one kind of layer, no pipeline), and no group of one layer
    looped = pp == 1 and cfg.layer_period == 1 and all(jax.tree.leaves(params[g])[0].shape[0] > 1 for g in layer_groups(params))
    body = _remat(functools.partial(_layer, cfg, mesh, cos, sin), cfg, looped)

    if pp > 1:
        if "dense_layers" in params or cfg.layer_types or cfg.hc_mult:  # (a stack a kind of mixers comes with layer_types)
            raise NotImplementedError(
                "pipeline parallelism over a stack that leads with dense layers, mixes attention kinds"
                " or carries several residual streams"
            )
        # pipeline the layer stack over the pp axis (embedding/head stay
        # outside the pipeline, replicated over pp)
        import math as _math

        from torchx_tpu.parallel.pipeline import pipeline_apply

        # auto mode picks the largest divisor of the batch <= 2*pp so the
        # schedule always validates; an EXPLICIT pp_microbatches passes
        # through untouched — pipeline_apply raises a clear error on a
        # non-divisor rather than silently degrading the pipeline. When the
        # batch also splits over dp*fsdp, keep each microbatch divisible by
        # that product so in-stage batch sharding (ring attention) holds.
        data_div = max(mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1), 1)
        div = x.shape[0] // data_div if x.shape[0] % data_div == 0 else x.shape[0]
        n_micro = cfg.pp_microbatches or _math.gcd(2 * pp, div)
        x, aux_total = pipeline_apply(
            body,
            params["layers"],
            x,
            mesh,
            n_microbatches=n_micro,
            with_aux=True,
            manual_axes=frozenset({"sp"}) if ring_in_pp else frozenset(),
            x_spec=P(None, "sp", None) if ring_in_pp else None,
        )
        # stages SUM aux over their layers; balance keeps the sum (Switch
        # semantics) but the monitoring stats (entropy/overflow) are
        # per-layer means, so divide the layer count back out
        aux_total = jnp.stack(
            [
                aux_total[AUX_BALANCE],
                aux_total[AUX_ENTROPY] / cfg.n_layers,
                aux_total[AUX_OVERFLOW] / cfg.n_layers,
            ]
        )
    else:
        # one scan a group of equal layers: leading dense layers, where
        # the tree has them, then the stack proper
        aux_groups, first = [], 0
        for group in layer_groups(params):
            x, aux_group = scan_layers(cfg, body, x, params[group], first, params.get("mixers"))
            aux_groups.append(aux_group)
            first += aux_group.shape[0]
        aux_per_layer = jnp.concatenate(aux_groups)
        # [L, AUX_LEN] per-layer aux: balance sums over layers (matches
        # the Switch loss), the monitoring stats average
        aux_total = jnp.stack(
            [
                aux_per_layer[:, AUX_BALANCE].sum(),
                aux_per_layer[:, AUX_ENTROPY].mean(),
                aux_per_layer[:, AUX_OVERFLOW].mean(),
            ]
        )
    x = hyper.collapse(cfg, x)
    with jax.named_scope(hot.NORM):
        x = rms_norm(x, norm_gain(cfg, params["final_norm"]), cfg.norm_eps, mesh=mesh)
    return x, aux_total


def lm_head(params: Params, cfg: LlamaConfig) -> jnp.ndarray:
    """[dim, vocab] output projection (the embedding transposed when
    tied); every head's columns side by side, ``[dim, pred_heads * vocab]``,
    where the model has several (:func:`next_token_head` is the first's)."""
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def next_token_head(params: Params, cfg: LlamaConfig):  # noqa: ANN201
    """The head that predicts the next token, ``[dim, vocab]``: what serving
    samples from and the loss is taken over. The whole of :func:`lm_head` but
    where the model has further heads beside it (``pred_heads``), whose columns
    are then never multiplied."""
    head = lm_head(params, cfg)
    return head[:, : cfg.vocab_size] if cfg.pred_heads > 1 else head


def forward_from_embeddings(
    params: Params,
    embeds: jnp.ndarray,  # [b, s, d]
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """-> logits [b, s, vocab] f32 from input embeddings (see
    :func:`features_from_embeddings`)."""
    x, _ = features_from_embeddings(params, embeds, cfg, mesh)
    with jax.named_scope(hot.LM_HEAD):
        logits = jnp.einsum(
            "bsd,dv->bsv", x, lm_head(params, cfg), preferred_element_type=jnp.float32
        )
        logits = scaled(logits, cfg.lm_head_multiplier)
    return _constraint(logits, mesh, ("dp", "fsdp"), "sp", "tp")


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [b, s] int32
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """-> logits [b, s, vocab] float32 (full materialization — use
    :func:`loss_fn` for training, which never builds this tensor)."""
    x, _ = forward_features(params, tokens, cfg, mesh)
    with jax.named_scope(hot.LM_HEAD):
        logits = jnp.einsum(
            "bsd,dv->bsv", x, lm_head(params, cfg), preferred_element_type=jnp.float32
        )
        logits = scaled(logits, cfg.lm_head_multiplier)
    # keep the vocab axis tp-sharded: the lm_head einsum produces it that
    # way, and all-gathering [b, s, vocab] f32 logits would cost ~GBs of
    # HBM + ICI per step at 128k vocab (log_softmax is fine sharded)
    return _constraint(logits, mesh, ("dp", "fsdp"), "sp", "tp")


def _head_logits(
    x: jnp.ndarray,  # [b, c, d] hidden states
    head: jnp.ndarray,  # [d, v]
    mesh: Optional[Mesh],
    f32_logits: bool,
) -> jnp.ndarray:
    """-> the logits the loss is taken over, ``[b, c, v]``, stored bf16 unless
    ``f32_logits`` (the MXU accumulates the matmul in f32 either way)."""
    with jax.named_scope(hot.LM_HEAD):
        logits = jnp.einsum(
            "bcd,dv->bcv",
            x,
            head,
            preferred_element_type=jnp.float32 if f32_logits else None,
        )
        # keep the vocab axis tp-sharded (same guard as forward(): never
        # all-gather [b, *, vocab] logits on a tensor-parallel mesh)
        return _constraint(logits, mesh, ("dp", "fsdp"), None, "tp")


def _nll_of_logits(
    logits: jnp.ndarray,  # [b, c, v]
    targets: jnp.ndarray,  # [b, c]
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """-> (nll ``[b, c]``, the logits in float32, their logsumexp ``[b, c, 1]``),
    all float32: what the loss sums, and what its gradient is made from."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1, keepdims=True)
    tgt = jnp.take_along_axis(lf, targets[..., None], axis=-1)
    return (lse - tgt)[..., 0], lf, lse


def _token_nll(
    x: jnp.ndarray,  # [b, c, d] hidden states
    head: jnp.ndarray,  # [d, v]
    targets: jnp.ndarray,  # [b, c]
    mesh: Optional[Mesh] = None,
    f32_logits: bool = False,
) -> jnp.ndarray:
    """-> per-token negative log-likelihood [b, c] float32.

    What the loss costs, each choice measured on v5e (docs/performance.md;
    the last PERF.md section 6, PR 47):

    * ``logsumexp(logits) - logits[target]`` instead of
      ``log_softmax + take``: log_softmax materializes a SECOND
      [b, c, vocab] tensor (2.1 GB f32 at 1B shapes) purely as an
      intermediate — avoiding it was worth +1.1pp MFU.
    * logits stored bf16 by default (``f32_logits=False``): the MXU
      accumulates the matmul in f32 either way, storage rounding halves
      the HBM traffic of every later pass (+0.3pp MFU, 53.5→53.8);
      reductions and
      the CE gradient (softmax - onehot) run in f32 from the bf16 tensor.
      Loss trajectories match f32 to 3 decimals at 1B scale; flip
      ``LlamaConfig.ce_f32_logits`` for exact-f32 CE.
    * summed a chunk of the sequence at a time (:func:`_chunked_loss`), only
      ``[b, chunk, vocab]`` logits ever exist, and under differentiation a
      chunk's logits are made ONCE: its gradient is formed from them in the
      pass that made them, three vocabulary matmuls a chunk (logits, ``dx``,
      ``dW``) where a rematerialized scan ran the first one twice.
    """
    logits = _head_logits(x, head, mesh, f32_logits)
    with jax.named_scope(hot.LOSS):
        return _nll_of_logits(logits, targets)[0]


def _loss_denominator(ts: jnp.ndarray, ms: Optional[jnp.ndarray]):  # noqa: ANN202
    """What the summed loss is divided by: the tokens, or the mask's sum (at least 1)."""
    return float(ts.size) if ms is None else jnp.maximum(ms.sum(), 1.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _chunked_loss(
    xs: jnp.ndarray,  # [n, b, c, d] hidden states, a chunk of the sequence a row
    head: jnp.ndarray,  # [d, v]
    ts: jnp.ndarray,  # [n, b, c] targets
    ms: Optional[jnp.ndarray],  # [n, b, c] float32 loss mask, None: every token counts
    mesh: Optional[Mesh],
    f32_logits: bool,
) -> jnp.ndarray:
    """-> the (masked) mean of :func:`_token_nll` over all chunks, one chunk's
    ``[b, c, v]`` logits alive at a time.

    Called plainly (an evaluation) this is a scan with one matmul a chunk.
    Under differentiation JAX runs :func:`_chunked_loss_fwd` in its place,
    which makes each chunk's gradient from the logits it has just made: the
    gradient is linear in the one scalar the backward pass brings, so nothing
    has to wait for it but a multiply. ``ops.attention.traced("loss")`` says
    which ran: ``chunked`` or ``fused``."""
    note_traced("loss", "chunked")

    def body(acc, xt):  # noqa: ANN001
        x_c, t_c, m_c = xt
        nll = _token_nll(x_c, head, t_c, mesh, f32_logits)
        return acc + (nll if m_c is None else nll * m_c).sum(), None

    total, _ = jax.lax.scan(body, jnp.float32(0), (xs, ts, ms))
    return total / _loss_denominator(ts, ms)


def _chunked_loss_fwd(xs, head, ts, ms, mesh, f32_logits):  # noqa: ANN001, ANN202
    """-> (loss, (d loss / d xs, d loss / d head)): one scan, each chunk's
    logits made once. ``dW`` is summed over the chunks in the head's dtype, as
    the transposed scan's carry was."""
    note_traced("loss", "fused")
    denominator = _loss_denominator(ts, ms)
    scale = 1.0 / denominator

    def body(carry, xt):  # noqa: ANN001
        total, dw = carry
        x_c, t_c, m_c = xt
        logits = _head_logits(x_c, head, mesh, f32_logits)
        with jax.named_scope(hot.LOSS):
            nll, lf, lse = _nll_of_logits(logits, t_c)
            w = scale if m_c is None else (m_c * scale)[..., None]
            onehot = jax.nn.one_hot(t_c, lf.shape[-1], dtype=jnp.float32)
            dlogits = ((jnp.exp(lf - lse) - onehot) * w).astype(logits.dtype)
        # the two gradient matmuls are autodiff's own transposes of the logits'
        # einsum (its operands' dtypes, its constraint). dW contracts x_c over
        # its rows: it reads a copy written [b, d, c], as the transposed scan
        # kept one; without it the chip's compiler transposes the operand inside
        # the matmul, 13.6 ms a step where this takes 12.5 (PERF.md, PR 47)
        x_t = jax.lax.optimization_barrier(x_c.swapaxes(1, 2))
        (dx_c,) = jax.linear_transpose(lambda x: _head_logits(x, head, mesh, f32_logits), x_c)(dlogits)
        (dw_c,) = jax.linear_transpose(lambda h: _head_logits(x_t.swapaxes(1, 2), h, mesh, f32_logits), head)(dlogits)
        return (total + (nll if m_c is None else nll * m_c).sum(), dw + dw_c), dx_c

    (total, dw), dxs = jax.lax.scan(body, (jnp.float32(0), jnp.zeros_like(head)), (xs, ts, ms))
    return total / denominator, (dxs, dw)


def _chunked_loss_bwd(mesh, f32_logits, residuals, g):  # noqa: ANN001, ANN202
    """The incoming scalar times the two gradients the forward pass made
    (multiplied in float32, so that ``g`` is not rounded to bf16); targets
    and mask get none."""
    dxs, dw = residuals
    times_g = lambda r: (r.astype(jnp.float32) * g).astype(r.dtype)  # noqa: E731
    return times_g(dxs), times_g(dw), None, None


_chunked_loss.defvjp(_chunked_loss_fwd, _chunked_loss_bwd)


def loss_fn(
    params: Params,
    batch: dict[str, jnp.ndarray],  # {"tokens": [b, s]} next-token LM
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Next-token cross-entropy loss (see :func:`loss_and_aux`)."""
    return loss_and_aux(params, batch, cfg, mesh)[0]


def loss_and_aux(
    params: Params,
    batch: dict[str, jnp.ndarray],
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """-> (total loss, router-health aux vector).

    aux is [balance, entropy, overflow] (index via AUX_*): the raw
    pre-coefficient Switch balance term (≈1 when experts are balanced,
    grows as routing collapses), the normalized router entropy, and the
    capacity-overflow fraction — all zeros for dense models. Only
    aux[AUX_BALANCE] is scaled into the loss."""
    tokens = batch["tokens"]
    x, aux = forward_features(params, tokens[:, :-1], cfg, mesh)
    x = scaled(x, cfg.lm_head_multiplier)  # on the head's input: the logits are made a chunk at a time
    aux_term = getattr(cfg, "router_aux_coef", 0.0) * aux[AUX_BALANCE]
    targets = tokens[:, 1:]
    head = next_token_head(params, cfg)
    mask = batch.get("loss_mask")
    m = mask[:, 1:].astype(jnp.float32) if mask is not None else None
    f32 = cfg.ce_f32_logits

    s = targets.shape[1]
    chunk = cfg.loss_chunk
    if chunk and s % chunk == 0 and s > chunk:
        # a chunk of the sequence at a time: only [b, chunk, vocab] logits
        # ever exist, and each chunk's are made once (see _chunked_loss)
        b = targets.shape[0]
        n = s // chunk
        chunks = lambda a: a.reshape(b, n, chunk, *a.shape[2:]).swapaxes(0, 1)  # noqa: E731
        loss = _chunked_loss(chunks(x), head, chunks(targets), None if m is None else chunks(m), mesh, f32)
        return loss + aux_term, aux

    note_traced("loss", "whole")
    nll = _token_nll(x, head, targets, mesh, f32)
    if m is not None:
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0) + aux_term, aux
    return nll.mean() + aux_term, aux
