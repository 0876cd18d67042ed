"""Gated DeltaNet mixer: a linear layer's recurrent branch, in attention's place.

Where ``cfg.layer_types`` names a layer ``"linear"`` the layer has this mixer and
no attention (``llama._layer``, ``generate._paged_layer_step``): it keeps no K/V.
``H`` value heads (``cfg.gdn_heads``) and ``Hk`` key heads (``cfg.gdn_key_heads``) of
``D = cfg.gdn_head_dim`` each, key head ``h // (H / Hk)`` serving value head ``h``::

    [q | k | v | z] = u W_in;   [b | a] = u W_ba          # Hk D | Hk D | H D | H D;  H | H: no bias
    [q | k | v] = silu(conv([q | k | v]))                 # depthwise, causal, cfg.gdn_conv taps, no bias
    q = l2norm(q) / sqrt(D);   k = l2norm(k)              # x / sqrt(sum x^2 + 1e-6), a head each
    beta = sigmoid(b);   g = -exp(A_log) softplus(a + dt_bias)          # float32, a value head each
    S <- exp(g_t) S;   u_t = beta_t (v_t - S^T k_t);   S <- S + k_t u_t^T;   o_t = S^T q_t     # S [D, D] a head, from zeros
    out = (gain * o / sqrt(mean(o^2) + eps) * silu(z)) W_out            # the norm over each head's D values, then the gate

The delta rule reads ``S^T k`` **before** it writes: not :mod:`torchx_tpu.models.ssm`'s
recurrence (decay, add an outer product, read out), which is why this is a module
and a kernel of its own. The state is kept ``[H, D_k, D_v]``: both of a step's
read-outs (``S^T k``, ``S^T q``) sum over ``D_k``, which then lies across a tile's
rows and costs adds, where along its lanes it would cost a shuffle a row (as
``ssm.py`` keeps ``[H, N, P]``).

Two forms that agree (``tests/test_qwen3_next.py``): :func:`step_core`, one position a
row from a carried state (a slot's decode row), and :func:`chunk_core`, many positions
from a carried state, solved inside sub-chunks of ``C = cfg.gdn_chunk`` positions and
carried between them (``G_i`` the running sum of ``g`` inside the sub-chunk, ``S_0`` the
state at its head)::

    A_ij = beta_i (k_i . k_j) exp(G_i - G_j)  (j < i, else 0);   T = (I + A)^-1 diag(beta)
    W = T (K * exp(G));   U = T V;   V' = U - W S_0
    O = (Q * exp(G)) S_0 + mask_{j<=i}(Q K^T * exp(G_i - G_j)) V'
    S_1 = exp(G_C) S_0 + (K * exp(G_C - G))^T V'

``(I + A)^-1`` is forward substitution over diagonal blocks of 16 rows, merged pairwise
(``[[X11, 0], [-X22 A21 X11, X22]]``): 15 dependent steps a chunk whatever ``C``, and no
power of ``A`` is ever formed (with keys that repeat, ``A``'s powers grow as binomials
before they cancel). Everything between the projections is float32 at the highest
matmul precision: the products are ``C x C x D`` a head, nothing beside the projections.

A serving engine keeps the state of every slot in a store beside its paged K/V pools
(:func:`init_store`), as ``ssm.py`` does: ``state [linear layers, 1 + slots, H, D, D]``
float32 and ``conv [linear layers, 1 + slots, (gdn_conv - 1) x conv width]`` in the model's
type, a row's inputs end to end; addressed by **row**; row 0 is the trash row.
:func:`decode_rows` and :func:`chunk_rows` read a part's rows out of the layer's slice of
the store, move them on and write them back. ``ops.attention.traced("gdn")`` answers
which forms a program traced (``step_pallas`` / ``step`` / ``chunk``).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from torchx_tpu.models.ssm import _layer_rows, _row, _set_row, _set_rows_where
from torchx_tpu.obs import hot
from torchx_tpu.ops.attention import note_traced
from torchx_tpu.ops.quant import maybe_matmul as mm

Store = dict[str, jnp.ndarray]  # {"state": [L, rows, H, D, D] f32, "conv": [L, rows, (gdn_conv - 1) x conv width]}
HI = jax.lax.Precision.HIGHEST
_L2_EPS = 1e-6


def leaf_shapes(cfg: Any) -> dict[str, tuple[int, ...]]:
    """A linear layer's mixer leaves by name, the layer axis left off; none without such layers."""
    if not cfg.gdn_heads:
        return {}
    h, inner = cfg.gdn_heads, cfg.gdn_heads * cfg.gdn_head_dim
    return {
        "gdn_in": (cfg.dim, cfg.gdn_conv_width + inner),  # [q | k | v | z]
        "gdn_ba": (cfg.dim, 2 * h),  # [b | a]
        "gdn_conv_w": (cfg.gdn_conv, cfg.gdn_conv_width),  # tap k multiplies the input gdn_conv - 1 - k positions back
        "gdn_dt_bias": (h,),
        "gdn_A_log": (h,),
        "gdn_norm": (cfg.gdn_head_dim,),  # one plain gain, shared by the heads
        "gdn_out": (inner, cfg.dim),
    }


def init_leaves(cfg: Any, key: jax.Array, layers: int) -> dict[str, jnp.ndarray]:
    """Seeded mixer leaves, ``layers`` deep: the matrices normal over their fan-in,
    the gain and ``A`` at one (``A_log`` 0), ``dt_bias`` zero."""
    fan_in = {"gdn_in": cfg.dim, "gdn_ba": cfg.dim, "gdn_out": cfg.gdn_heads * cfg.gdn_head_dim, "gdn_conv_w": cfg.gdn_conv}
    out = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        if name in fan_in:
            w = jax.random.normal(jax.random.fold_in(key, i), (layers, *shape), jnp.float32) * fan_in[name] ** -0.5
            out[name] = w.astype(cfg.dtype)
        else:
            out[name] = (jnp.ones if name == "gdn_norm" else jnp.zeros)((layers, *shape), cfg.dtype)
    return out


def param_count(cfg: Any) -> int:
    """One linear layer's mixer parameters."""
    return sum(int(np.prod(shape)) for shape in leaf_shapes(cfg).values())


def init_store(cfg: Any, rows: int) -> Store:
    """Zeroed state and convolution tails of ``rows`` rows a linear layer (a serving
    engine's ``1 + max_slots``: row 0 is the trash row)."""
    layers, h, d = cfg.layers_of("state"), cfg.gdn_heads, cfg.gdn_head_dim
    return {
        "state": jnp.zeros((layers, rows, h, d, d), jnp.float32),
        "conv": jnp.zeros((layers, rows, (cfg.gdn_conv - 1) * cfg.gdn_conv_width), cfg.dtype),
    }


# -- the pieces both forms share -------------------------------------------------


def project(cfg: Any, layer: dict, u: jnp.ndarray):  # noqa: ANN201
    """The layer's normed input ``u [..., d]`` -> ``(qkv [..., 2 Hk D + H D], z [..., H D],
    b [..., H], a [..., H])``: what the convolution takes, the output's gate, what makes
    ``beta`` and the decay."""
    with jax.named_scope(hot.GDN_PROJ):
        qkvz, ba = mm(u, layer["gdn_in"]), mm(u, layer["gdn_ba"])
        width, h = cfg.gdn_conv_width, cfg.gdn_heads
        return qkvz[..., :width], qkvz[..., width:], ba[..., :h], ba[..., h:]


def _conv(layer: dict, window: jnp.ndarray) -> jnp.ndarray:
    """``window [..., gdn_conv, width]``, a position's input last -> that position's
    ``silu(conv)`` in float32."""
    return jax.nn.silu(jnp.sum(window.astype(jnp.float32) * layer["gdn_conv_w"].astype(jnp.float32), axis=-2))


def _l2norm(x: jnp.ndarray) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + _L2_EPS)


def _heads(cfg: Any, qkv: jnp.ndarray):  # noqa: ANN202
    """The convolution's float32 output ``[..., width]`` -> ``(q, k [..., Hk, D]`` normed, ``q`` scaled,
    ``v [..., H, D])``."""
    hk, h, d = cfg.gdn_key_heads, cfg.gdn_heads, cfg.gdn_head_dim
    lead = qkv.shape[:-1]
    q = _l2norm(qkv[..., : hk * d].reshape(*lead, hk, d)) * d**-0.5
    k = _l2norm(qkv[..., hk * d : 2 * hk * d].reshape(*lead, hk, d))
    return q, k, qkv[..., 2 * hk * d :].reshape(*lead, h, d)


def _per_value_head(cfg: Any, x: jnp.ndarray) -> jnp.ndarray:
    """``[..., Hk, D]`` -> ``[..., H, D]``: value head ``h`` reads key head ``h // (H / Hk)``."""
    rep = cfg.gdn_heads // cfg.gdn_key_heads
    return x if rep == 1 else jnp.repeat(x, rep, axis=-2)


def _rates(layer: dict, b: jnp.ndarray, a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """-> float32 ``(beta, g) [..., H]``: how much of the correction a position writes, and
    the logarithm of what the state keeps across it (``g <= 0``)."""
    rate = jax.nn.softplus(a.astype(jnp.float32) + layer["gdn_dt_bias"].astype(jnp.float32))
    return jax.nn.sigmoid(b.astype(jnp.float32)), -jnp.exp(layer["gdn_A_log"].astype(jnp.float32)) * rate


def finish(cfg: Any, layer: dict, o: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """What the recurrence read out, ``o [..., H, D]`` float32, normed a head, **then** gated
    by ``z [..., H D]``, through ``W_out`` -> the mixer's output ``[..., d]``."""
    with jax.named_scope(hot.GDN_GATE_NORM):
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
        o = o * layer["gdn_norm"].astype(jnp.float32)
        o = (o.reshape(*o.shape[:-2], -1) * jax.nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
    with jax.named_scope(hot.GDN_PROJ):
        return mm(o, layer["gdn_out"])


# -- one position a row -----------------------------------------------------------


def _step_inputs(cfg: Any, layer: dict, qkv: jnp.ndarray, b: jnp.ndarray, a: jnp.ndarray, tail: jnp.ndarray):  # noqa: ANN202
    """What one position does to a state, from :func:`project`'s ``qkv [rows, width]``, ``b``
    and ``a [rows, H]`` and the convolution's ``tail [rows, gdn_conv - 1, width]``: float32 ``(q, k
    [rows, Hk, D], v [rows, H, D], beta, decay = exp(g) [rows, H]``, the new tail)."""
    with jax.named_scope(hot.GDN_CONV):
        window = jnp.concatenate((tail, qkv[:, None].astype(tail.dtype)), axis=1)
        qkv, tail = _conv(layer, window), window[:, 1:]
    with jax.named_scope(hot.GDN_STEP):
        q, k, v = _heads(cfg, qkv)
        beta, g = _rates(layer, b, a)
        return q, k, v, beta, jnp.exp(g), tail


def _advance(state: jnp.ndarray, decay: jnp.ndarray, beta: jnp.ndarray, q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray):  # noqa: ANN202
    """The delta rule in ``jax.numpy``: ``state [rows, H, D, D]``, ``q``, ``k`` and ``v [rows, H, D]``,
    ``decay`` and ``beta [rows, H]`` -> ``(o [rows, H, D], state)``."""
    s = state * decay[..., None, None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def step_core(cfg: Any, layer: dict, qkv: jnp.ndarray, b: jnp.ndarray, a: jnp.ndarray, state: jnp.ndarray, tail: jnp.ndarray):  # noqa: ANN201
    """One position a row between the projections -> ``(o [rows, H, D]`` float32 for
    :func:`finish`, ``state, tail)``."""
    note_traced("gdn", "step")
    q, k, v, beta, decay, tail = _step_inputs(cfg, layer, qkv, b, a, tail)
    with jax.named_scope(hot.GDN_STEP):
        o, state = _advance(state, decay, beta, _per_value_head(cfg, q), _per_value_head(cfg, k), v)
    return o, state, tail


# -- many positions a row, in sub-chunks ---------------------------------------------


def _inv_unit_lower(a: jnp.ndarray) -> jnp.ndarray:
    """``(I + a)^-1`` for strictly lower-triangular ``a [..., c, c]``: forward substitution
    over the diagonal blocks of 16 rows (all blocks at once: 15 dependent steps), then the
    blocks merged pairwise, ``[[X11, 0], [-X22 A21 X11, X22]]``, up to ``c``."""
    c = a.shape[-1]
    nb = min(c, 16)
    if c % nb or (c // nb) & (c // nb - 1):
        raise ValueError(f"a sub-chunk of {c} positions is not 16 times a power of two (or fewer than 16)")
    lead = a.shape[:-2]
    n = c // nb
    blocks = a.reshape(*lead, n, nb, n, nb)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(n)], axis=-3)  # [..., n, nb, nb]
    x = jnp.broadcast_to(jnp.eye(nb, dtype=a.dtype), diag.shape)
    for i in range(1, nb):  # row i of the inverse: e_i - sum_{j < i} a_ij x_j; the rows from i on are still the identity's
        x = x.at[..., i, :].add(-jnp.einsum("...j,...jk->...k", diag[..., i, :], x, precision=HI))
    size = nb
    while size < c:
        m = c // (2 * size)
        halves = a.reshape(*lead, m, 2, size, m, 2, size)
        a21 = jnp.stack([halves[..., i, 1, :, i, 0, :] for i in range(m)], axis=-3)  # [..., m, size, size]
        pairs = x.reshape(*lead, m, 2, size, size)
        x11, x22 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        x21 = -jnp.matmul(jnp.matmul(x22, a21, precision=HI), x11, precision=HI)
        top = jnp.concatenate((x11, jnp.zeros_like(x11)), axis=-1)
        x = jnp.concatenate((top, jnp.concatenate((x21, x22), axis=-1)), axis=-2)
        size *= 2
    return x[..., 0, :, :]


def chunk_core(cfg: Any, layer: dict, qkv: jnp.ndarray, b: jnp.ndarray, a: jnp.ndarray, state: jnp.ndarray, tail: jnp.ndarray, valid: Optional[jnp.ndarray] = None):  # noqa: ANN201
    """``t`` consecutive positions a row between the projections: ``qkv [b, t, width]``, ``b`` and ``a
    [b, t, H]`` from ``state [b, H, D, D]`` and ``tail [b, gdn_conv - 1, width]`` as they stand ahead of
    the first -> ``(o [b, t, H, D]`` float32, ``state, tail)`` as they stand behind the last position
    ``valid [b, t]`` admits (a row's valid positions lead; a padded position moves neither: it
    keeps everything and writes nothing, and its ``o`` is not to be read)."""
    note_traced("gdn", "chunk")
    bsz, t, _ = qkv.shape
    taps, c = cfg.gdn_conv, min(cfg.gdn_chunk, t)
    with jax.named_scope(hot.GDN_CONV):
        ext = jnp.concatenate((tail, qkv.astype(tail.dtype)), axis=1)  # [b, taps - 1 + t, width]
        qkv = _conv(layer, jnp.stack([ext[:, j : j + t] for j in range(taps)], axis=2))
        if valid is None:
            tail = ext[:, t:]
        else:
            last = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, taps - 1, axis=0))
            tail = last(ext, jnp.sum(valid, axis=1, dtype=jnp.int32))
    with jax.named_scope(hot.GDN_CHUNK):
        q, k, v = _heads(cfg, qkv)
        q, k = _per_value_head(cfg, q), _per_value_head(cfg, k)
        beta, g = _rates(layer, b, a)
        if valid is not None:
            beta, g = jnp.where(valid[..., None], beta, 0.0), jnp.where(valid[..., None], g, 0.0)
        pad = -t % c
        n = (t + pad) // c
        # [b, t, H, ...] -> [b, H, n, c, ...]: zeros behind the last position (beta 0 writes nothing, g 0 keeps everything)
        chunks = lambda x: jnp.moveaxis(  # noqa: E731
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)).reshape(bsz, n, c, *x.shape[2:]), 3, 1
        )
        q, k, v, beta, g = (chunks(x) for x in (q, k, v, beta, g))  # [b, H, n, c, D] x 3, [b, H, n, c] x 2
        total = jnp.cumsum(g, axis=-1)  # G_i: the logarithm of what is kept from the sub-chunk's head to position i
        i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
        kept = jnp.exp(jnp.where(j <= i, total[..., :, None] - total[..., None, :], -jnp.inf))  # exp(G_i - G_j), 0 above the diagonal
        a_mat = beta[..., None] * jnp.einsum("...id,...jd->...ij", k, k, precision=HI) * jnp.where(j < i, kept, 0.0)
        solve = _inv_unit_lower(a_mat) * beta[..., None, :]  # T = (I + A)^-1 diag(beta)
        grown = jnp.exp(total)[..., None]
        w = jnp.matmul(solve, k * grown, precision=HI)  # [b, H, n, c, D_k]
        u = jnp.matmul(solve, v, precision=HI)  # [b, H, n, c, D_v]
        scores = jnp.einsum("...id,...jd->...ij", q, k, precision=HI) * kept
        q_grown = q * grown
        end = total[..., -1]  # G_C
        k_end = k * jnp.exp(end[..., None] - total)[..., None]

        def sub_chunk(s, xs):  # noqa: ANN001, ANN202 - s [b, H, D_k, D_v]
            w_c, u_c, scores_c, q_c, k_c, end_c = xs
            fresh = u_c - jnp.matmul(w_c, s, precision=HI)  # V' = U - W S_0
            o = jnp.matmul(q_c, s, precision=HI) + jnp.matmul(scores_c, fresh, precision=HI)
            s = jnp.exp(end_c)[..., None, None] * s + jnp.einsum("...ck,...cv->...kv", k_c, fresh, precision=HI)
            return s, o

        first = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731 - the sub-chunks in front, for the scan
        state, o = jax.lax.scan(sub_chunk, state, tuple(first(x) for x in (w, u, scores, q_grown, k_end, end)))
        o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(bsz, n * c, cfg.gdn_heads, -1)[:, :t]  # [n, b, H, c, D] -> [b, t, H, D]
    return o, state, tail


def forward(cfg: Any, layer: dict, u: jnp.ndarray) -> jnp.ndarray:
    """The mixer over whole sequences ``u [b, s, d]`` from an empty state."""
    bsz = u.shape[0]
    state = jnp.zeros((bsz, cfg.gdn_heads, cfg.gdn_head_dim, cfg.gdn_head_dim), jnp.float32)
    tail = jnp.zeros((bsz, cfg.gdn_conv - 1, cfg.gdn_conv_width), cfg.dtype)
    with jax.named_scope(hot.GDN):
        qkv, z, b, a = project(cfg, layer, u)
        o, _, _ = chunk_core(cfg, layer, qkv, b, a, state, tail)
        return finish(cfg, layer, o, z)


# -- over a serving engine's store --------------------------------------------------


def kernel_eligible(state_shape: tuple[int, ...], key_heads: int, backend: str) -> bool:
    """Whether :func:`decode_rows` moves the state on through the Pallas kernel
    (``ops/gdn_step_kernel.py``): a pure function of the store's shape ``[..., H, D_k, D_v]``,
    the key heads and the backend. The kernel needs a TPU, lanes full of one head's values
    (``D_v`` a multiple of 128), whole tiles of keys (``D_k`` a multiple of 128: it transposes
    a key head's ``k`` and ``q``) and blocks of 8 value heads that hold whole key heads."""
    heads, dk, dv = state_shape[-3:]
    return backend == "tpu" and dv % 128 == 0 and dk % 128 == 0 and heads % 8 == 0 and heads % key_heads == 0 and 8 % (heads // key_heads) == 0


def decode_rows(cfg: Any, layer: dict, qkv: jnp.ndarray, b: jnp.ndarray, a: jnp.ndarray, store: Store, at, rows: jnp.ndarray):  # noqa: ANN001, ANN201
    """A decode part's rows between the projections (:func:`step_core`): slot ``i`` moved on
    one position in its own row ``i + 1`` of linear layer ``at``'s slice of ``store`` where ``rows[i]``
    says so; where ``rows[i]`` is the trash row 0 (a slot that is not decoding: empty, or
    mid-prompt and its chunk's to write) its own row is left as it was -> ``(o [slots, H, D], store)``.

    On a TPU, where :func:`kernel_eligible` allows, the states go through the Pallas kernel,
    each read once and written once to the row ``rows`` names (the trash row for a slot that
    does not move). Else, and for the convolution's tails (48 KB a slot), the slots' rows are
    read as one slice of the layer's store and written back as one, in place: never a gather
    of ``rows``, which copies its operand (``ssm.decode_rows``)."""
    slots = qkv.shape[0]
    if store["state"].shape[1] != slots + 1:
        raise ValueError(f"a decode part of {slots} slots needs a store of {slots + 1} rows, got {store['state'].shape[1]}")
    moves = rows == jnp.arange(1, slots + 1, dtype=rows.dtype)
    behind_trash = lambda x: jnp.pad(x, ((1, 0),) + ((0, 0),) * (x.ndim - 1))  # noqa: E731 - slot i's at row i + 1
    with jax.named_scope(hot.GDN_STEP):
        tails = _layer_rows(store["conv"], at)[1:].reshape(slots, cfg.gdn_conv - 1, -1)
    q, k, v, beta, decay, tail = _step_inputs(cfg, layer, qkv, b, a, tails)
    with jax.named_scope(hot.GDN_STEP):
        if kernel_eligible(store["state"].shape, cfg.gdn_key_heads, jax.default_backend()):
            from torchx_tpu.ops.gdn_step_kernel import gdn_step_pallas

            note_traced("gdn", "step_pallas")
            o, state = gdn_step_pallas(store["state"], jnp.where(moves, rows, 0), decay, beta, q, k, v, layer=at)
        else:
            note_traced("gdn", "step")
            o, new = _advance(_layer_rows(store["state"], at)[1:], decay, beta, _per_value_head(cfg, q), _per_value_head(cfg, k), v)
            state = _set_rows_where(store["state"], at, behind_trash(new), behind_trash(moves))
        tails = _set_rows_where(store["conv"], at, behind_trash(tail.reshape(slots, -1)), behind_trash(moves))
        return o, {"state": state, "conv": tails}


def chunk_rows(cfg: Any, layer: dict, qkv: jnp.ndarray, b: jnp.ndarray, a: jnp.ndarray, store: Store, at, rows: jnp.ndarray, fresh: jnp.ndarray, valid: jnp.ndarray):  # noqa: ANN001, ANN201
    """A chunk part's rows between the projections (:func:`chunk_core`): ``t`` consecutive
    positions of ``b`` sequences, each from row ``rows [b]`` of the store, or from zeros where
    ``fresh [b]`` (a sequence's first chunk: that is all a reset takes), left in that row as it
    stands behind the last position ``valid [b, t]`` admits -> ``(o [b, t, H, D], store)``. A row at
    a time, each one slice of the store: the engine's chunk is one sequence."""
    n = rows.shape[0]
    with jax.named_scope(hot.GDN_CHUNK):
        state = jnp.stack([_row(store["state"], at, rows[i]) for i in range(n)])
        tail = jnp.stack([_row(store["conv"], at, rows[i]) for i in range(n)]).reshape(n, cfg.gdn_conv - 1, -1)
        state = jnp.where(fresh[:, None, None, None], 0.0, state)
        tail = jnp.where(fresh[:, None, None], jnp.zeros((), tail.dtype), tail)
    o, state, tail = chunk_core(cfg, layer, qkv, b, a, state, tail, valid)
    with jax.named_scope(hot.GDN_CHUNK):
        for i in range(n):
            # the tails as one masked write of the layer's rows: a single row of a packed type, written where
            # it lies, has the chip's compiler re-lay the array out round the write (ssm.chunk_rows)
            everywhere = jnp.broadcast_to(tail[i].reshape(1, -1), store["conv"].shape[1:])
            only = jnp.arange(store["conv"].shape[1]) == rows[i]
            store = {
                "state": _set_row(store["state"], at, rows[i], state[i]),
                "conv": _set_rows_where(store["conv"], at, everywhere, only),
            }
        return o, store
