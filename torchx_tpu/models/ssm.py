"""Mamba-2 mixer (state-space duality): a layer's recurrent branch.

Where ``cfg.ssm_heads`` > 0 every layer runs this mixer beside its attention,
both off one norm and into one residual add (``llama._layer``,
``generate._paged_layer_step``). ``H`` heads of ``P`` channels share ``G``
groups of ``N``-wide input and output maps; the state a sequence carries from
one position to the next is ``S [H, P, N]`` in float32 (kept ``[H, N, P]``: the
read-out sums over ``N``, which then lies across a tile's rows and costs adds,
where along its lanes it would cost a shuffle a row) and the last
``ssm_conv - 1`` inputs of a depthwise causal convolution::

    [z | xBC | dt] = (u W_in) * m       # 2 H P + 2 G N + H wide; m = cfg.ssm_multipliers on the z, x, B, C, dt segments
    xBC = silu(conv(xBC) + b_conv);   [x | B | C] = xBC          # H P | G N | G N
    dt  = softplus(dt + dt_bias);   A = -exp(A_log)              # a head each
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t             # head h reads group h // (H / G)
    y_t = S_t C_t + D x_t
    out = RMSNorm_groups(y * silu(z)) W_out                      # gate, then a norm over each group's H P / G values

Two forms that agree (``tests/test_falcon_h1.py``): :func:`step`, one position
a row from a carried state (a slot's decode row), and :func:`scan`, many
positions from a carried state in chunks of ``cfg.ssm_chunk`` (the full
sequence; a prompt's chunk riding a decode step), which multiplies inside a
chunk and carries the state between chunks, so a chunk of a prompt costs
matmuls and not ``t`` dependent steps.

A serving engine keeps the state of every slot in a store beside its paged K/V
pools (:func:`init_store`): ``state [layers, 1 + slots, H, N, P]`` float32 and
``conv [layers, 1 + slots, (ssm_conv - 1) (H P + 2 G N)]`` (a row's inputs end to end:
as ``[.., ssm_conv - 1, width]`` the chip's compiler keeps the three inputs
innermost and re-lays the whole array out round every write, 12 ms a step that
carries a chunk, my chip run, PR 41), addressed by **row**,
not by position; row 0 is the trash row, as block 0 is the trash block. A
serving step projects all its rows at once (:func:`project`, :func:`finish`:
no weight is read a second time for a chunk that rides it); between the two
:func:`decode_rows` and :func:`chunk_rows` read a part's rows out of the layer's
slice of the store, move them on and write them back.
``ops.attention.traced("ssm")`` answers which forms a program traced.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from torchx_tpu.obs import hot
from torchx_tpu.ops.attention import note_traced
from torchx_tpu.ops.quant import maybe_matmul as mm

Store = dict[str, jnp.ndarray]  # {"state": [L, rows, H, N, P] f32, "conv": [L, rows, (ssm_conv - 1) x conv width]}


def leaf_shapes(cfg: Any) -> dict[str, tuple[int, ...]]:
    """A layer's mixer leaves by name, the layer axis left off; none without a mixer."""
    if not cfg.ssm_heads:
        return {}
    h, inner, width = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_width
    return {
        "ssm_in": (cfg.dim, 2 * inner + 2 * cfg.ssm_groups * cfg.ssm_state + h),
        "ssm_conv_w": (cfg.ssm_conv, width),  # tap k multiplies the input ssm_conv - 1 - k positions back
        "ssm_conv_b": (width,),
        "ssm_dt_bias": (h,),
        "ssm_A_log": (h,),
        "ssm_D": (h,),
        "ssm_norm": (inner,),
        "ssm_out": (inner, cfg.dim),
    }


def init_leaves(cfg: Any, key: jax.Array, layers: int) -> dict[str, jnp.ndarray]:
    """Seeded mixer leaves, ``layers`` deep: the matrices normal over their
    fan-in, the gains, ``D`` and ``A`` at one (``A_log`` 0), the biases zero."""
    fan_in = {"ssm_in": cfg.dim, "ssm_out": cfg.ssm_inner, "ssm_conv_w": cfg.ssm_conv}
    ones = ("ssm_D", "ssm_norm")
    out = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        if name in fan_in:
            w = jax.random.normal(jax.random.fold_in(key, i), (layers, *shape), jnp.float32) * fan_in[name] ** -0.5
            out[name] = w.astype(cfg.dtype)
        else:
            out[name] = (jnp.ones if name in ones else jnp.zeros)((layers, *shape), cfg.dtype)
    return out


def param_count(cfg: Any) -> int:
    """One layer's mixer parameters."""
    return sum(int(np.prod(shape)) for shape in leaf_shapes(cfg).values())


def init_store(cfg: Any, rows: int) -> Store:
    """Zeroed state and convolution tails of ``rows`` rows a layer (a serving
    engine's ``1 + max_slots``: row 0 is the trash row)."""
    return {
        "state": jnp.zeros((cfg.n_layers, rows, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, rows, (cfg.ssm_conv - 1) * cfg.ssm_conv_width), cfg.dtype),
    }


# -- the pieces both forms share -------------------------------------------------


def project(cfg: Any, layer: dict, u: jnp.ndarray):  # noqa: ANN201
    """The layer's normed input ``u [..., d]`` -> ``(z [..., H P], xBC [..., H
    P + 2 G N], dt [..., H])``: the gate, what the convolution takes, the step sizes."""
    with jax.named_scope(hot.SSM_PROJ):
        if cfg.ssm_in_multiplier != 1.0:
            u = u * cfg.ssm_in_multiplier
        zxbcdt = mm(u, layer["ssm_in"])
        inner, width = cfg.ssm_inner, cfg.ssm_conv_width
        if any(m != 1.0 for m in cfg.ssm_multipliers):
            gn = cfg.ssm_groups * cfg.ssm_state
            segments = (inner, inner, gn, gn, cfg.ssm_heads)
            zxbcdt = zxbcdt * jnp.asarray(np.repeat(cfg.ssm_multipliers, segments), zxbcdt.dtype)
        return zxbcdt[..., :inner], zxbcdt[..., inner : inner + width], zxbcdt[..., inner + width :]


def _split(cfg: Any, xbc: jnp.ndarray):  # noqa: ANN202
    """``xBC [..., H P + 2 G N]`` -> ``x [..., G, H / G, P]``, ``B`` and ``C [..., G, N]``:
    the heads by the group they read."""
    g, n, inner = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_inner
    lead = xbc.shape[:-1]
    x = xbc[..., :inner].reshape(*lead, g, cfg.ssm_heads // g, cfg.ssm_head_dim)
    return x, xbc[..., inner : inner + g * n].reshape(*lead, g, n), xbc[..., inner + g * n :].reshape(*lead, g, n)


def _by_group(cfg: Any, per_head: jnp.ndarray) -> jnp.ndarray:
    """``[..., H]`` -> ``[..., G, H / G]``."""
    return per_head.reshape(*per_head.shape[:-1], cfg.ssm_groups, cfg.ssm_heads // cfg.ssm_groups)


def _rates(cfg: Any, layer: dict, dt: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """-> float32 ``(dt [..., G, H / G]`` after its bias and softplus, ``A [G, H / G])``."""
    dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["ssm_dt_bias"].astype(jnp.float32))
    return _by_group(cfg, dt), _by_group(cfg, -jnp.exp(layer["ssm_A_log"].astype(jnp.float32)))


def finish(cfg: Any, layer: dict, y: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """What the recurrence read out, ``y [..., H P]`` float32, gated by ``z``,
    normed a group, through ``W_out`` -> the mixer's output ``[..., d]``."""
    with jax.named_scope(hot.SSM_GATE_NORM):
        y = y * jax.nn.silu(z.astype(jnp.float32))
        grouped = y.reshape(*y.shape[:-1], cfg.ssm_groups, -1)
        grouped = grouped * jax.lax.rsqrt(jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + cfg.norm_eps)
        y = (grouped.reshape(y.shape) * layer["ssm_norm"].astype(jnp.float32)).astype(cfg.dtype)
    with jax.named_scope(hot.SSM_PROJ):
        out = mm(y, layer["ssm_out"])
        return out * cfg.ssm_out_multiplier if cfg.ssm_out_multiplier != 1.0 else out


def _conv(cfg: Any, layer: dict, window: jnp.ndarray) -> jnp.ndarray:
    """``window [..., ssm_conv, width]``, a position's input last -> that
    position's ``silu(conv + b)`` in float32."""
    w = layer["ssm_conv_w"].astype(jnp.float32)
    return jax.nn.silu(jnp.sum(window.astype(jnp.float32) * w, axis=-2) + layer["ssm_conv_b"].astype(jnp.float32))


# -- one position a row -----------------------------------------------------------


def step(cfg: Any, layer: dict, u: jnp.ndarray, state: jnp.ndarray, tail: jnp.ndarray):  # noqa: ANN201
    """One position a row: ``u [rows, d]`` (the layer's normed input), ``state
    [rows, H, N, P]`` float32 and ``tail [rows, ssm_conv - 1, width]`` as they
    stand behind each row's last position -> ``(out [rows, d], state, tail)``."""
    z, xbc, dt = project(cfg, layer, u)
    y, state, tail = step_core(cfg, layer, xbc, dt, state, tail)
    return finish(cfg, layer, y, z), state, tail


def _step_inputs(cfg: Any, layer: dict, xbc: jnp.ndarray, dt: jnp.ndarray, tail: jnp.ndarray):  # noqa: ANN202
    """What one position does to a state, from :func:`project`'s ``xBC [rows,
    width]`` and ``dt [rows, H]`` and the convolution's ``tail``: float32 ``(x
    [rows, H, P], decay [rows, H], fed [rows, H, P] = dt x, B and C [rows, G, N],
    the new tail)``."""
    with jax.named_scope(hot.SSM_CONV):
        window = jnp.concatenate((tail, xbc[:, None].astype(tail.dtype)), axis=1)
        xbc, tail = _conv(cfg, layer, window), window[:, 1:]
    with jax.named_scope(hot.SSM_STEP):
        x, b, c = _split(cfg, xbc)
        dt, a = _rates(cfg, layer, dt)
        flat = lambda v: v.reshape(v.shape[0], cfg.ssm_heads, *v.shape[3:])  # noqa: E731 - [rows, G, H/G, ...] -> [rows, H, ...]
        return flat(x), flat(jnp.exp(dt * a)), flat(dt[..., None] * x), b, c, tail


def _advance(cfg: Any, state: jnp.ndarray, decay: jnp.ndarray, fed: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray):  # noqa: ANN202
    """``S <- decay S + B (outer) fed`` and ``y = C . S`` in ``jax.numpy``:
    ``state [rows, H, N, P]`` -> ``(y [rows, H, P], state)``."""
    by_group = lambda v: v.reshape(v.shape[0], cfg.ssm_groups, -1, *v.shape[2:])  # noqa: E731
    s = by_group(state) * by_group(decay)[..., None, None] + b[:, :, None, :, None] * by_group(fed)[..., None, :]
    y = jnp.sum(s * c[:, :, None, :, None], axis=-2)
    return y.reshape(fed.shape), s.reshape(state.shape)


def _skip(cfg: Any, layer: dict, y: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``y + D x`` over ``[..., H, P]``, flattened to ``[..., H P]``."""
    y = y + layer["ssm_D"].astype(jnp.float32)[:, None] * x
    return y.reshape(*y.shape[:-2], -1)


def step_core(cfg: Any, layer: dict, xbc: jnp.ndarray, dt: jnp.ndarray, state: jnp.ndarray, tail: jnp.ndarray):  # noqa: ANN201
    """:func:`step` between the projections: ``(xBC [rows, width], dt [rows,
    H])`` of :func:`project` -> ``(y [rows, H P]`` float32 for :func:`finish`, ``state, tail)``."""
    note_traced("ssm", "step")
    x, decay, fed, b, c, tail = _step_inputs(cfg, layer, xbc, dt, tail)
    with jax.named_scope(hot.SSM_STEP):
        y, state = _advance(cfg, state, decay, fed, b, c)
        return _skip(cfg, layer, y, x), state, tail


# -- many positions a row, in chunks ----------------------------------------------


def scan(cfg: Any, layer: dict, u: jnp.ndarray, state: jnp.ndarray, tail: jnp.ndarray, valid: Optional[jnp.ndarray] = None):  # noqa: ANN201
    """``t`` consecutive positions a row: ``u [b, t, d]`` from ``state [b, H,
    N, P]`` and ``tail [b, ssm_conv - 1, width]`` as they stand ahead of the
    first -> ``(out [b, t, d], state, tail)`` as they stand behind the last
    position ``valid [b, t]`` admits (a row's valid positions lead; a padded
    position moves neither, and its ``out`` is not to be read)."""
    z, xbc, dt = project(cfg, layer, u)
    y, state, tail = scan_core(cfg, layer, xbc, dt, state, tail, valid)
    return finish(cfg, layer, y, z), state, tail


def scan_core(cfg: Any, layer: dict, xbc: jnp.ndarray, dt: jnp.ndarray, state: jnp.ndarray, tail: jnp.ndarray, valid: Optional[jnp.ndarray] = None):  # noqa: ANN201
    """:func:`scan` between the projections: ``(xBC [b, t, width], dt [b, t,
    H])`` -> ``(y [b, t, H P]`` float32, ``state, tail)``."""
    note_traced("ssm", "scan")
    bsz, t, _ = xbc.shape
    g, k, q = cfg.ssm_groups, cfg.ssm_conv, min(cfg.ssm_chunk, t)
    with jax.named_scope(hot.SSM_CONV):
        ext = jnp.concatenate((tail, xbc.astype(tail.dtype)), axis=1)  # [b, k - 1 + t, width]
        xbc = _conv(cfg, layer, jnp.stack([ext[:, j : j + t] for j in range(k)], axis=2))
        if valid is None:
            tail = ext[:, t:]
        else:
            last = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, k - 1, axis=0))
            tail = last(ext, jnp.sum(valid, axis=1, dtype=jnp.int32))
    with jax.named_scope(hot.SSM_SCAN):
        x, b, c = _split(cfg, xbc.astype(cfg.dtype))  # [b, t, G, H/G, P], [b, t, G, N] twice
        dt, a = _rates(cfg, layer, dt)  # [b, t, G, H/G], [G, H/G]
        if valid is not None:
            dt = jnp.where(valid[:, :, None, None], dt, 0.0)
        pad = -t % q
        chunks = lambda v: jnp.moveaxis(  # noqa: E731 - [b, t, ...] -> [chunks, b, q, ...], zeros behind the last
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)).reshape(bsz, -1, q, *v.shape[2:]), 1, 0
        )
        causal = jnp.tril(jnp.ones((q, q), bool))[:, :, None, None]

        def chunk(s, xs):  # noqa: ANN001, ANN202 - s [b, G, H/G, N, P] float32
            x_c, b_c, c_c, dt_c = xs
            log_decay = jnp.cumsum(dt_c * a, axis=1)  # [b, q, G, H/G]: the decay from the chunk's start to each position
            total = log_decay[:, -1]
            # inside the chunk: position i reads every j <= i through C_i . B_j, decayed from j to i
            between = jnp.exp(jnp.where(causal, log_decay[:, :, None] - log_decay[:, None, :], -jnp.inf))  # [b, i, j, G, H/G]
            scores = jnp.einsum("bign,bjgn->bijg", c_c, b_c, preferred_element_type=jnp.float32)
            weights = (scores[..., None] * between * dt_c[:, None]).astype(x_c.dtype)
            y = jnp.einsum("bijgh,bjghp->bighp", weights, x_c, preferred_element_type=jnp.float32)
            # from the state the chunk began with
            carried = jnp.einsum("bign,bghnp->bighp", c_c, s.astype(c_c.dtype), preferred_element_type=jnp.float32)
            y = y + carried * jnp.exp(log_decay)[..., None]
            # the state behind the chunk
            fed = (x_c * (jnp.exp(total[:, None] - log_decay) * dt_c)[..., None]).astype(x_c.dtype)
            s = s * jnp.exp(total)[..., None, None] + jnp.einsum(
                "bjgn,bjghp->bghnp", b_c, fed, preferred_element_type=jnp.float32
            )
            return s, y

        s = state.reshape(bsz, g, -1, *state.shape[2:])
        s, y = jax.lax.scan(chunk, s, (chunks(x), chunks(b), chunks(c), chunks(dt)))
        y = jnp.moveaxis(y, 0, 1).reshape(bsz, -1, cfg.ssm_heads, cfg.ssm_head_dim)[:, :t]  # [b, t, H, P]
        y = _skip(cfg, layer, y, x.reshape(y.shape))
    return y, s.reshape(state.shape), tail


def forward(cfg: Any, layer: dict, u: jnp.ndarray) -> jnp.ndarray:
    """The mixer over whole sequences ``u [b, s, d]`` from an empty state."""
    bsz = u.shape[0]
    state = jnp.zeros((bsz, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32)
    tail = jnp.zeros((bsz, cfg.ssm_conv - 1, cfg.ssm_conv_width), cfg.dtype)
    with jax.named_scope(hot.SSM):
        return scan(cfg, layer, u, state, tail)[0]


# -- over a serving engine's store --------------------------------------------------


def kernel_eligible(state_shape: tuple[int, ...], groups: int, backend: str) -> bool:
    """Whether :func:`decode_rows` moves the state on through the Pallas kernel
    (``ops/ssm_step_kernel.py``): a pure function of the store's shape ``[...,
    H, N, P]`` and the backend. The kernel needs a TPU, lanes full of one head's
    channels (``P`` a multiple of 128), whole tiles of states (``N`` a multiple
    of 128: it transposes a group's ``B`` and ``C``) and groups of whole blocks
    of 8 heads."""
    heads, n, p = state_shape[-3:]
    return backend == "tpu" and p % 128 == 0 and n % 128 == 0 and heads % (8 * groups) == 0


def _row(p: jnp.ndarray, at, row) -> jnp.ndarray:  # noqa: ANN001
    """``p[at, row]`` as one slice (a gather would copy its operand)."""
    zero = jnp.zeros((), jnp.int32)
    return jax.lax.dynamic_slice(p, (at, row) + (zero,) * (p.ndim - 2), (1, 1, *p.shape[2:]))[0, 0]


def _set_row(p: jnp.ndarray, at, row, new: jnp.ndarray) -> jnp.ndarray:  # noqa: ANN001
    zero = jnp.zeros((), jnp.int32)
    return jax.lax.dynamic_update_slice(p, new[None, None].astype(p.dtype), (at, row) + (zero,) * (p.ndim - 2))


def _layer_rows(p: jnp.ndarray, at) -> jnp.ndarray:  # noqa: ANN001
    return jax.lax.dynamic_index_in_dim(p, at, axis=0, keepdims=False)


def _set_rows_where(p: jnp.ndarray, at, new: jnp.ndarray, where: jnp.ndarray) -> jnp.ndarray:  # noqa: ANN001
    """Layer ``at``'s rows written in place as one slice: ``new`` where ``where
    [rows]``, a row that is not to move keeping what it held."""
    keep = where.reshape(-1, *(1,) * (new.ndim - 1))
    return jax.lax.dynamic_update_index_in_dim(p, jnp.where(keep, new.astype(p.dtype), _layer_rows(p, at)), at, 0)


def decode_rows(cfg: Any, layer: dict, xbc: jnp.ndarray, dt: jnp.ndarray, store: Store, at, rows: jnp.ndarray):  # noqa: ANN001, ANN201
    """A decode part's rows between the projections (:func:`step_core`): slot
    ``i`` moved on one position in its own row ``i + 1`` of layer ``at``'s slice
    of ``store`` where ``rows[i]`` says so; where ``rows[i]`` is the trash row 0
    (a slot that is not decoding: empty, or mid-prompt and its chunk's to
    write) its own row is left as it was -> ``(y [slots, H P], store)``.

    On a TPU, where :func:`kernel_eligible` allows, the states go through the
    Pallas kernel, each read once and written once to the row ``rows`` names
    (the trash row for a slot that does not move). Else, and for the
    convolution's tails (a few KB a slot), the slots' rows are read as one slice
    of the layer's store and written back as one, in place. A gather of ``rows``
    is what to avoid: the chip's compiler splits a gather's operand first and so
    copies the whole store a layer (1.6 GB at 64 slots of 4 MB: my rehearsal, PR 41)."""
    slots = xbc.shape[0]
    if store["state"].shape[1] != slots + 1:
        raise ValueError(f"a decode part of {slots} slots needs a store of {slots + 1} rows, got {store['state'].shape[1]}")
    moves = rows == jnp.arange(1, slots + 1, dtype=rows.dtype)
    behind_trash = lambda v: jnp.pad(v, ((1, 0),) + ((0, 0),) * (v.ndim - 1))  # noqa: E731 - slot i's at row i + 1
    with jax.named_scope(hot.SSM_STEP):
        tails = _layer_rows(store["conv"], at)[1:].reshape(slots, cfg.ssm_conv - 1, -1)
    x, decay, fed, b, c, tail = _step_inputs(cfg, layer, xbc, dt, tails)
    with jax.named_scope(hot.SSM_STEP):
        if kernel_eligible(store["state"].shape, cfg.ssm_groups, jax.default_backend()):
            from torchx_tpu.ops.ssm_step_kernel import ssm_step_pallas

            note_traced("ssm", "step_pallas")
            y, state = ssm_step_pallas(store["state"], jnp.where(moves, rows, 0), decay, fed, b, c, layer=at)
        else:
            note_traced("ssm", "step")
            y, new = _advance(cfg, _layer_rows(store["state"], at)[1:], decay, fed, b, c)
            state = _set_rows_where(store["state"], at, behind_trash(new), behind_trash(moves))
        tails = _set_rows_where(store["conv"], at, behind_trash(tail.reshape(slots, -1)), behind_trash(moves))
        return _skip(cfg, layer, y, x), {"state": state, "conv": tails}


def chunk_rows(cfg: Any, layer: dict, xbc: jnp.ndarray, dt: jnp.ndarray, store: Store, at, rows: jnp.ndarray, fresh: jnp.ndarray, valid: jnp.ndarray):  # noqa: ANN001, ANN201
    """A chunk part's rows between the projections (:func:`scan_core`): ``t``
    consecutive positions of ``b`` sequences, each from row ``rows [b]`` of the
    store, or from zeros where ``fresh [b]`` (a sequence's first chunk: that is
    all a reset takes), left in that row as it stands behind the last position
    ``valid [b, t]`` admits -> ``(y [b, t, H P], store)``. A row at a time, each
    one slice of the store: the engine's chunk is one sequence."""
    n = rows.shape[0]
    with jax.named_scope(hot.SSM_SCAN):
        state = jnp.stack([_row(store["state"], at, rows[i]) for i in range(n)])
        tail = jnp.stack([_row(store["conv"], at, rows[i]) for i in range(n)]).reshape(n, cfg.ssm_conv - 1, -1)
        state = jnp.where(fresh[:, None, None, None], 0.0, state)
        tail = jnp.where(fresh[:, None, None], jnp.zeros((), tail.dtype), tail)
    y, state, tail = scan_core(cfg, layer, xbc, dt, state, tail, valid)
    with jax.named_scope(hot.SSM_SCAN):
        for i in range(n):
            # the tails as one masked write of the layer's rows (2 MB): a single row of a packed type, written
            # where it lies, has the chip's compiler re-lay the array out round the write
            everywhere = jnp.broadcast_to(tail[i].reshape(1, -1), store["conv"].shape[1:])
            only = jnp.arange(store["conv"].shape[1]) == rows[i]
            store = {
                "state": _set_row(store["state"], at, rows[i], state[i]),
                "conv": _set_rows_where(store["conv"], at, everywhere, only),
            }
        return y, store
