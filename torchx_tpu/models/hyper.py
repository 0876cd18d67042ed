"""The residual path: ``x + f(x)``, or manifold-constrained hyper-connections.

With ``cfg.hc_mult = n > 0`` the residual stream of a token is ``X [n, d]``: the
embedding copied into all ``n`` rows (:func:`expand`), the rows summed ahead of
the final norm (:func:`collapse`). The program carries it flattened, ``[..., n
d]`` with row ``j`` at ``[j d, (j + 1) d)``: whole lanes whatever ``n`` is, where
a ``[..., 4, d]`` array's tiles pad 4 rows to 8 or 16 and every reshape to the
flat form the projection wants is a copy (rehearsal, PR 33). Around a sublayer ``F`` (attention with its
norm, or the FFN with its norm) with its own ``phi [n d, 2 n + n^2]``,
``b [2 n + n^2]`` and three scalars ``a = (a_pre, a_post, a_res)``::

    z      = flatten(X) / sqrt(mean(flatten(X)^2) + hc_eps)         no learned gain
    m      = z phi
    H_pre  = sigmoid(a_pre m[:n] + b[:n])                           [n]
    H_post = 2 sigmoid(a_post m[n:2n] + b[n:2n])                    [n]
    M      = exp(clip(a_res m[2n:] + b[2n:], *hc_res_clamp))        as [n, n]
    hc_sinkhorn_iters times:  M = M / (rowsum(M) + hc_eps);  M = M / (colsum(M) + hc_eps)
    H_res  = M                                                      doubly stochastic
    y      = F(H_pre X)                                             [d] in, [d] out
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

The coefficients are float32, the stream stays in the model's dtype. The
Sinkhorn loop is unrolled in Python over ``[rows, n, n]``, and the read-in and
the write-back over ``n``: elementwise work XLA fuses, no loop of the device's
under an ``hc_*`` scope (20 trips of several launches each around 16 sublayers
would be thousands of launches a decode step). The per-row norm is a scalar, so
it is applied to the ``2 n + n^2`` products and not to the ``n d`` values. On the
chip the write-back is no operation of its own in decode: the compiler computes
it again inside each of the next sublayer's readers (its norm, its projection,
its read-in) and writes the stream once a layer, as the scan's carry; the whole
path is 0.2 of a 21.6 ms step at 128 rows (my chip run, PR 33).

:func:`residual` is what every layer step calls around each of its two
sublayers (``llama._layer``, ``generate._layer_step``, ``_paged_layer_step``);
with ``hc_mult = 0`` it is the plain add and
traces nothing else. ``ops.attention.traced("residual")`` answers ``hyper`` or
``add``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from torchx_tpu.obs import hot
from torchx_tpu.ops.attention import note_traced

#: the sublayers of a layer, as the leaves of its hyper-connections are named: ``hc_<sublayer>_<phi|b|a>``
SUBLAYERS = ("attn", "mlp")


def leaf_shapes(cfg) -> dict[str, tuple[int, ...]]:  # noqa: ANN001
    """One layer's hyper-connection leaves -> their shapes; empty where ``hc_mult`` is 0."""
    n = cfg.hc_mult
    if not n:
        return {}
    width = 2 * n + n * n
    return {
        f"hc_{sub}_{leaf}": shape
        for sub in SUBLAYERS
        for leaf, shape in (("phi", (n * cfg.dim, width)), ("b", (width,)), ("a", (3,)))
    }


def init_leaves(cfg, key: jax.Array, layers: int) -> dict[str, jnp.ndarray]:  # noqa: ANN001
    """``[layers, ...]`` stacks of :func:`leaf_shapes`: ``phi`` normal of
    deviation ``(n d)^-0.5``, ``a`` ones, ``b`` zero but for 4 on the diagonal
    of the write-back block, so that a fresh layer leans towards keeping each
    stream in its row."""
    n = cfg.hc_mult
    out = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        if name.endswith("_phi"):
            w = jax.random.normal(jax.random.fold_in(key, i), (layers, *shape), jnp.float32) * shape[0] ** -0.5
        elif name.endswith("_a"):
            w = jnp.ones((layers, *shape), jnp.float32)
        else:
            b = jnp.concatenate((jnp.zeros((2 * n,), jnp.float32), 4.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)))
            w = jnp.broadcast_to(b, (layers, *shape))
        out[name] = w.astype(cfg.dtype)
    return out


def _rows(cfg, x: jnp.ndarray) -> list[jnp.ndarray]:  # noqa: ANN001
    """The ``hc_mult`` rows ``[..., d]`` of a flattened stream ``[..., n d]``, float32."""
    d = x.shape[-1] // cfg.hc_mult
    return [x[..., j * d : (j + 1) * d].astype(jnp.float32) for j in range(cfg.hc_mult)]


def expand(cfg, x: jnp.ndarray) -> jnp.ndarray:  # noqa: ANN001
    """The embedding ``[..., d]`` as the stream a layer takes: copied into
    ``hc_mult`` rows ``[..., n d]``, or itself."""
    if not cfg.hc_mult:
        return x
    return jnp.concatenate([x] * cfg.hc_mult, axis=-1)


def collapse(cfg, x: jnp.ndarray) -> jnp.ndarray:  # noqa: ANN001
    """The stream behind the last layer as the final norm takes it: its
    ``hc_mult`` rows summed (float32 inside), or itself."""
    if not cfg.hc_mult:
        return x
    with jax.named_scope(hot.HC_HEAD):
        return sum(_rows(cfg, x)).astype(x.dtype)


def coefficients(cfg, layer: dict, sublayer: str, x: jnp.ndarray):  # noqa: ANN001, ANN201
    """The flattened stream ``x [..., n d]`` -> float32 ``(H_pre [..., n],
    H_post [..., n], H_res [..., n, n])`` of ``sublayer``'s hyper-connection in
    ``layer``."""
    n, eps = cfg.hc_mult, cfg.hc_eps
    phi, b, a = (layer[f"hc_{sublayer}_{leaf}"] for leaf in ("phi", "b", "a"))
    b, a = b.astype(jnp.float32), a.astype(jnp.float32)
    with jax.named_scope(hot.HC_PRE):
        mean_square = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        m = jnp.einsum("...k,kc->...c", x, phi, preferred_element_type=jnp.float32)
        m = m * jax.lax.rsqrt(mean_square + eps)
        h_pre = jax.nn.sigmoid(a[0] * m[..., :n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * m[..., n : 2 * n] + b[n : 2 * n])
    with jax.named_scope(hot.HC_SINKHORN):
        lo, hi = cfg.hc_res_clamp
        h_res = jnp.exp(jnp.clip(a[2] * m[..., 2 * n :] + b[2 * n :], lo, hi)).reshape(*m.shape[:-1], n, n)
        for _ in range(cfg.hc_sinkhorn_iters):
            h_res = h_res / (h_res.sum(axis=-1, keepdims=True) + eps)  # rows, then
            h_res = h_res / (h_res.sum(axis=-2, keepdims=True) + eps)  # columns
    return h_pre, h_post, h_res


def residual(cfg, layer: dict, sublayer: str, x: jnp.ndarray, f: Callable[[jnp.ndarray], tuple[jnp.ndarray, Any]]):  # noqa: ANN001, ANN201
    """The stream after ``sublayer`` (``"attn"`` or ``"mlp"``) of ``layer``:
    ``f`` takes the sublayer's input ``[..., d]``, norm included, and returns
    ``(its output [..., d], whatever else it has to hand back)``. -> (the new
    stream, that). ``x`` is ``[..., d]`` and the result ``x + f(x)`` where
    ``cfg.hc_mult`` is 0, else the flattened ``[..., n d]`` mixed as the module says."""
    n = cfg.hc_mult
    note_traced("residual", "hyper" if n else "add")
    if not n:
        y, rest = f(x)
        if cfg.fp32_skip_add:  # the add in float32, rounded once to the stream's type
            return (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype), rest
        return x + y, rest
    h_pre, h_post, h_res = coefficients(cfg, layer, sublayer, x)
    streams = _rows(cfg, x)
    with jax.named_scope(hot.HC_PRE):
        read_in = sum(h_pre[..., j, None] * streams[j] for j in range(n)).astype(x.dtype)
    y, rest = f(read_in)
    with jax.named_scope(hot.HC_POST):
        y = y.astype(jnp.float32)
        rows = [
            sum(h_res[..., i, j, None] * streams[j] for j in range(n)) + h_post[..., i, None] * y for i in range(n)
        ]
        return jnp.concatenate(rows, axis=-1).astype(x.dtype), rest
