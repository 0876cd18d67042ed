"""The ragged Pallas decode-attention kernel against the XLA function, on the CPU.

The kernel runs in Pallas's interpreter (``interpret=True``), which executes
the same program (scalar prefetch, the copies of live blocks, the two
buffers, the online softmax) without a TPU. Tolerances, largest absolute
difference plus a relative part: float32 pools ``2e-6 + 1e-5 |x|`` (both
sides sum in float32, in another order); bfloat16 pools ``1e-2 + 1e-2 |x|``
(the XLA function rounds normalised probabilities to bfloat16, the kernel
rounds them before the division: one unit in the last place of a result
near 2 is 0.0156).
"""

from __future__ import annotations

import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from torchx_tpu.models import generate as gen
from torchx_tpu.models import llama, moe
from torchx_tpu.ops import paged_attention as pa
from torchx_tpu.ops import paged_attention_kernel as pk
from torchx_tpu.ops.rope import YarnScaling

attn_ops = importlib.import_module("torchx_tpu.ops.attention")  # the package exports the function under this name
TOLERANCE = {jnp.float32: dict(atol=2e-6, rtol=1e-5), jnp.bfloat16: dict(atol=1e-2, rtol=1e-2)}
HD = 128


def _problem(lengths, h, kvh, bs, bpr, dtype, shared_blocks=0, seed=0):
    """Random query and pools, and block tables in shuffled physical order;
    the first ``shared_blocks`` table entries of every slot are the same
    physical blocks (a cached prefix). Entries past a slot's live blocks are
    the trash block."""
    rng = np.random.default_rng(seed)
    slots = len(lengths)
    live = [-(-n // bs) for n in lengths]
    nb = 1 + shared_blocks + sum(max(n - shared_blocks, 0) for n in live)
    perm = rng.permutation(np.arange(1, nb))
    shared, own = perm[:shared_blocks], perm[shared_blocks:]
    tables = np.full((slots, bpr), pa.TRASH_BLOCK, np.int32)
    at = 0
    for i, n in enumerate(live):
        tables[i, : min(n, shared_blocks)] = shared[:n]
        rest = max(n - shared_blocks, 0)
        tables[i, shared_blocks : shared_blocks + rest] = own[at : at + rest]
        at += rest
    k = rng.standard_normal((nb, bs, kvh, HD)).astype(np.float32)
    v = rng.standard_normal((nb, bs, kvh, HD)).astype(np.float32)
    q = rng.standard_normal((slots, h, HD)).astype(np.float32)
    return q, k, v, tables, np.asarray(lengths, np.int32)


def _poison(k, v, tables, lengths, bs):
    """NaN in the trash block and in every block no slot's live range reaches,
    K and V; in K also past the last position any slot holds of its last
    block. (V there must hold numbers for either path: 0 * NaN is NaN.)"""
    k, v = k.copy(), v.copy()
    live_upto = np.zeros(k.shape[0], np.int64)  # positions of a block some slot attends
    for row, n in zip(tables, lengths):
        for j in range(-(-int(n) // bs)):
            live_upto[row[j]] = max(live_upto[row[j]], min(bs, int(n) - j * bs))
    live_upto[pa.TRASH_BLOCK] = 0
    for blk, upto in enumerate(live_upto):
        k[blk, upto:] = np.nan
        if upto == 0:
            v[blk] = np.nan
    return k, v


def _shrink_the_kernels_geometry(monkeypatch, rows, row_bytes):
    """Chunks of 4 blocks in two parts of two groups of one, so that a table of 10-12 blocks spans several of each."""
    monkeypatch.setattr(pk, "_CHUNK_BYTES", 4 * rows * row_bytes)
    monkeypatch.setattr(pk, "_PART_ROWS", 2 * rows)
    monkeypatch.setattr(pk, "_GROUP_BYTES", rows * row_bytes)
    assert pk._geometry(rows, row_bytes, 10) == (4, 2, 1)


# window = bs * bpr; the kernel's chunk is shrunk to 4 blocks below, so
# "mid" spans several chunks and "full" ends on the table's last entry
def _case(id, lengths, h=8, kvh=2, bs=16, bpr=12, dtype=jnp.float32, **kw):
    return pytest.param(dict(lengths=lengths, h=h, kvh=kvh, bs=bs, bpr=bpr, dtype=dtype, **kw), id=id)


CASES = [
    _case("length-1", [1, 1]),
    _case("length-bs-1", [15, 3]),
    _case("length-bs", [16, 32]),
    _case("length-bs+1", [17, 33]),
    _case("length-mid-window", [100, 65, 7]),
    _case("length-full-window", [192, 191, 1]),
    _case("group-1", [40, 130, 1], h=2, kvh=2),
    _case("group-4", [40, 130, 1], h=8, kvh=2),
    _case("group-8", [40, 130, 1], h=16, kvh=2),
    _case("block-16", [5, 77, 160], bs=16, bpr=10),
    _case("block-32", [5, 77, 320], bs=32, bpr=10),
    _case("inactive-slot", [50, 1, 90], inactive=(1,)),
    _case("shared-prefix-blocks", [70, 100, 40], shared_blocks=2),
    _case("nan-past-lengths", [1, 17, 100, 192], poison=True),
    _case("nan-past-lengths-bf16", [1, 17, 100, 192], poison=True, dtype=jnp.bfloat16),
    _case("pool-float32", [9, 120, 64, 33]),
    _case("pool-bfloat16", [9, 120, 64, 33], dtype=jnp.bfloat16),
    _case("benchmark-heads-real-chunk", [1, 300, 530], h=32, kvh=8, bs=16, bpr=34, real_chunk=True,
          dtype=jnp.bfloat16),  # fmt: skip
    _case("twenty-over-four-heads-real-chunk", [1, 300, 530], h=20, kvh=4, bs=16, bpr=34, real_chunk=True,
          dtype=jnp.bfloat16),  # fmt: skip
    # evabyte-serve-decode-long: one query head a cache head, 32 cache heads a position, a block 512 rows of 128
    _case("one-query-head-a-cache-head", [40, 130, 1], h=4, kvh=4),
    _case("thirty-two-over-thirty-two-heads-real-chunk", [1, 300, 530, 16], h=32, kvh=32, bs=16, bpr=34, real_chunk=True,
          dtype=jnp.bfloat16),  # fmt: skip
]


@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_the_xla_function(case, monkeypatch):
    case = dict(case)
    dtype, bs = case.pop("dtype"), case["bs"]
    poison, inactive = case.pop("poison", False), case.pop("inactive", ())
    if not case.pop("real_chunk", False):
        _shrink_the_kernels_geometry(monkeypatch, bs * case["kvh"], HD * jnp.dtype(dtype).itemsize)
    q, k, v, tables, lengths = _problem(dtype=dtype, **case)
    for i in inactive:  # as the engine leaves a slot nobody holds
        tables[i, :] = pa.TRASH_BLOCK
    as_dev = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    want = pa.paged_attention_xla(as_dev(q), as_dev(k), as_dev(v), jnp.asarray(tables), jnp.asarray(lengths))
    if poison:
        k, v = _poison(k, v, tables, lengths, bs)
        assert np.isnan(k[pa.TRASH_BLOCK]).all() and np.isnan(v).any()
    got = pk.paged_attention_pallas(
        as_dev(q), as_dev(k), as_dev(v), jnp.asarray(tables), jnp.asarray(lengths), interpret=True
    )
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    keep = [i for i in range(len(lengths)) if i not in inactive]  # what an inactive slot returns is never read
    np.testing.assert_allclose(got[keep], want[keep], **TOLERANCE[dtype])


# -- the stack of a layer scan, read at a layer index ------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_kernel_reads_its_layer_out_of_the_stack(dtype, monkeypatch):
    """``layer=i`` on the stack ``[layers, num_blocks, ...]`` against the same call on
    ``stack[i]`` (bit for bit: the same blocks, chunks and arithmetic) and against the XLA
    function at that layer: ragged lengths, a slot of length 0, an inactive slot on the
    trash block, which holds NaN in every layer."""
    bs, kvh, h, bpr, layers = 16, 2, 8, 12, 3
    _shrink_the_kernels_geometry(monkeypatch, bs * kvh, HD * jnp.dtype(dtype).itemsize)
    lengths = [100, 0, 17, 1, 192]
    problems = [_problem(lengths, h, kvh, bs, bpr, dtype, seed=s) for s in range(layers)]
    q, _, _, tables, lens = problems[0]  # one query and one set of tables; a layer's K and V differ
    tables[3, :] = pa.TRASH_BLOCK
    poisoned = [_poison(k, v, tables, lens, bs) for _, k, v, _, _ in problems]
    as_dev = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    k_stack, v_stack = (as_dev(np.stack(x)) for x in zip(*poisoned))
    clean_k, clean_v = (as_dev(np.stack([p[i] for p in problems])) for i in (1, 2))
    q, tables, lens = as_dev(q), jnp.asarray(tables), jnp.asarray(lens)
    keep = [0, 2, 4]  # slots 1 (no length) and 3 (inactive) return numbers nobody reads
    for i in range(layers):
        got = pk.paged_attention_pallas(q, k_stack, v_stack, tables, lens, interpret=True, layer=jnp.int32(i))
        one = pk.paged_attention_pallas(q, k_stack[i], v_stack[i], tables, lens, interpret=True)
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(one, np.float32))
        assert np.isfinite(np.asarray(got, np.float32)[keep]).all()
        want = pa.paged_attention_xla(q, clean_k, clean_v, tables, lens, jnp.int32(i))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(pa.paged_attention_xla(q, clean_k[i], clean_v[i], tables, lens)))
        np.testing.assert_allclose(np.asarray(got, np.float32)[keep], np.asarray(want, np.float32)[keep], **TOLERANCE[dtype])
    assert not np.array_equal(np.asarray(got, np.float32)[keep], np.asarray(pk.paged_attention_pallas(
        q, k_stack, v_stack, tables, lens, interpret=True, layer=0), np.float32)[keep])  # the layers differ


def test_writes_land_in_their_layer_of_the_stack_and_nowhere_else():
    """``append_kv`` and ``scatter_kv_chunk`` with ``layer=i`` against the one-layer form
    on ``stack[i]``; every other layer is left as it was."""
    rng = np.random.default_rng(2)
    stack = jnp.asarray(rng.standard_normal((3, 7, 16, 2, 8)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6], [0, 0, 0]], jnp.int32)
    new = jnp.asarray(rng.standard_normal((3, 2, 8)), jnp.float32)
    chunk = jnp.asarray(rng.standard_normal((3, 20, 2, 8)), jnp.float32)
    positions = jnp.asarray([17, 40, 0], jnp.int32)
    at = jnp.asarray([10, 28, 0], jnp.int32)[:, None] + jnp.arange(20, dtype=jnp.int32)[None]
    valid = jnp.arange(20)[None] < jnp.asarray([20, 13, 0])[:, None]
    for i in range(3):
        for got, want in (
            (pa.append_kv(stack, tables, positions, new, jnp.int32(i)), pa.append_kv(stack[i], tables, positions, new)),
            (pa.scatter_kv_chunk(stack, tables, at, chunk, valid, jnp.int32(i)),
             pa.scatter_kv_chunk(stack[i], tables, at, chunk, valid)),
        ):  # fmt: skip
            np.testing.assert_array_equal(got[i], want)
            assert not np.array_equal(got[i], stack[i])
            others = [j for j in range(3) if j != i]
            np.testing.assert_array_equal(got[jnp.asarray(others)], stack[jnp.asarray(others)])


# -- the copies' bookkeeping, in the TPU interpreter -------------------------------------
# Every edge the kernel's bookkeeping has, at its real geometry (float32 pools: two buffers of 512 KiB of K
# and of V, multiplied 1,024 rows at a time, copied in groups of 128 KiB: a chunk of 256 positions in two
# parts of four groups at 4 cache heads, of 128 in one part of four groups at 8). A case is a function of
# the positions a group, a part and a chunk hold.

EDGES = [
    pytest.param(lambda g, p, c: [0, 1, 40], 40, id="no-length-then-one-position"),
    pytest.param(lambda g, p, c: [g - 1, g, g + 1], 40, id="one-short-of-a-group-a-group-and-one-past"),
    pytest.param(lambda g, p, c: [p - 1, p, p + 1], 40, id="one-short-of-a-part-a-part-and-one-past"),
    pytest.param(lambda g, p, c: [c - 1, c, c + 1], 40, id="one-short-of-a-chunk-a-chunk-and-one-past"),
    pytest.param(lambda g, p, c: [640, 16], 40, id="every-block-of-the-table"),
    pytest.param(lambda g, p, c: [2 * c + 100], 40, id="one-slot-more-chunks-than-buffers"),
    pytest.param(lambda g, p, c: [2 * c - 7, 5], 40, id="long-then-short"),
    pytest.param(lambda g, p, c: [5, 2 * c - 7], 40, id="short-then-long"),
    pytest.param(lambda g, p, c: [p, c + p, c + p, p], 40, id="whole-parts-only"),
    pytest.param(lambda g, p, c: [c, 2 * c, c, 2 * c], 40, id="whole-chunks-only-the-first-buffer-alternates"),
    pytest.param(lambda g, p, c: [c + g + 3, 0, 0, p + 1], 40, id="slots-of-no-length-between"),
    pytest.param(lambda g, p, c: [96, 1, 50, 81], 6, id="a-table-shorter-than-a-chunk"),
    pytest.param(lambda g, p, c: [112, 97], 7, id="a-table-of-a-prime-number-of-blocks"),
]
NOT_A_BLOCK = 2**30  # in a table entry no live block reaches: an id that must never become an address


def _held_in_the_tpu_interpreter(capfd, dma, want, live, q, k, v, tables, lens, **kw):
    """``paged_attention_pallas`` in the TPU interpreter (semaphores simulated, scratch full of
    NaN) against ``want`` on every slot that has positions, and nothing left over or raced for."""
    got = pk.paged_attention_pallas(
        q, k, v, tables, lens, **kw, interpret=pltpu.InterpretParams(dma_execution_mode=dma, detect_races=True))
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], **TOLERANCE[jnp.float32])
    assert np.isfinite(np.asarray(got)[live]).all()
    out = capfd.readouterr().out
    assert "non-zero count" not in out and "RACE DETECTED" not in out, out


def _one_head_a_cache_head(edge):
    return pytest.param(*edge.values, 32, 32, id=f"{edge.id}-thirty-two-cache-heads")


@pytest.mark.parametrize("dma", ["on_wait", "eager"])
@pytest.mark.parametrize("lengths,bpr,h,kvh", [
    *(pytest.param(*e.values, h, kvh, id=f"{e.id}-{name}") for name, h, kvh in (("four-cache-heads", 20, 4), ("eight-cache-heads", 16, 8))
      for e in EDGES),
    # one query head a cache head (evabyte-serve-decode-long): a block is 512 rows, a chunk two of them, a part both
    *(_one_head_a_cache_head(e) for e in (EDGES[0], EDGES[3], EDGES[5], EDGES[10])),
])  # fmt: skip
def test_kernel_bookkeeping_on_every_edge(lengths, bpr, h, kvh, dma, capfd):
    """The kernel in the TPU interpreter, which simulates the copies' semaphores and hands out
    scratch memory full of NaN, against the XLA function in float32. ``on_wait``: a copy lands
    only when its semaphore is waited for, so a part read before its wait, or a group started
    and never waited for, reads NaN or stale rows. ``eager``: every byte started must have been
    waited for when the kernel ends, or the interpreter says so. (A wait for bytes nobody
    started would hang here as on the chip.) The trash block holds NaN where no slot is empty,
    and every table entry past a slot's live blocks is no block at all: past its last live
    block a slot reads that block again, never the table."""
    bs = 16
    chunk, part, group = pk._geometry(bs * kvh, HD * 4, bpr)
    lengths = lengths(group * bs, part * bs, chunk * bs)
    assert max(lengths) <= bpr * bs
    q, k, v, tables, lens = _problem(lengths, h, kvh, bs, bpr, jnp.float32)
    want = pa.paged_attention_xla(*map(jnp.asarray, (q, k, v, tables, lens)))
    if min(lengths) > 0:
        k[pa.TRASH_BLOCK] = v[pa.TRASH_BLOCK] = np.nan
    for row, n in zip(tables, lengths):
        row[max(-(-n // bs), 1):] = NOT_A_BLOCK  # a slot of no length reads its first entry: the trash block
    _held_in_the_tpu_interpreter(capfd, dma, want, lens > 0, *map(jnp.asarray, (q, k, v, tables, lens)))


def _ring_problem(lengths, window, ring, h, kvh, bs=16, seed=0):
    """Pools that hold only the blocks each slot's window touches, block ``b`` of the sequence
    at ring entry ``b % ring`` and the positions of ``b`` below the length written: a pair with
    zeros wherever nothing lies (the reference's), and a pair with NaN in the trash block, in
    every block the window has left and, K's, in the unwritten tail of a slot's last block."""
    rng = np.random.default_rng(seed)
    nb = 1 + len(lengths) * ring
    pools = rng.standard_normal((2, nb, bs, kvh, HD)).astype(np.float32)
    written = np.zeros((nb, bs), bool)
    tables = np.full((len(lengths), ring), pa.TRASH_BLOCK, np.int32)
    perm = iter(rng.permutation(np.arange(1, nb)))
    for i, n in enumerate(lengths):
        for b in range(max(0, n - window) // bs, -(-n // bs)):
            tables[i, b % ring] = blk = next(perm)
            written[blk, : min(n, (b + 1) * bs) - b * bs] = True
    clean = np.where(written[None, :, :, None, None], pools, 0.0)
    poisoned = clean.copy()
    poisoned[0][~written] = np.nan  # K wherever nothing lies; V must hold numbers in a live block's tail (0 * NaN is NaN)
    poisoned[1][~written.any(axis=1)] = np.nan
    q = rng.standard_normal((len(lengths), h, HD)).astype(np.float32)
    return q, clean, poisoned, tables, np.asarray(lengths, np.int32)


@pytest.mark.parametrize("dma", ["on_wait", "eager"])
@pytest.mark.parametrize("h,kvh", [pytest.param(20, 4, id="four-cache-heads"), pytest.param(64, 8, id="eight-cache-heads")])
@pytest.mark.parametrize("lengths", [
    pytest.param([1, 15, 16, 17, 100], id="below-the-window"),
    pytest.param([127, 128, 129, 0], id="at-the-window-and-a-slot-of-no-length"),
    pytest.param([144, 145, 1000, 1007, 4096], id="well-past-the-window"),
])  # fmt: skip
def test_kernel_bookkeeping_on_the_ring_of_a_windowed_layer(lengths, h, kvh, dma, capfd):
    """The same accounting on ``k-exaone``'s ring: 10 entries of 16 positions under a window of
    128, which touches 8 or 9 of them; what is copied past a slot's last live block is that
    block again, clamped before the ring's modulo, never an entry the window has left (NaN)."""
    window, ring = 128, 10
    q, clean, poisoned, tables, lens = _ring_problem(lengths, window, ring, h, kvh)
    want = pa.paged_attention_xla(*map(jnp.asarray, (q, *clean, tables, lens)), None, window)
    assert np.isnan(poisoned[:, pa.TRASH_BLOCK]).all()
    _held_in_the_tpu_interpreter(capfd, dma, want, lens > 0, *map(jnp.asarray, (q, *poisoned, tables, lens)), window=window)


@pytest.mark.parametrize("layer", [0, 2], ids=["first-layer", "last-layer"])
def test_kernel_bookkeeping_into_a_stack(layer, capfd):
    """The same accounting with the block ids moved into layer ``layer`` of a stack seen flat, up
    to the stack's last block: the clip that stands in for the compiler's check admits it."""
    bs, kvh, h, bpr = 16, 4, 20, 40
    lengths = [300, 0, 513, 16]
    problems = [_problem(lengths, h, kvh, bs, bpr, jnp.float32, seed=s) for s in range(3)]
    q, _, _, tables, lens = problems[0]
    tables[0, 0] = problems[0][1].shape[0] - 1  # the pool's last block is somebody's
    k_stack, v_stack = (np.stack([p[i] for p in problems]) for i in (1, 2))
    want = pa.paged_attention_xla(*map(jnp.asarray, (q, k_stack, v_stack, tables, lens)), jnp.int32(layer))
    for row, n in zip(tables, lengths):
        row[max(-(-n // bs), 1):] = NOT_A_BLOCK
    _held_in_the_tpu_interpreter(
        capfd, "eager", want, lens > 0, *map(jnp.asarray, (q, k_stack, v_stack, tables, lens)), layer=jnp.int32(layer))


def test_no_copy_leaves_the_pool_whatever_a_table_holds(capfd):
    """The compiler's bounds check in front of every copy is off, so the kernel clips every id:
    a slot nobody reads (no length) copies its first entry's block, and where that entry is no
    block at all, too large or negative, the copy still lands inside the pool and the other
    slots' results are what they were."""
    bs, kvh, h, bpr = 16, 4, 20, 40
    q, k, v, tables, lens = _problem([70, 0, 0, 33], h, kvh, bs, bpr, jnp.float32)
    want = pa.paged_attention_xla(*map(jnp.asarray, (q, k, v, tables, lens)))
    tables[1, :], tables[2, :] = NOT_A_BLOCK, -7
    _held_in_the_tpu_interpreter(capfd, "eager", want, lens > 0, *map(jnp.asarray, (q, k, v, tables, lens)))


def test_one_pair_of_starts_is_traced_however_many_a_chunk_holds():
    """A chunk at ``falcon-h1``'s shape is 32 blocks, 64 copies, started at two places in the
    kernel. The loops multiply one traced pair: written out in Python the starts cost each of a
    server's two programs 3 s of tracing at every start-up, warm compile cache or not, and the
    cell's ``setup_s`` a fifth (PERF.md section 6, PR 42)."""
    sds = jax.ShapeDtypeStruct
    pool = sds((6, 8449, 16, 4, 128), jnp.bfloat16)
    fn = lambda q, k, v, t, n, i: pk.paged_attention_pallas(q, k, v, t, n, layer=i)  # noqa: E731
    jaxpr = jax.make_jaxpr(fn)(
        sds((64, 20, 128), jnp.bfloat16), pool, pool, sds((64, 264), jnp.int32), sds((64,), jnp.int32), sds((), jnp.int32))
    assert str(jaxpr).count("dma_start") == 4  # K and V, ahead of a slot's first chunk and ahead of every other


GEOMETRY = [  # rows a block, bytes a row, the most blocks a slot has live -> blocks a chunk, a part, a group
    pytest.param(16 * 4, 256, 264, (32, 16, 8), id="falcon-h1-serve-decode-long"),
    pytest.param(16 * 8, 256, 264, (16, 8, 4), id="k-exaone-full"),
    pytest.param(16 * 8, 256, 9, (9, 9, 3), id="k-exaone-what-a-window-of-128-touches"),
    pytest.param(16 * 8, 256, 256, (16, 8, 4), id="mistral7b-serve-chat"),
    pytest.param(16 * 8, 512, 128, (8, 8, 2), id="float32-pools"),
    pytest.param(16 * 8, 256, 6, (6, 6, 3), id="a-short-table"),
    pytest.param(16 * 8, 256, 7, (7, 7, 1), id="a-prime-table"),
    pytest.param(32 * 16, 1024, 3, (1, 1, 1), id="a-block-larger-than-a-chunk"),
    pytest.param(16 * 32, 256, 176, (4, 2, 1), id="evabyte-serve-decode-long"),
]


@pytest.mark.parametrize("rows,row_bytes,span,want", GEOMETRY)
def test_the_kernels_geometry_is_a_function_of_the_shapes(rows, row_bytes, span, want):
    assert pk._geometry(rows, row_bytes, span) == want


def _geometry_said(bs, kvh, bpr):
    """What ``traced("paged_geometry")`` says the one lowering since ``TRACED`` was emptied chose,
    in blocks, held to the rule: whole groups a part, whole parts a chunk, no chunk longer than the table."""
    said = attn_ops.traced("paged_geometry")
    words = said.split()
    assert words[0::2][:3] == ["chunk", "part", "group"] and said.endswith("rows, 2 buffers"), said
    rows = [int(w) for w in words[1:6:2]]
    assert all(r % (bs * kvh) == 0 for r in rows), said
    chunk, part, group = (r // (bs * kvh) for r in rows)
    assert chunk % part == 0 and part % group == 0 and 1 <= group and chunk <= bpr, said
    return chunk, part, group


# -- which path a call takes -----------------------------------------------------------

MISTRAL = dict(q=(16, 32, 128), pool=(2049, 16, 8, 128))  # both benchmark configurations: 32/8 heads of 128, block 16
RULE = [
    ("benchmark-shapes-on-tpu", MISTRAL, jnp.bfloat16, jnp.bfloat16, "tpu", True),
    ("float32-on-tpu", MISTRAL, jnp.float32, jnp.float32, "tpu", True),
    ("group-1-block-32", dict(q=(4, 8, 128), pool=(9, 32, 8, 128)), jnp.bfloat16, jnp.bfloat16, "tpu", True),
    ("head-dim-256", dict(q=(4, 16, 256), pool=(9, 16, 8, 256)), jnp.bfloat16, jnp.bfloat16, "tpu", True),
    ("four-cache-heads", dict(q=(64, 20, 128), pool=(8449, 16, 4, 128)), jnp.bfloat16, jnp.bfloat16, "tpu", True),
    ("cpu", MISTRAL, jnp.bfloat16, jnp.bfloat16, "cpu", False),
    ("gpu", MISTRAL, jnp.bfloat16, jnp.bfloat16, "gpu", False),
    ("head-dim-64", dict(q=(4, 32, 64), pool=(9, 16, 8, 64)), jnp.bfloat16, jnp.bfloat16, "tpu", False),
    ("heads-do-not-group", dict(q=(4, 12, 128), pool=(9, 16, 8, 128)), jnp.bfloat16, jnp.bfloat16, "tpu", False),
    ("two-cache-heads", dict(q=(4, 4, 128), pool=(9, 16, 2, 128)), jnp.float32, jnp.float32, "tpu", False),
    ("block-4", dict(q=(4, 32, 128), pool=(9, 4, 8, 128)), jnp.bfloat16, jnp.bfloat16, "tpu", False),
    ("query-and-pool-dtypes-differ", MISTRAL, jnp.float32, jnp.bfloat16, "tpu", False),
    ("float16", MISTRAL, jnp.float16, jnp.float16, "tpu", False),
]


@pytest.mark.parametrize("shapes,q_dtype,pool_dtype,backend,want", [pytest.param(*r[1:], id=r[0]) for r in RULE])
def test_eligibility_is_a_function_of_shapes_dtypes_and_backend(shapes, q_dtype, pool_dtype, backend, want):
    assert pa.kernel_eligible(shapes["q"], shapes["pool"], jnp.dtype(q_dtype), jnp.dtype(pool_dtype), backend) is want


def _pretend_tpu(monkeypatch):
    """The real rule with the backend said to be a TPU, and the kernel it then
    picks run in the interpreter."""
    rule = pa.kernel_eligible
    monkeypatch.setattr(pa, "kernel_eligible", lambda qs, ps, qd, pd, _backend: rule(qs, ps, qd, pd, "tpu"))
    monkeypatch.setattr(pk, "paged_attention_pallas", functools.partial(pk.paged_attention_pallas, interpret=True))


@pytest.mark.parametrize("h,kvh,hd,pretend,want", [
    pytest.param(8, 8, 128, False, "paged_xla", id="cpu"),
    pytest.param(8, 8, 64, True, "paged_xla", id="head-dim-64"),
    pytest.param(8, 8, 128, True, "paged_pallas", id="eligible"),
])  # fmt: skip
def test_traced_says_which_path_lowered(h, kvh, hd, pretend, want, monkeypatch):
    monkeypatch.setattr(attn_ops, "TRACED", {})
    if pretend:
        _pretend_tpu(monkeypatch)
    q = jnp.ones((2, h, hd), jnp.float32)
    pool = jnp.ones((3, 16, kvh, hd), jnp.float32)
    tables = jnp.asarray([[1, 0], [2, 0]], jnp.int32)
    out = pa.paged_attention(q, pool, pool, tables, jnp.asarray([5, 16], jnp.int32))
    assert attn_ops.traced("attention") == want
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-6)  # the mean of ones, whatever the path


# -- the decode program with the kernel in it --------------------------------------------


def _greedy_tokens(cfg, steps=8):
    init, _ = llama.model_fns(cfg)
    params = init(cfg, jax.random.PRNGKey(3))
    slots, bs = 3, 16
    bps = cfg.max_seq // bs
    pools = gen.init_kv_pools(cfg, 1 + slots * bps, bs)
    tables = np.full((slots, bps), pa.TRASH_BLOCK, np.int32)
    tables[0], tables[1] = 1 + np.arange(bps), 1 + bps + np.arange(bps)  # slot 2 stays inactive
    tables = jnp.asarray(tables)
    step = jax.jit(lambda p, t, pos, pl: gen.paged_decode_step(
        p, t, pos, tables, pl, cfg, jnp.zeros((slots, 2), jnp.uint32), jnp.zeros((slots,), jnp.float32)))  # fmt: skip
    tokens, out = jnp.asarray([5, 9, 0], jnp.int32), []
    for i in range(steps):
        tokens, pools = step(params, tokens, jnp.asarray([i, i, 0], jnp.int32), pools)
        out.append(np.asarray(tokens)[:2])
    return np.stack(out)


@pytest.mark.parametrize("make", [
    pytest.param(lambda: llama.llama_tiny(dim=1024, n_heads=8, n_kv_heads=8, max_seq=64), id="dense"),
    pytest.param(lambda: moe.moe_tiny(dim=1024, n_heads=8, n_kv_heads=8, max_seq=64), id="moe"),
])  # fmt: skip
def test_decode_step_gives_the_same_greedy_tokens(make, monkeypatch):
    cfg = make()
    assert cfg.head_dim == 128
    want = _greedy_tokens(cfg)
    monkeypatch.setattr(attn_ops, "TRACED", {})
    _pretend_tpu(monkeypatch)
    got = _greedy_tokens(cfg)
    assert attn_ops.traced("attention") == "paged_pallas"
    np.testing.assert_array_equal(got, want)
    assert len({tuple(r) for r in want}) > 1  # the tokens move: not a constant answer


# -- the chip's compiler takes the kernel at the benchmark's shapes ------------------------
# Interpret mode cannot see what Mosaic refuses (tiling, VMEM). The TPU's compiler is
# installed without a chip; the topology is described inside a fixture and nowhere else,
# so only the worker that runs this file loads the library.


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("bpr,dtype", [
    pytest.param(256, jnp.bfloat16, id="mistral7b-serve-chat"),
    pytest.param(128, jnp.bfloat16, id="mixtral8x7b-serve-backlog"),
    pytest.param(256, jnp.float32, id="float32-pools"),
])  # fmt: skip
def test_kernel_compiles_for_the_chip(one_chip, bpr, dtype, monkeypatch):
    monkeypatch.setattr(attn_ops, "TRACED", {})
    slots, h, kvh, bs, nb = 16, 32, 8, 16, 2049
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    pool = shape((nb, bs, kvh, HD), dtype)
    assert pa.kernel_eligible((slots, h, HD), pool.shape, jnp.dtype(dtype), pool.dtype, "tpu")
    compiled = jax.jit(pk.paged_attention_pallas).lower(
        shape((slots, h, HD), dtype), pool, pool, shape((slots, bpr), jnp.int32), shape((slots,), jnp.int32)
    ).compile()  # fmt: skip
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the pools go in as they lie in HBM: no relayout of 67 MB a layer in front of the kernel
    pool_type = f"{jnp.dtype(dtype).name.replace('bfloat16', 'bf16').replace('float32', 'f32')}[{nb},"
    assert not [ln for ln in text.splitlines() if " copy(" in ln and pool_type in ln]
    assert _geometry_said(bs, kvh, bpr) == ((16, 8, 4) if dtype == jnp.bfloat16 else (8, 8, 2))


# The latent-attention decode kernel and the grouped matmul of the expert layers (PR 27),
# at the widths of the kimi-vl-a3b-serve-backlog cell. Kept in this file: the worker that
# holds the TPU's library is the one that runs it.


def _compiled_into_a_stack(one_chip, q_shape, pool_shape, bpr, window=0):
    """The compiled program's text of the kernel lowered for the chip at a cell's shapes, handed
    a stack of layers' pools and a traced layer, as the cells' decode programs call it; the
    stack goes in as it lies in HBM (no relayout of it in front of the kernel)."""
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    pool = shape(pool_shape, jnp.bfloat16)
    assert pa.kernel_eligible(q_shape, pool.shape[1:], jnp.dtype(jnp.bfloat16), pool.dtype, "tpu")
    fn = lambda q, k, v, t, n, i: pk.paged_attention_pallas(q, k, v, t, n, layer=i, window=window)  # noqa: E731
    text = jax.jit(fn).lower(
        shape(q_shape, jnp.bfloat16), pool, pool, shape((q_shape[0], bpr), jnp.int32), shape((q_shape[0],), jnp.int32),
        shape((), jnp.int32)
    ).compile().as_text()  # fmt: skip
    assert "paged_attention_decode" in text
    stack_types = [f"bf16[{pool_shape[0]},{pool_shape[1]},", f"bf16[{pool_shape[0] * pool_shape[1]},"]  # as given, and seen flat
    assert not [ln for ln in text.splitlines() if " copy(" in ln and any(t in ln for t in stack_types)]
    return text


def test_kernel_compiles_for_the_chip_over_four_cache_heads(one_chip, monkeypatch):
    """``falcon-h1-serve-decode-long``: 64 slots of 20 query heads over 4 cache heads, into a
    stack of six layers' pools, a block 64 rows of 128: 32 blocks a buffer, multiplied 16 at
    a time, copied in groups of 8."""
    monkeypatch.setattr(attn_ops, "TRACED", {})
    _compiled_into_a_stack(one_chip, (64, 20, 128), (6, 8449, 16, 4, 128), 264)
    assert _geometry_said(16, 4, 264) == (32, 16, 8)


def test_kernel_compiles_for_the_chip_at_64_heads_over_eight(one_chip, monkeypatch):
    """``k-exaone-serve-decode-long``'s full layers: 64 slots of 64 query heads over 8 cache
    heads, tables of 264 blocks into the stack of its two full layers' pools."""
    monkeypatch.setattr(attn_ops, "TRACED", {})
    _compiled_into_a_stack(one_chip, (64, 64, 128), (2, 8449, 16, 8, 128), 264)
    assert _geometry_said(16, 8, 264) == (16, 8, 4)


def test_kernel_compiles_for_the_chip_at_one_query_head_a_cache_head(one_chip, monkeypatch):
    """``evabyte-serve-decode-long``: 32 slots of 32 query heads over 32 cache heads (no grouping: the work a byte
    read is a grouped model's), tables of 176 entries (six finished windows' 8 blocks of pooled rows and a window's
    128) into a stack of eight layers' pools of 4,609 blocks, a block 512 rows of 128 (128 KiB of K): 4 blocks a
    buffer, multiplied 2 at a time, copied one by one."""
    monkeypatch.setattr(attn_ops, "TRACED", {})
    _compiled_into_a_stack(one_chip, (32, 32, 128), (8, 4609, 16, 32, 128), 176)
    assert _geometry_said(16, 32, 176) == (4, 2, 1)


def test_the_programs_of_a_window_and_pooled_rows_compile_for_the_chip(one_chip, monkeypatch):
    """EvaByte's stack at its published widths (d 4096, 32/32 heads of 128, ffn 11008, a window of 2,048, chunks of
    16, eight heads of 320), three layers: the step that carries a chunk, as ``evabyte-serve-decode-long`` runs it
    (32 slots, tables of 176 entries and 8 staging blocks a slot beside them, the engine's default pool). The decode
    kernel is handed the stack; the pooling, its gather of a block a slot and its scatter of a row included, moves
    nothing of a layer's pool's size; no weight is moved; and of the eight heads' 2,560 columns 320 are multiplied."""
    from torchx_tpu.obs.hlo import loop_moves, program_moves
    from torchx_tpu.serve.kv_pool import EvaTables

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attn_ops, "TRACED", {})
    cfg = llama.llama_tiny(vocab_size=320, dim=4096, n_heads=32, n_kv_heads=32, ffn_dim=11008, n_layers=3, max_seq=12416,
                           dtype=jnp.bfloat16, rope_theta=1e5, eva_window=2048, eva_chunk=16, norm_unit_offset=True,
                           fp32_skip_add=True, pred_heads=8)  # fmt: skip
    slots, width, bs = 32, 256, 16
    host = EvaTables(slots, cfg.max_seq, cfg.eva_window, cfg.eva_chunk, bs)
    assert (host.blocks_per_slot, host.pooled_blocks, host.most_blocks) == (176, 8, 184)
    on_chip = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)  # noqa: E731
    shape = lambda s, d=jnp.int32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    pools = on_chip(jax.eval_shape(lambda: gen.init_kv_pools(cfg, 1 + slots * (8 * 7 + 88), bs)))
    tables = lambda n: {"full": shape((n, host.blocks_per_slot)), "stage": shape((n, host.pooled_blocks))}  # noqa: E731

    def fn(p, tok, pos, tab, chunk, start, n, chunk_tab, pl, keys, temps):
        return gen.paged_decode_chunk_step(p, tok, pos, tab, chunk, start, n, chunk_tab, pl, cfg, keys, temps)

    compiled = jax.jit(fn, donate_argnums=(8,)).lower(
        params, shape((slots,)), shape((slots,)), tables(slots), shape((width,)), shape(()), shape(()),
        tables(1), pools, shape((slots + 1, 2), jnp.uint32), shape((slots + 1,), jnp.float32),
    ).compile()  # fmt: skip
    text = compiled.as_text()
    assert attn_ops.traced("kv_pools") == "carried" and attn_ops.traced("eva") == "paged"
    assert attn_ops.traced("attention") == "paged_pallas+paged_walk" and "paged_attention_decode" in text
    layer_bytes = pools["k"].size // pools["k"].shape[0] * 2
    assert loop_moves(text, layer_bytes) == []
    moved = program_moves(text, 4096 * 4096 * 2)  # a layer's smallest projection
    assert not [m for m in moved if any(w in m for w in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"))], moved
    assert "bf16[4096,2560]" in text and ",2560]{" not in text.replace("bf16[4096,2560]", "")  # the head goes in whole; no product is 2,560 wide
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_state_step_kernel_compiles_for_the_chip_in_place(one_chip):
    """``falcon-h1-serve-decode-long``'s recurrent state: 64 slots' rows of 32 heads x 256 x 128
    float32 in a store of six layers, moved on where they lie: the store goes in and comes out
    the same buffer, and nothing of its size is copied in front of or behind the kernel."""
    from torchx_tpu.models import ssm
    from torchx_tpu.ops.ssm_step_kernel import ssm_step_pallas

    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    store = shape((6, 65, 32, 256, 128), jnp.float32)
    assert ssm.kernel_eligible(store.shape, 2, "tpu")
    fn = lambda st, r, d, f, b, c, i: ssm_step_pallas(st, r, d, f, b, c, layer=i)  # noqa: E731
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        store, shape((64,), jnp.int32), shape((64, 32), jnp.float32), shape((64, 32, 128), jnp.float32),
        shape((64, 2, 256), jnp.float32), shape((64, 2, 256), jnp.float32), shape((), jnp.int32)
    ).compile()  # fmt: skip
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "ssm_step" in text and "tpu_custom_call" in text
    assert memory.alias_size_in_bytes >= 6 * 65 * 32 * 256 * 128 * 4 and memory.temp_size_in_bytes < 2**26
    assert not [ln for ln in text.splitlines() if " copy(" in ln and "f32[6,65," in ln]


def test_windowed_kernel_compiles_for_the_chip(one_chip, monkeypatch):
    """``k-exaone-serve-decode-long``'s sliding layers: 64 slots, rings of 10 blocks into a stack of six layers'
    window pools; a window of 128 touches 9 blocks at most, which is the chunk and the one part: three groups of three."""
    monkeypatch.setattr(attn_ops, "TRACED", {})
    _compiled_into_a_stack(one_chip, (64, 64, 128), (6, 1169, 16, 8, 128), 10, window=128)
    assert _geometry_said(16, 8, 10) == (9, 9, 3)


@pytest.mark.parametrize("slots,h,pool_shape", [
    pytest.param(64, 16, (8449, 16, 640), id="one-layers-pool"),
    # the two cells that run the kernel, as their decode programs call it: the larger layer group's stack, a traced layer
    pytest.param(64, 16, (8, 8449, 16, 640), id="kimi-vl-a3b-serve-backlog"),
    pytest.param(128, 32, (6, 16897, 16, 640), id="xing4-serve-decode-long"),
])  # fmt: skip
def test_latent_kernel_compiles_for_the_chip(one_chip, slots, h, pool_shape):
    """The chip's compiler takes the latent kernel at each cell's exact shapes (what it
    refuses costs a run on the chip otherwise: VMEM, SMEM for a table of 128 x 264 entries,
    an operation Mosaic has not at 32 heads), and the pool goes in as it lies."""
    from torchx_tpu.ops import paged_mla as pm
    from torchx_tpu.ops import paged_mla_kernel as pmk

    bpr, rank, width = 264, 512, 640
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    pool = shape(pool_shape, jnp.bfloat16)
    assert pm.kernel_eligible((slots, h, width), pool.shape[-3:], rank, jnp.dtype(jnp.bfloat16), pool.dtype, "tpu")
    args = [shape((slots, h, width), jnp.bfloat16), pool, shape((slots, bpr), jnp.int32), shape((slots,), jnp.int32)]
    if len(pool_shape) == 4:
        fn = lambda q, p, t, n, i: pmk.paged_mla_pallas(q, p, t, n, rank, 192**-0.5, layer=i)  # noqa: E731
        args.append(shape((), jnp.int32))
    else:
        fn = functools.partial(pmk.paged_mla_pallas, rank=rank, scale=192**-0.5)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_mla_decode" in text
    # the pool goes in as it lies in HBM: no relayout of 168 MB a layer in front of the kernel, no temporary of its order
    pool_types = ["bf16[" + ",".join(map(str, pool_shape[:2])), f"bf16[{math.prod(pool_shape[:-2])},"]  # as given, and seen flat
    assert not [ln for ln in text.splitlines() if " copy(" in ln and any(t in ln for t in pool_types)]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# The serving programs whole, with the pools donated: the chip's compiler keeps the stack the
# layer scan carries where it lies (tests/test_pools_in_carry.py holds the CPU's to the same).


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("make,kernel", [
    pytest.param(lambda: llama.llama_tiny(dim=1024, n_heads=8, n_kv_heads=8, n_layers=3, max_seq=256, dtype=jnp.bfloat16),
                 "paged_attention_decode", id="kv-pools"),
    pytest.param(lambda: moe.moe_tiny(
        dim=256, n_heads=8, n_kv_heads=8, n_layers=3, max_seq=256, dtype=jnp.bfloat16, ffn_dim=256, n_experts=8, top_k=3,
        expert_ffn_dim=128, n_shared_experts=1, router_score="sigmoid", router_bias=True, n_dense_layers=1,
        capacity_factor=0.0, kv_lora_rank=128, qk_nope_dim=64, qk_rope_dim=64, v_head_dim=64), "paged_mla_decode",
        id="latent-pools-two-groups"),
    pytest.param(lambda: moe.moe_tiny(
        dim=512, n_heads=8, n_kv_heads=8, attn_head_dim=128, n_layers=12, max_seq=256, dtype=jnp.bfloat16, ffn_dim=256,
        n_experts=16, experts_held=4, experts_held_from=4, top_k=3, expert_ffn_dim=128, n_shared_experts=1,
        router_score="sigmoid", router_bias=True, n_dense_layers=1, capacity_factor=0.0, qk_norm=True, rope_full_layers=False,
        layer_types=("sliding", "sliding", "sliding", "full") * 3, sliding_window=40), "paged_attention_decode",
        id="window-and-full-pools-two-groups-periods-scanned"),
])  # fmt: skip
def test_serving_programs_keep_the_pools_where_they_lie_on_the_chip(one_chip, make, kernel, program, monkeypatch):
    from torchx_tpu.obs.hlo import loop_moves

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # what every kernel_eligible will be told there
    monkeypatch.setattr(attn_ops, "TRACED", {})
    cfg = make()
    slots, rows, width, bs = 8, 2, 128, 16
    bpr = cfg.max_seq // bs
    on_chip = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)  # noqa: E731
    shape = lambda s, d=jnp.int32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0))))
    pools = on_chip(jax.eval_shape(lambda: gen.init_kv_pools(cfg, 4097, bs, 513)))

    def tables(n, window_width):  # one array, or one a cache kind where sliding and full layers mix
        return {"full": shape((n, bpr)), "window": shape((n, window_width))} if cfg.layer_types else shape((n, bpr))

    if program == "decode":
        fn = lambda p, tok, pos, tab, pl, keys, temps: gen.paged_decode_step(p, tok, pos, tab, pl, cfg, keys, temps)  # noqa: E731
        args = (params, shape((slots,)), shape((slots,)), tables(slots, 5), pools,
                shape((slots, 2), jnp.uint32), shape((slots,), jnp.float32))  # fmt: skip
    else:
        fn = lambda p, tok, pre, suf, tab, pl, keys, temps: gen.paged_prefill_chunk(p, tok, pre, suf, tab, pl, cfg, keys, temps)  # noqa: E731
        args = (params, shape((rows, width)), shape((rows,)), shape((rows,)), tables(rows, bpr), pools,
                shape((rows, 2), jnp.uint32), shape((rows,), jnp.float32))  # fmt: skip
    text = jax.jit(fn, donate_argnums=(len(args) - 3,)).lower(*args).compile().as_text()
    assert attn_ops.traced("kv_pools") == "carried"
    assert " while(" in text
    if program == "decode":
        assert kernel in text  # the Pallas call is in the loop, handed the stack
    layer_bytes = min(p.size // p.shape[0] * p.dtype.itemsize for p in jax.tree.leaves(pools))
    assert loop_moves(text, layer_bytes) == []


# The decode program's attention projections (PR 32): each layer's wq/wk/wv/wo is multiplied where it
# lies in its stack. Before, the head split that followed a projection was folded into its matmul, the
# chip's compiler asked for the weight as [heads, hd, d], and every layer's wq/wk/wv was sliced out and
# written transposed each step: inside the layer loop of a scanned stack, in the entry computation where
# the loop is short enough to be unrolled (where ``loop_moves`` does not look).


CELL_STACKS = [
    # Mistral-7B's attention (d 4096, 32/8 heads of 128) and FFN, 3 layers: one scan over one kind
    pytest.param(lambda: llama.llama_tiny(
        vocab_size=32768, dim=4096, n_heads=32, n_kv_heads=8, ffn_dim=14336, n_layers=3, max_seq=256, dtype=jnp.bfloat16),
        16, "paged_attention_decode", id="dense-gqa-one-scan"),
    # Mixtral-8x7B's: the same attention in front of 8 experts, top-2, routed by capacity as its cell runs it
    pytest.param(lambda: moe.moe_tiny(
        vocab_size=32000, dim=4096, n_heads=32, n_kv_heads=8, ffn_dim=14336, n_experts=8, top_k=2, capacity_factor=4.0,
        n_layers=2, max_seq=256, dtype=jnp.bfloat16), 16, "paged_attention_decode", id="mixtral-shaped"),
    # K-EXAONE's (d 6144, 64/8 heads of 128: h * hd != d), QK-norm, one dense layer and one period L L L G of
    # expert layers: the layer loop is a few layers and one period, which the compiler unrolls
    pytest.param(lambda: moe.moe_tiny(
        vocab_size=19200, dim=6144, n_heads=64, n_kv_heads=8, attn_head_dim=128, n_layers=5, max_seq=256, dtype=jnp.bfloat16,
        ffn_dim=18432, n_experts=128, experts_held=16, top_k=8, expert_ffn_dim=2048, n_shared_experts=1,
        router_score="sigmoid", router_bias=True, n_dense_layers=1, capacity_factor=0.0, qk_norm=True, rope_full_layers=False,
        layer_types=("sliding", "sliding", "sliding", "sliding", "full"), sliding_window=128), 64,
        "paged_attention_decode", id="window-and-full-unrolled"),
    # Xing4.0's (PR 33): latent attention at d 3584, 32 heads of 128 + 64, the query through its own latent of 768
    # (W_qb split into heads behind the same barrier), W_kvb held as the absorbed decode multiplies it (w_uk, w_uv:
    # the heads outermost, each read by its one batched product), four residual streams round every sublayer; 2 dense
    # + 3 expert layers. 64 slots, so that no activation is as large as the smallest weight (at the cell's 128 the
    # absorbed query [32, 512, 128] is re-laid, 4 MiB a layer: rehearsal, PR 33)
    pytest.param(lambda: moe.moe_tiny(
        vocab_size=16384, dim=3584, n_heads=32, n_kv_heads=32, n_layers=5, max_seq=256, dtype=jnp.bfloat16, ffn_dim=9216,
        n_experts=64, top_k=4, expert_ffn_dim=1024, n_shared_experts=1, router_score="sigmoid", router_bias=True,
        routed_scale=2.0, n_dense_layers=2, capacity_factor=0.0, kv_lora_rank=512, q_lora_rank=768, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, hc_mult=4, hc_sinkhorn_iters=20, norm_eps=1e-6, rope_theta=10000.0,
        rope_scaling=YarnScaling(factor=64.0, original_max_seq=4096, mscale_all_dim=1.0)), 64,
        "paged_mla_decode", id="latent-compressed-query-four-streams"),
]  # fmt: skip
# Kimi-VL-A3B's language model: latent attention at d 2048, 16 heads of 128 + 64 behind an uncompressed query, one dense
# layer ahead of the expert layers, 64 experts top-6 + 2 shared. Its wq and w_kvb are still sliced out of their stacks
# (ROADMAP queue 1 item 6), so it is not among the stacks whose projections are held to "where they lie"
KIMI_STACK = pytest.param(lambda: moe.moe_tiny(
    vocab_size=16384, dim=2048, n_heads=16, n_kv_heads=16, n_layers=4, max_seq=256, dtype=jnp.bfloat16, ffn_dim=11264,
    n_experts=64, top_k=6, expert_ffn_dim=1408, n_shared_experts=2, router_score="sigmoid", router_bias=True,
    routed_scale=2.446, n_dense_layers=1, capacity_factor=0.0, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128), 64, "paged_mla_decode", id="latent-uncompressed-query")  # fmt: skip


@pytest.mark.parametrize("make,slots,kernel", CELL_STACKS)
def test_decode_program_multiplies_the_projections_where_they_lie_on_the_chip(one_chip, make, slots, kernel, monkeypatch):
    from torchx_tpu.obs.hlo import program_moves

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attn_ops, "TRACED", {})
    cfg = make()
    bs, bpr = 16, cfg.max_seq // 16
    on_chip = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)  # noqa: E731
    shape = lambda s, d=jnp.int32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0))))
    pools = on_chip(jax.eval_shape(lambda: gen.init_kv_pools(cfg, 1 + slots * bpr, bs, 1 + slots * 10)))
    tables = {"full": shape((slots, bpr)), "window": shape((slots, 10))} if cfg.layer_types else shape((slots, bpr))
    fn = lambda p, tok, pos, tab, pl, keys, temps: gen.paged_decode_step(p, tok, pos, tab, pl, cfg, keys, temps)  # noqa: E731
    text = jax.jit(fn, donate_argnums=(4,)).lower(
        params, shape((slots,)), shape((slots,)), tables, pools, shape((slots, 2), jnp.uint32), shape((slots,), jnp.float32)
    ).compile().as_text()  # fmt: skip
    assert attn_ops.traced("projections") == "in_place"
    assert kernel in text
    names = ("wq", "wk", "wv", "wo", "w_qa", "w_qb", "w_kva", "w_uk", "w_uv")
    stacks = [params[g][w] for g in llama.layer_groups(params) for w in names if w in params[g]]
    smallest = min(w.size // w.shape[0] * w.dtype.itemsize for w in stacks)  # a layer's wk, or w_kva
    assert program_moves(text, smallest) == []


@pytest.mark.parametrize("m,k,n,held,spread", [
    pytest.param(384, 2048, 1408, 64, 64, id="decode-gate-up"),
    pytest.param(384, 1408, 2048, 64, 64, id="decode-down"),
    pytest.param(49152, 2048, 1408, 64, 64, id="widest-prefill-gate-up"),
    # a chip's share: 16 of 128 experts held, the rows spread over all 128 (a tile of 512 rows, which
    # 16 groups alone would ask for, ran the kernel out of VMEM at these widths: rehearsal, PR 31)
    pytest.param(512, 6144, 2048, 16, 128, id="share-decode-gate-up"),
    pytest.param(16384, 6144, 2048, 16, 128, id="share-widest-prefill-gate-up"),
    pytest.param(16384, 2048, 6144, 16, 128, id="share-widest-prefill-down"),
])  # fmt: skip
def test_grouped_matmul_compiles_for_the_chip(one_chip, m, k, n, held, spread):
    """The entry the program calls (``ops/grouped_matmul_kernel.py::walk``, PR 46: megablox until then), with the
    tiling ``grouped_matmul`` hands it at this shape: the ring of a group's weights fits beside the row tiles."""
    from torchx_tpu.ops import grouped_matmul as gm
    from torchx_tpu.ops import grouped_matmul_kernel as gk

    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    assert gm.kernel_eligible((m, k), (held, k, n), jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.bfloat16), "tpu")
    tiling = gm._tiling(m, k, n, 2, groups=spread)
    compiled = jax.jit(lambda l, r, g, at: gk.walk(l, r, g, at, tiling=tiling)).lower(
        shape((m, k), jnp.bfloat16), shape((2, held, k, n), jnp.bfloat16), shape((held,), jnp.int32), shape((), jnp.int32)
    ).compile()  # fmt: skip
    assert "tpu_custom_call" in compiled.as_text() and gk.KERNEL_NAME in compiled.as_text()


@pytest.fixture(scope="module")
def train_cell_step(one_chip):
    """``mistral7b-train-4k``'s step as ``benchmark/rehearse_compile.py`` builds it (the cell's config, splash named,
    state and batch as shapes), compiled once for the described chip: the program's text, its ``memory_analysis``
    and what its trace left in ``ops.attention.TRACED``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.lib import models, spec
    from torchx_tpu.parallel.mesh import make_mesh
    from torchx_tpu.parallel.mesh_config import parse_mesh_spec
    from torchx_tpu.train import step as tl

    cell = spec.load_cell("mistral7b-train-4k")
    dep, job = cell.config["deployment"], cell.traffic
    batch, seq = int(dep["batch"]), int(job["seq"])
    cfg = models.program_config(cell.config, max_seq=seq, remat_policy=dep["remat_policy"], kernels="reference", attn_impl="splash")  # fmt: skip
    assert cfg.loss_chunk and seq % cfg.loss_chunk == 0 and seq > cfg.loss_chunk
    mesh = make_mesh(parse_mesh_spec(dep["mesh"]), devices=list(one_chip.device_set))
    whole = NamedSharding(mesh, P())
    on_chip = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=whole), tree)  # noqa: E731
    optimizer = tl.make_optimizer(lr=job["lr"], warmup=job["warmup"])
    state = on_chip(jax.eval_shape(lambda: tl.init_state(cfg, mesh, optimizer)))
    tokens = on_chip({"tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)})
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attn_ops, "TRACED", {})
        step = tl.make_train_step(cfg, mesh, optimizer, state_shardings=jax.tree.map(lambda x: x.sharding, state))
        compiled = step.lower(state, tokens).compile()
        said = {op: attn_ops.traced(op) for op in ("loss", "attention", "attention_bwd", "remat", "rotation")}
    return compiled.as_text(), compiled.memory_analysis(), said


def test_the_train_cells_step_compiles_for_the_chip_with_a_chunks_logits_made_once(train_cell_step):
    """The loss's loop holds no recomputed head matmul (PR 47: the backward loop of a rematerialized scan made every
    chunk's ``[2, 512, 32768]`` logits again), and the program still fits the chip."""
    text, m, said = train_cell_step
    assert said["loss"] == "fused" and said["attention"] == "splash"
    assert "lm_head" in text and "rematted_computation/lm_head" not in text
    assert "rematted_computation/mlp" in text  # the layers' recomputation is the file's remat_policy, and stays
    live = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert live <= 13.85 * 2**30  # 13.57 GiB before this form, 13.80 with it (rehearsal, PR 47) and since PR 50


def test_the_train_cells_step_makes_attentions_scores_once_in_its_backward(train_cell_step):
    """Three splash calls a layer where there were four (PR 50): the forward kernel in the forward loop, the same as
    the file's recomputation in the backward loop, and one ``dkv`` kernel that also writes ``dq``'s partials, one
    ``[2, 32, 4096, 128]`` a kv block; no ``dq`` kernel walks the block pairs a second time."""
    text, _, said = train_cell_step
    assert said["attention_bwd"] == "fused"
    calls = [line.split(" = ")[0].split()[-1] for line in text.splitlines() if "custom_call_target=\"tpu_custom_call\"" in line]
    splash = sorted(c.lstrip("%").split(".")[0] for c in calls if "splash_mha" in c)
    assert splash == ["splash_mha_dkv_no_residuals", "splash_mha_fwd_residuals", "splash_mha_fwd_residuals"], calls
    partials = 4096 // attn_ops._backward_blocks(4096, 4096, 128, 512, 1024)["block_kv_dkv"]
    assert partials <= attn_ops._DQ_PARTIALS and f"bf16[2,{partials},32,4096,128]" in text


#: What the layers' two loops of ``mistral7b-train-4k``'s compiled step still write a turn without computing on it, 8 MiB
#: or more a move (PR 51; ``scripts/rehearse_train_step.py`` prints them by name): ``array{layout}: (how many, why it
#: stays)``. The parent's loops moved 160 and 1,040 MiB a turn: ``jax.checkpoint``'s CSE guard, an optimization barrier
#: round everything the recomputation reads, made a buffer of each of its operands (a layer's seven weights out of their
#: stacks, 416 MiB; rope's float32 halves and their copies, 320) and kept the two sides of it from fusing. The rotation
#: itself splits no head (``ops/rope.py::apply_rope_whole``), so no float32 half is among them under either form.
LAYER_LOOP_MOVES = {
    "jit(step)/jvp(layers)/while": {  # 160 MiB a turn, as the parent's
        "bf16[1,4096,4096]{2,1,0}": (1, "wq out of its stack: its product is split into heads, which the chip runs as a convolution"),
        "bf16[1,4096,4096]{1,2,0}": (1, "... that wants the weight as [heads, hd, d]: PR 32's barrier cures it and un-fuses rope (576 MiB)"),
        "bf16[1,4096,1024]{2,1,0}": (2, "wk, wv: the same"),
        "bf16[1,4096,1024]{1,2,0}": (2, "wk, wv: the same"),
        "bf16[2,4096,32,128]{1,3,2,0}": (1, "the kernel writes [b, h, s, d], wo's matmul reads the sequence minor-most"),
    },
    "jit(step)/transpose(jvp(layers))/while": {  # 320 MiB a turn, of the parent's 1,040
        "bf16[1,4096,4096]{1,2,0}": (1, "wq's slice, re-laid for the recomputed projection"),
        "bf16[1,4096,4096]{2,1,0}": (1, "... and back for the gradient's"),
        "bf16[4096,4096]{1,0}": (1, "wq for dx: prefetched into fast memory, 4,400 cycles by the compiler's estimate"),
        "bf16[4096,4096]{0,1}": (1, "wq transposed, one user"),
        "bf16[1024,4096]{1,0}": (2, "wk, wv: as wq"),
        "bf16[1024,4096]{0,1}": (2, "wk, wv: as wq"),
        "bf16[2,4096,32,128]{1,3,2,0}": (2, "the recomputed kernel's output to wo's matmul; dq back from the kernel's [b, h, s, d]"),
        "bf16[2,4096,8,128]{1,3,2,0}": (2, "dk, dv back from the kernel's layout"),
    },
}


def test_the_train_cells_layer_loops_move_what_is_listed_and_no_more(train_cell_step):
    """The layers' rematerialization runs without ``jax.checkpoint``'s CSE guard where a layer is a scan's turn
    (``models/llama.py::_remat``, PR 51) and q and k are rotated as whole heads, and the compiled step's two layer
    loops write 160 + 320 MiB a turn that nothing multiplies where the parent's wrote 160 + 1,040: every survivor
    is on the list with its reason, none of them a float32 half of a head."""
    from torchx_tpu.obs.hlo import instruction_lines, moves_by_loop

    text, m, said = train_cell_step
    assert said["remat"] == "in_loop" and said["rotation"] == "whole_heads"
    assert "rematted_computation/mlp" in text and "rematted_computation/attn" in text  # still recomputed: the file's policy
    lines = instruction_lines(text)
    by_loop = moves_by_loop(text, 8 * 2**20)
    for loop, allowed in LAYER_LOOP_MOVES.items():
        assert by_loop[loop]["turns"] == 5
        moved: dict[str, int] = {}
        for inst in by_loop[loop]["moves"]:
            array = re.sub(r":[^}]*\}", "}", lines[inst].split(" = ")[1].split(" ")[0])  # the tiling and memory space off
            moved[array] = moved.get(array, 0) + 1
        assert moved == {array: count for array, (count, _) in allowed.items()}, (loop, moved)
        assert not [array for array in moved if array.startswith("f32")]
    a_turn = {loop: sum(found["moves"].values()) / 2**20 for loop, found in by_loop.items()}
    assert a_turn["jit(step)/jvp(layers)/while"] == 160 and a_turn["jit(step)/transpose(jvp(layers))/while"] == 320
    live = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert live <= 13.30 * 2**30  # 13.26 GiB (rehearsal, PR 51): 0.54 under the parent's, nothing is kept that was recomputed


@pytest.mark.parametrize("b,s,h,kv_h,d,window,packed,form", [
    pytest.param(2, 4096, 32, 8, 128, 0, False, "fused", id="the-train-cell"),
    pytest.param(2, 4096, 32, 8, 128, 0, True, "fused", id="packed"),
    pytest.param(2, 4096, 32, 8, 128, 128, False, "fused", id="a-window-of-128"),
    pytest.param(1, 8192, 32, 8, 128, 0, True, "fused", id="8k-packed-kv-blocks-of-2048"),
    pytest.param(1, 8192, 16, 4, 256, 0, True, "fused", id="8k-packed-heads-of-256"),
    pytest.param(1, 8192, 32, 8, 64, 0, False, "fused", id="8k-heads-of-64"),
    pytest.param(1, 16384, 32, 8, 128, 0, False, "split", id="16k-two-kernels"),
])  # fmt: skip
def test_splash_backward_compiles_for_the_chip(one_chip, b, s, h, kv_h, d, window, packed, form, monkeypatch):
    """The blocks ``ops/attention.py::_backward_blocks`` picks fit the kernel's fast memory at the shapes its rule
    reaches (PR 50: a q block of 1,024 under kv blocks of 2,048 is refused at heads of 256, so the rule asks ``d``)."""
    monkeypatch.setattr(attn_ops, "TRACED", {})
    shape = lambda heads: jax.ShapeDtypeStruct((b, s, heads, d), jnp.bfloat16, sharding=one_chip)  # noqa: E731
    seg = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)

    def loss(q, k, v, seg):  # noqa: ANN001, ANN202
        return attn_ops.splash_attention(q, k, v, window=window, segment_ids=seg if packed else None).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(shape(h), shape(kv_h), shape(kv_h), seg).compile().as_text()
    assert attn_ops.traced("attention_bwd") == form
    assert ("splash_mha_dq" in text or "splash_mha_segmented_dq" in text) == (form == "split") and "dkv" in text


# The step that carries a chunk of a prompt (PR 40), at the five serving cells' widths and slots (their layers
# cut to a few: a scan's temporaries do not grow with its length), compiled for the chip: the slots' rows still
# go through the decode kernel, the pools still ride the carry, the experts' and the FFN's stacks are read where
# they lie, and what the program needs beside its arguments fits beside the cell's weights and pools.

#: a serving cell's weights and pools as its programs take them, GiB of the chip's 15.75 (rehearsal, PR 40:
#: ``scripts/rehearse_serve_cell.py``, ``args`` of the decode step and of the step carrying a chunk alike)
CELL_ARGS_GIB = {
    "dense-gqa-one-scan": 9.00,  # mistral7b-serve-chat
    "mixtral-shaped": 11.55,
    "window-and-full-unrolled": 12.60,  # k-exaone-serve-decode-long
    "latent-compressed-query-four-streams": 13.13,  # xing4-serve-decode-long, at its 128 slots below
    "latent-uncompressed-query": 11.57,  # kimi-vl-a3b-serve-backlog
}


@pytest.mark.parametrize("make,slots,kernel", [*CELL_STACKS, KIMI_STACK])
def test_the_step_that_carries_a_chunk_compiles_for_the_chip(one_chip, make, slots, kernel, monkeypatch, request):
    from torchx_tpu.obs.hlo import loop_moves

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attn_ops, "TRACED", {})
    cfg = make()
    cell = request.node.callspec.id
    slots = 128 if cfg.hc_mult else slots  # the cell's own: the projections' test above stays under one weight's size
    width, bs, bpr = 256, 16, cfg.max_seq // 16
    on_chip = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)  # noqa: E731
    shape = lambda s, d=jnp.int32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda: llama.model_fns(cfg)[0](cfg, jax.random.PRNGKey(0))))
    # the pools at the cells' block counts (half of 4,096 positions a slot), so that "a layer's pool" is the size it is there
    pools = on_chip(jax.eval_shape(lambda: gen.init_kv_pools(cfg, 1 + slots * 128, bs, 1 + slots * 10 + 2 * 256)))

    def tables(n, window_width):
        return {"full": shape((n, bpr)), "window": shape((n, window_width))} if cfg.layer_types else shape((n, bpr))

    def fn(p, tok, pos, tab, chunk, start, n, chunk_tab, pl, keys, temps):
        return gen.paged_decode_chunk_step(p, tok, pos, tab, chunk, start, n, chunk_tab, pl, cfg, keys, temps)

    compiled = jax.jit(fn, donate_argnums=(8,)).lower(
        params, shape((slots,)), shape((slots,)), tables(slots, 10), shape((width,)), shape(()), shape(()),
        tables(1, bpr), pools, shape((slots + 1, 2), jnp.uint32), shape((slots + 1,), jnp.float32),
    ).compile()  # fmt: skip
    text = compiled.as_text()
    assert attn_ops.traced("kv_pools") == "carried"
    assert "tpu_custom_call" in text and kernel in text  # the slots' rows: the decode kernel, handed the stack
    layer_bytes = min(p.size // p.shape[0] * p.dtype.itemsize for p in jax.tree.leaves(pools))
    # the stacks a step's bytes are made of: a layer's experts or its FFN (a latent layer's w_uk / w_uv, 4 MiB each,
    # are still written out in front of the chunk's expanded keys and values: ROADMAP queue 1 item 6)
    heavy = [params[g][w] for g in llama.layer_groups(params) for w in ("w_gate", "w_up", "w_down") if w in params[g]]
    stack_bytes = min(w.size // w.shape[0] * w.dtype.itemsize for w in heavy)
    assert loop_moves(text, min(layer_bytes, stack_bytes)) == []
    # what the program needs beside its arguments, at this width of rows whatever the depth: within the cell's HBM
    assert compiled.memory_analysis().temp_size_in_bytes <= (15.75 - CELL_ARGS_GIB[cell]) * 2**30



# -- two cache heads of 256 (PR 49): a position as four rows of 128, and the Gated DeltaNet step ----------


def _two_heads_of_256(lengths, h, bs, bpr, dtype, seed=0):
    """:func:`_problem` at 2 cache heads of 256: ``q [slots, h, 256]``, the pools as the heads they are ``[nb, bs, 2,
    256]`` and as they lie (``LlamaConfig.cache_row``) ``[nb, bs, 4, 128]``, the same bytes."""
    _, _, _, tables, lens = _problem(lengths, h, 2, bs, bpr, dtype, seed=seed)
    rng = np.random.default_rng(seed + 1)
    nb = int(tables.max()) + 1
    q, k, v = (jnp.asarray(rng.standard_normal(shape), dtype) for shape in ((len(lengths), h, 256), (nb, bs, 2, 256), (nb, bs, 2, 256)))
    return q, k, v, k.reshape(nb, bs, 4, 128), v.reshape(nb, bs, 4, 128), jnp.asarray(tables), jnp.asarray(lens)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_heads_of_two_rows_match_the_xla_function(dtype):
    """``qwen3-next-serve-decode-long``'s attention at its real geometry (16 query heads over 2 cache heads of 256, a
    block 64 rows of 128): each half of a query against the rows that hold that half, the pair summed, one softmax;
    against the XLA function over the pool as it lies and over the same bytes seen as the heads they are."""
    q, k, v, k_rows, v_rows, tables, lens = _two_heads_of_256([1, 300, 530, 16], 16, 16, 34, dtype)
    want = pa.paged_attention_xla(q, k, v, tables, lens)
    np.testing.assert_array_equal(pa.paged_attention_xla(q, k_rows, v_rows, tables, lens), want)
    got = pk.paged_attention_pallas(q, k_rows, v_rows, tables, lens, interpret=True)
    assert got.shape == want.shape == (4, 16, 256) and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **TOLERANCE[dtype])


@pytest.mark.parametrize("dma", ["on_wait", "eager"])
def test_kernel_bookkeeping_at_heads_of_two_rows(dma, capfd):
    """The same in the TPU interpreter, into a stack of two layers' pools: chunks, parts and groups as at four cache
    heads of 128 (the copies know rows, not heads), nothing raced for or left over."""
    q, k, v, k_rows, v_rows, tables, lens = _two_heads_of_256([0, 1, 255, 257, 520], 16, 16, 40, jnp.float32, seed=3)
    stack = lambda p: jnp.stack([jnp.full_like(p, jnp.nan), p])  # noqa: E731 - layer 0 is nobody's
    want = pa.paged_attention_xla(q, k, v, tables, lens)
    _held_in_the_tpu_interpreter(capfd, dma, want, np.asarray(lens) > 0, q, stack(k_rows), stack(v_rows), tables, lens, layer=jnp.int32(1))


def test_kernel_compiles_for_the_chip_over_two_heads_of_256(one_chip, monkeypatch):
    """``qwen3-next-serve-decode-long``: 128 slots of 16 query heads over 2 cache heads of 256, into the stack of its two
    attending layers' pools, a position 4 rows of 128 and a block 64: ``falcon-h1``'s geometry, 32 blocks a buffer,
    multiplied 16 at a time, copied in groups of 8. As the heads they are, ``[.., 16, 2, 256]``, the chip lays a position
    out as two tiles of two packed rows and re-lays the whole stack in front of the kernel (553 MB a layer)."""
    monkeypatch.setattr(attn_ops, "TRACED", {})
    _compiled_into_a_stack(one_chip, (128, 16, 256), (2, 16897, 16, 4, 128), 264)
    assert _geometry_said(16, 4, 264) == (32, 16, 8)


def test_delta_step_kernel_compiles_for_the_chip_in_place(one_chip):
    """``qwen3-next-serve-decode-long``'s recurrent state: 128 slots' rows of 32 heads x 128 x 128 float32 in a store of
    six linear layers, moved on by the delta rule where they lie: the store goes in and comes out the same buffer, and
    nothing of its size is copied in front of or behind the kernel."""
    from torchx_tpu.models import gdn
    from torchx_tpu.ops.gdn_step_kernel import gdn_step_pallas

    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    store = shape((6, 129, 32, 128, 128), jnp.float32)
    assert gdn.kernel_eligible(store.shape, 16, "tpu") and not gdn.kernel_eligible((6, 129, 4, 16, 16), 2, "tpu")
    fn = lambda st, r, d, b, q, k, v, i: gdn_step_pallas(st, r, d, b, q, k, v, layer=i)  # noqa: E731
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        store, shape((128,), jnp.int32), shape((128, 32), jnp.float32), shape((128, 32), jnp.float32),
        shape((128, 16, 128), jnp.float32), shape((128, 16, 128), jnp.float32), shape((128, 32, 128), jnp.float32), shape((), jnp.int32)
    ).compile()  # fmt: skip
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "gdn_step" in text and "tpu_custom_call" in text
    assert memory.alias_size_in_bytes >= 6 * 129 * 32 * 128 * 128 * 4 and memory.temp_size_in_bytes < 2**26
    assert not [ln for ln in text.splitlines() if " copy(" in ln and "f32[6,129," in ln]


#: sha256 of the decode kernel's jaxpr (the ``pallas_call`` with its body) at the older cells' shapes as the parent commit
#: (PR 48's tree, 9c2bd17) traced it: heads of two rows are a static branch, and a pool of whole heads takes none of it
KERNEL_AT_THE_PARENT = {
    "falcon-h1": ((64, 20, 128), (6, 8449, 16, 4, 128), 264, 0, jnp.bfloat16, "ef0edc1a61df5245"),
    "k-exaone.full": ((64, 64, 128), (2, 8449, 16, 8, 128), 264, 0, jnp.bfloat16, "bb8632eb243c218b"),
    "k-exaone.ring": ((64, 64, 128), (6, 1169, 16, 8, 128), 10, 128, jnp.bfloat16, "e7e18fea8083c46f"),
    "evabyte": ((32, 32, 128), (8, 4609, 16, 32, 128), 176, 0, jnp.bfloat16, "812528310ad44250"),
    "float32-pools": ((16, 32, 128), (1, 2049, 16, 8, 128), 256, 0, jnp.float32, "b04df7e7cff38296"),
}


@pytest.mark.parametrize("cell", sorted(KERNEL_AT_THE_PARENT))
def test_a_pool_of_whole_heads_traces_the_kernel_it_traced_at_the_parent(cell):
    import hashlib

    q, pool, bpr, window, dtype, want = KERNEL_AT_THE_PARENT[cell]
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    fn = lambda q_, k, v, t, n, i: pk.paged_attention_pallas(q_, k, v, t, n, layer=i, window=window)  # noqa: E731
    traced = jax.make_jaxpr(fn)(shape(q, dtype), shape(pool, dtype), shape(pool, dtype), shape((q[0], bpr), jnp.int32),
                                shape((q[0],), jnp.int32), shape((), jnp.int32))  # fmt: skip
    assert hashlib.sha256(str(traced).encode()).hexdigest()[:16] == want
