"""Pallas TPU kernels: bit-packed GF(2) column reduction.

The inner loop of persistent-homology reduction is "add (mod 2) column i into
column j" — on bit-packed uint32 words one VREG XOR covers 8x128x32 = 32,768
matrix entries.  Three kernels:

* ``gf2_find_low`` — per-column index of the first set bit (the paper's
  ``low``): word-granular scan + count-trailing-zeros arithmetic, fully
  vectorized on the VPU.
* ``gf2_serial_reduce`` — the *serial phase* of the paper's serial-parallel
  algorithm (§4.4) for one batch block held entirely in VMEM: walk the block
  columns in filtration order; while a column's low collides with an earlier
  column's low, XOR the earlier column in.  Grid parallelizes over blocks
  (= the paper's thread batches / our mesh shards); the data-dependent inner
  walk is a ``lax.while_loop`` inside the kernel.
* ``gf2_parallel_xor`` — the *parallel phase* counterpart: XOR a column
  block against a gathered addend block (each batch column against the
  committed pivot column owning its low) in one elementwise VREG pass,
  returning each row's new low from the same pass.  The engine no longer
  calls it: its parallel phase adds in place on the host block, since one
  device round trip costs far more than the few thousand words it XORs.
  ``gf2_find_low`` has no caller in the engine either; only tests reach
  the two.

The host-side rank-compression vocabulary lives here too: the sorted
unique ``universe`` of active cofacet keys maps key ``universe[i]`` to bit
``i``, so ascending key order equals ascending bit order and the kernels'
first-set-bit *is* the engines' ``low``.  The packed engine
(``core/packed_reduce.py``) moves between key arrays and bit blocks with
the primitive trio ``scatter_bits`` / ``scatter_xor_bits`` /
``set_bit_positions`` (plus ``find_low_np``, the word-level numpy mirror
of ``gf2_find_low``); ``pack_keys_to_bits`` / ``bits_to_keys`` are the
whole-block reference forms of the same mapping (the oracle the property
tests check the primitives and kernels against).

Block geometry: a (C=128 cols, W=2048 words) block = 1 MB of VMEM, i.e. a
65,536-row bit space per block — comfortably double-bufferable in ~16 MB
VMEM.  Column count per block stays modest because the serial walk is O(C)
deep; wide row spaces are nearly free (vector XOR).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .backend import pad_to_multiple, resolve_interpret

NO_LOW = 2**31 - 1  # python int: kernels must not capture traced constants


# ---------------------------------------------------------------------------
# Host-side bit packing (rank compression into the block bit-space)
# ---------------------------------------------------------------------------

def pack_keys_to_bits(rows: Sequence[np.ndarray], universe: np.ndarray,
                      n_words: Optional[int] = None) -> np.ndarray:
    """Pack sorted int64 key rows into a (B, W) uint32 bit block.

    ``universe`` is the sorted unique key array of the compressed bit-space;
    every key of every row must be present in it.  Key ``universe[i]`` maps
    to bit ``i`` (word ``i >> 5``, bit ``i & 31``) — ascending keys become
    ascending bit indices, so ``gf2_find_low`` on the packed block returns
    the rank of each row's minimum key.  ``n_words`` widens the block (extra
    zero words) so callers can append augmentation bits.
    """
    W = max(1, (len(universe) + 31) // 32)
    if n_words is not None:
        W = max(W, int(n_words))
    B = len(rows)
    packed = np.zeros((B, W), dtype=np.uint32)
    lens = np.array([len(r) for r in rows], dtype=np.int64)
    if lens.sum() == 0:
        return packed
    keys = np.concatenate([np.asarray(r, dtype=np.int64) for r in rows])
    ridx = np.repeat(np.arange(B, dtype=np.int64), lens)
    pos = np.searchsorted(universe, keys)
    scatter_bits(packed, ridx, pos)
    return packed


def _scatter_groups(block: np.ndarray, ridx: np.ndarray, pos: np.ndarray):
    """Shared grouping for the bit scatters: flat word indices + per-word
    bit sums.

    ``pos`` must be ascending within each row and each (row, rank) pair
    unique — then the flat word index is globally sorted, distinct bits of
    one word sum without carries, and the whole grouping is one
    ``add.reduceat`` over the nnz coordinates (no full-width buffer, unlike
    ``bincount``; no per-element loop, unlike ``ufunc.at``)."""
    W = block.shape[1]
    word = ridx * W + (pos >> 5)
    val = np.uint32(1) << (pos & 31).astype(np.uint32)
    first = np.empty(len(word), dtype=bool)
    first[0] = True
    np.not_equal(word[1:], word[:-1], out=first[1:])
    starts = np.flatnonzero(first)
    return word[starts], np.add.reduceat(val, starts)


def scatter_bits(block: np.ndarray, ridx: np.ndarray,
                 pos: np.ndarray) -> None:
    """OR bits at ``(row, bit-rank)`` coordinates into a uint32 block
    (packing into fresh/zero words; see :func:`_scatter_groups` for the
    coordinate contract)."""
    if not pos.size:
        return
    idx, sums = _scatter_groups(block, ridx, pos)
    block.reshape(-1)[idx] |= sums


def scatter_xor_bits(block: np.ndarray, ridx: np.ndarray,
                     pos: np.ndarray) -> None:
    """XOR bits at ``(row, bit-rank)`` coordinates into a uint32 block —
    the in-place GF(2) column add of the packed engine's parallel phase
    (same coordinate contract as :func:`scatter_bits`)."""
    if not pos.size:
        return
    idx, sums = _scatter_groups(block, ridx, pos)
    block.reshape(-1)[idx] ^= sums


def set_bit_positions(block: np.ndarray):
    """Set-bit coordinates of a (B, W) uint32 block, word-granular.

    Returns ``(ridx, pos, counts)`` — row index and bit rank of every set
    bit (ascending rank within each row) and the per-row set-bit counts.
    Only the non-zero *words* are expanded to bits, so sparse blocks cost
    ``O(B·W)`` word scans plus ``O(32·nnz_words)``, not ``O(32·B·W)``.
    """
    block = np.ascontiguousarray(block, dtype=np.uint32)
    B, _ = block.shape
    rw, cw = np.nonzero(block)
    words = block[rw, cw]
    bits = np.unpackbits(words.view(np.uint8).reshape(-1, 4),
                         axis=1, bitorder="little")
    m, b = np.nonzero(bits)
    ridx = rw[m]
    pos = cw[m] * 32 + b
    counts = np.bincount(ridx, minlength=B).astype(np.int64)
    return ridx, pos, counts


def bits_to_keys(block: np.ndarray, universe: np.ndarray) -> List[np.ndarray]:
    """Inverse of :func:`pack_keys_to_bits`: bit block -> sorted key rows.

    Bits at rank >= len(universe) (augmentation words) are ignored.
    """
    ridx, pos, counts = set_bit_positions(block)
    keep = pos < len(universe)
    if not keep.all():
        counts = np.bincount(ridx[keep],
                             minlength=block.shape[0]).astype(np.int64)
        pos = pos[keep]
    return np.split(universe[pos], np.cumsum(counts)[:-1])


def find_low_np(block: np.ndarray) -> np.ndarray:
    """Numpy mirror of :func:`gf2_find_low` (host fast path): first-set-bit
    rank per row of a (B, W) uint32 block; NO_LOW for all-zero rows.

    Word-granular like the kernel: first non-zero word by argmax, then the
    isolated lowest set bit's exponent via ``frexp`` (exact for powers of
    two) — no per-bit expansion of the block.
    """
    block = np.asarray(block, dtype=np.uint32)
    B, _ = block.shape
    nzw = block != 0
    any_set = nzw.any(axis=1)
    w = nzw.argmax(axis=1)
    words = block[np.arange(B), w].astype(np.int64)
    lsb = (words & -words).astype(np.float64)
    bit = np.frexp(lsb)[1] - 1
    return np.where(any_set, w * 32 + bit, NO_LOW).astype(np.int32)


def stack_wire_payloads(payloads: Sequence[np.ndarray],
                        min_words: int = 1024):
    """Stack per-shard packed uint32 wire payloads into one ``(P, L)``
    collective buffer, ``L`` bucketed to a power of two.

    The distributed engine's pivot exchange cross-ships the buffer through
    ``jax.lax.all_gather``; bucketing ``L`` keeps the jitted collective at
    a handful of retraces instead of one per superstep, and ``min_words``
    floors the bucket so early (small) rounds share one trace.  Returns
    ``(buf, lens)``; :func:`unstack_wire_payloads` crops the gather result
    back to the real payloads.
    """
    lens = [int(p.size) for p in payloads]
    L = max(int(min_words), max(lens, default=1))
    L = 1 << (L - 1).bit_length()
    buf = np.zeros((len(payloads), L), dtype=np.uint32)
    for k, p in enumerate(payloads):
        buf[k, :p.size] = p
    return buf, lens


def unstack_wire_payloads(gathered: np.ndarray,
                          lens: Sequence[int]) -> List[np.ndarray]:
    """Inverse of :func:`stack_wire_payloads` on the gathered ``(P, L)``
    buffer: every shard's payload, zero padding cropped."""
    out = np.asarray(gathered, dtype=np.uint32)
    return [out[k, :n] for k, n in enumerate(lens)]


def _bit_positions(words: jnp.ndarray) -> jnp.ndarray:
    """Per word of a uint32 array: the bit rank of its lowest set bit
    (``32 * word index + bit``, word index along the last axis), NO_LOW
    for zero words.  A min over the last axis is then the row's low.

    Elementwise plus one int32 min, which is what Mosaic lowers: no
    ``argmax``/``take_along_axis`` and no unsigned reductions."""
    lsb = words & (~words + jnp.uint32(1))  # isolate lowest set bit
    bit = jax.lax.population_count(lsb - jnp.uint32(1)).astype(jnp.int32)
    widx = jax.lax.broadcasted_iota(jnp.int32, words.shape, words.ndim - 1)
    return jnp.where(words != 0, widx * 32 + bit, jnp.int32(NO_LOW))


def _find_low_kernel(cols_ref, lows_ref):
    lows_ref[...] = jnp.min(_bit_positions(cols_ref[...]), axis=1,
                            keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def gf2_find_low(cols: jnp.ndarray, block_c: int = 128,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """First-set-bit index per bit-packed column. cols: (C, W) uint32.

    Odd column counts are zero-padded to the block multiple and sliced back
    (a padded all-zero column reads as NO_LOW and is dropped anyway).
    ``interpret=None`` resolves per backend (compiled on TPU only).
    """
    interpret = resolve_interpret(interpret)
    c, w = cols.shape
    cols = pad_to_multiple(cols, block_c, axis=0)
    cp = cols.shape[0]
    lows = pl.pallas_call(
        _find_low_kernel,
        grid=(cp // block_c,),
        in_specs=[pl.BlockSpec((block_c, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, 1), jnp.int32),
        interpret=interpret,
        name="gf2_find_low",
    )(cols)
    return lows[:c, 0]


def _serial_reduce_kernel(in_ref, out_ref, lows_ref, reds_ref):
    """One block: in-order column reduction with collision XOR (paper serial
    phase).  The block lives in the output ref and rows are read and
    written by dynamic row index (``pl.ds``); only the column being reduced
    and the ``(1, C)`` lows vector ride the loop carries."""
    C = in_ref.shape[1]
    out_ref[...] = in_ref[...]
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)

    def row_low(col):
        return jnp.min(_bit_positions(col))

    def reduce_one(c, state):
        lows, n_red = state
        earlier = col_ids < c

        def owner(low):
            return (lows == low) & earlier & (low != jnp.int32(NO_LOW))

        def cond(st):
            _, low, _ = st
            return jnp.any(owner(low))

        def body(st):
            col, low, n = st
            j = jnp.min(jnp.where(owner(low), col_ids, C))
            col = col ^ out_ref[0, pl.ds(j, 1), :]
            return col, row_low(col), n + 1

        col0 = out_ref[0, pl.ds(c, 1), :]
        col, low, n_red = jax.lax.while_loop(
            cond, body, (col0, row_low(col0), n_red))
        out_ref[0, pl.ds(c, 1), :] = col
        return jnp.where(col_ids == c, low, lows), n_red

    lows, n_red = jax.lax.fori_loop(
        0, C, reduce_one,
        (jnp.full((1, C), NO_LOW, dtype=jnp.int32), jnp.int32(0)))
    lows_ref[0] = lows
    reds_ref[0] = jnp.full((1, 1), n_red, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gf2_serial_reduce(blocks: jnp.ndarray, interpret: Optional[bool] = None):
    """Intra-block serial reduction per grid step.

    blocks: (G, C, W) uint32 bit-packed columns, filtration order along C.
    Returns (reduced (G, C, W), lows (G, C) int32, n_reductions (G,) int32).
    After the call every block's non-empty columns have pairwise-distinct
    lows — the invariant the paper's clearance step commits.  Lows and
    counts leave the kernel as ``(1, C)`` / ``(1, 1)`` blocks of rank-3
    arrays, so every block spans its array's last two dims for any G.
    """
    interpret = resolve_interpret(interpret)
    g, c, w = blocks.shape
    red, lows, reds = pl.pallas_call(
        _serial_reduce_kernel,
        grid=(g,),
        in_specs=[pl.BlockSpec((1, c, w), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, c, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, c, w), jnp.uint32),
            jax.ShapeDtypeStruct((g, 1, c), jnp.int32),
            jax.ShapeDtypeStruct((g, 1, 1), jnp.int32),
        ],
        interpret=interpret,
        name="gf2_serial_reduce",
    )(blocks)
    return red, lows[:, 0, :], reds[:, 0, 0]


def _parallel_xor_kernel(cols_ref, addends_ref, out_ref, lows_ref):
    xored = cols_ref[...] ^ addends_ref[...]
    out_ref[...] = xored
    lows_ref[...] = jnp.min(_bit_positions(xored), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def gf2_parallel_xor(cols: jnp.ndarray, addends: jnp.ndarray,
                     block_c: int = 128,
                     interpret: Optional[bool] = None):
    """Parallel-phase GF(2) add: XOR a column block against a gathered
    addend block.  cols, addends: (C, W) uint32; returns ``(xored, lows)``
    — the (C, W) uint32 sum and its (C,) int32 first-set-bit rank per row
    (NO_LOW for all-zero rows), the low found in the same pass so the
    caller needs no find-low round trip after the add.

    The addend block is the host-side gather of committed pivot columns
    (one per batch column, zero rows where a column has no hit) packed into
    the same bit-space as ``cols`` — one VREG XOR covers 32,768 matrix
    entries.  Odd column counts self-pad to the block multiple.
    """
    interpret = resolve_interpret(interpret)
    c, w = cols.shape
    cols = pad_to_multiple(cols, block_c, axis=0)
    addends = pad_to_multiple(addends, block_c, axis=0)
    cp = cols.shape[0]
    out, lows = pl.pallas_call(
        _parallel_xor_kernel,
        grid=(cp // block_c,),
        in_specs=[pl.BlockSpec((block_c, w), lambda i: (i, 0)),
                  pl.BlockSpec((block_c, w), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_c, w), lambda i: (i, 0)),
                   pl.BlockSpec((block_c, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((cp, w), jnp.uint32),
                   jax.ShapeDtypeStruct((cp, 1), jnp.int32)],
        interpret=interpret,
        name="gf2_parallel_xor",
    )(cols, addends)
    return out[:c], lows[:c, 0]
