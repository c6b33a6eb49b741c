"""Pallas TPU decoder kernel for APack streams.

TPU mapping of the paper's decoder array (§V-A): one *grid program* decodes a
block of ``BLOCK_STREAMS`` substreams, one stream per vector lane, stepping
``fori_loop`` over symbols — the lane dimension plays the role of the paper's
replicated decoder engines, the loop plays the per-cycle step.  BlockSpecs
tile the word-interleaved planes so each program's working set (compressed
words in + decoded block out) sits in VMEM; on real hardware the HBM->VMEM
DMA moves only compressed words, which is exactly where the paper's off-chip
traffic saving materializes.

Per-step state (HI/LO/CODE registers, bit cursors) is a handful of
``[1, BLOCK_STREAMS]`` i32 rows, one vreg each — the Pallas analogue of
the paper's "3 16b and 1 8b registers" per engine.

Word fetch.  Each lane reads its own stream at its own bit cursor, so the
fetch is a per-lane gather along the word (sublane) axis.  Mosaic gathers
only within one vreg (8 sublanes), so the decoder keeps, per plane, a
16-word lane window: the two 8-word groups ``[8g, 8g + 16)`` holding the
cursor.  Cursors only move forward, by at most 16 bits per step, so a
window loaded at the start of a group of ``WINDOW_STEPS`` steps covers
every read of that group (at most 7 + 4 + 1 words past ``8g``).  Loading a
window is a compare-select over the plane's 8-word groups; the reads
inside it are two one-vreg gathers and a select.  The 16/17-entry table
lookups are select chains over SMEM scalars, in cumulative-count order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ac_golden import PCOUNT_BITS, TOP
from .ref import WORD_GROUP, decode_renorm, rev16, shl32, shr32

I32 = jnp.int32
U32 = jnp.uint32
BLOCK_STREAMS = 128
# steps decoded per window load: a 16-word window covers 8 steps of <= 16
# bits each from any start word inside its first group (see module doc)
WINDOW_STEPS = 8


def _load_window(plane, pos):
    """Words ``[8g, 8g + 16)`` of every lane's stream, g = pos // 256.

    ``plane`` is a ``[W, NS]`` ref with ``W % 8 == 0``; words past its end
    read as zero (the decoder over-reads its CODE window near stream end).
    Returns ``(lo, hi, base)``: the two 8-word groups and ``8g``."""
    g = pos >> 8
    lo = hi = jnp.zeros((WORD_GROUP, plane.shape[1]), U32)
    for k in range(plane.shape[0] // WORD_GROUP):
        blk = plane[k * WORD_GROUP:(k + 1) * WORD_GROUP, :].astype(U32)
        lo = jnp.where(g == k, blk, lo)
        hi = jnp.where(g == k - 1, blk, hi)
    return lo, hi, g * WORD_GROUP


def _window_bits(window, pos):
    """The 32 stream bits starting at bit ``pos`` (LSB-first), per lane."""
    lo, hi, base = window

    def lane_word(w):                  # gathers need full-vreg indices
        i = jnp.broadcast_to(w & (WORD_GROUP - 1), lo.shape)
        return jnp.where(w < WORD_GROUP,
                         jnp.take_along_axis(lo, i, axis=0)[:1],
                         jnp.take_along_axis(hi, i, axis=0)[:1])

    w = (pos >> 5) - base
    off = pos & 31
    return shr32(lane_word(w), off) | shl32(lane_word(w + 1), 32 - off)


def _mask(k):
    return shl32(jnp.ones(k.shape, U32), k) - U32(1)


def table_scalars(vm_ref, ol_ref, cum_ref, *lead):
    """The 17 / 16 / 17 table entries as scalars, read from SMEM refs
    whose last axis indexes the entry (``lead`` indexes the others)."""
    return tuple([ref[(*lead, t)] for t in range(n)]
                 for ref, n in ((vm_ref, 17), (ol_ref, 16), (cum_ref, 17)))


def decode_block(sym, ofs, stored, tables, out_ref, *,
                 n_steps: int, bits: int, post=None):
    """Decode a block of NS streams into ``out_ref[n_steps, NS]``.

    Args:
      sym, ofs: ``[W, NS]`` u32 plane refs (``W % 8 == 0``).
      stored:   ``[1, NS]`` bool verbatim-mode flags.
      tables:   ``(v_min, ol, cum)`` entry scalars (``table_scalars``).
      out_ref:  ``[n_steps, NS]`` ref; row ``i`` receives value ``i`` of
                every stream, passed through ``post`` (i32[8, NS] -> the
                ref's dtype) one ``WINDOW_STEPS``-row group at a time.

    Body shared by the standalone decoder kernel, the fused
    decompress+matmul kernel and both paged-KV kernels."""
    ns = sym.shape[1]
    assert sym.shape[0] % WORD_GROUP == 0 and ofs.shape[0] % WORD_GROUP == 0, \
        "plane capacities must be whole 8-word groups (see pad_words)"
    shape = (1, ns)
    post = post or (lambda v: v)
    stored = jnp.broadcast_to(stored, shape)
    vm_t, ol_t, cum_t = tables
    bits_mask = _mask(jnp.full(shape, bits, I32))
    row = jax.lax.broadcasted_iota(I32, (WINDOW_STEPS, ns), 0)

    def symbol_step(carry, sym_win, ofs_win):
        low, high, code, spos, opos = carry
        rng = high - low + 1
        cum_val = ((code - low + 1) * (1 << PCOUNT_BITS) - 1) // rng
        # symbol = largest s with cum[s] <= cum_val (the HW comparator
        # array); cum is non-decreasing, so a select chain in s order
        # ends on it
        vm_s = jnp.full(shape, vm_t[0])
        ol_s = jnp.full(shape, ol_t[0])
        clo = jnp.full(shape, cum_t[0])
        chi = jnp.full(shape, cum_t[1])
        for t in range(1, 16):
            hit = cum_val >= cum_t[t]
            vm_s = jnp.where(hit, vm_t[t], vm_s)
            ol_s = jnp.where(hit, ol_t[t], ol_s)
            clo = jnp.where(hit, cum_t[t], clo)
            chi = jnp.where(hit, cum_t[t + 1], chi)
        word = _window_bits(ofs_win, opos)
        value = jnp.where(stored, (word & bits_mask).astype(I32),
                          vm_s + (word & _mask(ol_s)).astype(I32))
        opos = opos + jnp.where(stored, bits, ol_s)
        high2 = low + ((rng * chi) >> PCOUNT_BITS) - 1
        low2 = low + ((rng * clo) >> PCOUNT_BITS)
        low, high, code, spos = decode_renorm(
            low, high, code, spos, low2, high2,
            lambda pos, k: _window_bits(sym_win, pos) & _mask(k), stored)
        return (low, high, code, spos, opos), value

    def window_group(carry, n):
        """Decode ``n`` (<= WINDOW_STEPS) steps from one window pair."""
        sym_win = _load_window(sym, carry[3])
        ofs_win = _load_window(ofs, carry[4])

        def window_step(r, state):
            carry, vals = state
            carry, value = symbol_step(carry, sym_win, ofs_win)
            return carry, jnp.where(row == r, value, vals)

        return jax.lax.fori_loop(0, n, window_step,
                                 (carry, jnp.zeros(row.shape, I32)))

    zeros = jnp.zeros(shape, I32)
    # initial CODE register: one 16-bit read, bit-reversed to MSB-first
    first = _window_bits(_load_window(sym, zeros), zeros)
    code0 = rev16(first & U32(0xFFFF)).astype(I32)
    init = (zeros, jnp.full(shape, TOP, I32), code0,
            jnp.full(shape, 16, I32), zeros)

    n_groups, tail = divmod(n_steps, WINDOW_STEPS)

    def full_group(gi, carry):
        carry, vals = window_group(carry, WINDOW_STEPS)
        base = pl.multiple_of(gi * WINDOW_STEPS, WINDOW_STEPS)
        out_ref[pl.ds(base, WINDOW_STEPS), :] = post(vals)
        return carry

    carry = jax.lax.fori_loop(0, n_groups, full_group, init)
    if tail:
        _, vals = window_group(carry, tail)
        out_ref[n_groups * WINDOW_STEPS:, :] = post(vals)[:tail]


def pad_words(plane: jax.Array) -> jax.Array:
    """Zero-pad a trimmed ``[W, S]`` plane (``fastpath`` containers keep
    only the words used) to whole 8-word groups; fixed-capacity planes
    already are."""
    return jnp.pad(plane, ((0, -plane.shape[0] % WORD_GROUP), (0, 0)))


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> the platform's choice (``ops._default_backend``)."""
    if interpret is None:
        from .ops import _default_backend
        interpret = _default_backend() == "pallas_interpret"
    return interpret


SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _decode_kernel(sym_ref, ofs_ref, stored_ref, vmin_ref, ol_ref, cum_ref,
                   out_ref, *, n_steps: int, bits: int):
    decode_block(sym_ref, ofs_ref, stored_ref[...] != 0,
                 table_scalars(vmin_ref, ol_ref, cum_ref), out_ref,
                 n_steps=n_steps, bits=bits)


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "bits", "block_streams",
                                    "interpret"))
def decode_pallas(sym_plane: jax.Array, ofs_plane: jax.Array,
                  stored: jax.Array, v_min: jax.Array, ol: jax.Array,
                  cum: jax.Array, *, n_steps: int, bits: int = 8,
                  block_streams: int = BLOCK_STREAMS,
                  interpret: bool | None = None) -> jax.Array:
    """Decode S streams (S must be a multiple of ``block_streams``;
    ``ops.apack_decode`` handles padding).  Returns i32[S, n_steps]."""
    sym_plane, ofs_plane = pad_words(sym_plane), pad_words(ofs_plane)
    ws, s = sym_plane.shape
    wo = ofs_plane.shape[0]
    assert s % block_streams == 0, "pad streams before calling the kernel"
    grid = (s // block_streams,)
    kernel = functools.partial(_decode_kernel, n_steps=n_steps, bits=bits)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ws, block_streams), lambda j: (0, j)),
            pl.BlockSpec((wo, block_streams), lambda j: (0, j)),
            pl.BlockSpec((1, block_streams), lambda j: (0, j)),
            SMEM_SPEC, SMEM_SPEC, SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((n_steps, block_streams), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n_steps, s), I32),
        interpret=resolve_interpret(interpret),
    )(sym_plane.astype(U32), ofs_plane.astype(U32),
      stored.astype(I32).reshape(1, s), v_min.astype(I32), ol.astype(I32),
      cum.astype(I32))
    return out.T
