"""Pallas TPU encoder kernel for APack streams.

Mirror of ``apack_decode``: one grid program arithmetically encodes a block
of ``BLOCK_STREAMS`` substreams lane-parallel (paper §V "each encoder can
encode one value per cycle" -> one value per lane per loop step).  The
64-bit software bit-buffer (two u32 vectors + length) plays the role of the
paper's CODE_out/OUT_u port pair: each renormalization iteration appends the
emitted bit plus any pending underflow bits, and full words retire into the
word-interleaved output plane.

The kernel always produces the AC encoding plus per-stream bit counts and
overflow flags; stored-mode selection (AC-inflated or overflowed streams
fall back to verbatim packing) happens in ``ops.apack_encode`` exactly as in
the jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.ac_golden import MAX_PENDING, PCOUNT_BITS, QUARTER, TOP
from .apack_decode import resolve_interpret
from .ref import (encode_renorm, ofs_capacity_words, shl32, shr32,
                  sym_capacity_words)

I32 = jnp.int32
U32 = jnp.uint32
BLOCK_STREAMS = 128


def _append(buf_lo, buf_hi, buflen, val, k):
    buf_lo = buf_lo | shl32(val, buflen)
    buf_hi = buf_hi | shr32(val, 32 - buflen)
    return buf_lo, buf_hi, buflen + k


def _flush(plane, widx, buf_lo, buf_hi, buflen):
    """Retire one full word per stream where buflen >= 32 (functional)."""
    do = buflen >= 32
    w = jnp.clip(widx, 0, plane.shape[0] - 1)
    cur = jnp.take_along_axis(plane, w[None, :], axis=0)[0]
    new = jnp.where(do, buf_lo, cur)
    plane = plane.at[w, jnp.arange(plane.shape[1])].set(new)
    buf_lo = jnp.where(do, buf_hi, buf_lo)
    buf_hi = jnp.where(do, U32(0), buf_hi)
    buflen = jnp.where(do, buflen - 32, buflen)
    return plane, widx + do.astype(I32), buf_lo, buf_hi, buflen


def _encode_kernel(values_ref, vmin_ref, ol_ref, cum_ref,
                   sym_ref, ofs_ref, sym_bits_ref, ofs_bits_ref, ovf_ref,
                   *, n_steps: int, bits: int):
    values = values_ref[...]                  # [NS, E] i32
    v_min = vmin_ref[...]
    ol = ol_ref[...]
    cum = cum_ref[...]
    ns = values.shape[0]
    ws = sym_ref.shape[0]
    wo = ofs_ref.shape[0]
    zeros = jnp.zeros((ns,), I32)
    zerosu = jnp.zeros((ns,), U32)

    # hoisted symbol search + table gathers, vectorized over the whole
    # [NS, E] block (16 unrolled compares stand in for the HW comparator
    # array); the serial loop below only touches AC state and bit buffers.
    s_idx = -jnp.ones(values.shape, I32)
    for i in range(16):
        s_idx = s_idx + (values >= v_min[i]).astype(I32)
    ol_all = jnp.take(ol, s_idx)                         # [NS, E]
    off_all = (values - jnp.take(v_min, s_idx)).astype(U32)
    clo_all = jnp.take(cum, s_idx)
    chi_all = jnp.take(cum, s_idx + 1)

    def step(i, carry):
        (low, high, pending, overflow,
         s_plane, s_widx, s_lo, s_hi, s_len, s_bits,
         o_plane, o_widx, o_lo, o_hi, o_len, o_bits) = carry
        ol_s = jax.lax.dynamic_slice(ol_all, (0, i), (ns, 1))[:, 0]
        off = jax.lax.dynamic_slice(off_all, (0, i), (ns, 1))[:, 0]
        clo = jax.lax.dynamic_slice(clo_all, (0, i), (ns, 1))[:, 0]
        chi = jax.lax.dynamic_slice(chi_all, (0, i), (ns, 1))[:, 0]
        o_lo, o_hi, o_len = _append(o_lo, o_hi, o_len, off, ol_s)
        o_bits = o_bits + ol_s
        o_plane, o_widx, o_lo, o_hi, o_len = _flush(o_plane, o_widx,
                                                    o_lo, o_hi, o_len)
        rng = high - low + 1
        high2 = low + ((rng * chi) >> PCOUNT_BITS) - 1
        low2 = low + ((rng * clo) >> PCOUNT_BITS)

        # multi-bit renormalization: all matched leading bits + pending
        # underflow bits emitted in two appends (see ref.encode_renorm)
        low, high, pending, pat1, k1, pat2, k2 = encode_renorm(
            low2, high2, pending)
        s_lo, s_hi, s_len = _append(s_lo, s_hi, s_len, pat1, k1)
        s_bits = s_bits + k1
        s_plane, s_widx, s_lo, s_hi, s_len = _flush(s_plane, s_widx,
                                                    s_lo, s_hi, s_len)
        s_lo, s_hi, s_len = _append(s_lo, s_hi, s_len, pat2, k2)
        s_bits = s_bits + k2
        s_plane, s_widx, s_lo, s_hi, s_len = _flush(s_plane, s_widx,
                                                    s_lo, s_hi, s_len)
        overflow = overflow | (pending > MAX_PENDING)
        return (low, high, pending, overflow,
                s_plane, s_widx, s_lo, s_hi, s_len, s_bits,
                o_plane, o_widx, o_lo, o_hi, o_len, o_bits)

    init = (zeros, jnp.full((ns,), TOP, I32), zeros, jnp.zeros((ns,), bool),
            jnp.zeros((ws, ns), U32), zeros, zerosu, zerosu, zeros, zeros,
            jnp.zeros((wo, ns), U32), zeros, zerosu, zerosu, zeros, zeros)
    (low, high, pending, overflow,
     s_plane, s_widx, s_lo, s_hi, s_len, s_bits,
     o_plane, o_widx, o_lo, o_hi, o_len, o_bits) = jax.lax.fori_loop(
        0, n_steps, step, init)

    # termination
    pending = pending + 1
    b = (low >= QUARTER).astype(U32)
    inv_run = (shl32(jnp.ones_like(b), pending) - U32(1)) * (U32(1) - b)
    pattern = b | (inv_run << 1)
    s_lo, s_hi, s_len = _append(s_lo, s_hi, s_len, pattern, 1 + pending)
    s_bits = s_bits + 1 + pending
    for _ in range(3):
        s_plane, s_widx, s_lo, s_hi, s_len = _flush(s_plane, s_widx,
                                                    s_lo, s_hi, s_len)

    def drain(plane, widx, blo, blen):
        do = blen > 0
        w = jnp.clip(widx, 0, plane.shape[0] - 1)
        cur = jnp.take_along_axis(plane, w[None, :], axis=0)[0]
        return plane.at[w, jnp.arange(ns)].set(jnp.where(do, blo, cur))

    sym_ref[...] = drain(s_plane, s_widx, s_lo, s_len)
    ofs_ref[...] = drain(o_plane, o_widx, o_lo, o_len)
    sym_bits_ref[...] = s_bits
    ofs_bits_ref[...] = o_bits
    ovf_ref[...] = overflow.astype(I32)


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "bits", "block_streams",
                                    "interpret"))
def encode_pallas(values: jax.Array, v_min: jax.Array, ol: jax.Array,
                  cum: jax.Array, *, n_steps: int, bits: int = 8,
                  block_streams: int = BLOCK_STREAMS,
                  interpret: bool | None = None):
    """AC-encode S streams of values i32[S, E].  S % block_streams == 0.

    Returns (sym_plane u32[Ws,S], ofs_plane u32[Wo,S], sym_bits, ofs_bits,
    overflow) — identical contract to ``ref.encode_ac``.  ``interpret=None``
    takes the platform's choice (``ops._default_backend``)."""
    s, e = values.shape
    assert e == n_steps and s % block_streams == 0
    ws = sym_capacity_words(n_steps)
    wo = ofs_capacity_words(n_steps, bits)
    grid = (s // block_streams,)
    kernel = functools.partial(_encode_kernel, n_steps=n_steps, bits=bits)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_streams, n_steps), lambda j: (j, 0)),
            pl.BlockSpec((17,), lambda j: (0,)),
            pl.BlockSpec((16,), lambda j: (0,)),
            pl.BlockSpec((17,), lambda j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((ws, block_streams), lambda j: (0, j)),
            pl.BlockSpec((wo, block_streams), lambda j: (0, j)),
            pl.BlockSpec((block_streams,), lambda j: (j,)),
            pl.BlockSpec((block_streams,), lambda j: (j,)),
            pl.BlockSpec((block_streams,), lambda j: (j,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ws, s), U32),
            jax.ShapeDtypeStruct((wo, s), U32),
            jax.ShapeDtypeStruct((s,), I32),
            jax.ShapeDtypeStruct((s,), I32),
            jax.ShapeDtypeStruct((s,), I32),
        ],
        interpret=resolve_interpret(interpret),
    )(values.astype(I32), v_min.astype(I32), ol.astype(I32), cum.astype(I32))
    return outs
