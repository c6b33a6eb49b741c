"""Vectorized pure-jnp multi-stream APack codec — the kernel oracle.

This is the paper's §V-B replication strategy in TPU-native form: instead of
64 discrete encoder/decoder engines, S independent substreams are coded in
lockstep, one stream per vector lane, with ``lax.scan`` playing the role of
the hardware's per-cycle step.  The arithmetic is the *identical*
finite-precision coder as ``core/ac_golden.py`` (16-bit HI/LO windows,
10-bit counts, WNC renormalization) and is asserted bit-exact against it.

The Pallas kernels in ``apack_decode.py`` / ``apack_encode.py`` mirror this
file's arithmetic operation-for-operation (the decoder kernel has its own
word reader, see ``apack_decode``); this module doubles as the production
software path on CPU.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ac_golden import (HALF, MAX_PENDING, MAX_RENORM, PCOUNT_BITS,
                                  QUARTER, TOP)
from repro.core.tables import ApackTable

U32 = jnp.uint32
I32 = jnp.int32


class TableArrays(NamedTuple):
    """jnp view of an ApackTable (17/16/17-entry vectors)."""
    v_min: jax.Array   # i32[17]
    ol: jax.Array      # i32[16]
    cum: jax.Array     # i32[17]

    @classmethod
    def from_table(cls, t: ApackTable) -> "TableArrays":
        return cls(jnp.asarray(t.v_min, I32), jnp.asarray(t.ol, I32),
                   jnp.asarray(t.cum, I32))


# --------------------------------------------------------------- bit helpers
def shr32(x: jax.Array, k: jax.Array) -> jax.Array:
    """Logical right shift, correct for k in [0, 32]."""
    kc = jnp.minimum(k, 31).astype(U32)
    return jnp.where(k >= 32, U32(0), (x.astype(U32) >> kc))


def shl32(x: jax.Array, k: jax.Array) -> jax.Array:
    """Left shift, correct for k in [0, 32]."""
    kc = jnp.minimum(k, 31).astype(U32)
    return jnp.where(k >= 32, U32(0), (x.astype(U32) << kc))


def bitlen16(x: jax.Array) -> jax.Array:
    """Bit length of x in [0, 0xFFFF] (0 -> 0), branch-free binary search."""
    x = x.astype(I32)
    b = jnp.zeros_like(x)
    for s in (8, 4, 2, 1):
        big = x >= (1 << s)
        b = b + jnp.where(big, s, 0)
        x = jnp.where(big, x >> s, x)
    return b + (x > 0).astype(I32)


def rev16(w: jax.Array) -> jax.Array:
    """Reverse the low 16 bits (bit 0 <-> bit 15), u32 in/out."""
    w = w.astype(U32)
    w = ((w & U32(0x5555)) << 1) | ((w >> 1) & U32(0x5555))
    w = ((w & U32(0x3333)) << 2) | ((w >> 2) & U32(0x3333))
    w = ((w & U32(0x0F0F)) << 4) | ((w >> 4) & U32(0x0F0F))
    w = ((w & U32(0x00FF)) << 8) | ((w >> 8) & U32(0x00FF))
    return w & U32(0xFFFF)


def renorm_counts(low: jax.Array, high: jax.Array):
    """O(1) replacement for the per-bit WNC renormalization loop.

    After a range update the loop is provably a run of ``m`` emit-shifts
    (the matched leading bits of low/high) followed by a run of ``u``
    underflow-shifts (the straddle positions ``low=..01x``/``high=..10x``
    directly below the matched prefix), then it stops: an underflow shift
    clears bit15 of low and sets bit15 of high, so an emit can never follow
    an underflow within one symbol.  Returns ``(m, u, low', high')`` where
    ``low'``/``high'`` are the fully renormalized interval bounds.
    """
    m = 16 - bitlen16(low ^ high)
    low_m = (shl32(low.astype(U32), m) & U32(0xFFFF)).astype(I32)
    high_m = ((shl32(high.astype(U32), m)
               | (shl32(jnp.ones_like(low, U32), m) - U32(1)))
              & U32(0xFFFF)).astype(I32)
    # straddle run: consecutive positions below the MSB where low has 1 and
    # high has 0; count-leading-ones of (low & ~high) << 1
    t = (low_m & ~high_m) & 0xFFFF
    u = 16 - bitlen16(~(t << 1) & 0xFFFF)
    ufill = (shl32(jnp.ones_like(low, U32), u) - U32(1)).astype(I32)
    low_f = (shl32(low_m.astype(U32), u) & U32(0x7FFF)).astype(I32)
    high_f = ((shl32(high_m.astype(U32), u) & U32(0x7FFF)).astype(I32)
              | HALF | ufill)
    return m, u, low_f, high_f


def decode_renorm(low, high, code, spos, low2, high2, read, stored):
    """Decoder side of the multi-bit renormalization: renormalize the
    post-update interval ``low2``/``high2``, consume all m+u stream bits in
    one read, and update the CODE register in closed form.  Shared by
    ``decode`` and the Pallas ``decode_block``, which differ only in how
    they fetch stream words: ``read(pos, k)`` returns the ``k`` symbol-plane
    bits at bit position ``pos`` of each stream.

    ``low``/``high``/``code``/``spos`` are the pre-update values, returned
    unchanged for stored lanes.  Valid streams need at most 16 bits per
    step (m + u <= MAX_RENORM); the clamp guards the garbage padding lanes
    whose output is discarded.
    """
    m, u, low3, high3 = renorm_counts(low2, high2)
    k = jnp.minimum(m + u, 16)
    u = jnp.minimum(u, k - jnp.minimum(m, k))
    w = read(spos, k)
    r = shr32(rev16(w), 16 - k).astype(I32)           # first-read bit = MSB
    r_m = shr32(r.astype(U32), u).astype(I32)
    ufill = (shl32(jnp.ones_like(u, U32), u) - U32(1)).astype(I32)
    code_m = (shl32(code.astype(U32), m) & U32(0xFFFF)).astype(I32) | r_m
    code3 = (shl32(code_m.astype(U32), u).astype(I32)
             - HALF * ufill + (r & ufill))
    # stored streams keep AC state frozen
    low3 = jnp.where(stored, low, low3)
    high3 = jnp.where(stored, high, high3)
    code3 = jnp.where(stored, code, code3)
    spos3 = spos + jnp.where(stored, 0, k)
    return low3, high3, code3, spos3


def encode_renorm(low2, high2, pending):
    """Encoder side of the multi-bit renormalization: renormalize the
    post-update interval and express the emitted bits as two append
    patterns.  Shared by ``encode_ac`` and the Pallas encoder kernel.

    Returns ``(low, high, pending', pat1, k1, pat2, k2)``: append ``pat1``
    (``k1`` bits — the first matched bit followed by the pending inverse
    run, LSB-first emission order) then ``pat2`` (``k2`` bits — the
    remaining matched leading bits of ``low2``).  ``k1``/``k2`` are zero
    when nothing is emitted; the caller flags overflow when ``pending'``
    exceeds ``MAX_PENDING``.
    """
    m, u, low, high = renorm_counts(low2, high2)
    has = m > 0
    ones = jnp.ones_like(low2).astype(U32)
    prefix = rev16(low2.astype(U32)) & (shl32(ones, m) - U32(1))
    b1 = prefix & U32(1)
    inv_run = (shl32(ones, pending) - U32(1)) * (U32(1) - b1)
    k1 = jnp.where(has, 1 + pending, 0)
    pat1 = jnp.where(has, b1 | (inv_run << 1), U32(0))
    k2 = jnp.where(has, m - 1, 0)
    pending = jnp.where(has, u, pending + u)
    return low, high, pending, pat1, k1, prefix >> 1, k2


def gather_word(plane: jax.Array, w: jax.Array) -> jax.Array:
    """plane[w[s], s] for each stream s.  plane: u32[W, S], w: i32[S]."""
    wc = jnp.clip(w, 0, plane.shape[0] - 1)
    return jnp.take_along_axis(plane, wc[None, :], axis=0)[0]


def read_bits(plane: jax.Array, pos: jax.Array, k: jax.Array) -> jax.Array:
    """Read k (<=16) bits LSB-first at bit position pos, per stream.

    Reads past the padded plane return zero bits (the decoder legitimately
    over-reads its CODE window by up to 16 bits near stream end)."""
    w = pos >> 5
    off = (pos & 31).astype(U32)
    r0 = gather_word(plane, w)
    r1 = gather_word(plane, w + 1)
    in0 = w < plane.shape[0]
    in1 = (w + 1) < plane.shape[0]
    r0 = jnp.where(in0, r0, U32(0))
    r1 = jnp.where(in1, r1, U32(0))
    window = shr32(r0, off) | shl32(r1, 32 - off.astype(I32))
    mask = shl32(jnp.ones_like(window), k) - U32(1)
    return window & mask


# ------------------------------------------------------------------- decode
@partial(jax.jit, static_argnames=("n_steps", "bits"))
def decode(sym_plane: jax.Array, ofs_plane: jax.Array, stored: jax.Array,
           table: TableArrays, n_steps: int, bits: int = 8) -> jax.Array:
    """Decode S streams of ``n_steps`` values each.

    Args:
      sym_plane: u32[W_s, S] word-interleaved symbol bitstreams.
      ofs_plane: u32[W_o, S] word-interleaved offset bitstreams.
      stored:    bool[S] verbatim-mode flags.
      table:     TableArrays.
      n_steps:   values per stream (E).
      bits:      value bit width.

    Returns: i32[S, n_steps] decoded values.
    """
    S = sym_plane.shape[1]
    sym_plane = sym_plane.astype(U32)
    ofs_plane = ofs_plane.astype(U32)
    cum = table.cum
    v_min = table.v_min
    ol = table.ol

    # initial CODE register: one 16-bit read; stream order = MSB of CODE first
    zeros = jnp.zeros((S,), I32)
    code0 = rev16(read_bits(sym_plane, zeros,
                            jnp.full((S,), 16, I32))).astype(I32)
    spos0 = jnp.full((S,), 16, I32)

    def step(carry, _):
        low, high, code, spos, opos = carry
        rng = high - low + 1
        cum_val = ((code - low + 1) * (1 << PCOUNT_BITS) - 1) // rng
        # largest s with cum[s] <= cum_val  (the HW comparator array)
        s_idx = jnp.sum((cum_val[:, None] >= cum[None, :-1]).astype(I32),
                        axis=1) - 1
        ol_s = jnp.take(ol, s_idx)
        clo = jnp.take(cum, s_idx)
        chi = jnp.take(cum, s_idx + 1)
        off_val = read_bits(ofs_plane, opos, ol_s).astype(I32)
        value_ac = jnp.take(v_min, s_idx) + off_val
        # stored-mode bypass
        value_st = read_bits(ofs_plane, opos, jnp.full_like(opos, bits)).astype(I32)
        value = jnp.where(stored, value_st, value_ac)
        opos = opos + jnp.where(stored, bits, ol_s)
        high2 = low + ((rng * chi) >> PCOUNT_BITS) - 1
        low2 = low + ((rng * clo) >> PCOUNT_BITS)
        low3, high3, code3, spos3 = decode_renorm(
            low, high, code, spos, low2, high2,
            partial(read_bits, sym_plane), stored)
        return (low3, high3, code3, spos3, opos), value

    init = (zeros, jnp.full((S,), TOP, I32), code0, spos0, zeros)
    _, values = jax.lax.scan(step, init, None, length=n_steps)
    return values.T   # [S, n_steps]


# ------------------------------------------------------------------- encode
def _append(buf_lo, buf_hi, buflen, val, k):
    """Append k (<=25) bits of val into the 64-bit stream buffer."""
    buf_lo = buf_lo | shl32(val, buflen)
    buf_hi = buf_hi | shr32(val, 32 - buflen)
    return buf_lo, buf_hi, buflen + k


def _flush(plane, widx, sidx, buf_lo, buf_hi, buflen):
    """Write one full word where buflen >= 32."""
    do = buflen >= 32
    cur = gather_word(plane, widx)
    new = jnp.where(do, buf_lo, cur)
    plane = plane.at[jnp.clip(widx, 0, plane.shape[0] - 1), sidx].set(new)
    buf_lo = jnp.where(do, buf_hi, buf_lo)
    buf_hi = jnp.where(do, U32(0), buf_hi)
    buflen = jnp.where(do, buflen - 32, buflen)
    widx = widx + do.astype(I32)
    return plane, widx, buf_lo, buf_hi, buflen


# Fixed-capacity planes are sized in whole (8, 128) u32 tiles: the TPU
# stores and DMAs 8-word row groups anyway, and the Pallas decoder's word
# window (``apack_decode``) loads the plane one 8-row group at a time.
WORD_GROUP = 8


def _whole_groups(words: int) -> int:
    return -(-words // WORD_GROUP) * WORD_GROUP


def sym_capacity_words(n_steps: int) -> int:
    # <= MAX_RENORM bits/step sustained + termination & slack
    return _whole_groups(
        (n_steps * (MAX_RENORM + 2) + MAX_PENDING + 64 + 31) // 32)


def ofs_capacity_words(n_steps: int, bits: int) -> int:
    return _whole_groups((n_steps * bits + 63) // 32)


@partial(jax.jit, static_argnames=("n_steps", "bits"))
def encode_ac(values: jax.Array, table: TableArrays, n_steps: int,
              bits: int = 8):
    """Arithmetic-encode S streams (no stored-mode selection — see encode()).

    Args:
      values: i32[S, n_steps] uint values.

    Returns: (sym_plane u32[Ws,S], ofs_plane u32[Wo,S],
              sym_bits i32[S], ofs_bits i32[S], overflow bool[S])
    """
    S = values.shape[0]
    cum, v_min, ol = table.cum, table.v_min, table.ol
    Ws = sym_capacity_words(n_steps)
    Wo = ofs_capacity_words(n_steps, bits)
    sidx = jnp.arange(S)

    # hoisted symbol search + table gathers: one vectorized pass over the
    # whole [S, E] block; the serial scan below only touches AC state and
    # the bit buffers.
    vals = values.astype(I32)
    s_idx = (jnp.searchsorted(v_min[:-1], vals.reshape(-1),
                              side="right").astype(I32) - 1).reshape(vals.shape)
    ol_all = jnp.take(ol, s_idx)                         # [S, E]
    off_all = (vals - jnp.take(v_min, s_idx)).astype(U32)
    clo_all = jnp.take(cum, s_idx)
    chi_all = jnp.take(cum, s_idx + 1)

    def step(carry, xs):
        (low, high, pending, overflow,
         s_plane, s_widx, s_lo, s_hi, s_len, s_bits,
         o_plane, o_widx, o_lo, o_hi, o_len, o_bits) = carry
        off, ol_s, clo, chi = xs
        # offset emission
        o_lo, o_hi, o_len = _append(o_lo, o_hi, o_len, off, ol_s)
        o_bits = o_bits + ol_s
        o_plane, o_widx, o_lo, o_hi, o_len = _flush(o_plane, o_widx, sidx,
                                                    o_lo, o_hi, o_len)
        # range update
        rng = high - low + 1
        high2 = low + ((rng * chi) >> PCOUNT_BITS) - 1
        low2 = low + ((rng * clo) >> PCOUNT_BITS)

        # multi-bit renormalization: all matched leading bits + pending
        # underflow bits emitted in two appends (see encode_renorm)
        low, high, pending, pat1, k1, pat2, k2 = encode_renorm(
            low2, high2, pending)
        s_lo, s_hi, s_len = _append(s_lo, s_hi, s_len, pat1, k1)
        s_bits = s_bits + k1
        s_plane, s_widx, s_lo, s_hi, s_len = _flush(s_plane, s_widx, sidx,
                                                    s_lo, s_hi, s_len)
        s_lo, s_hi, s_len = _append(s_lo, s_hi, s_len, pat2, k2)
        s_bits = s_bits + k2
        s_plane, s_widx, s_lo, s_hi, s_len = _flush(s_plane, s_widx, sidx,
                                                    s_lo, s_hi, s_len)
        overflow = overflow | (pending > MAX_PENDING)
        return (low, high, pending, overflow,
                s_plane, s_widx, s_lo, s_hi, s_len, s_bits,
                o_plane, o_widx, o_lo, o_hi, o_len, o_bits), None

    zeros = jnp.zeros((S,), I32)
    zerosu = jnp.zeros((S,), U32)
    init = (zeros, jnp.full((S,), TOP, I32), zeros, jnp.zeros((S,), bool),
            jnp.zeros((Ws, S), U32), zeros, zerosu, zerosu, zeros, zeros,
            jnp.zeros((Wo, S), U32), zeros, zerosu, zerosu, zeros, zeros)
    carry, _ = jax.lax.scan(step, init,
                            (off_all.T, ol_all.T, clo_all.T, chi_all.T))
    (low, high, pending, overflow,
     s_plane, s_widx, s_lo, s_hi, s_len, s_bits,
     o_plane, o_widx, o_lo, o_hi, o_len, o_bits) = carry

    # termination: disambiguate the final quarter (golden encode_stream)
    pending = pending + 1
    b = (low >= QUARTER).astype(U32)
    inv_run = (shl32(jnp.ones_like(b), pending) - U32(1)) * (U32(1) - b)
    pattern = b | (inv_run << 1)
    k = 1 + pending
    s_lo, s_hi, s_len = _append(s_lo, s_hi, s_len, pattern, k)
    s_bits = s_bits + k
    for _ in range(3):      # drain buffer (<= 56 + 25 bits)
        s_plane, s_widx, s_lo, s_hi, s_len = _flush(
            s_plane, s_widx, sidx, s_lo, s_hi, s_len)
    # final partial words
    def drain(plane, widx, blo, blen):
        do = blen > 0
        cur = gather_word(plane, widx)
        new = jnp.where(do, blo, cur)
        return plane.at[jnp.clip(widx, 0, plane.shape[0] - 1), sidx].set(new)
    s_plane = drain(s_plane, s_widx, s_lo, s_len)
    o_plane = drain(o_plane, o_widx, o_lo, o_len)
    return s_plane, o_plane, s_bits, o_bits, overflow


@partial(jax.jit, static_argnames=("n_steps", "bits"))
def pack_raw(values: jax.Array, n_steps: int, bits: int = 8):
    """Verbatim bit-pack (stored mode): i32[S, E] -> u32[Wo, S]."""
    S = values.shape[0]
    Wo = ofs_capacity_words(n_steps, bits)
    sidx = jnp.arange(S)
    zeros = jnp.zeros((S,), I32)
    zerosu = jnp.zeros((S,), U32)

    def step(carry, v):
        plane, widx, blo, bhi, blen = carry
        blo, bhi, blen = _append(blo, bhi, blen, v.astype(U32),
                                 jnp.full((S,), bits, I32))
        plane, widx, blo, bhi, blen = _flush(plane, widx, sidx, blo, bhi, blen)
        return (plane, widx, blo, bhi, blen), None

    init = (jnp.zeros((Wo, S), U32), zeros, zerosu, zerosu, zeros)
    (plane, widx, blo, bhi, blen), _ = jax.lax.scan(step, init,
                                                    values.T.astype(I32))
    do = blen > 0
    cur = gather_word(plane, widx)
    plane = plane.at[jnp.clip(widx, 0, plane.shape[0] - 1), sidx].set(
        jnp.where(do, blo, cur))
    return plane


ENCODER_ROW = 17 + 16 + 17


def encoder_row(t: ApackTable) -> np.ndarray:
    """One table as the i32[ENCODER_ROW] row ``encode_pages`` reads:
    ``v_min`` [17] | ``ol`` [16] | ``cum`` [17]."""
    return np.concatenate([np.asarray(t.v_min, np.int32),
                           np.asarray(t.ol, np.int32),
                           np.asarray(t.cum, np.int32)])


def _put_row(plane, widx, word, do):
    """Write ``word`` at row ``widx`` of each lane where ``do``, as a
    select over every row: the cost does not grow with the lane count the
    way a scatter's does.  plane: u32[P, W, S]; the rest [P, S].  A
    ``widx`` past the plane writes nothing (only stored-mode lanes, whose
    planes are replaced, get there)."""
    rows = jax.lax.broadcasted_iota(I32, plane.shape, 1)
    hit = (rows == widx[:, None, :]) & do[:, None, :]
    return jnp.where(hit, word[:, None, :], plane)


def _flush_rows(plane, widx, buf_lo, buf_hi, buflen):
    """``_flush`` over [P, S] lanes through ``_put_row``."""
    do = buflen >= 32
    plane = _put_row(plane, widx, buf_lo, do)
    buf_lo = jnp.where(do, buf_hi, buf_lo)
    buf_hi = jnp.where(do, U32(0), buf_hi)
    buflen = jnp.where(do, buflen - 32, buflen)
    return plane, widx + do.astype(I32), buf_lo, buf_hi, buflen


@jax.jit
def encode_pages(values: jax.Array, rows: jax.Array):
    """Encode P pages of 8-bit values at once, each under its own table:
    the full encoder (``encode``: AC coding, verbatim pack, stored-mode
    selection) in one program, bit-identical to ``encode`` page by page.

    Args:
      values: u8[P, S, E], S streams of E values per page.
      rows:   i32[P, ENCODER_ROW], each page's ``encoder_row``.

    Returns (sym u32[P, Ws, S], ofs u32[P, Wo, S], sym_bits i32[P, S],
    ofs_bits i32[P, S], stored bool[P, S]).

    The serial scan runs E steps whatever P is.  Its body holds no
    scatter or gather: the symbol search (a comparison count, as in
    ``decode``) and the table picks run before it over every value, as
    compares and selects, and words are written by ``_put_row``.
    """
    P, S, E = values.shape
    bits = 8
    Ws = sym_capacity_words(E)
    Wo = ofs_capacity_words(E, bits)
    col = [rows[:, r:r + 1] for r in range(rows.shape[1])]
    v_min, ol, cum = col[0:17], col[17:33], col[33:50]

    # symbol search and table picks, hoisted out of the serial scan: s is
    # the last range whose start is <= v (starts ascend), found by
    # comparisons and selects.  Each value's (offset, offset length,
    # cum[s], cum[s+1] - 1) is packed into one u32 (8 + 4 + 10 + 10 bits).
    v = values.astype(I32)
    ol_s, vlo, clo, chi = (jnp.broadcast_to(x[:, :, None], v.shape)
                           for x in (ol[0], v_min[0], cum[0], cum[1]))
    for r in range(1, 16):
        ge = v >= v_min[r][:, :, None]
        ol_s = jnp.where(ge, ol[r][:, :, None], ol_s)
        vlo = jnp.where(ge, v_min[r][:, :, None], vlo)
        clo = jnp.where(ge, cum[r][:, :, None], clo)
        chi = jnp.where(ge, cum[r + 1][:, :, None], chi)
    sym = ((v - vlo) | (ol_s << 8) | (clo << 12) | ((chi - 1) << 22))
    sym = jnp.transpose(sym.astype(U32), (2, 0, 1))          # [E, P, S]

    def step(carry, x):
        (low, high, pending, overflow,
         s_plane, s_widx, s_lo, s_hi, s_len, s_bits,
         o_plane, o_widx, o_lo, o_hi, o_len, o_bits) = carry
        off = x & U32(0xFF)
        ol_s = ((x >> 8) & U32(0xF)).astype(I32)
        clo = ((x >> 12) & U32(0x3FF)).astype(I32)
        chi = (x >> 22).astype(I32) + 1
        o_lo, o_hi, o_len = _append(o_lo, o_hi, o_len, off, ol_s)
        o_bits = o_bits + ol_s
        o_plane, o_widx, o_lo, o_hi, o_len = _flush_rows(
            o_plane, o_widx, o_lo, o_hi, o_len)
        rng = high - low + 1
        high2 = low + ((rng * chi) >> PCOUNT_BITS) - 1
        low2 = low + ((rng * clo) >> PCOUNT_BITS)
        low, high, pending, pat1, k1, pat2, k2 = encode_renorm(
            low2, high2, pending)
        s_lo, s_hi, s_len = _append(s_lo, s_hi, s_len, pat1, k1)
        s_plane, s_widx, s_lo, s_hi, s_len = _flush_rows(
            s_plane, s_widx, s_lo, s_hi, s_len)
        s_lo, s_hi, s_len = _append(s_lo, s_hi, s_len, pat2, k2)
        s_plane, s_widx, s_lo, s_hi, s_len = _flush_rows(
            s_plane, s_widx, s_lo, s_hi, s_len)
        s_bits = s_bits + k1 + k2
        overflow = overflow | (pending > MAX_PENDING)
        return (low, high, pending, overflow,
                s_plane, s_widx, s_lo, s_hi, s_len, s_bits,
                o_plane, o_widx, o_lo, o_hi, o_len, o_bits), None

    zeros = jnp.zeros((P, S), I32)
    zerosu = jnp.zeros((P, S), U32)
    init = (zeros, jnp.full((P, S), TOP, I32), zeros, jnp.zeros((P, S), bool),
            jnp.zeros((P, Ws, S), U32), zeros, zerosu, zerosu, zeros, zeros,
            jnp.zeros((P, Wo, S), U32), zeros, zerosu, zerosu, zeros, zeros)
    carry, _ = jax.lax.scan(step, init, sym)
    (low, high, pending, overflow,
     s_plane, s_widx, s_lo, s_hi, s_len, s_bits,
     o_plane, o_widx, o_lo, o_hi, o_len, o_bits) = carry

    # termination, as encode_ac
    pending = pending + 1
    b = (low >= QUARTER).astype(U32)
    inv_run = (shl32(jnp.ones_like(b), pending) - U32(1)) * (U32(1) - b)
    k = 1 + pending
    s_lo, s_hi, s_len = _append(s_lo, s_hi, s_len, b | (inv_run << 1), k)
    s_bits = s_bits + k
    for _ in range(3):
        s_plane, s_widx, s_lo, s_hi, s_len = _flush_rows(
            s_plane, s_widx, s_lo, s_hi, s_len)
    s_plane = _put_row(s_plane, s_widx, s_lo, s_len > 0)
    o_plane = _put_row(o_plane, o_widx, o_lo, o_len > 0)

    # verbatim pack (pack_raw): four values a word, the first lowest
    v = jnp.pad(values.astype(U32), ((0, 0), (0, 0), (0, -E % 4)))
    v = v.reshape(P, S, -1, 4)
    raw = v[..., 0] | (v[..., 1] << 8) | (v[..., 2] << 16) | (v[..., 3] << 24)
    raw = jnp.pad(jnp.transpose(raw, (0, 2, 1)),
                  ((0, 0), (0, Wo - raw.shape[2]), (0, 0)))

    stored = overflow | ((s_bits + o_bits) >= E * bits)
    st = stored[:, None, :]
    return (jnp.where(st, U32(0), s_plane), jnp.where(st, raw, o_plane),
            jnp.where(stored, 0, s_bits), jnp.where(stored, E * bits, o_bits),
            stored)


def encode(values: jax.Array, table: TableArrays, n_steps: int,
           bits: int = 8):
    """Full encoder: AC encode + per-stream stored-mode selection.

    Returns (sym_plane, ofs_plane, sym_bits, ofs_bits, stored).
    Stored streams hold verbatim values in the offset plane; their symbol
    column is zeroed.  Bit-identical to ``core.format.compress``.
    """
    s_plane, o_plane, s_bits, o_bits, overflow = encode_ac(
        values, table, n_steps, bits)
    raw_plane = pack_raw(values, n_steps, bits)
    stored = overflow | ((s_bits + o_bits) >= n_steps * bits)
    Wo = max(o_plane.shape[0], raw_plane.shape[0])

    def pad_to(p, w):
        return jnp.pad(p, ((0, w - p.shape[0]), (0, 0)))

    o_plane = jnp.where(stored[None, :], pad_to(raw_plane, Wo),
                        pad_to(o_plane, Wo))
    s_plane = jnp.where(stored[None, :], U32(0), s_plane)
    s_bits = jnp.where(stored, 0, s_bits)
    o_bits = jnp.where(stored, n_steps * bits, o_bits)
    return s_plane, o_plane, s_bits, o_bits, stored
