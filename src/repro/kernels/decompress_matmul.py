"""Fused APack-decompress + matmul Pallas kernel.

This is the TPU materialization of the paper's Figure 1: the accelerator's
compute units (here: the MXU ``jnp.dot``) consume *decompressed* values that
never exist in off-chip memory.  The weight matrix lives in HBM as
word-interleaved APack planes; each grid step DMAs one compressed tile's
slot into VMEM (BlockSpec), lane-decodes it (``decode_block``), feeds the
int8 codes to the MXU and dequantizes the accumulated product, so the
decompressed weights never reach HBM.

Weight layout: W[K, N] is tiled into (K // E) x (N // NS) tiles; stream
``c`` of tile (k, j) holds column ``j*NS + c`` over rows ``k*E..(k+1)*E``.
Streams of one tile are adjacent columns of the planes, so the BlockSpec
slice [*, NS] is one tile's slot.  Fixed-size slots (global max words) keep
the layout BlockSpec-indexable; on real hardware the per-stream directory
enables dynamic-length DMA instead (documented trade-off: the *slotted*
ratio vs the *payload* ratio of ``CompressedTensor``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import format as fmt
from repro.core import quant
from repro.core.tables import ApackTable, find_table, histogram
from .apack_decode import (SMEM_SPEC, decode_block, resolve_interpret,
                           table_scalars)
from . import ref as _ref

I32 = jnp.int32
U32 = jnp.uint32
TILE_N = 128      # streams per tile == lane count
DEFAULT_TILE_K = 512
# Smallest element count for which the serving layer compresses a weight
# tensor.  Shared by ``serve.compress_params``, ``model.pack_weights`` and
# the ``--weight-min-size`` CLI flag — one default, no silent divergence.
DEFAULT_WEIGHT_MIN_SIZE = 16384


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompressedLinear:
    """An APack-compressed [K, N] weight matrix + dequant metadata."""

    sym_plane: jax.Array     # u32[Ws, S_total]
    ofs_plane: jax.Array     # u32[Wo, S_total]
    stored: jax.Array        # i32[S_total]
    v_min: jax.Array
    ol: jax.Array
    cum: jax.Array
    scale: jax.Array         # f32[N_pad] per-output-channel dequant scale
    k: int                   # original K
    n: int                   # original N
    tile_k: int
    payload_bits: int        # actual compressed payload (for traffic models)

    def tree_flatten(self):
        return ((self.sym_plane, self.ofs_plane, self.stored, self.v_min,
                 self.ol, self.cum, self.scale),
                (self.k, self.n, self.tile_k, self.payload_bits))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def k_pad(self) -> int:
        return -(-self.k // self.tile_k) * self.tile_k

    @property
    def n_pad(self) -> int:
        return -(-self.n // TILE_N) * TILE_N


def compress_quantized(q: np.ndarray, scale: np.ndarray,
                       tile_k: int = DEFAULT_TILE_K,
                       table: ApackTable | None = None) -> CompressedLinear:
    """APack-compress an already-quantized int8 weight matrix.

    ``q``: int8-valued [K, N]; ``scale``: f32 [N] per-output-column dequant
    scale.  This is the shared encode tail of ``compress_linear`` and the
    serving layer's ``pack_weights`` — both quantize through
    ``quant.quantize_symmetric`` first, so a tensor compressed by either
    path dequantizes bit-identically through the other."""
    q = np.asarray(q)
    k, n = q.shape
    scale = np.asarray(scale, np.float32).reshape(-1)
    assert scale.shape == (n,), (scale.shape, n)
    u = (q.astype(np.int64) & 0xFF).astype(np.uint8)     # two's complement view
    k_pad = -(-k // tile_k) * tile_k
    n_pad = -(-n // TILE_N) * TILE_N
    up = np.zeros((k_pad, n_pad), np.uint8)              # pad with 0 == q 0
    up[:k, :n] = u
    if table is None:
        table = find_table(histogram(up), bits=8, is_activation=False)
    # stream layout: tile (kt, jt), stream c -> column of planes
    nk, nn = k_pad // tile_k, n_pad // TILE_N
    streams = (up.reshape(nk, tile_k, nn, TILE_N)
                 .transpose(0, 2, 3, 1)                  # [nk, nn, NS, E]
                 .reshape(nk * nn * TILE_N, tile_k))
    ta = _ref.TableArrays.from_table(table)
    sp, op, sb, ob, stored = _ref.encode(jnp.asarray(streams.astype(np.int64)),
                                         ta, tile_k, 8)
    payload = int(np.asarray(sb).sum() + np.asarray(ob).sum())
    scale_pad = np.zeros(n_pad, np.float32)
    scale_pad[:n] = scale
    return CompressedLinear(sym_plane=sp, ofs_plane=op,
                            stored=stored.astype(I32), v_min=ta.v_min,
                            ol=ta.ol, cum=ta.cum,
                            scale=jnp.asarray(scale_pad), k=k, n=n,
                            tile_k=tile_k, payload_bits=payload)


def compress_linear(w: np.ndarray, tile_k: int = DEFAULT_TILE_K,
                    table: ApackTable | None = None) -> CompressedLinear:
    """Quantize (symmetric int8 per output column) + APack-compress a
    weight matrix.

    Quantization goes through ``quant.quantize_symmetric(..., axis=-1)``
    — the same call ``serve.compress_params`` makes — so the two weight
    codecs share one convention (per-channel over the LAST axis, reduced
    over all leading axes) and cross-path dequantization is bit-exact.
    The previous private ``np.abs(w).max(axis=0)`` formula was the
    quantization-axis mismatch bug for >2-D tensors."""
    w = np.asarray(w, np.float32)
    q, qp = quant.quantize_symmetric(jnp.asarray(w), axis=-1)
    return compress_quantized(np.asarray(q),
                              np.asarray(qp.scale, np.float32).reshape(-1),
                              tile_k, table)


def stack_compressed(cws: list[CompressedLinear]) -> CompressedLinear:
    """Stack per-layer ``CompressedLinear``s into one whose array leaves
    carry a leading layer axis — the shape ``jax.lax.scan`` consumes for
    the scanned block stack (scan slices pytree leaves per iteration and
    rebuilds a per-layer ``CompressedLinear`` with the shared static aux).

    Per-layer sym/ofs planes are zero-padded to the stack's max word
    count (``decode_block`` reads exactly ``tile_k`` values per stream,
    so trailing pad words are never touched).  Static aux (k, n, tile_k)
    must match across layers; ``payload_bits`` becomes the stack total
    (it only feeds traffic accounting)."""
    assert cws, "empty stack"
    k, n, tile_k = cws[0].k, cws[0].n, cws[0].tile_k
    assert all((c.k, c.n, c.tile_k) == (k, n, tile_k) for c in cws)
    ws = max(c.sym_plane.shape[0] for c in cws)
    wo = max(c.ofs_plane.shape[0] for c in cws)

    def pad_rows(p, rows):
        return jnp.pad(p, ((0, rows - p.shape[0]), (0, 0)))

    return CompressedLinear(
        sym_plane=jnp.stack([pad_rows(c.sym_plane, ws) for c in cws]),
        ofs_plane=jnp.stack([pad_rows(c.ofs_plane, wo) for c in cws]),
        stored=jnp.stack([c.stored for c in cws]),
        v_min=jnp.stack([c.v_min for c in cws]),
        ol=jnp.stack([c.ol for c in cws]),
        cum=jnp.stack([c.cum for c in cws]),
        scale=jnp.stack([c.scale for c in cws]),
        k=k, n=n, tile_k=tile_k,
        payload_bits=sum(c.payload_bits for c in cws))


def _fused_kernel(x_ref, sym_ref, ofs_ref, stored_ref, vmin_ref, ol_ref,
                  cum_ref, scale_ref, out_ref, w_tile_ref, acc_ref, *,
                  tile_k: int, nk: int):
    kt = pl.program_id(1)
    i = pl.program_id(2)
    block_m = x_ref.shape[0]

    # The grid iterates M innermost, so each compressed weight tile (j, kt)
    # is decoded exactly once — at its first row-block visit — and the
    # tile's int8 codes persist in VMEM scratch for the remaining
    # m_pad // block_m - 1 visits (EIE-style decode-once amortization).
    @pl.when(i == 0)
    def _decode_tile():
        def signed(vals):               # two's-complement reinterpret
            return jnp.where(vals >= 128, vals - 256, vals).astype(
                jnp.float32)

        # decoded rows land as [E, NS]: the tile's K-major layout
        decode_block(sym_ref, ofs_ref, stored_ref[...] != 0,
                     table_scalars(vmin_ref, ol_ref, cum_ref), w_tile_ref,
                     n_steps=tile_k, bits=8, post=signed)

    # The codes are exact in every MXU pass and HIGHEST keeps x at f32
    # precision (the default takes one bf16 pass), so each product is
    # exact and only the f32 summation order can differ from the host.
    part = jnp.dot(x_ref[...].astype(jnp.float32), w_tile_ref[...],
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)

    # Accumulate in a VMEM scratch strip, not in out_ref: the out-block
    # revisits across kt are non-consecutive (other M-blocks run in
    # between), and Mosaic only guarantees a revisited output block's
    # prior contents for *consecutive* grid steps.  Scratch persists for
    # the whole kernel, so the strip holds each row-block's running sum
    # across the interleaved visits; out_ref is written exactly once, at
    # the final K-tile, with the per-channel dequant scale applied there.
    rows = pl.ds(pl.multiple_of(i * block_m, block_m), block_m)

    @pl.when(kt == 0)
    def _init():
        acc_ref[rows, :] = part

    @pl.when(kt > 0)
    def _accum():
        acc_ref[rows, :] += part

    @pl.when(kt == nk - 1)
    def _flush():
        out_ref[...] = acc_ref[rows, :] * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "block_m"))
def compressed_matmul(x: jax.Array, cw: CompressedLinear,
                      interpret: bool | None = None,
                      block_m: int = 256) -> jax.Array:
    """``x @ W`` where W is APack-compressed; x: f32/bf16 [M, K].

    Grid order is (N-tiles, K-tiles, M-blocks) with M innermost: decode work
    is independent of M (each tile decoded once into scratch), at the cost
    of revisiting output blocks once per K-tile — the decode is orders of
    magnitude more expensive than the extra out-block traffic.

    Partial products accumulate in a VMEM scratch strip [m_pad, TILE_N]
    and flush to the output block exactly once, at kt == nk - 1, so the
    kernel never relies on Mosaic preserving a revisited output block
    across non-consecutive grid steps.  The dot multiplies the exact
    int8 codes at HIGHEST precision and the per-channel scale is applied
    once, at the flush, so compiled and interpret mode differ only in
    the f32 summation order inside a K-tile; a product of exactly
    representable partial sums (``x`` = identity) is bit-exact in both.

    ``interpret=None`` takes the platform's choice (``ops._default_backend``).
    """
    m, k = x.shape
    assert k == cw.k, f"K mismatch: {k} vs {cw.k}"
    k_pad, n_pad = cw.k_pad, cw.n_pad
    nk, nn = k_pad // cw.tile_k, n_pad // TILE_N
    m_pad = -(-m // block_m) * block_m
    xp = jnp.pad(x, ((0, m_pad - m), (0, k_pad - k)))
    ws, wo = cw.sym_plane.shape[0], cw.ofs_plane.shape[0]
    grid = (nn, nk, m_pad // block_m)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, tile_k=cw.tile_k, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, cw.tile_k), lambda j, kt, i: (i, kt)),
            pl.BlockSpec((ws, TILE_N), lambda j, kt, i: (0, kt * nn + j)),
            pl.BlockSpec((wo, TILE_N), lambda j, kt, i: (0, kt * nn + j)),
            pl.BlockSpec((1, TILE_N), lambda j, kt, i: (0, kt * nn + j)),
            SMEM_SPEC, SMEM_SPEC, SMEM_SPEC,
            pl.BlockSpec((1, TILE_N), lambda j, kt, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, TILE_N), lambda j, kt, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((cw.tile_k, TILE_N), jnp.float32),
                        pltpu.VMEM((m_pad, TILE_N), jnp.float32)],
        interpret=resolve_interpret(interpret),
        name="compressed_matmul",
    )(xp, cw.sym_plane, cw.ofs_plane, cw.stored.reshape(1, -1), cw.v_min,
      cw.ol, cw.cum, cw.scale.reshape(1, -1))
    return out[:m, :cw.n]


def reference_matmul(x: jax.Array, cw: CompressedLinear) -> jax.Array:
    """Oracle: decode with the jnp reference, dequant, dense matmul."""
    e = cw.tile_k
    table = _ref.TableArrays(cw.v_min, cw.ol, cw.cum)
    vals = _ref.decode(cw.sym_plane, cw.ofs_plane, cw.stored.astype(bool),
                       table, e, 8)
    nk, nn = cw.k_pad // e, cw.n_pad // TILE_N
    w = (vals.reshape(nk, nn, TILE_N, e).transpose(0, 3, 1, 2)
             .reshape(cw.k_pad, cw.n_pad))
    signed = jnp.where(w >= 128, w - 256, w).astype(jnp.float32)
    wf = signed * cw.scale[None, :]
    return (x.astype(jnp.float32) @ wf[:cw.k])[:, :cw.n]
