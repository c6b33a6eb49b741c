"""Public jit'd wrappers for the APack kernels.

``apack_encode`` / ``apack_decode`` operate on ``CompressedArrays`` — the
jnp-native view of ``core.format.CompressedTensor`` — and dispatch to the
Pallas kernels (interpret mode on CPU, compiled on TPU) or to the jnp
reference (``backend="ref"``).  All paths are bit-identical; tests assert it.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import format as fmt
from repro.core.tables import ApackTable
from . import ref as _ref
from .apack_decode import BLOCK_STREAMS, decode_pallas
from .apack_encode import encode_pallas

I32 = jnp.int32
U32 = jnp.uint32


def _default_backend() -> str:
    """The one place the kernel backend is chosen: Pallas compiled for the
    TPU, the Pallas interpreter on the CPU (tests and rehearsals).  Any
    other platform is an error, never a silent interpreter run."""
    platform = jax.default_backend()
    if platform == "tpu":
        return "pallas"
    if platform == "cpu":
        return "pallas_interpret"
    raise RuntimeError(f"APack kernels run on TPU or (interpreted) CPU, "
                       f"not on {platform!r}")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompressedArrays:
    """jnp container for one APack-compressed tensor."""

    sym_plane: jax.Array     # u32[Ws, S]
    ofs_plane: jax.Array     # u32[Wo, S]
    sym_bits: jax.Array      # i32[S]
    ofs_bits: jax.Array      # i32[S]
    stored: jax.Array        # bool[S]
    v_min: jax.Array         # i32[17]
    ol: jax.Array            # i32[16]
    cum: jax.Array           # i32[17]
    shape: tuple[int, ...]   # static
    bits: int                # static
    elems_per_stream: int    # static
    n_valid: int             # static

    def tree_flatten(self):
        leaves = (self.sym_plane, self.ofs_plane, self.sym_bits,
                  self.ofs_bits, self.stored, self.v_min, self.ol, self.cum)
        aux = (self.shape, self.bits, self.elems_per_stream, self.n_valid)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def payload_bits(self) -> int:
        return int(jnp.sum(self.sym_bits) + jnp.sum(self.ofs_bits))

    @classmethod
    def from_compressed_tensor(cls, ct: fmt.CompressedTensor) -> "CompressedArrays":
        v_min, ol, cum = ct.table.as_arrays()
        return cls(sym_plane=jnp.asarray(ct.sym_plane.astype(np.uint32)),
                   ofs_plane=jnp.asarray(ct.ofs_plane.astype(np.uint32)),
                   sym_bits=jnp.asarray(ct.sym_bits), ofs_bits=jnp.asarray(ct.ofs_bits),
                   stored=jnp.asarray(ct.stored), v_min=jnp.asarray(v_min),
                   ol=jnp.asarray(ol), cum=jnp.asarray(cum), shape=tuple(ct.shape),
                   bits=ct.bits, elems_per_stream=ct.elems_per_stream,
                   n_valid=ct.n_valid)


def _pad_streams(x: jax.Array, s_padded: int, axis: int) -> jax.Array:
    pad = s_padded - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def apack_encode(values: Any, table: ApackTable,
                 elems_per_stream: int = fmt.DEFAULT_ELEMS_PER_STREAM,
                 backend: str | None = None) -> CompressedArrays:
    """Compress an unsigned-value tensor with a given table."""
    backend = backend or _default_backend()
    arr = jnp.asarray(values)
    shape = tuple(arr.shape)
    flat = arr.reshape(-1).astype(I32)
    n = flat.shape[0]
    e = elems_per_stream
    s = max(1, -(-n // e))
    ta = _ref.TableArrays.from_table(table)
    pad_val = int(table.v_min[int(np.argmax(np.diff(np.asarray(table.cum))))])
    flat = jnp.pad(flat, (0, s * e - n), constant_values=pad_val)
    streams = flat.reshape(s, e)
    if backend == "ref":
        sp, op, sb, ob, ovf = _ref.encode_ac(streams, ta, e, table.bits)
    else:
        s_pad = -(-s // BLOCK_STREAMS) * BLOCK_STREAMS
        streams_p = _pad_streams(streams, s_pad, 0)
        sp, op, sb, ob, ovf = encode_pallas(
            streams_p, ta.v_min, ta.ol, ta.cum, n_steps=e, bits=table.bits,
            interpret=(backend == "pallas_interpret"))
        sp, op = sp[:, :s], op[:, :s]
        sb, ob, ovf = sb[:s], ob[:s], ovf[:s].astype(bool)
    # stored-mode selection (shared logic)
    raw = _ref.pack_raw(streams, e, table.bits)
    stored = jnp.asarray(ovf).astype(bool) | ((sb + ob) >= e * table.bits)
    wo = max(op.shape[0], raw.shape[0])

    def pad_to(p, w):
        return jnp.pad(p, ((0, w - p.shape[0]), (0, 0)))

    op = jnp.where(stored[None, :], pad_to(raw, wo), pad_to(op, wo))
    sp = jnp.where(stored[None, :], U32(0), sp)
    sb = jnp.where(stored, 0, sb)
    ob = jnp.where(stored, e * table.bits, ob)
    return CompressedArrays(sym_plane=sp, ofs_plane=op, sym_bits=sb,
                            ofs_bits=ob, stored=stored, v_min=ta.v_min,
                            ol=ta.ol, cum=ta.cum, shape=shape,
                            bits=table.bits, elems_per_stream=e, n_valid=n)


def apack_decode(ca: CompressedArrays, backend: str | None = None,
                 dtype=None) -> jax.Array:
    """Decompress back to the original unsigned-value tensor."""
    backend = backend or _default_backend()
    e = ca.elems_per_stream
    s = ca.sym_bits.shape[0]
    table = _ref.TableArrays(ca.v_min, ca.ol, ca.cum)
    sym = ca.sym_plane if ca.sym_plane.shape[0] > 0 else jnp.zeros((1, s), U32)
    ofs = ca.ofs_plane if ca.ofs_plane.shape[0] > 0 else jnp.zeros((1, s), U32)
    if backend == "ref":
        vals = _ref.decode(sym, ofs, ca.stored, table, e, ca.bits)
    else:
        s_pad = -(-s // BLOCK_STREAMS) * BLOCK_STREAMS
        vals = decode_pallas(
            _pad_streams(sym, s_pad, 1), _pad_streams(ofs, s_pad, 1),
            # padding streams decode as stored zeros (discarded)
            _pad_streams(ca.stored.astype(I32), s_pad, 0),
            ca.v_min, ca.ol, ca.cum, n_steps=e, bits=ca.bits,
            interpret=(backend == "pallas_interpret"))
        vals = vals[:s]
    flat = vals.reshape(-1)[:ca.n_valid]
    if dtype is None:
        dtype = jnp.uint8 if ca.bits <= 8 else jnp.uint16
    return flat.astype(dtype).reshape(ca.shape)


def apack_roundtrip_check(values, table: ApackTable, **kw) -> bool:
    ca = apack_encode(values, table, **kw)
    out = apack_decode(ca)
    return bool(jnp.all(out.astype(I32) == jnp.asarray(values).astype(I32)))
