"""Pallas paged gather-decode kernel for the APack-compressed KV cache.

The serving engine stores cold KV pages as fixed-capacity APack planes
stacked in a block pool (``models/modules.py::KVPagePool``): page ``p``'s
symbol plane lives at ``sym[p]`` (u32[Ws, S]), its offset plane at
``ofs[p]``.  On every attention read the engine needs an arbitrary *subset*
of pages — the per-request page tables of the active batch — decoded into
dense int8 K/V.

This kernel is that read path: a scalar-prefetched page-index vector drives
the BlockSpec index_map, so grid program ``g`` DMAs exactly page
``page_idx[g]``'s compressed words HBM->VMEM and decodes it with the shared
``decode_block`` body (one stream per lane, ``fori_loop`` over symbols).
A *second* scalar-prefetch vector carries a per-page table id into the
table-array BlockSpecs: pages encoded with different tables batch into ONE
kernel launch — the engine issues two calls per step (one per K/V kind)
instead of two per layer.  Off-chip traffic is the *compressed* footprint —
the paper's Figure-1 saving applied to KV-cache decode reads instead of
weight reads.

The table id is a flat ``(generation, layer, kind)`` address (``table_row``
below) into the stacked table pool: activation tables are *refreshed* on
drifting serving traffic (``model.PagedKVCache.maybe_refresh``), each
refresh appending a new generation of ``2 * n_layers`` rows, and every
PACKED page carries the generation it was coded under — so pages from
before and after a refresh coexist in one gather/attention call and decode
bit-exactly with *their own* table while the background re-pack migrates
them generation by generation.

Interpret mode is bit-exact with ``fastpath.decompress_np`` per page
(tests/test_paged_kv.py); on TPU the same kernel compiles with the pages
resident in HBM.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref
from .apack_decode import decode_block, resolve_interpret, table_scalars

I32 = jnp.int32
U32 = jnp.uint32
_log = logging.getLogger(__name__)

# jit-compile buckets for the gather size: pad the page-index vector up to
# the next bucket so a serving loop with a growing working set compiles
# O(log pages) kernels, not one per distinct page count.  Beyond the fixed
# table the bucket keeps doubling (next power of two) — the compiled-size
# set stays O(log pages) for arbitrarily large pools instead of one kernel
# per 1024-page increment.
GATHER_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)

# recompile-storm guard: a long-running serve should settle into a handful
# of gather sizes; warn (once per new size past the threshold) if the set
# of distinct buckets keeps growing — each one is a fresh XLA compile.
# Deliberately process-global (not per pool/engine): the jit cache whose
# growth this tracks is process-global too.
GATHER_BUCKET_WARN_THRESHOLD = 12
_seen_buckets: set[int] = set()


def table_row(gen: int, layer: int, kind: int, n_layers: int) -> int:
    """Flat row of table ``(generation, layer, kind)`` in the stacked
    ``[(G+1) * 2 * n_layers, ...]`` table pool.

    ``kind`` (0 = K, 1 = V) is the fastest-varying axis — a hard contract:
    ``kernels/fused_page_attention.py`` receives only the K row per page
    and addresses the V table as ``row + 1``.  Generation is the slowest
    axis so a refresh appends rows without renumbering existing pages'
    table ids (old PACKED pages stay decodable mid-refresh)."""
    return (gen * n_layers + layer) * 2 + kind


def gather_bucket(n: int) -> int:
    for b in GATHER_BUCKETS:
        if n <= b:
            bucket = b
            break
    else:
        bucket = GATHER_BUCKETS[-1]
        while bucket < n:
            bucket *= 2
    if bucket not in _seen_buckets:
        _seen_buckets.add(bucket)
        if len(_seen_buckets) > GATHER_BUCKET_WARN_THRESHOLD:
            _log.warning(
                "gather_decode has now been asked for %d distinct jit "
                "bucket sizes (latest: %d) — each is a fresh kernel "
                "compile; a long-running serve hitting this repeatedly "
                "indicates a recompile storm (consider a larger fixed "
                "bucket or pre-warming)", len(_seen_buckets), bucket)
    return bucket


# Per-job page-count buckets for the fused attention grid: the engine sizes
# the kernel's pages axis to the next bucket above the *occupied* page count
# of the busiest active slot instead of the static per-slot maximum, so a
# batch of mostly-short requests stops paying for the max-pages grid.
# Powers of two keep the distinct compiled grid set O(log pages); masked
# (FREE/out-of-range) pages leave the online-softmax accumulator bit-exactly
# unchanged, so any bucket >= the true count decodes identically.
PAGE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
PAGE_BUCKET_WARN_THRESHOLD = 12
_seen_page_buckets: set[int] = set()


def page_bucket(n: int) -> int:
    n = max(int(n), 1)
    for b in PAGE_BUCKETS:
        if n <= b:
            bucket = b
            break
    else:
        bucket = PAGE_BUCKETS[-1]
        while bucket < n:
            bucket *= 2
    if bucket not in _seen_page_buckets:
        _seen_page_buckets.add(bucket)
        if len(_seen_page_buckets) > PAGE_BUCKET_WARN_THRESHOLD:
            _log.warning(
                "fused_page_attention has now been asked for %d distinct "
                "page-grid bucket sizes (latest: %d) — each is a fresh "
                "kernel compile; a long-running serve hitting this "
                "repeatedly indicates a recompile storm (consider a larger "
                "fixed bucket or pre-warming)",
                len(_seen_page_buckets), bucket)
    return bucket


def _as_table_stack(v_min, ol, cum, page_idx, table_idx):
    """Canonicalize table arrays to stacked [T, ...] form + per-page ids.

    1-D tables (the single-table call signature) become a one-row stack
    with every page pointing at row 0."""
    v_min = jnp.asarray(v_min)
    if v_min.ndim == 1:
        v_min, ol, cum = (v_min[None], jnp.asarray(ol)[None],
                          jnp.asarray(cum)[None])
    if table_idx is None:
        table_idx = jnp.zeros(page_idx.shape, I32)
    return v_min, jnp.asarray(ol), jnp.asarray(cum), table_idx


def _gather_decode_kernel(idx_ref, tid_ref, sym_ref, ofs_ref, stored_ref,
                          vmin_ref, ol_ref, cum_ref, out_ref, *, n_steps: int,
                          bits: int):
    del idx_ref, tid_ref            # consumed by the BlockSpec index_maps
    decode_block(sym_ref.at[0], ofs_ref.at[0], stored_ref[0] != 0,
                 table_scalars(vmin_ref, ol_ref, cum_ref, 0, 0),
                 out_ref.at[0], n_steps=n_steps, bits=bits)


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "bits", "interpret"))
def gather_decode_pallas(sym: jax.Array, ofs: jax.Array, stored: jax.Array,
                         page_idx: jax.Array, v_min: jax.Array,
                         ol: jax.Array, cum: jax.Array, *, n_steps: int,
                         bits: int = 8, interpret: bool | None = None,
                         table_idx: jax.Array | None = None) -> jax.Array:
    """Decode pages ``page_idx`` out of a pooled compressed-plane stack.

    Args:
      sym:      u32[P, Ws, S] pooled symbol planes (word-interleaved).
      ofs:      u32[P, Wo, S] pooled offset planes.
      stored:   bool/i32[P, S] per-stream verbatim-mode flags.
      page_idx: i32[G] page ids to decode (duplicates allowed — callers pad
                to a jit bucket by repeating a valid id).
      v_min/ol/cum: table arrays — either a single table ([17]/[16]/[17])
                or a stack ([T, 17]/[T, 16]/[T, 17]) indexed per page by
                ``table_idx``.
      table_idx: i32[G] table-stack row for each gathered page (None with
                1-D tables: every page uses the single table).
      n_steps:  values per stream (E).
      interpret: None takes the platform's choice (``ops._default_backend``).

    Returns: i32[G, S, n_steps] decoded unsigned values, gather order.
    """
    p, ws, s = sym.shape
    wo = ofs.shape[1]
    g = page_idx.shape[0]
    v_min, ol, cum, table_idx = _as_table_stack(v_min, ol, cum, page_idx,
                                                table_idx)
    kernel = functools.partial(_gather_decode_kernel, n_steps=n_steps,
                               bits=bits)

    def tab_spec(n):
        return pl.BlockSpec((1, 1, n), lambda i, idx, tid: (tid[i], 0, 0),
                            memory_space=pltpu.SMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, ws, s), lambda i, idx, tid: (idx[i], 0, 0)),
            pl.BlockSpec((1, wo, s), lambda i, idx, tid: (idx[i], 0, 0)),
            pl.BlockSpec((1, 1, s), lambda i, idx, tid: (idx[i], 0, 0)),
            tab_spec(17), tab_spec(16), tab_spec(17),
        ],
        out_specs=pl.BlockSpec((1, n_steps, s), lambda i, idx, tid: (i, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, n_steps, s), I32),
        interpret=resolve_interpret(interpret),
    )(page_idx.astype(I32), table_idx.astype(I32), sym.astype(U32),
      ofs.astype(U32), stored.astype(I32)[:, None, :],
      *(t.astype(I32)[:, None, :] for t in (v_min, ol, cum)))
    return out.transpose(0, 2, 1)


@functools.partial(jax.jit, static_argnames=("n_steps", "bits"))
def gather_decode_ref(sym: jax.Array, ofs: jax.Array, stored: jax.Array,
                      page_idx: jax.Array, v_min: jax.Array, ol: jax.Array,
                      cum: jax.Array, *, n_steps: int, bits: int = 8,
                      table_idx: jax.Array | None = None) -> jax.Array:
    """jnp reference for ``gather_decode_pallas`` (bit-identical)."""
    v_min, ol, cum, table_idx = _as_table_stack(v_min, ol, cum, page_idx,
                                                table_idx)
    sym_g = jnp.take(sym.astype(U32), page_idx, axis=0)
    ofs_g = jnp.take(ofs.astype(U32), page_idx, axis=0)
    st_g = jnp.take(stored.astype(bool), page_idx, axis=0)
    vm_g = jnp.take(v_min.astype(I32), table_idx, axis=0)
    ol_g = jnp.take(ol.astype(I32), table_idx, axis=0)
    cum_g = jnp.take(cum.astype(I32), table_idx, axis=0)
    return jax.vmap(
        lambda sp, op, st, vm, olr, cm: _ref.decode(
            sp, op, st, _ref.TableArrays(vm, olr, cm), n_steps, bits)
    )(sym_g, ofs_g, st_g, vm_g, ol_g, cum_g)


def gather_decode(sym, ofs, stored, page_idx, v_min, ol, cum, *,
                  n_steps: int, bits: int = 8, backend: str | None = None,
                  table_idx=None) -> jax.Array:
    """Backend dispatch, shared with ``ops``: pallas on TPU,
    pallas-interpret on CPU, ``backend="ref"`` for the pure-jnp path."""
    if backend is None:
        from .ops import _default_backend
        backend = _default_backend()
    if backend == "ref":
        return gather_decode_ref(sym, ofs, stored, page_idx, v_min, ol, cum,
                                 n_steps=n_steps, bits=bits,
                                 table_idx=table_idx)
    return gather_decode_pallas(sym, ofs, stored, page_idx, v_min, ol, cum,
                                n_steps=n_steps, bits=bits,
                                interpret=(backend == "pallas_interpret"),
                                table_idx=table_idx)
