"""Fused paged gather-decode + attention Pallas kernel.

Decode-side analogue of ``decompress_matmul``: the APack-compressed KV page
pool stays in HBM and each grid step decodes ONE page tile into VMEM
scratch and immediately computes its QK^T / PV contribution with an
online-softmax accumulator — attention never reads a dense materialized
cache for PACKED pages, so the off-chip KV stream is the *compressed*
footprint (paper Fig. 1 applied to the decode read path).

Grid is ``(jobs, pages)`` with pages innermost; a job is one (batch slot)
of one attention layer.  Two scalar-prefetch vectors drive the BlockSpec
index maps exactly like ``kernels/paged_decode.py``: ``page_idx`` selects
which pool page each grid step DMAs, ``table_idx`` selects the K-table row
of the stacked activation tables (the V row is always ``table_idx + 1``).
Table rows are the flat ``(generation, layer, kind)`` address of
``paged_decode.table_row`` — the pool is ``[(G+1) * 2 * n_layers, ...]``
with one generation appended per table refresh, so pages packed before and
after a refresh attend side by side in one launch, each decoding with the
table generation it was coded under (the per-page id rides the scalar
prefetch, nothing in the kernel body changes across refreshes).

Per-page state dispatch happens in-kernel (``pl.when`` on the page
lifecycle):

* ``HOT``    — raw per-token int8 + per-(token, head) scales, read directly
               (the newest, not-yet-sealed tokens);
* ``COLD``   — page-requantized int8 + per-(page, head) scales;
* ``PACKED`` — APack planes, decoded via the shared ``decode_block`` body.

Masking is by *absolute* token position: ``t0 + offset < qpos`` (causal;
the current token's contribution is merged by the caller, see
``modules.paged_attention_step``) and ``t0 + offset > qpos - window`` for
rolling layers — evicted and partially-rolled-out pages mask in-kernel, no
ring buffer is ever materialized.  The online-softmax accumulator
``(acc, m, l)`` is returned *unnormalized* so the caller can merge the
current token's self-attention term before dividing.

Interpret mode is the validated contract on CPU (bit-identical to the
pure-jnp ``fused_page_attention_ref``); the same kernel compiles on TPU
with the pool planes resident in HBM.  The online-softmax state lives in
VMEM scratch across a job's page-innermost grid steps and is written to
the output block once, at the job's last page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref
from .apack_decode import decode_block, resolve_interpret, table_scalars

I32 = jnp.int32
U32 = jnp.uint32
F32 = jnp.float32

# page lifecycle states — must match models/modules.py (re-declared here so
# the kernel module has no model dependency)
PAGE_FREE, PAGE_HOT, PAGE_COLD, PAGE_PACKED = 0, 1, 2, 3

NEG_INF = -1e30          # same mask value as the dense attention paths


def _page_tile(state, h0, tok_ref, tok_s_ref, cold_ref, pscale_ref, sym_ref,
               ofs_ref, stored_ref, vm_ref, ol_ref, cum_ref, tile_ref,
               dec_ref, full_ref, *, ps, h, dh, h_full, n_steps, bits):
    """Fill ``tile_ref`` ([ps, H, dh] f32 VMEM scratch) with the
    dequantized K or V payload of the current page, by lifecycle state.

    Under head tensor-parallelism the dense planes hold only this shard's
    ``h`` heads but a PACKED page always decodes all ``h_full`` heads —
    the APack streams interleave heads, so the compressed payload cannot
    be split — and the shard's block is read out of ``full_ref`` at the
    traced ``h0`` offset (``full_ref`` is None on a single device, where
    ``h == h_full``)."""
    scale = pscale_ref[0][None]                              # [1, H, 1]

    @pl.when(state == PAGE_HOT)
    def _hot():
        tile_ref[...] = (tok_ref[0].astype(F32)
                         * tok_s_ref[0].astype(F32)[..., None])

    @pl.when(state == PAGE_COLD)
    def _cold():
        tile_ref[...] = cold_ref[0].astype(F32) * scale

    @pl.when(state == PAGE_PACKED)
    def _packed():
        decode_block(sym_ref.at[0], ofs_ref.at[0], stored_ref[0] != 0,
                     table_scalars(vm_ref, ol_ref, cum_ref, 0, 0), dec_ref,
                     n_steps=n_steps, bits=bits)
        u = dec_ref[...].T                                  # [S, E]
        signed = jnp.where(u >= 128, u - 256, u).astype(F32)
        signed = signed.reshape(ps, h_full, dh)
        if full_ref is not None:
            full_ref[...] = signed
            signed = full_ref[:, pl.ds(h0, h), :]
        tile_ref[...] = signed * scale


def _fused_kernel(idx_ref, tid_ref, q_ref, jm_ref, meta_ref,
                  tok_k_ref, tok_sk_ref, tok_v_ref, tok_sv_ref,
                  cold_k_ref, cold_v_ref, pscale_k_ref, pscale_v_ref,
                  sym_k_ref, ofs_k_ref, st_k_ref,
                  sym_v_ref, ofs_v_ref, st_v_ref,
                  vm_k_ref, ol_k_ref, cum_k_ref,
                  vm_v_ref, ol_v_ref, cum_v_ref,
                  acc_ref, m_ref, l_ref,
                  kt_ref, vt_ref, acc_s, m_s, l_s, dec_ref, *full_ref,
                  ps: int, hkv: int, g: int, dh: int, h_full: int,
                  n_steps: int, bits: int, softcap: float):
    del idx_ref, tid_ref                 # consumed by BlockSpec index_maps
    full_ref = full_ref[0] if full_ref else None
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        acc_s[...] = jnp.zeros((hkv, g, dh), F32)
        m_s[...] = jnp.full((hkv, g), NEG_INF, F32)
        l_s[...] = jnp.zeros((hkv, g), F32)

    state = meta_ref[0, 0, 0]
    t0 = meta_ref[0, 0, 1]
    qpos = jm_ref[0, 0, 0]
    window = jm_ref[0, 0, 1]
    h0 = jm_ref[0, 0, 2]

    fill_tile = functools.partial(
        _page_tile, state, h0, dec_ref=dec_ref, full_ref=full_ref, ps=ps,
        h=hkv, dh=dh, h_full=h_full, n_steps=n_steps, bits=bits)
    fill_tile(tok_k_ref, tok_sk_ref, cold_k_ref, pscale_k_ref, sym_k_ref,
              ofs_k_ref, st_k_ref, vm_k_ref, ol_k_ref, cum_k_ref, kt_ref)
    fill_tile(tok_v_ref, tok_sv_ref, cold_v_ref, pscale_v_ref, sym_v_ref,
              ofs_v_ref, st_v_ref, vm_v_ref, ol_v_ref, cum_v_ref, vt_ref)

    q = q_ref[0].astype(F32)                                 # [H, g, dh]
    k_tile = kt_ref[...]                                     # [ps, H, dh]
    v_tile = vt_ref[...]
    scores = jnp.einsum("kgd,skd->kgs", q, k_tile) * (dh ** -0.5)
    pos = t0 + jax.lax.broadcasted_iota(I32, (1, 1, ps), 2)
    # window == 0 means global: every position clears the lower bound
    lower = jnp.where(window > 0, qpos - window, t0 - 1)
    valid = (pos < qpos) & (pos > lower) & (state != PAGE_FREE)
    scores = jnp.where(valid, scores, NEG_INF)
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    m_new = jnp.maximum(m_s[...], jnp.max(scores, axis=-1))
    # explicit * valid: with a fully-masked page m stays at NEG_INF and
    # exp(NEG_INF - NEG_INF) == 1 would otherwise pollute l
    w = jnp.exp(scores - m_new[..., None]) * valid.astype(F32)
    alpha = jnp.exp(m_s[...] - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(w, axis=-1)
    acc_s[...] = (acc_s[...] * alpha[..., None]
                  + jnp.einsum("kgs,skd->kgd", w, v_tile))
    m_s[...] = m_new

    @pl.when(p == n_pages - 1)
    def _flush():
        acc_ref[0] = acc_s[...]
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]


# apack: allow-jit-cache(softcap is one value per served model config --
# bounded by the config set, unlike per-request shapes)
@functools.partial(
    jax.jit, static_argnames=("n_steps", "num_heads", "h_full", "bits",
                              "softcap", "interpret"))
def fused_page_attention_pallas(
        q: jax.Array, page_idx: jax.Array, table_idx: jax.Array,
        meta: jax.Array, jobmeta: jax.Array,
        tok_k, tok_sk, tok_v, tok_sv, cold_k, cold_v, pscale_k, pscale_v,
        sym_k, ofs_k, stored_k, sym_v, ofs_v, stored_v, vm, ol, cum, *,
        n_steps: int, num_heads: int, h_full: int | None = None,
        bits: int = 8, softcap: float = 0.0, interpret: bool | None = None):
    """Fused paged attention over a job batch.

    Args:
      q:         f32[J, Hq, dh] per-job queries (rope'd, unscaled).
      page_idx:  i32[J, P] pool page id per (job, page slot); padding slots
                 may carry any in-range id — they are masked by state.
      table_idx: i32[J, P] K-table row in the stacked table arrays
                 (``2 * layer``); the V row is ``table_idx + 1``.
      meta:      i32[J, P, 2] per-(job, page): (lifecycle state, absolute
                 position of the page's first token).
      jobmeta:   i32[J, 3] per job: (qpos, window, h0) — ``window == 0``
                 means global (no lower bound); ``h0`` is the first kv
                 head of this shard's dense-plane block (0 off-mesh).  A
                 legacy [J, 2] jobmeta is padded with h0 = 0.
      tok_* / cold_* / pscale_* / sym_* / ofs_* / stored_*: per-kind pool
                 planes ([P_pool, ...], kind split by the caller; under
                 head-TP the dense planes carry only the shard's heads
                 while sym/ofs/stored stay full — see ``h_full``).
      vm/ol/cum: stacked table arrays [T, 17] / [T, 16] / [T, 17].
      h_full:    total kv heads a PACKED page decodes to (defaults to the
                 dense planes' head count; differs only under head-TP).
      interpret: None takes the platform's choice (``ops._default_backend``).

    Returns (acc f32[J, Hq, dh], m f32[J, Hq], l f32[J, Hq]) — the
    *unnormalized* online-softmax state; callers merge the current token
    and divide (see ``modules.paged_attention_step``).

    Per-page and per-job scalars (lifecycle state, positions, tables) ride
    in SMEM blocks; every VMEM block's trailing two dims are whole array
    dims, as Mosaic requires, so small per-page vectors get a unit axis.
    """
    j, hq, dh = q.shape
    p_slots = page_idx.shape[1]
    n_pool = tok_k.shape[0]
    ps = tok_k.shape[1]
    hkv = tok_k.shape[2]
    g = hq // hkv
    if h_full is None:
        h_full = hkv
    if jobmeta.shape[1] == 2:
        jobmeta = jnp.concatenate(
            [jobmeta, jnp.zeros((j, 1), jobmeta.dtype)], axis=1)
    ws, s = sym_k.shape[1], sym_k.shape[2]
    wo = ofs_k.shape[1]
    idx_flat = page_idx.reshape(-1).astype(I32)
    tid_flat = table_idx.reshape(-1).astype(I32)
    kernel = functools.partial(
        _fused_kernel, ps=ps, hkv=hkv, g=g, dh=dh, h_full=h_full,
        n_steps=n_steps, bits=bits, softcap=float(softcap))

    def page_spec(shape):
        return pl.BlockSpec((1, *shape),
                            lambda i, p, idx, tid:
                            (idx[i * p_slots + p],) + (0,) * len(shape))

    def tab_spec(n, kind):
        return pl.BlockSpec(
            (1, 1, n), lambda i, p, idx, tid: (tid[i * p_slots + p] + kind,
                                               0, 0),
            memory_space=pltpu.SMEM)

    job_spec = pl.BlockSpec((1, 1, 3), lambda i, p, idx, tid: (i, 0, 0),
                            memory_space=pltpu.SMEM)
    meta_spec = pl.BlockSpec((1, 1, 2),
                             lambda i, p, idx, tid: (i * p_slots + p, 0, 0),
                             memory_space=pltpu.SMEM)
    scratch = [
        pltpu.VMEM((ps, hkv, dh), F32),    # k tile
        pltpu.VMEM((ps, hkv, dh), F32),    # v tile
        pltpu.VMEM((hkv, g, dh), F32),     # acc
        pltpu.VMEM((hkv, g), F32),         # m
        pltpu.VMEM((hkv, g), F32),         # l
        pltpu.VMEM((n_steps, s), I32),     # decoded PACKED page
    ]
    if hkv != h_full:
        scratch.append(pltpu.VMEM((ps, h_full, dh), F32))   # all heads
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(j, p_slots),
        in_specs=[
            pl.BlockSpec((1, hkv, g, dh),
                         lambda i, p, idx, tid: (i, 0, 0, 0)),
            job_spec,
            meta_spec,
            page_spec((ps, hkv, dh)),          # tok_k
            page_spec((ps, hkv)),              # tok_sk
            page_spec((ps, hkv, dh)),          # tok_v
            page_spec((ps, hkv)),              # tok_sv
            page_spec((ps, hkv, dh)),          # cold_k
            page_spec((ps, hkv, dh)),          # cold_v
            page_spec((hkv, 1)),               # pscale_k
            page_spec((hkv, 1)),               # pscale_v
            page_spec((ws, s)),                # sym_k
            page_spec((wo, s)),                # ofs_k
            page_spec((1, s)),                 # stored_k
            page_spec((ws, s)),                # sym_v
            page_spec((wo, s)),                # ofs_v
            page_spec((1, s)),                 # stored_v
            tab_spec(17, 0), tab_spec(16, 0), tab_spec(17, 0),
            tab_spec(17, 1), tab_spec(16, 1), tab_spec(17, 1),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, g, dh),
                         lambda i, p, idx, tid: (i, 0, 0, 0)),
            pl.BlockSpec((1, hkv, g), lambda i, p, idx, tid: (i, 0, 0)),
            pl.BlockSpec((1, hkv, g), lambda i, p, idx, tid: (i, 0, 0)),
        ],
        scratch_shapes=scratch,
    )
    vm3, ol3, cum3 = (t.astype(I32)[:, None, :] for t in (vm, ol, cum))
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((j, hkv, g, dh), F32),
            jax.ShapeDtypeStruct((j, hkv, g), F32),
            jax.ShapeDtypeStruct((j, hkv, g), F32),
        ],
        interpret=resolve_interpret(interpret),
        name="fused_page_attention",
    )(idx_flat, tid_flat, q.astype(F32).reshape(j, hkv, g, dh),
      jobmeta.astype(I32)[:, None, :],
      meta.astype(I32).reshape(j * p_slots, 1, 2), tok_k,
      tok_sk.astype(F32), tok_v, tok_sv.astype(F32), cold_k, cold_v,
      pscale_k.astype(F32).reshape(n_pool, hkv, 1),
      pscale_v.astype(F32).reshape(n_pool, hkv, 1),
      sym_k.astype(U32), ofs_k.astype(U32),
      stored_k.astype(I32)[:, None, :], sym_v.astype(U32),
      ofs_v.astype(U32), stored_v.astype(I32)[:, None, :],
      vm3, ol3, cum3, vm3, ol3, cum3)
    return acc.reshape(j, hq, dh), m.reshape(j, hq), l.reshape(j, hq)


# apack: allow-jit-cache(softcap is one value per served model config --
# bounded by the config set, unlike per-request shapes)
@functools.partial(
    jax.jit, static_argnames=("n_steps", "num_heads", "h_full", "bits",
                              "softcap"))
def fused_page_attention_ref(
        q, page_idx, table_idx, meta, jobmeta,
        tok_k, tok_sk, tok_v, tok_sv, cold_k, cold_v, pscale_k, pscale_v,
        sym_k, ofs_k, stored_k, sym_v, ofs_v, stored_v, vm, ol, cum, *,
        n_steps: int, num_heads: int, h_full: int | None = None,
        bits: int = 8, softcap: float = 0.0):
    """jnp reference for the fused kernel: identical page-by-page
    online-softmax update order (bit-comparable in interpret mode)."""
    j, hq, dh = q.shape
    p_slots = page_idx.shape[1]
    ps, hkv = tok_k.shape[1], tok_k.shape[2]
    g = hq // hkv
    if h_full is None:
        h_full = hkv
    if jobmeta.shape[1] == 2:
        jobmeta = jnp.concatenate(
            [jobmeta, jnp.zeros((j, 1), jobmeta.dtype)], axis=1)

    def dequant_page(pid, tid, state, h0):
        hot = tok_k[pid].astype(F32), tok_v[pid].astype(F32)
        hot = (hot[0] * tok_sk[pid].astype(F32)[..., None],
               hot[1] * tok_sv[pid].astype(F32)[..., None])
        cold = (cold_k[pid].astype(F32)
                * pscale_k[pid].astype(F32)[None, :, None],
                cold_v[pid].astype(F32)
                * pscale_v[pid].astype(F32)[None, :, None])

        def dec(sym, ofs, stored, t):
            u = _ref.decode(sym[pid].astype(U32), ofs[pid].astype(U32),
                            stored[pid].astype(bool),
                            _ref.TableArrays(vm[t], ol[t], cum[t]),
                            n_steps, bits)
            sgn = jnp.where(u >= 128, u - 256, u).astype(F32)
            # full-head decode, local-head slice — see _page_tile
            return jax.lax.dynamic_slice_in_dim(
                sgn.reshape(ps, h_full, dh), h0, hkv, axis=1)

        packed = (dec(sym_k, ofs_k, stored_k, tid)
                  * pscale_k[pid].astype(F32)[None, :, None],
                  dec(sym_v, ofs_v, stored_v, tid + 1)
                  * pscale_v[pid].astype(F32)[None, :, None])
        kt = jnp.where(state == PAGE_HOT, hot[0],
                       jnp.where(state == PAGE_COLD, cold[0], packed[0]))
        vt = jnp.where(state == PAGE_HOT, hot[1],
                       jnp.where(state == PAGE_COLD, cold[1], packed[1]))
        return kt, vt

    def one_job(qj, pids, tids, mj, jm):
        q3 = qj.reshape(hkv, g, dh).astype(F32)
        acc = jnp.zeros((hkv, g, dh), F32)
        m_run = jnp.full((hkv, g), NEG_INF, F32)
        l_run = jnp.zeros((hkv, g), F32)
        for p in range(p_slots):
            state, t0 = mj[p, 0], mj[p, 1]
            kt, vt = dequant_page(pids[p], tids[p], state, jm[2])
            scores = jnp.einsum("kgd,skd->kgs", q3, kt) * (dh ** -0.5)
            pos = t0 + jnp.arange(ps, dtype=I32)
            valid = (pos < jm[0]) & (state != PAGE_FREE)
            valid &= jnp.where(jm[1] > 0, pos > jm[0] - jm[1], True)
            scores = jnp.where(valid[None, None, :], scores, NEG_INF)
            if softcap > 0:
                scores = softcap * jnp.tanh(scores / softcap)
            m_new = jnp.maximum(m_run, jnp.max(scores, axis=-1))
            w = jnp.exp(scores - m_new[..., None]) * valid[None, None, :]
            alpha = jnp.exp(m_run - m_new)
            l_run = l_run * alpha + jnp.sum(w, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("kgs,skd->kgd", w, vt)
            m_run = m_new
        return (acc.reshape(hq, dh), m_run.reshape(hq), l_run.reshape(hq))

    return jax.vmap(one_job)(q.astype(F32), page_idx.astype(I32),
                             table_idx.astype(I32), meta.astype(I32),
                             jobmeta.astype(I32))


def fused_page_attention(q, page_idx, table_idx, meta, jobmeta, planes, *,
                         n_steps: int, num_heads: int,
                         h_full: int | None = None, bits: int = 8,
                         softcap: float = 0.0, backend: str | None = None):
    """Backend dispatch (mirrors ``paged_decode.gather_decode``): pallas on
    TPU, pallas-interpret on CPU, ``backend="ref"`` for the pure-jnp path.
    ``planes`` is the device plane dict built by
    ``model.DevicePoolPlanes`` (kind-split pool arrays + table stacks)."""
    if backend is None:
        from .ops import _default_backend
        backend = _default_backend()
    args = (q, page_idx, table_idx, meta, jobmeta,
            planes["tok_k"], planes["tok_sk"], planes["tok_v"],
            planes["tok_sv"], planes["cold_k"], planes["cold_v"],
            planes["pscale_k"], planes["pscale_v"],
            planes["sym_k"], planes["ofs_k"], planes["stored_k"],
            planes["sym_v"], planes["ofs_v"], planes["stored_v"],
            planes["vm"], planes["ol"], planes["cum"])
    if backend == "ref":
        return fused_page_attention_ref(
            *args, n_steps=n_steps, num_heads=num_heads, h_full=h_full,
            bits=bits, softcap=softcap)
    return fused_page_attention_pallas(
        *args, n_steps=n_steps, num_heads=num_heads, h_full=h_full,
        bits=bits, softcap=softcap,
        interpret=(backend == "pallas_interpret"))
