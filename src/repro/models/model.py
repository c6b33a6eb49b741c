"""Family assembly: embeddings -> scanned block stack -> head.

All families share one forward skeleton; the per-layer ``block_pattern``
cycle selects block kinds (attention global/local, RG-LRU recurrent, mLSTM,
sLSTM).  Layers are stacked and driven by ``lax.scan`` over pattern cycles so
the HLO is O(one cycle) regardless of depth — required for fast 512-device
dry-run compiles and for the roofline's while-body accounting.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_decode import table_row
from repro.runtime.spans import span

from . import modules as m
from . import sharding as shd
from .config import ModelConfig

F32 = jnp.float32


@jax.custom_vjp
def _residual_barrier(h: jax.Array) -> jax.Array:
    """``optimization_barrier`` with a defined gradient (identity).

    ``lax.optimization_barrier`` has no differentiation rule, so the bare
    primitive breaks every ``jax.grad`` trace through the train scan.  The
    custom_vjp hides it from autodiff while keeping the barrier in both the
    forward and backward HLO (the backward residual stack has the same
    bf16->f32 hoisting hazard the forward one does)."""
    return jax.lax.optimization_barrier(h)


def _residual_barrier_fwd(h):
    return jax.lax.optimization_barrier(h), None


def _residual_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_residual_barrier.defvjp(_residual_barrier_fwd, _residual_barrier_bwd)


# ------------------------------------------------------------------- init
def _init_block(cfg: ModelConfig, kind: str, key) -> dict:
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    p: dict[str, Any] = {"norm1": jnp.zeros((cfg.d_model,), dt)}
    if kind in ("global", "local"):
        p["inner"] = m.init_attention(cfg, ks[0])
    elif kind == "recurrent":
        p["inner"] = m.init_recurrent(cfg, ks[0])
    elif kind == "mlstm":
        p["inner"] = m.init_mlstm(cfg, ks[0])
    elif kind == "slstm":
        p["inner"] = m.init_slstm(cfg, ks[0])
    else:
        raise ValueError(kind)
    if kind in ("global", "local", "recurrent"):
        p["norm2"] = jnp.zeros((cfg.d_model,), dt)
        if cfg.num_experts > 0:
            p["ffn"] = m.init_moe(cfg, ks[1])
        elif cfg.d_ff > 0:
            p["ffn"] = m.init_mlp(cfg, ks[1])
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    keys = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.param_dtype)
    params: dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(dt),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab_size)) * cfg.d_model ** -0.5
        ).astype(dt)
    # unscanned leading layers (kimi's dense-FFN first layer, griffin's
    # leading recurrent pair); prefix blocks always use the dense MLP
    if cfg.prefix_pattern:
        dense_cfg = dataclasses.replace(cfg, num_experts=0)
        params["prefix"] = [
            _init_block(dense_cfg, kind, k)
            for kind, k in zip(cfg.prefix_pattern,
                               jax.random.split(keys[2],
                                                len(cfg.prefix_pattern)))]
    # scanned stack: one stacked tree per position in the cycle
    n = _n_cycles(cfg)
    stacked = []
    for i, kind in enumerate(cfg.cycle):
        ks = jax.random.split(keys[3 + (i % 5)], n)
        stacked.append(jax.vmap(lambda k, kind=kind: _init_block(cfg, kind, k))(ks))
    params["blocks"] = tuple(stacked)
    return params


def _n_cycles(cfg: ModelConfig) -> int:
    return cfg.n_cycles


def exact_param_count(cfg: ModelConfig) -> int:
    """Parameter count from the abstract init tree (no allocation).

    ``cfg.param_count()`` is analytic and exact for attention families but
    approximates xLSTM internals; the roofline uses this exact version."""
    import numpy as np
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


# ------------------------------------------------------------------ block
def _ffn(cfg: ModelConfig, p: dict, h: jax.Array,
         tp: tuple[str, int] | None = None):
    if cfg.num_experts > 0 and "router" in p["ffn"]:
        return m.moe(p["ffn"], h, cfg)
    return m.mlp(p["ffn"], h, cfg, tp=tp), {}


def block_full(cfg: ModelConfig, kind: str, p: dict, h: jax.Array,
               collect_cache: bool = True, *, pad_mask=None, true_len=None):
    """Full-sequence (train / prefill) block.  Returns (h, cache, aux).

    ``pad_mask``/``true_len`` (both set, or neither): the bucketed-prefill
    path — the sequence is end-padded to a jit bucket and every stateful
    construction (local rolling ring, recurrent/mLSTM/sLSTM carried
    state) must ignore positions past ``true_len``.  Attention math needs
    no masking beyond causality (pad keys sit *after* every real query)."""
    aux: dict = {}
    hn = m.rms_norm(h, p["norm1"], cfg.norm_eps)
    if kind in ("global", "local"):
        inner, cache = m.attention_full(p["inner"], hn, cfg,
                                        local=(kind == "local"),
                                        true_len=true_len)
    elif kind == "recurrent":
        inner, cache = m.recurrent_full(p["inner"], hn, cfg,
                                        pad_mask=pad_mask,
                                        true_len=true_len)
    elif kind == "mlstm":
        inner, cache = m.mlstm_full(p["inner"], hn, cfg, pad_mask=pad_mask)
    elif kind == "slstm":
        inner, cache = m.slstm_full(p["inner"], hn, cfg, pad_mask=pad_mask)
    else:
        raise ValueError(kind)
    if not collect_cache:
        cache = ()        # keep the train scan free of stacked cache ys
    if "ffn" in p:
        if cfg.parallel_block:
            f, aux = _ffn(cfg, p, hn)
            h = h + inner + f
        else:
            h = h + inner
            f, aux = _ffn(cfg, p, m.rms_norm(h, p["norm2"], cfg.norm_eps))
            h = h + f
    else:
        h = h + inner
    return h, cache, aux


def _join_block(cfg: ModelConfig, p: dict, h: jax.Array, hn: jax.Array,
                inner: jax.Array,
                tp: tuple[str, int] | None = None) -> jax.Array:
    """Residual + FFN tail shared by the dense and paged decode blocks."""
    if "ffn" in p:
        if cfg.parallel_block:
            f, _ = _ffn(cfg, p, hn, tp=tp)
            return h + inner + f
        h = h + inner
        f, _ = _ffn(cfg, p, m.rms_norm(h, p["norm2"], cfg.norm_eps), tp=tp)
        return h + f
    return h + inner


def block_step(cfg: ModelConfig, kind: str, p: dict, h: jax.Array,
               cache, pos):
    """Single-token decode block.  Returns (h, new_cache)."""
    hn = m.rms_norm(h, p["norm1"], cfg.norm_eps)
    if kind in ("global", "local"):
        inner, cache = m.attention_step(p["inner"], hn, cache, pos, cfg,
                                        local=(kind == "local"))
    elif kind == "recurrent":
        inner, cache = m.recurrent_step(p["inner"], hn, cache, cfg)
    elif kind == "mlstm":
        inner, cache = m.mlstm_step(p["inner"], hn, cache, cfg)
    elif kind == "slstm":
        inner, cache = m.slstm_step(p["inner"], hn, cache, cfg)
    else:
        raise ValueError(kind)
    return _join_block(cfg, p, h, hn, inner), cache


def block_step_paged(cfg: ModelConfig, kind: str, p: dict, h: jax.Array,
                     planes: dict, meta, cache, pos,
                     backend: str | None = None,
                     tp: tuple[str, int] | None = None):
    """Decode block against the device-resident paged KV store.

    Attention kinds read pages through the fused gather-decode kernel and
    return the new token's quantized K/V (for the on-device append);
    recurrent-kind blocks are unchanged — their fixed-size state rides in
    ``cache`` (the device state store) exactly like the dense path.
    ``tp=(axis_name, size)`` runs the fused kernel tensor-parallel over
    kv-head blocks inside a ``shard_map`` body (see
    ``modules.paged_attention_step``)."""
    if kind not in ATTN_KINDS:
        return block_step(cfg, kind, p, h, cache, pos)
    hn = m.rms_norm(h, p["norm1"], cfg.norm_eps)
    inner, new_kv = m.paged_attention_step(p["inner"], hn, planes, meta,
                                           pos, cfg, backend=backend, tp=tp)
    return _join_block(cfg, p, h, hn, inner, tp=tp), new_kv


# ---------------------------------------------------------------- forward
def embed_inputs(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    """tokens (+ frontend embeddings) -> [B, S, D] hidden states.

    Modality frontends are stubs per the assignment: ``patch_embeds`` /
    ``frame_embeds`` arrive precomputed."""
    parts = []
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        parts.append(batch["patch_embeds"])
    if cfg.frontend == "audio":
        h = batch["frame_embeds"]
        return h.astype(jnp.bfloat16)
    tok = params["embed"][batch["tokens"]].astype(jnp.bfloat16)
    if cfg.frontend == "vision":
        tok = tok * jnp.asarray(cfg.d_model ** 0.5, tok.dtype)  # gemma scale
    parts.append(tok)
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate([p.astype(jnp.bfloat16) for p in parts], axis=1)


def _scan_blocks(cfg: ModelConfig, params: dict, h: jax.Array, *,
                 remat: bool = True, collect_cache: bool = True,
                 pad_mask=None, true_len=None):
    """Scan the stacked cycle over the sequence hiddens (full mode)."""
    def cycle_fn(carry, p_cycle):
        h, lb, rz = carry
        # barrier: stops XLA from hoisting the body's bf16->f32 convert out
        # of the loop, which would store the stacked per-layer residuals in
        # fp32 (measured 2x memory on the backward stack)
        h = _residual_barrier(h)
        h = shd.constrain(h, "residual")
        caches = []
        for i, kind in enumerate(cfg.cycle):
            h, cache, aux = block_full(cfg, kind, p_cycle[i], h,
                                       collect_cache, pad_mask=pad_mask,
                                       true_len=true_len)
            h = shd.constrain(h, "residual")
            caches.append(cache)
            lb = lb + aux.get("load_balance", 0.0)
            rz = rz + aux.get("router_z", 0.0)
        return (h, lb, rz), tuple(caches)

    fn = jax.checkpoint(cycle_fn,
                        policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else cycle_fn
    (h, lb, rz), caches = jax.lax.scan(fn, (h, 0.0, 0.0), params["blocks"])
    return h, caches, {"load_balance": lb, "router_z": rz}


def forward(cfg: ModelConfig, params: dict, batch: dict, *,
            remat: bool = True, collect_cache: bool = False,
            last_only: bool = False, true_len=None):
    """Full forward.  Returns (logits, caches, aux).  ``collect_cache``
    is for prefill only — training must not stack per-layer caches.
    ``last_only`` computes the LM head for the final position only
    (prefill: the all-position full-vocab logits would otherwise
    materialize tens of GB per device).

    ``true_len`` (traced i32 scalar, bucketed prefill): tokens are
    end-padded to a power-of-two jit bucket so varied-length traffic
    reuses compiles; only the first ``true_len`` positions are real.
    Stateful layers freeze past the true end (see ``block_full``) and
    ``last_only`` slices the logits at ``true_len - 1`` — the masked
    last-token logits — instead of the padded sequence end."""
    h = shd.constrain(embed_inputs(cfg, params, batch), "residual")
    pad_mask = None
    if true_len is not None:
        true_len = jnp.asarray(true_len, jnp.int32)
        pad_mask = jnp.arange(h.shape[1]) >= true_len      # [S] bool
    prefix_caches = []
    for kind, p in zip(cfg.prefix_pattern, params.get("prefix", [])):
        h, cache, _ = block_full(cfg, kind, p, h, collect_cache,
                                 pad_mask=pad_mask, true_len=true_len)
        h = shd.constrain(h, "residual")
        prefix_caches.append(cache)
    h, caches, aux = _scan_blocks(cfg, params, h, remat=remat,
                                  collect_cache=collect_cache,
                                  pad_mask=pad_mask, true_len=true_len)
    if last_only:
        h = (h[:, -1:] if true_len is None else
             jax.lax.dynamic_slice_in_dim(h, true_len - 1, 1, axis=1))
    h = m.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(cfg, params, h)
    return logits, {"prefix": prefix_caches, "blocks": caches}, aux


def _head(cfg: ModelConfig, params: dict, h: jax.Array,
          tp: tuple[str, int] | None = None) -> jax.Array:
    if cfg.tie_embeddings:
        # tied embeddings stay dense (the same tensor serves the token
        # lookup in ``embed_inputs``), so the head einsum is always dense
        logits = jnp.einsum("bsd,vd->bsv", h,
                            params["embed"].astype(h.dtype))
    else:
        logits = m.proj(h, params["unembed"], "bsd,dv->bsv", tp=tp)
    return shd.constrain(logits.astype(F32), "logits")


def loss_fn(cfg: ModelConfig, logits: jax.Array, batch: dict,
            aux: dict | None = None) -> jax.Array:
    """Next-token CE (causal LM) or per-frame CE (encoder), fp32, masked."""
    labels = batch.get("labels")
    if cfg.is_encoder:
        targets, mask = labels, jnp.ones(labels.shape, F32)
    else:
        tok = batch["tokens"]
        targets = tok[:, 1:]
        mask = batch.get("loss_mask", jnp.ones_like(tok, F32))[:, 1:].astype(F32)
        n_img = logits.shape[1] - tok.shape[1]
        if n_img > 0:                       # vlm: image prefix predicts nothing
            logits = logits[:, n_img:]
        logits = logits[:, :-1]
    lse = jax.nn.logsumexp(logits, axis=-1)
    # one-hot contraction instead of take_along_axis: keeps the vocab dim
    # sharded (a sharded-dim gather would force a full fp32 logits
    # all-gather — tens of GB/device at 152k-256k vocabs)
    ll = jnp.sum(logits * jax.nn.one_hot(targets, logits.shape[-1],
                                         dtype=logits.dtype), axis=-1)
    nll = jnp.sum((lse - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    z_loss = 1e-4 * jnp.sum((lse * mask) ** 2) / jnp.maximum(jnp.sum(mask), 1.0)
    total = nll + z_loss
    if aux:
        total = total + 0.01 * aux.get("load_balance", 0.0) \
            + 0.001 * aux.get("router_z", 0.0)
    return total


# ------------------------------------------------------------------ cache
def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                      dtype=jnp.bfloat16):
    if kind in ("global", "local"):
        return m.init_attention_cache(cfg, batch, seq_len,
                                      local=(kind == "local"), dtype=dtype)
    if kind == "recurrent":
        return m.init_recurrent_cache(cfg, batch)
    if kind == "mlstm":
        return m.init_mlstm_cache(cfg, batch)
    if kind == "slstm":
        return m.init_slstm_cache(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Decode cache pytree: per cycle position, leaves stacked [n_cycles,...]."""
    n = _n_cycles(cfg)
    stacked = []
    for kind in cfg.cycle:
        one = _init_block_cache(cfg, kind, batch, seq_len, dtype)
        stacked.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), one))
    prefix = [_init_block_cache(cfg, kind, batch, seq_len, dtype)
              for kind in cfg.prefix_pattern]
    return {"prefix": prefix, "blocks": tuple(stacked)}


def decode_step(cfg: ModelConfig, params: dict, caches: dict,
                tokens: jax.Array, pos: jax.Array):
    """One decode step.  tokens: [B, 1] -> (logits [B, 1, V], new caches)."""
    h = params["embed"][tokens].astype(jnp.bfloat16)
    if cfg.frontend == "vision":
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    new_prefix = []
    for kind, p, c in zip(cfg.prefix_pattern, params.get("prefix", []),
                          caches["prefix"]):
        h, c = block_step(cfg, kind, p, h, c, pos)
        new_prefix.append(c)

    def cycle_fn(h, xs):
        p_cycle, c_cycle = xs
        new_c = []
        for i, kind in enumerate(cfg.cycle):
            h, c = block_step(cfg, kind, p_cycle[i], h, c_cycle[i], pos)
            new_c.append(c)
        return h, tuple(new_c)

    h, new_caches = jax.lax.scan(cycle_fn, h,
                                 (params["blocks"], caches["blocks"]))
    h = m.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(cfg, params, h)
    return logits, {"prefix": new_prefix, "blocks": new_caches}


# apack: hot-path-root(traced)
def decode_step_paged(cfg: ModelConfig, params: dict, planes: dict,
                      states: dict, meta: dict, tokens: jax.Array,
                      pos: jax.Array, backend: str | None = None,
                      tp: tuple[str, int] | None = None):
    """One decode step with the KV cache *device-resident in page form*.

    The dense-cache pytree of ``decode_step`` is replaced by:

    * ``planes`` — the ``DevicePoolPlanes`` dict (pool payload + stacked
      activation tables), shared by every attention layer;
    * ``states`` — the device state store (``init_state_store``): dense
      fixed-size recurrent/mLSTM/sLSTM states, ``{}`` at attention
      positions;
    * ``meta``  — per-step page-table metadata (``PagedKVCache.step_meta``):
      tiny i32 arrays, the only per-step host->device upload.

    Attention layers read pages through the fused gather-decode+attention
    kernel and *return* the new token's quantized K/V instead of writing a
    dense cache; the engine scatters those into the pool planes on-device
    (``device_append``).  Returns (logits, new_cache) where new_cache
    holds kv dicts at attention positions and updated states elsewhere.
    """
    h = params["embed"][tokens].astype(jnp.bfloat16)
    if cfg.frontend == "vision":
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    new_prefix = []
    for kind, p, mt, st in zip(cfg.prefix_pattern, params.get("prefix", []),
                               meta["prefix"], states["prefix"]):
        h, new = block_step_paged(cfg, kind, p, h, planes, mt, st, pos,
                                  backend, tp)
        new_prefix.append(new)

    def cycle_fn(h, xs):
        p_cycle, m_cycle, s_cycle = xs
        news = []
        for i, kind in enumerate(cfg.cycle):
            h, new = block_step_paged(cfg, kind, p_cycle[i], h, planes,
                                      m_cycle[i], s_cycle[i], pos, backend,
                                      tp)
            news.append(new)
        return h, tuple(news)

    h, new_blocks = jax.lax.scan(
        cycle_fn, h, (params["blocks"], meta["blocks"], states["blocks"]))
    h = m.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(cfg, params, h, tp=tp)
    return logits, {"prefix": new_prefix, "blocks": new_blocks}


def init_state_store(cfg: ModelConfig, batch: int) -> dict:
    """Device-resident store for recurrent-kind layer states (the paged
    decode path keeps them on device between steps — no per-step
    ``device_get``/re-upload).  Attention positions hold ``{}``: their
    state lives in the page pool."""
    n = cfg.n_cycles
    prefix = [({} if kind in ATTN_KINDS
               else _init_block_cache(cfg, kind, batch, 1))
              for kind in cfg.prefix_pattern]
    blocks = []
    for kind in cfg.cycle:
        if kind in ATTN_KINDS:
            blocks.append({})
        else:
            one = _init_block_cache(cfg, kind, batch, 1)
            blocks.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), one))
    return {"prefix": prefix, "blocks": tuple(blocks)}


def states_from_step(cfg: ModelConfig, new_cache: dict) -> dict:
    """Project ``decode_step_paged``'s output onto the state-store shape:
    keep the updated recurrent-kind states (still on device), drop the
    attention entries (their K/V went to the pool via the append)."""
    prefix = [({} if kind in ATTN_KINDS else c)
              for kind, c in zip(cfg.prefix_pattern, new_cache["prefix"])]
    blocks = tuple(({} if kind in ATTN_KINDS else c)
                   for kind, c in zip(cfg.cycle, new_cache["blocks"]))
    return {"prefix": prefix, "blocks": blocks}


def device_append(cfg: ModelConfig, planes: dict, new_cache: dict,
                  targets: dict,
                  tp: tuple[str, int] | None = None) -> dict:
    """On-device page append: scatter every attention layer's new-token
    K/V (from ``decode_step_paged``) into the HOT token planes at the
    (page, offset) slots claimed by ``PagedKVCache.claim_append_targets``.

    Pure jnp under jit — one dynamic-slice scatter per plane per step, no
    host round-trip.  Inactive slots carry the out-of-range page sentinel
    and are dropped by ``mode="drop"``.

    ``tp=(axis_name, size)`` (inside a ``shard_map`` body): the token
    planes hold only this model shard's kv-head block, while the model
    computed the full-head K/V on every model shard — slice the local
    block at ``axis_index * h_local`` before scattering."""
    rows = {"k": [], "v": [], "k_scale": [], "v_scale": []}
    pids, offs = [], []

    def add(entry, tg):
        pid, off = tg
        for f in rows:
            x = entry[f]                 # [B, ...] or [n_stack, B, ...]
            tail = 2 if f in ("k", "v") else 1    # [H, dh] vs [H]
            rows[f].append(x.reshape(-1, *x.shape[x.ndim - tail:]))
        pids.append(jnp.asarray(pid).reshape(-1))
        offs.append(jnp.asarray(off).reshape(-1))

    for kind, entry, tg in zip(cfg.prefix_pattern, new_cache["prefix"],
                               targets["prefix"]):
        if kind in ATTN_KINDS:
            add(entry, tg)
    for c, kind in enumerate(cfg.cycle):
        if kind in ATTN_KINDS:
            add(new_cache["blocks"][c], targets["blocks"][c])
    if not pids:
        return planes
    pid = jnp.concatenate(pids).astype(jnp.int32)
    off = jnp.concatenate(offs).astype(jnp.int32)
    vals = {f: jnp.concatenate(rows[f]) for f in rows}
    if tp is not None and tp[1] > 1:
        h_loc = planes["tok_k"].shape[2]
        h0 = (jax.lax.axis_index(tp[0]) * h_loc).astype(jnp.int32)
        for f in vals:
            vals[f] = jax.lax.dynamic_slice_in_dim(vals[f], h0, h_loc,
                                                   axis=1)
    out = dict(planes)
    out["tok_k"] = planes["tok_k"].at[pid, off].set(vals["k"], mode="drop")
    out["tok_v"] = planes["tok_v"].at[pid, off].set(vals["v"], mode="drop")
    out["tok_sk"] = planes["tok_sk"].at[pid, off].set(vals["k_scale"],
                                                      mode="drop")
    out["tok_sv"] = planes["tok_sv"].at[pid, off].set(vals["v_scale"],
                                                      mode="drop")
    return out


# --------------------------------------------------------- packed weights
def _pack_quantize(arr: np.ndarray, n_contract: int):
    """Quantize a dense >=2-D tensor with the shared serving convention
    (``quant.quantize_symmetric(..., axis=-1)`` on the ORIGINAL shape —
    identical to ``serve.compress_params``), then fold to the 2-D
    [K, N_flat] matmul view.  The per-last-axis scale is constant along
    every contracted (leading) axis, so tiling it across the flattened
    output axes is exact for the matmul dequantization."""
    from repro.core import quant
    shape = arr.shape
    q, qp = quant.quantize_symmetric(jnp.asarray(arr, jnp.float32), axis=-1)
    k = int(np.prod(shape[:n_contract]))
    nf = int(np.prod(shape[n_contract:]))
    q2 = np.asarray(q).reshape(k, nf)
    sc = np.broadcast_to(np.asarray(qp.scale, np.float32),
                         shape).reshape(k, nf)[0]
    return q2, np.ascontiguousarray(sc)


def pack_weights(cfg: ModelConfig, params: dict, *,
                 min_size: int | None = None,
                 tile_k: int | None = None) -> tuple[dict, dict]:
    """Convert the param tree's large projection/FFN matrices to
    device-resident APack planes (``modules.PackedWeight``), making the
    compressed form the *live* weight store for serving.

    Packed sites: attention wq/wk/wv (contract d) and wo (contract
    h, dh), non-MoE FFN w_up/w_gate/w_down, and the untied lm head.
    Dense by design: the embedding (it serves the token *lookup*), MoE
    expert stacks and recurrent/mLSTM/sLSTM internals (their einsum
    structure doesn't reduce to the [K, N] projection the fused kernel
    serves), and anything under ``min_size`` elements (table + scale
    overhead would beat the savings).

    Scanned stacks are packed per layer (per-layer weight-mode tables
    track per-layer statistics) and re-stacked with a leading layer axis
    (``stack_compressed``) so ``lax.scan`` drives them unchanged.

    Returns ``(packed_params, stats)`` — stats carries the byte
    accounting the engine's ``weight_stats`` reports (dense/native,
    int8, payload, slotted, scale streams)."""
    from repro.kernels import decompress_matmul as dm
    if min_size is None:
        min_size = dm.DEFAULT_WEIGHT_MIN_SIZE

    stats = {"packed_tensors": 0, "native_bytes": 0, "int8_bytes": 0,
             "payload_bytes": 0, "slotted_bytes": 0, "scale_bytes": 0}

    def _account(cws, arr):
        stats["packed_tensors"] += 1
        stats["native_bytes"] += arr.size * arr.dtype.itemsize
        stats["int8_bytes"] += arr.size
        for cw in cws:
            stats["payload_bytes"] += -(-cw.payload_bits // 8)
            stats["slotted_bytes"] += (cw.sym_plane.size * 4
                                       + cw.ofs_plane.size * 4
                                       + cw.stored.size * 4)
            stats["scale_bytes"] += cw.scale.size * 4

    def _tile_k(k: int) -> int:
        return tile_k or min(dm.DEFAULT_TILE_K, k)

    def _pack_tensor(w, n_contract):
        arr = np.asarray(jax.device_get(w))
        q2, sc = _pack_quantize(arr, n_contract)
        cw = dm.compress_quantized(q2, sc, _tile_k(q2.shape[0]))
        _account([cw], arr)
        return m.PackedWeight(cw, tuple(arr.shape), n_contract,
                              str(arr.dtype))

    def _pack_stacked(w, n_contract):
        arr = np.asarray(jax.device_get(w))           # [L, ...]
        cws = []
        for l in range(arr.shape[0]):
            q2, sc = _pack_quantize(arr[l], n_contract)
            cws.append(dm.compress_quantized(q2, sc, _tile_k(q2.shape[0])))
        _account(cws, arr)
        return m.PackedWeight(dm.stack_compressed(cws), tuple(arr.shape[1:]),
                              n_contract, str(arr.dtype))

    def _elig(w, stacked):
        per_layer = int(np.prod(w.shape[1:] if stacked else w.shape))
        return per_layer >= min_size

    def _pack_block(blk, kind, stacked):
        pack = _pack_stacked if stacked else _pack_tensor
        out = dict(blk)
        if kind in ATTN_KINDS:
            inner = dict(blk["inner"])
            for name, nc in (("wq", 1), ("wk", 1), ("wv", 1), ("wo", 2)):
                if _elig(inner[name], stacked):
                    inner[name] = pack(inner[name], nc)
            out["inner"] = inner
        if "ffn" in blk and "router" not in blk["ffn"]:
            ffn = dict(blk["ffn"])
            for name in ("w_up", "w_gate", "w_down"):
                if name in ffn and _elig(ffn[name], stacked):
                    ffn[name] = pack(ffn[name], 1)
            out["ffn"] = ffn
        return out

    out = dict(params)
    if "unembed" in params and _elig(params["unembed"], False):
        out["unembed"] = _pack_tensor(params["unembed"], 1)
    if "prefix" in params:
        out["prefix"] = [_pack_block(b, kind, False)
                         for kind, b in zip(cfg.prefix_pattern,
                                            params["prefix"])]
    out["blocks"] = tuple(_pack_block(b, kind, True)
                          for kind, b in zip(cfg.cycle, params["blocks"]))
    return out, stats


def packed_param_specs(params: dict, n_model: int):
    """Param-tree PartitionSpecs for the mesh step: dense leaves
    replicate (``P()``, the pre-packing behavior), PACKED plane leaves
    K-split over "model" when the layout divides (``sharding.
    packed_leaf_pspecs``) — the stream axis is kt-major, so a contiguous
    stream shard is a contiguous K-tile range and ``modules.packed_proj``
    reassembles the row-parallel partials with a ``psum``."""
    from jax.sharding import PartitionSpec as P

    def one(x):
        if not isinstance(x, m.PackedWeight):
            return P()
        cw = x.cw
        nk = cw.k_pad // cw.tile_k
        splittable = (n_model > 1 and cw.k == cw.k_pad
                      and nk % n_model == 0)
        leaves, treedef = jax.tree_util.tree_flatten(x)
        return jax.tree_util.tree_unflatten(
            treedef, shd.packed_leaf_pspecs(leaves, splittable=splittable))

    flat, treedef = jax.tree_util.tree_flatten(
        params, is_leaf=lambda x: isinstance(x, m.PackedWeight))
    return jax.tree_util.tree_unflatten(treedef, [one(x) for x in flat])


# ------------------------------------------------ mesh-sharded decode step
def mesh_axis_sizes(mesh) -> tuple[int, int]:
    """(n_data, n_model) of a serving mesh; absent axes count as 1."""
    shape = dict(mesh.shape)
    return int(shape.get("data", 1)), int(shape.get("model", 1))


def _localize_meta(cfg: ModelConfig, meta: dict, p_loc, d0):
    """Global page ids -> this data shard's local plane indices.

    Shard ``s`` owns the contiguous page range ``[s*p_loc, (s+1)*p_loc)``
    (matching the pool's per-shard free lists), and the engine binds every
    request to exactly one shard — so an *active* slot of this shard only
    references owned pages.  Masked entries (state == FREE, or rows of
    slots bound to other shards) may carry any global id; ``clip`` keeps
    them in-range and the state mask makes their value irrelevant."""
    def one(md):
        if not md:
            return md
        out = dict(md)
        out["pid"] = jnp.clip(md["pid"] - d0, 0, p_loc - 1)
        return out

    return {"prefix": [one(md) for md in meta["prefix"]],
            "blocks": tuple(one(md) for md in meta["blocks"])}


def _localize_targets(cfg: ModelConfig, targets: dict, p_loc, d0):
    """Append targets -> local plane indices; anything this shard does not
    own (idle-slot sentinels, other shards' pages) maps to the local
    out-of-range sentinel ``p_loc`` and is dropped by the scatter's
    ``mode="drop"`` — each shard appends only into its own page range."""
    def one(tg):
        if tg is None:
            return None
        pid, off = tg
        lp = pid - d0
        lp = jnp.where((lp >= 0) & (lp < p_loc), lp, p_loc)
        return (lp.astype(jnp.int32), off)

    return {"prefix": [one(tg) for tg in targets["prefix"]],
            "blocks": tuple(one(tg) for tg in targets["blocks"])}


def _paged_tree_specs(cfg: ModelConfig, prefix_spec, block_spec,
                      empty):
    """Spec pytree matching the state/meta/target tree shapes: attention
    positions get the batch-sharded spec, recurrent-kind positions the
    empty node their argument carries (``{}`` for states/meta, ``None``
    for targets).  Prefix leaves are [B, ...], scanned block leaves
    [n_stack, B, ...] — hence the two specs."""
    prefix = [(prefix_spec if kind in ATTN_KINDS else empty)
              for kind in cfg.prefix_pattern]
    blocks = tuple((block_spec if kind in ATTN_KINDS else empty)
                   for kind in cfg.cycle)
    return {"prefix": prefix, "blocks": blocks}


def _state_specs(cfg: ModelConfig, P):
    """State-store specs: batch-sharded over "data" at every
    recurrent-kind position, ``{}`` at attention positions (their state
    lives in the page pool)."""
    prefix = [({} if kind in ATTN_KINDS else P("data"))
              for kind in cfg.prefix_pattern]
    blocks = tuple(({} if kind in ATTN_KINDS else P(None, "data"))
                   for kind in cfg.cycle)
    return {"prefix": prefix, "blocks": blocks}


def build_sharded_step(cfg: ModelConfig, mesh, *, backend: str | None = None,
                       params: dict | None = None):
    """The mesh-sharded fused decode step: ONE ``jit(shard_map(...))``
    combining ``decode_step_paged`` + ``device_append`` +
    ``states_from_step`` per step.

    Partitioning (DESIGN.md §11): decode jobs data-parallel over "data"
    (batch rows, state store, step meta, append targets and the page
    planes all shard with their jobs — each data shard owns a contiguous
    page range matching its free list), kv-heads tensor-parallel over
    "model" for the fused gather-decode-attention kernel.  PACKED planes
    replicate over "model" (the APack stream layout interleaves heads);
    each model shard decodes the full page and slices its local head
    block, then an ``all_gather`` over "model" reassembles head-major
    accumulators before the output projection.  Per-kv-head attention
    has no cross-head reduction and the gather restores exact head
    order, so the partitioning changes no arithmetic; a TPU may still
    round the unsharded single-device step differently (DESIGN.md §11).

    Returns ``step(params, planes, states, meta, tokens, pos, targets)
    -> (logits, toks, planes', states')`` where ``toks`` is the greedy
    argmax over the final-position logits, computed *inside* the device
    program: the engine's per-step host pull shrinks from a
    ``[batch, vocab]`` logits gather (plus an eager cross-shard argmax
    dispatch) to ``batch`` int32s.  Targets must be claimed *before*
    the call (host metadata is independent of the decode output), which
    is what lets the whole step stay a single device program with zero
    ``device_get`` per shard.

    ``params``: pass the (possibly APack-packed) param tree to derive
    per-leaf weight specs — packed plane leaves K-split over "model"
    where the layout divides (see ``packed_param_specs``); ``None``
    keeps the legacy fully-replicated ``P()``."""
    from jax.sharding import PartitionSpec as P
    n_data, n_model = mesh_axis_sizes(mesh)
    if n_model > 1 and cfg.num_kv_heads % n_model:
        raise ValueError(
            f"num_kv_heads={cfg.num_kv_heads} must divide over the "
            f"{n_model}-way model axis for tensor-parallel paged decode")
    tp = ("model", n_model) if n_model > 1 else None

    def _body(params, planes, states, meta, tokens, pos, targets):
        p_loc = planes["tok_k"].shape[0]
        d0 = (jax.lax.axis_index("data") * p_loc).astype(jnp.int32)
        logits, new_cache = decode_step_paged(
            cfg, params, planes, states,
            _localize_meta(cfg, meta, p_loc, d0), tokens, pos,
            backend=backend, tp=tp)
        planes2 = device_append(
            cfg, planes, new_cache,
            _localize_targets(cfg, targets, p_loc, d0), tp=tp)
        toks = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return logits, toks, planes2, states_from_step(cfg, new_cache)

    plane_specs = shd.plane_pspecs()
    state_specs = _state_specs(cfg, P)
    meta_specs = _paged_tree_specs(cfg, P("data"), P(None, "data"), {})
    target_specs = _paged_tree_specs(cfg, P("data"), P(None, "data"), None)
    param_specs = (P() if params is None
                   else packed_param_specs(params, n_model))
    stepped = jax.shard_map(
        _body, mesh=shd.auto_mesh(mesh),
        in_specs=(param_specs, plane_specs, state_specs, meta_specs,
                  P("data"), P("data"), target_specs),
        out_specs=(P("data"), P("data"), plane_specs, state_specs),
        check_vma=False)
    return jax.jit(stepped)


def extend_caches(cfg: ModelConfig, caches: dict, max_len: int) -> dict:
    """Pad prefill caches (global-attention k/v of length S) to decode
    capacity ``max_len``.  Rolling/local and recurrent caches are already
    fixed-size."""
    def pad(kind, cache):
        if kind == "global":
            s = cache["k"].shape[-3]
            if s < max_len:
                def pad_one(name, v):
                    # seq axis: ndim-3 for k/v, ndim-2 for per-head scales
                    ax = v.ndim - (2 if name.endswith("_scale") else 3)
                    widths = [(0, 0)] * v.ndim
                    widths[ax] = (0, max_len - s)
                    return jnp.pad(v, widths)
                return {k: pad_one(k, v) for k, v in cache.items()}
        return cache

    blocks = tuple(pad(kind, c)
                   for kind, c in zip(cfg.cycle, caches["blocks"]))
    prefix = [pad(kind, c)
              for kind, c in zip(cfg.prefix_pattern, caches["prefix"])]
    return {"prefix": prefix, "blocks": blocks}


def prefill(cfg: ModelConfig, params: dict, batch: dict,
            max_len: int | None = None):
    """Process a prompt, returning (last-position logits, decode caches)."""
    logits, caches, _ = forward(cfg, params, batch, remat=False,
                                collect_cache=True, last_only=True)
    if max_len is not None:
        caches = extend_caches(cfg, caches, max_len)
    return logits, caches


# ------------------------------------------------------- paged APack KV
ATTN_KINDS = ("global", "local")
STATE_KINDS = ("recurrent", "mlstm", "slstm")

# Chunk shapes of the batched page encode (``PagedKVCache._pack_pending``),
# in page-kinds (the K or the V half of one page).  A decode step seals one
# page per layer of the few sequences that crossed a page boundary; an
# admission one per prompt page and layer.  The encode's serial scan takes
# as many steps whatever the chunk holds, so a large chunk costs about as
# much as ``SEAL_SMALL_PER_LARGE`` small ones.  Both sizes are even.
SEAL_CHUNK_SMALL = 16
SEAL_CHUNK_LARGE = 128
SEAL_SMALL_PER_LARGE = 4


def seal_chunks(n: int) -> list[int]:
    """Chunk sizes that cover ``n`` page-kinds: large chunks, and small
    ones for the rest where fewer than ``SEAL_SMALL_PER_LARGE`` do."""
    large, rest = divmod(n, SEAL_CHUNK_LARGE)
    small = -(-rest // SEAL_CHUNK_SMALL)
    if small >= SEAL_SMALL_PER_LARGE:
        large, small = large + 1, 0
    return [SEAL_CHUNK_LARGE] * large + [SEAL_CHUNK_SMALL] * small


def _layer_kinds(cfg: ModelConfig) -> list[str]:
    """Network-layer kind list: prefix layers first, then the scanned
    stack in layer order ``n_prefix + j * n_cycle + c``."""
    return list(cfg.prefix_pattern) + [
        cfg.cycle[c] for j in range(cfg.n_cycles)
        for c in range(len(cfg.cycle))]


class DevicePoolPlanes:
    """Device-resident mirror of the ``KVPagePool`` storage planes.

    Kind-split (``_k`` / ``_v`` arrays instead of a leading kind axis) so
    the fused kernel's BlockSpecs index pages directly.  The decode hot
    path reads these and the on-device append writes them; the host pool
    stays the metadata + seal/pack source of truth, synced per *page
    event* (seal, pack, calibration, prefill ingest) rather than per step
    — that sync is the only payload that ever crosses host<->device in
    steady-state decode."""

    def __init__(self, pool: m.KVPagePool, n_tables: int, mesh=None):
        p, ps = pool.num_pages, pool.page_size
        h, dh, s = pool.kv_heads, pool.head_dim, pool.n_streams
        self.n_tables = n_tables
        self.mesh = mesh
        z = jnp.zeros
        self.planes: dict[str, jax.Array] = {
            "tok_k": z((p, ps, h, dh), jnp.int8),
            "tok_v": z((p, ps, h, dh), jnp.int8),
            "tok_sk": z((p, ps, h), F32),
            "tok_sv": z((p, ps, h), F32),
            "cold_k": z((p, ps, h, dh), jnp.int8),
            "cold_v": z((p, ps, h, dh), jnp.int8),
            "pscale_k": z((p, h), F32),
            "pscale_v": z((p, h), F32),
            "sym_k": z((p, pool.sym_words, s), jnp.uint32),
            "sym_v": z((p, pool.sym_words, s), jnp.uint32),
            "ofs_k": z((p, pool.ofs_words, s), jnp.uint32),
            "ofs_v": z((p, pool.ofs_words, s), jnp.uint32),
            "stored_k": z((p, s), jnp.int32),
            "stored_v": z((p, s), jnp.int32),
            "vm": z((n_tables, 17), jnp.int32),
            "ol": z((n_tables, 16), jnp.int32),
            "cum": z((n_tables, 17), jnp.int32),
        }
        self.repin()

    def repin(self) -> None:
        """Re-place every plane under the mesh partitioning rules
        (``sharding.plane_pspecs``): pages shard over "data" (matching the
        per-shard free lists), dense payload heads over "model", PACKED
        streams and tables replicated over "model".  Called at
        construction and after host-sync *events* — eager ``.at[].set``
        scatters may leave an event-updated plane with a degraded layout,
        and repinning there keeps the steady-state step free of implicit
        reshards.  No-op without a mesh."""
        if self.mesh is None:
            return
        sh = shd.plane_shardings(self.mesh, self.planes)
        # only re-place planes whose layout actually degraded: an event
        # flush typically touches one state's planes, and device_put on
        # the 17 untouched ones is pure per-event dispatch overhead
        self.planes = {
            k: v if v.sharding.is_equivalent_to(sh[k], v.ndim)
            else jax.device_put(v, sh[k])
            for k, v in self.planes.items()}

    def ensure_table_capacity(self, n_rows: int) -> bool:
        """Grow the device table planes to hold ``n_rows`` rows (doubling,
        so a long-running refresh schedule causes O(log generations) plane
        reallocations / decode-jit recompiles, each at a refresh boundary
        — never in the steady-state loop).  Returns True if reallocated;
        the caller must then re-upload every table row."""
        if n_rows <= self.n_tables:
            return False
        cap = self.n_tables
        while cap < n_rows:
            cap *= 2
        self.n_tables = cap
        z = jnp.zeros
        self.planes["vm"] = z((cap, 17), jnp.int32)
        self.planes["ol"] = z((cap, 16), jnp.int32)
        self.planes["cum"] = z((cap, 17), jnp.int32)
        return True


class PagedKVCache:
    """Paged, APack-compressed KV cache for ``kv_cache_dtype="apack-int8"``.

    Supports heterogeneous stacks — any mix of ``global`` / ``local``
    attention and ``recurrent`` / ``mlstm`` / ``slstm`` fixed-state layers,
    scanned or prefix.  Three stream kinds:

    * **global** attention layers: the off-chip store is a
      ``modules.KVPagePool`` shared by every layer; each request owns a
      per-layer list of page ids (the page table).  Token ``t`` of a
      sequence lives at page ``t // page_size`` offset ``t % page_size`` —
      the same absolute layout as the dense cache, so ``materialize`` can
      rebuild the exact int8 cache pytree ``decode_step`` consumes.
    * **local** (rolling-window) attention layers: same page layout, plus
      page-granular eviction — once every token in the oldest page has
      rolled out of the attention window the page returns to the free list
      (``pool.evict``).  A rolling layer therefore holds at most
      ``window_pages`` pages regardless of sequence length, and
      ``materialize`` rebuilds the rolling *ring* layout (slot
      ``pos % ring``) ``attention_step`` expects.
    * **recurrent/mLSTM/sLSTM state** layers: fixed-size f32 states stay
      dense on the hot path (stored per request, stitched into the
      materialized pytree every step) and are APack-compressed losslessly
      with weight-mode tables only at snapshot boundaries
      (``snapshot_state`` / ``restore_state`` — the engine
      checkpoint/preemption path).

    Compression policy (paper §VI activations): each attention layer ×
    {K, V} gets its own activation-mode table, calibrated *online* from
    the histogram of the first ``calib_pages`` sealed pages of that layer
    — the probability slack for empty ranges guarantees any later,
    unprofiled value stays encodable (lossless).  Pages sealed before
    calibration completes stay COLD (uncompressed int8, page-granular
    scales) and are retro-packed the moment the table exists.  Reads of
    PACKED pages go through the Pallas gather-decode kernel
    (``kernels/paged_decode.py``), batched across *all* layers per K/V
    kind via the per-page table-id prefetch vector — compressed words are
    the only thing that crosses the "off-chip" boundary, which is where
    the traffic saving in ``self.traffic`` comes from.
    """

    def __init__(self, cfg: ModelConfig, num_pages: int, *,
                 page_size: int = 16, calib_pages: int = 4,
                 elems_per_stream: int = 128, backend: str | None = None,
                 refresh_every_pages: int | None = None,
                 refresh_threshold: float = 0.15,
                 refresh_min_pages: int = 4,
                 verify_on_repack: bool = False,
                 transfer_retries: int = 2,
                 n_shards: int = 1):
        self.cfg = cfg
        self.page_size = page_size
        self.calib_pages = calib_pages
        self.backend = backend
        # table-refresh policy (drift-adaptive serving): refresh a layer's
        # tables when the drift sketch's expected coded size regresses
        # ``refresh_threshold`` past the calibration-time expectation, or
        # unconditionally every ``refresh_every_pages`` sealed pages; both
        # triggers arm only after ``refresh_min_pages`` pages of sketch.
        # Triggers are only *checked* when maybe_refresh()/refresh_step()
        # is called (the engine's kv_refresh knob) — sketches always
        # accumulate, so enabling refresh mid-serve needs no warmup.
        self.refresh_every_pages = refresh_every_pages
        self.refresh_threshold = refresh_threshold
        self.refresh_min_pages = refresh_min_pages
        self.n_prefix = len(cfg.prefix_pattern)
        self.n_cycle = len(cfg.cycle)
        self.n_stack = cfg.n_cycles
        self.layer_kinds = _layer_kinds(cfg)
        self.n_layers = len(self.layer_kinds)
        self.attn_layers = [i for i, k in enumerate(self.layer_kinds)
                            if k in ATTN_KINDS]
        self.local_layers = [i for i, k in enumerate(self.layer_kinds)
                             if k == "local"]
        self.state_layers = [i for i, k in enumerate(self.layer_kinds)
                             if k in STATE_KINDS]
        self.window = cfg.window_size
        self.pool = m.KVPagePool(num_pages, page_size, cfg.num_kv_heads,
                                 cfg.head_dim, elems_per_stream,
                                 n_shards=n_shards)
        # mesh-sharded serving: every request is bound to one page shard
        # (= one "data" mesh slice) at admission; its pages allocate from
        # that shard's free list only, so admission and the on-device
        # append never serialize on a global lock and every page a data
        # shard's kernel reads lives in its own contiguous page range.
        self.n_shards = n_shards
        self.request_shard: dict[int, int] = {}
        # per (layer, kind=K/V): activation-mode table + calibration state
        self.tables: list[list] = [[None, None] for _ in range(self.n_layers)]
        self.hists = np.zeros((self.n_layers, 2, 256), np.int64)
        self.hist_pages = np.zeros((self.n_layers, 2), np.int32)
        self._cold: list[set[int]] = [set() for _ in range(self.n_layers)]
        # sealed COLD pages waiting for ``_pack_pending``: pid -> layer
        self._pending: dict[int, int] = {}
        self._packed: list[set[int]] = [set() for _ in range(self.n_layers)]
        self._table_stack = None   # lazy [(G+1)*2*n_layers, ...] np stack
        # generation-versioned table pool: ``self.tables`` is always the
        # *current* generation; each refresh snapshots the previous set so
        # pages packed under older tables keep decoding bit-exactly while
        # the budgeted re-pack migrates them.  Table row addressing is
        # ``paged_decode.table_row(gen, layer, kind, n_layers)``.
        self.generation = 0
        self._gen_snapshots: list[list[list]] = []   # per past gen: [L][2]
        # generation -> row-block *slot* in the stacked table pool.  Rows
        # are addressed through this indirection so ``compact_table_rows``
        # can reclaim the 2*n_layers block of a generation that no longer
        # owns any PACKED page (resident or spilled) — the stacked pool
        # stops growing monotonically with refresh count.  Generation 0 is
        # always live (HOT/COLD pages carry gen 0 in their meta rows).
        self.gen_rows: dict[int, int] = {0: 0}
        self.table_gen = np.zeros(self.n_layers, np.int32)
        self.page_gen = np.zeros(num_pages, np.int32)
        # page metadata alongside page_gen: integrity checksum of the
        # PACKED planes (stamped at pack/re-pack/unspill, verified on
        # unspill and — when ``verify_on_repack`` — before every re-pack
        # decode) and a read-clock LRU stamp driving cold-first spill
        self.page_crc = np.zeros(num_pages, np.uint32)
        self.page_last_read = np.zeros(num_pages, np.int64)
        self._read_clock = 0
        self.verify_on_repack = verify_on_repack
        # host spill tier: compressed pages of preempted requests parked
        # off-pool (negative page-table entries are ``-handle - 1`` refs)
        self.spill_tier = m.HostSpillTier()
        # fault injection (serve/faults.py) + bounded transfer retry
        self.faults = None
        self.transfer_retries = transfer_retries
        # drift monitor: symbol-frequency sketch of pages sealed since the
        # layer's last (re)calibration + the expected bits/value its
        # current table promised on the histogram it was built from
        self.drift_hists = np.zeros((self.n_layers, 2, 256), np.int64)
        self.drift_pages = np.zeros(self.n_layers, np.int32)
        self.calib_bits = np.zeros((self.n_layers, 2), np.float64)
        self._drift_changed: set[int] = set()   # sketch moved since check
        self._repack_queue: deque[tuple[int, int]] = deque()
        self._state_templates: dict[str, dict] = {}
        self.page_tables: dict[int, list[list[int]]] = {}
        self.page_base: dict[int, list[int]] = {}   # evicted-page count
        self.states: dict[int, dict[int, dict[str, np.ndarray]]] = {}
        self.seq_len: dict[int, int] = {}
        self.traffic = {"kv_raw_bytes": 0, "kv_read_bytes": 0,
                        "kv_table_bytes": 0, "kv_pages_packed": 0,
                        "kv_seal_encode_calls": 0,
                        "kv_raw_bytes_global": 0, "kv_read_bytes_global": 0,
                        "kv_raw_bytes_local": 0, "kv_read_bytes_local": 0,
                        "state_raw_bytes": 0, "state_snapshot_bytes": 0,
                        "state_snapshots": 0,
                        # table-refresh re-pack traffic: the read of the
                        # old planes + write of the new ones.  Kept OUT of
                        # kv_read_bytes/kv_raw_bytes — a re-pack is not an
                        # attention read, and folding it in would
                        # double-count the page against the stream ratios
                        "kv_repack_read_bytes": 0, "kv_repack_write_bytes": 0,
                        "kv_repack_pages": 0, "kv_repack_kept": 0,
                        "kv_refresh_count": 0,
                        # spill / readahead traffic: host-tier writes of
                        # compressed pages and the batched h2d that brings
                        # them back.  Own streams, same rule as repack —
                        # NEVER folded into the attention-read ratios
                        "kv_spill_bytes": 0, "kv_spill_raw_bytes": 0,
                        "kv_spill_pages": 0, "kv_spill_calls": 0,
                        "kv_readahead_bytes": 0, "kv_readahead_pages": 0,
                        "kv_readahead_calls": 0,
                        "kv_integrity_failures": 0, "kv_quarantined_pages": 0,
                        "kv_transfer_drops": 0, "kv_transfer_retries": 0}
        # host<->device transfer accounting: every byte the KV path moves
        # across the boundary goes through _fetch/_put so the decode bench
        # and the steady-state zero-device_get guard have ground truth
        self.transfers = {"h2d_bytes": 0, "d2h_bytes": 0,
                          "h2d_calls": 0, "d2h_calls": 0}
        # device-resident mode (fused decode): plane mirror + state store
        self.dev: DevicePoolPlanes | None = None
        self.dev_states: dict | None = None
        self._dirty: set[int] = set()       # pages needing a device sync
        self._tables_dirty = False
        self._page_pull = None              # cached jitted seal-pull gather
        self._plane_push = None             # cached jitted event-sync scatter

    # ------------------------------------------------------------ sizing
    def pages_per_seq(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    @property
    def window_pages(self) -> int:
        """Max live pages of a rolling layer: the window can straddle one
        more page boundary than ``ceil(window / page_size)`` covers."""
        return -(-self.window // self.page_size) + 1

    def pages_needed(self, n_tokens: int) -> int:
        """Pool pages a request storing ``n_tokens`` occupies, summed over
        layers with per-kind reservation: global layers hold the full
        sequence, rolling layers at most ``window_pages``, recurrent-kind
        layers none (their state is not paged)."""
        return self.pages_for_config(self.cfg, n_tokens, self.page_size)

    @staticmethod
    def pages_for_config(cfg: ModelConfig, n_tokens: int,
                         page_size: int) -> int:
        """Worst-case per-request page count (shared with the engine's
        pool sizing, so the default pool can be computed pre-construction)."""
        full = -(-n_tokens // page_size)
        rolling = min(full, -(-cfg.window_size // page_size) + 1)
        total = 0
        for kind in _layer_kinds(cfg):
            if kind == "global":
                total += full
            elif kind == "local":
                total += rolling
        return total

    @property
    def free_pages(self) -> int:
        return self.pool.free_count

    def kv_ratio(self) -> float | None:
        """Cumulative compressed-vs-raw KV read traffic (< 1.0 is a win).

        ``None`` before any read has moved a byte: reporting 1.0 there
        would claim break-even for an engine that has not served anything
        (and would hide table overhead already accrued)."""
        raw = self.traffic["kv_raw_bytes"]
        if raw == 0:
            return None
        return (self.traffic["kv_read_bytes"]
                + self.traffic["kv_table_bytes"]) / raw

    def stream_stats(self) -> dict:
        """Per-stream accounting: global KV reads, rolling/local KV reads,
        recurrent-state snapshot bytes.  Stream ratios are payload-only
        (table overhead is global, counted once in ``kv_ratio``)."""
        out = {}
        for kind in ("global", "local"):
            raw = self.traffic[f"kv_raw_bytes_{kind}"]
            read = self.traffic[f"kv_read_bytes_{kind}"]
            out[kind] = {"raw_bytes": raw, "read_bytes": read,
                         "ratio": (read / raw) if raw else None}
        raw = self.traffic["state_raw_bytes"]
        comp = self.traffic["state_snapshot_bytes"]
        out["state"] = {"raw_bytes": raw, "snapshot_bytes": comp,
                        "snapshots": self.traffic["state_snapshots"],
                        "ratio": (comp / raw) if raw else None}
        # table-refresh re-pack overhead: its own stream (read old planes
        # + write new ones), never folded into the read-path ratios above
        out["repack"] = {
            "read_bytes": self.traffic["kv_repack_read_bytes"],
            "write_bytes": self.traffic["kv_repack_write_bytes"],
            "pages": self.traffic["kv_repack_pages"],
            "kept": self.traffic["kv_repack_kept"],
            "refreshes": self.traffic["kv_refresh_count"],
            "generation": self.generation,
            "pending": len(self._repack_queue)}
        # spill tier: compressed bytes parked on host vs the dense-int8
        # working set they replace (< 1.0 == spilling compressed pays),
        # plus the readahead leg that restores them.  Own stream — spill
        # traffic is not an attention read
        sp, spraw = (self.traffic["kv_spill_bytes"],
                     self.traffic["kv_spill_raw_bytes"])
        out["spill"] = {
            "spill_bytes": sp, "raw_bytes": spraw,
            "ratio": (sp / spraw) if spraw else None,
            "pages": self.traffic["kv_spill_pages"],
            "calls": self.traffic["kv_spill_calls"],
            "readahead_bytes": self.traffic["kv_readahead_bytes"],
            "readahead_pages": self.traffic["kv_readahead_pages"],
            "readahead_calls": self.traffic["kv_readahead_calls"],
            "live_records": self.spill_tier.live_count,
            "live_bytes": self.spill_tier.live_bytes,
            "integrity_failures": self.traffic["kv_integrity_failures"],
            "quarantined": self.traffic["kv_quarantined_pages"]}
        return out

    # ----------------------------------------------------------- requests
    def add_request(self, rid: int, shard: int = 0) -> None:
        if rid in self.page_tables:
            raise ValueError(f"duplicate request id {rid}")
        if not 0 <= shard < self.n_shards:
            raise ValueError(f"shard {shard} out of range "
                             f"(pool has {self.n_shards})")
        self.page_tables[rid] = [[] for _ in range(self.n_layers)]
        self.page_base[rid] = [0] * self.n_layers
        self.request_shard[rid] = shard
        self.states[rid] = {}
        self.seq_len[rid] = 0

    def release(self, rid: int) -> None:
        for layer, pids in enumerate(self.page_tables.pop(rid)):
            for pid in pids:
                if pid < 0:                    # SPILLED: park in tier only
                    self.spill_tier.drop(-pid - 1)
                    continue
                self._cold[layer].discard(pid)
                self._packed[layer].discard(pid)
                self.page_gen[pid] = 0
                self.page_crc[pid] = 0
                self.page_last_read[pid] = 0
                self.pool.free(pid)
        del self.page_base[rid]
        self.request_shard.pop(rid, None)
        del self.states[rid]
        del self.seq_len[rid]

    # ------------------------------------------------------------ appends
    def _claim_page(self, rid: int, layer: int, t: int) -> int:
        """Page that token ``t`` of (rid, layer) writes into, allocating a
        fresh one at page boundaries (shared by the host append path and
        the on-device append's target claim)."""
        pids = self.page_tables[rid][layer]
        if t % self.page_size == 0:
            if t // self.page_size != self.page_base[rid][layer] + len(pids):
                raise RuntimeError(
                    f"page-table desync for rid={rid} layer={layer}: token "
                    f"{t} vs base={self.page_base[rid][layer]} "
                    f"live={len(pids)}")
            shard = self.request_shard.get(rid, 0)
            pid = self.pool.alloc(shard)
            if pid is None:
                raise RuntimeError(
                    f"page shard {shard} exhausted mid-flight "
                    "(admission must reserve per shard)")
            pids.append(pid)
        if pids[-1] < 0:
            raise m.PageIntegrityError(
                f"append into SPILLED page of rid={rid} layer={layer} — "
                "readahead must restore the request before it decodes",
                rid=rid, layer=layer)
        return pids[-1]

    def _append_layer_token(self, rid: int, layer: int, kq, vq, ks, vs,
                            t: int) -> None:
        pid = self._claim_page(rid, layer, t)
        self.pool.write_token(pid, kq, vq, ks, vs)
        if int(self.pool.fill[pid]) == self.page_size:
            self._seal(layer, pid)

    def append_token(self, rid: int, kq: np.ndarray, vq: np.ndarray,
                     ks: np.ndarray, vs: np.ndarray) -> None:
        """Append one token's KV for every attention layer.  kq/vq:
        [n_layers, H, dh] int8; ks/vs: [n_layers, H] f32 (the model's
        per-token scales).  Rows of recurrent-kind layers are ignored —
        their state is not per-token (see ``append_step_tokens``)."""
        t = self.seq_len[rid]
        for layer in self.attn_layers:
            self._append_layer_token(rid, layer, kq[layer], vq[layer],
                                     ks[layer], vs[layer], t)
        self._pack_pending()
        self.seq_len[rid] = t + 1
        self.evict_rolled(rid)

    def evict_rolled(self, rid: int) -> None:
        """Rolling-window eviction: free every local-layer page whose
        tokens have *all* left the attention window.  Page ``p`` holds
        tokens ``[p*ps, (p+1)*ps)``; with the next decode position at
        ``qpos = seq_len`` the attention mask keeps ``kpos > qpos -
        window``, so the page is dead once ``(p+1)*ps - 1 <= qpos -
        window``.  Only the oldest live page can die, and it is always
        sealed (COLD/PACKED) because pages seal the moment they fill."""
        qpos = self.seq_len[rid]
        ps = self.page_size
        for layer in self.local_layers:
            pids = self.page_tables[rid][layer]
            base = self.page_base[rid][layer]
            while pids and (base + 1) * ps - 1 <= qpos - self.window:
                pid = pids.pop(0)
                if pid < 0:                   # SPILLED page rolled out
                    self.spill_tier.drop(-pid - 1)
                    base += 1
                    continue
                self._cold[layer].discard(pid)
                self._packed[layer].discard(pid)
                self.page_gen[pid] = 0
                self.page_crc[pid] = 0
                self.pool.evict(pid)
                base += 1
            self.page_base[rid][layer] = base

    # --------------------------------------------------- cache plumbing
    def _layer_cache(self, caches: dict, layer: int):
        """(leaf-dict, stack-index) of one network layer in a cache pytree
        — prefix leaves are [B, ...], scanned leaves [n_stack, B, ...]."""
        if layer < self.n_prefix:
            return caches["prefix"][layer], None
        off = layer - self.n_prefix
        return caches["blocks"][off % self.n_cycle], off // self.n_cycle

    def _state_template(self, kind: str) -> dict[str, np.ndarray]:
        """Init-value state leaves (batch dim stripped) for empty slots."""
        if kind not in self._state_templates:
            one = _init_block_cache(self.cfg, kind, 1, 1)
            self._state_templates[kind] = {
                f: np.asarray(self._fetch(x))[0] for f, x in one.items()}
        return self._state_templates[kind]

    def _ring(self, max_len: int) -> int:
        """Rolling-layer dense-cache width (matches init_attention_cache)."""
        return min(self.window, max_len)

    def append_step_tokens(self, caches: dict, slot_rids: list,
                           positions) -> None:
        """Extract what a decode step wrote for every active slot of a
        dense cache pytree: the token at ``positions[slot]`` (ring slot
        ``pos % ring`` for rolling layers) for attention layers, the whole
        updated fixed-size state for recurrent-kind layers."""
        b = len(slot_rids)
        positions = np.asarray(positions, np.int32)
        barange = jnp.arange(b)
        fetched: dict[int, dict[str, np.ndarray]] = {}
        done_groups = set()
        for layer in range(self.n_layers):
            kind = self.layer_kinds[layer]
            leaf, j = self._layer_cache(caches, layer)
            group = ("p", layer) if j is None else ("c",
                                                    (layer - self.n_prefix)
                                                    % self.n_cycle)
            if group in done_groups:
                continue
            done_groups.add(group)
            if kind in ATTN_KINDS:
                sc = leaf["k"].shape[-3]
                slot_idx = jnp.asarray(
                    positions % sc if kind == "local" else positions)
                vals = {}
                for f in ("k", "v", "k_scale", "v_scale"):
                    x = leaf[f]
                    if j is None:
                        vals[f] = np.asarray(
                            self._fetch(x[barange, slot_idx]))[None]
                    else:
                        vals[f] = np.asarray(
                            self._fetch(x[:, barange, slot_idx]))
            else:
                vals = {f: (np.asarray(self._fetch(x))[None] if j is None
                            else np.asarray(self._fetch(x)))
                        for f, x in leaf.items()}
            # vals leaves are [n_stack(or 1), B, ...]; distribute to layers
            if j is None:
                fetched[layer] = {f: v[0] for f, v in vals.items()}
            else:
                c = (layer - self.n_prefix) % self.n_cycle
                for jj in range(self.n_stack):
                    fetched[self.n_prefix + jj * self.n_cycle + c] = {
                        f: v[jj] for f, v in vals.items()}
        h, dh = self.pool.kv_heads, self.pool.head_dim
        for slot, rid in enumerate(slot_rids):
            if rid is None:
                continue
            kq = np.zeros((self.n_layers, h, dh), np.int8)
            vq = np.zeros((self.n_layers, h, dh), np.int8)
            ks = np.zeros((self.n_layers, h), np.float32)
            vs = np.zeros((self.n_layers, h), np.float32)
            for layer in self.attn_layers:
                kq[layer] = fetched[layer]["k"][slot]
                vq[layer] = fetched[layer]["v"][slot]
                ks[layer] = fetched[layer]["k_scale"][slot]
                vs[layer] = fetched[layer]["v_scale"][slot]
            self.append_token(rid, kq, vq, ks, vs)
            for layer in self.state_layers:
                self.states[rid][layer] = {
                    f: v[slot].copy() for f, v in fetched[layer].items()}

    def ingest_prefill(self, rid: int, caches: dict, s: int) -> None:
        """Chop a (batch-1) prefill cache into pages, token order.

        Global layers ingest every position.  Rolling layers only have
        the last ``min(s, window)`` positions in the prefill cache (the
        model emits the rolling ring, not the full sequence) — exactly
        the live window: fully-dead leading pages are skipped outright
        (``page_base`` starts past them) and in-page positions older than
        the window ingest as zeros (dead by construction, never
        materialized).  Recurrent-kind layers store their final state.

        This is the monolithic wrapper over the resumable chunk API
        (``prefill_host_view`` -> ``ingest_prefill_chunk``* ->
        ``finish_prefill``) that the async engine paginates across decode
        steps, so one long prompt never stalls the batch."""
        with span("kv.ingest"):
            view = self.prefill_host_view(caches)
            self.ingest_prefill_chunk(rid, view, 0, s, s)
            self.finish_prefill(rid, view, s)

    def prefill_host_view(self, caches: dict) -> dict:
        """One batched d2h pull of a (batch-1) prefill cache into host
        numpy — attn layers as ``(k, v, k_scale, v_scale)`` tuples, state
        layers as their field dicts.  Forcing the view blocks on the
        prefill computation, so the async engine calls this during the
        overlap window where the wait rides the in-flight decode step."""
        with span("kv.ingest.pull"):
            view: dict = {}
            for layer in self.attn_layers:
                leaf, j = self._layer_cache(caches, layer)

                def one(f, leaf=leaf, j=j):
                    x = leaf[f] if j is None else leaf[f][j]
                    return np.asarray(self._fetch(x))[0]

                view[layer] = (one("k"), one("v"), one("k_scale"),
                               one("v_scale"))
            for layer in self.state_layers:
                leaf, j = self._layer_cache(caches, layer)
                view[layer] = {
                    f: np.asarray(self._fetch(x if j is None else x[j]))[0]
                    for f, x in leaf.items()}
            return view

    def ingest_prefill_chunk(self, rid: int, view: dict, t0: int, t1: int,
                             s: int) -> None:
        """Ingest prompt positions ``[t0, t1)`` of an ``s``-token prefill
        from a host view.  Resumable: chunks may arrive across decode
        steps; page/seal/sketch work is identical to a single monolithic
        call (same tokens, same order)."""
        ps = self.page_size
        for layer in self.attn_layers:
            kind = self.layer_kinds[layer]
            k, v, ksc, vsc = view[layer]               # [S or window, H, dh]
            if kind == "local":
                w = k.shape[0]                         # ring width == window
                start = (max(0, s - w) // ps) * ps
                self.page_base[rid][layer] = start // ps
            else:
                w, start = None, 0
            for t in range(max(t0, start), t1):
                if kind == "local":
                    if t < s - w:
                        kq, vq = np.zeros_like(k[0]), np.zeros_like(v[0])
                        kss, vss = np.zeros_like(ksc[0]), np.zeros_like(vsc[0])
                    else:
                        kq, vq = k[t % w], v[t % w]
                        kss, vss = ksc[t % w], vsc[t % w]
                else:
                    kq, vq, kss, vss = k[t], v[t], ksc[t], vsc[t]
                self._append_layer_token(rid, layer, kq, vq, kss, vss, t)
        self._pack_pending()

    def finish_prefill(self, rid: int, view: dict, s: int) -> None:
        """Final chunk bookkeeping: store recurrent-kind final states,
        stamp the sequence length, evict rolled-out local pages."""
        for layer in self.state_layers:
            self.states[rid][layer] = dict(view[layer])
        self.seq_len[rid] = s
        self.evict_rolled(rid)

    # ------------------------------------------------- seal/calibrate/pack
    def _seal(self, layer: int, pid: int) -> None:
        """Full HOT page -> COLD: re-quantize to one scale per (page, head)
        — scale amortization — then calibrate, or queue the page for
        ``_pack_pending``."""
        with span("kv.seal"):
            from repro.core import quant, tables as ctables
            from repro.core.tables import TABLE_OVERHEAD_BITS
            pool = self.pool
            q2 = np.zeros((2, self.page_size, pool.kv_heads, pool.head_dim),
                          np.int8)
            scale2 = np.zeros((2, pool.kv_heads), np.float32)
            with span("kv.seal.requantize"):
                for kind in (0, 1):
                    f = (pool.tok_q[kind, pid].astype(np.float32)
                         * pool.tok_scale[kind, pid][..., None])
                    sc = np.maximum(np.abs(f).max(axis=(0, 2)), 1e-8) / 127.0
                    q2[kind] = np.clip(np.round(f / sc[None, :, None]),
                                       -127, 127).astype(np.int8)
                    scale2[kind] = sc
            pool.seal(pid, q2, scale2)
            self._cold[layer].add(pid)
            self._mark_dirty(pid)
            if self.tables[layer][0] is not None:
                # drift monitor: every post-calibration sealed page feeds
                # the layer's symbol-frequency sketch — the same 256-bin
                # histogram calibration used, accumulated here where the
                # page payload is already in host memory (zero extra
                # transfers; in fused mode this rides the amortized seal
                # pull)
                for kind in (0, 1):
                    u = quant.to_unsigned(q2[kind]).reshape(-1)
                    self.drift_hists[layer, kind] += np.bincount(u,
                                                                 minlength=256)
                self.drift_pages[layer] += 1
                self._drift_changed.add(layer)
                self._pending[pid] = layer
                return
            for kind in (0, 1):
                u = quant.to_unsigned(q2[kind]).reshape(-1)
                self.hists[layer, kind] += np.bincount(u, minlength=256)
                self.hist_pages[layer, kind] += 1
            if int(self.hist_pages[layer, 0]) >= self.calib_pages:
                for kind in (0, 1):
                    self.tables[layer][kind] = ctables.find_table(
                        self.hists[layer, kind], bits=8, is_activation=True)
                    self.calib_bits[layer, kind] = \
                        ctables.expected_bits_per_value(
                            self.hists[layer, kind], self.tables[layer][kind])
                # a late-calibrating layer installs into the *current*
                # generation (its rows in older generations stay zero and are
                # never referenced: no page of this layer is PACKED yet)
                self.table_gen[layer] = self.generation
                self._table_stack = None
                self._tables_dirty = True
                self.traffic["kv_table_bytes"] += 2 * TABLE_OVERHEAD_BITS // 8
                for cold_pid in sorted(self._cold[layer]):
                    self._pending[cold_pid] = layer

    def _pack_pending(self) -> None:
        """COLD -> PACKED for every page ``_seal`` queued: APack-encode
        both kinds of all of them with their layers' activation tables, in
        ``seal_chunks`` device calls of ``kernels/ref.encode_pages`` (one
        push of values, one of table rows and one pull of the planes
        each), then store each page's planes, table generation and CRC.
        Runs at the end of every public method that can seal, so no other
        method sees a queued page."""
        from repro.kernels import ref as _codec
        todo = [(pid, layer) for pid, layer in self._pending.items()
                if pid in self._cold[layer]]
        self._pending.clear()
        if not todo:
            return
        pool = self.pool
        with span("kv.seal"):
            pids = [pid for pid, _ in todo]
            # page-kind j = 2 * page + kind; int8 viewed as uint8 is
            # quant.to_unsigned
            vals = pool.cold_q[:, pids].view(np.uint8).swapaxes(0, 1)
            vals = vals.reshape(-1, pool.n_streams, pool.elems_per_stream)
            layer_rows = {layer: [_codec.encoder_row(t)
                                  for t in self.tables[layer]]
                          for layer in {layer for _, layer in todo}}
            rows = np.stack([r for _, layer in todo
                             for r in layer_rows[layer]])
            at = 0
            for size in seal_chunks(len(rows)):
                # the last chunk is padded with zero pages, discarded; the
                # sizes are even, so a page's K and V share a chunk
                n = min(size, len(rows) - at)
                v = np.zeros((size,) + vals.shape[1:], np.uint8)
                r = np.repeat(rows[at:at + 1], size, axis=0)
                v[:n], r[:n] = vals[at:at + n], rows[at:at + n]
                with span("kv.seal.encode"):
                    # apack: allow-transfer(page-seal event: one push of the
                    # chunk's values and table rows, one pull of its planes)
                    out = jax.device_get(_codec.encode_pages(
                        jnp.asarray(v), jnp.asarray(r)))
                self.traffic["kv_seal_encode_calls"] += 1
                for q in range(0, n, 2):
                    pid, layer = todo[(at + q) // 2]
                    pool.pack(pid, tuple(o[q:q + 2] for o in out))
                    self._cold[layer].discard(pid)
                    self._packed[layer].add(pid)
                    # stamp the generation the coding table belongs to
                    # (earliest generation holding this content — stays
                    # valid across later refreshes of *other* layers
                    # thanks to copy-forward stacking)
                    self.page_gen[pid] = int(self.table_gen[layer])
                    self.page_crc[pid] = self._plane_crc(pid)
                    self._mark_dirty(pid)
                    self.traffic["kv_pages_packed"] += 1
                at += n

    def _plane_crc(self, pid: int) -> int:
        """Integrity checksum of a PACKED page's compressed planes + page
        scales — the page metadata companion of ``page_gen``."""
        with span("kv.seal.crc"):
            pool = self.pool
            return m.payload_crc({"sym": pool.sym[:, pid],
                                  "ofs": pool.ofs[:, pid],
                                  "sym_bits": pool.sym_bits[:, pid],
                                  "ofs_bits": pool.ofs_bits[:, pid],
                                  "stored": pool.stored[:, pid],
                                  "page_scale": pool.page_scale[:, pid]})

    @property
    def n_table_rows(self) -> int:
        """Rows in the stacked table pool: one ``2 * n_layers`` block per
        *live* generation (``gen_rows`` slot addressing — compacted, not
        one block per historical generation)."""
        return 2 * self.n_layers * (max(self.gen_rows.values()) + 1)

    def _row(self, gen: int, layer: int, kind: int) -> int:
        """Stacked-pool row of ``(gen, layer, kind)`` through the
        compacted ``gen_rows`` slot map — the ONLY way table ids reach
        the kernels, so a compaction is visible everywhere at the next
        ``step_meta``/``materialize`` build."""
        return table_row(self.gen_rows[gen], layer, kind, self.n_layers)

    def _checked_gen(self, pid: int, rid, layer: int) -> int:
        """A page's table generation, validated against the live
        ``gen_rows`` map.  Every read-side consumer (``step_meta`` table
        build, read-traffic accrual) must go through this rather than
        indexing ``gen_rows`` directly: a poisoned/stale generation is an
        *integrity failure of one request*, and it has to surface as
        ``PageIntegrityError`` (so the engine fails the owner and keeps
        serving) — never as a bare ``KeyError`` out of the compacted
        slot map."""
        gen = int(self.page_gen[pid])
        if gen not in self.gen_rows:
            self.traffic["kv_integrity_failures"] += 1
            raise m.PageIntegrityError(
                f"page {pid} of rid={rid} layer={layer} carries "
                f"poisoned table generation {gen} (live: "
                f"{sorted(self.gen_rows)}) — refusing to decode "
                "with an out-of-pool table row",
                rid=rid, layer=layer, pid=pid)
        return gen

    def _table_at(self, gen: int, layer: int, kind: int):
        """The table a page packed at generation ``gen`` was coded with."""
        if gen < len(self._gen_snapshots):
            return self._gen_snapshots[gen][layer][kind]
        return self.tables[layer][kind]

    def _live_generations(self) -> set[int]:
        """Generations that must keep a row block: the current one (new
        packs address it), generation 0 (HOT/COLD pages carry gen 0 in
        their — masked but bounds-checked — meta rows), every generation
        owning a resident PACKED page, and every generation of a page
        parked in the host spill tier (it returns at readahead and must
        still decode with its own table)."""
        live = {0, self.generation}
        for packed in self._packed:
            for pid in packed:
                live.add(int(self.page_gen[pid]))
        live |= {int(g) for g in self.spill_tier.live_gens()}
        return live

    def compact_table_rows(self) -> int:
        """Reclaim stacked-table row blocks of dead generations: after the
        budgeted re-pack migrates (or eviction frees) the last PACKED page
        coded under generation ``g``, nothing can ever reference ``g``'s
        rows again — drop it from ``gen_rows`` and renumber the surviving
        generations onto contiguous slots.  Without this the device table
        planes grow a ``2 * n_layers`` block per refresh *forever* on a
        long-running server.  Returns the number of rows reclaimed;
        on any change the stack rebuilds and the device mirror re-uploads
        at the next flush (an event, never the steady-state step)."""
        live = self._live_generations()
        kept = sorted(g for g in self.gen_rows if g in live)
        new_rows = {g: i for i, g in enumerate(kept)}
        if new_rows == self.gen_rows:
            return 0
        reclaimed = 2 * self.n_layers * (
            max(self.gen_rows.values()) - max(new_rows.values()))
        self.gen_rows = new_rows
        self._table_stack = None
        self._tables_dirty = True
        return reclaimed

    def _tables_stacked(self):
        """np table arrays stacked ``[n_live_gens * 2 * n_layers, ...]``,
        row ``table_row(gen_rows[gen], layer, kind)`` — the per-page
        table-id space of the batched gather-decode and fused-attention
        calls.  The current generation's block is the live
        ``self.tables``; earlier live blocks come from the refresh
        snapshots (copy-forward: a layer that did not refresh at
        generation g repeats its previous table there, so any (gen,
        layer) a PACKED page can reference is populated).  Rebuilt lazily
        on calibration/refresh/compaction — individual tables are
        immutable.  Uncalibrated rows stay zero and are never referenced
        (PACKED requires a table)."""
        if self._table_stack is None:
            rows = self.n_table_rows
            vm = np.zeros((rows, 17), np.int32)
            ol = np.zeros((rows, 16), np.int32)
            cm = np.zeros((rows, 17), np.int32)
            for gen in self.gen_rows:
                for layer in range(self.n_layers):
                    for kind in (0, 1):
                        t = self._table_at(gen, layer, kind)
                        if t is not None:
                            a, b, c = t.as_arrays()
                            row = self._row(gen, layer, kind)
                            vm[row], ol[row], cm[row] = a, b, c
            self._table_stack = (vm, ol, cm)
        return self._table_stack

    # ------------------------------------------- table refresh / re-pack
    def drift_status(self, layer: int) -> dict | None:
        """Drift-monitor readout for one layer: expected bits/value of the
        post-calibration sketch under the layer's *current* table vs. what
        the table promised on the histogram it was built from.  ``None``
        until the layer is calibrated and ``refresh_min_pages`` pages of
        sketch exist."""
        from repro.core import tables as ctables
        if self.tables[layer][0] is None:
            return None
        pages = int(self.drift_pages[layer])
        if pages < self.refresh_min_pages:
            return None
        cur = [ctables.expected_bits_per_value(self.drift_hists[layer, k],
                                               self.tables[layer][k])
               for k in (0, 1)]
        regress = max(cur[k] / max(float(self.calib_bits[layer, k]), 1e-9)
                      for k in (0, 1))
        return {"pages": pages, "cur_bits": cur,
                "calib_bits": [float(b) for b in self.calib_bits[layer]],
                "regression": regress}

    def check_refresh(self) -> list[int]:
        """Layers whose refresh trigger fired: sketch compression regressed
        ``refresh_threshold`` past the calibration-time expectation, or
        ``refresh_every_pages`` pages sealed since the last
        (re)calibration.  Only layers whose sketch *moved* since the last
        check are evaluated (triggers can only change state at a page
        seal), so the per-decode-step call is O(1) host work on non-seal
        steps.  ``maybe_refresh`` acts on the result."""
        due = []
        for layer in sorted(self._drift_changed):
            st = self.drift_status(layer)
            if st is None:
                continue
            if (self.refresh_every_pages is not None
                    and st["pages"] >= self.refresh_every_pages):
                due.append(layer)
            elif st["regression"] > 1.0 + self.refresh_threshold:
                due.append(layer)
        self._drift_changed.clear()
        return due

    def maybe_refresh(self) -> list[int]:
        """Check drift triggers and re-calibrate every due layer under a
        single generation bump.  Returns the refreshed layers."""
        due = self.check_refresh()
        if due:
            self._refresh(due)
        return due

    def _refresh(self, layers: list[int]) -> None:
        """Re-calibrate ``layers`` from their drift sketches: snapshot the
        current table set as generation ``G`` (copy-forward — unrefreshed
        layers repeat their table there), bump to ``G+1``, install new
        activation-mode tables via the same ``find_table`` heuristic
        calibration used, and queue every PACKED page of the refreshed
        layers for re-pack.  Old pages stay decodable throughout: their
        ``page_gen`` keeps addressing the snapshot rows until the
        (budgeted, incremental) re-pack atomically swaps their planes."""
        from repro.core import tables as ctables
        from repro.core.tables import TABLE_OVERHEAD_BITS
        self._gen_snapshots.append([list(t) for t in self.tables])
        self.generation += 1
        self.gen_rows[self.generation] = max(self.gen_rows.values()) + 1
        for layer in layers:
            for kind in (0, 1):
                self.tables[layer][kind] = ctables.find_table(
                    self.drift_hists[layer, kind], bits=8,
                    is_activation=True)
                self.calib_bits[layer, kind] = \
                    ctables.expected_bits_per_value(
                        self.drift_hists[layer, kind],
                        self.tables[layer][kind])
            self.table_gen[layer] = self.generation
            self.drift_hists[layer] = 0
            self.drift_pages[layer] = 0
            # a refreshed table ships off-chip like the original did
            self.traffic["kv_table_bytes"] += 2 * TABLE_OVERHEAD_BITS // 8
            self.traffic["kv_refresh_count"] += 1
            # newest-first: recently sealed pages are the ones whose
            # content resembles the sketch the new table was fitted to,
            # so they gain the most from migrating early (pool ids are
            # allocation-ordered — an approximate recency order)
            for pid in sorted(self._packed[layer], reverse=True):
                self._repack_queue.append((layer, pid))
        self._table_stack = None
        self._tables_dirty = True
        # a refresh can also *retire* generations (pages of the refreshed
        # layers may have been the last references) — reclaim before the
        # new stack builds so the bumped pool doesn't carry dead blocks
        self.compact_table_rows()

    def repack_pending(self, budget: int | None = None, *,
                       force: bool = False) -> int:
        """Re-code up to ``budget`` queued stale pages (all of them when
        ``budget`` is None) under their layer's current tables.  The queue
        drains across decode steps so refresh never stalls serving; pages
        freed/evicted or already re-packed since being queued are skipped.
        Returns the number of pages processed (swapped + size-gate kept;
        see ``_repack``).  ``force=True`` migrates unconditionally (e.g.
        to drain a generation for compaction)."""
        done = 0
        while self._repack_queue and (budget is None or done < budget):
            layer, pid = self._repack_queue.popleft()
            if pid not in self._packed[layer]:
                continue                      # freed/evicted since queued
            if int(self.page_gen[pid]) >= int(self.table_gen[layer]):
                continue                      # already current
            self._repack(layer, pid, force=force)
            done += 1
        if done:
            # migrations may have drained a generation's last PACKED page
            self.compact_table_rows()
        return done

    def _repack(self, layer: int, pid: int, *, force: bool = False) -> bool:
        """Decode one PACKED page with the table generation it was coded
        under and re-encode with the layer's current tables.  The swap is
        **size-gated**: if the re-code came out larger the old planes are
        kept and ``page_gen`` stays put — an old page whose content still
        matches its old table is already optimally coded, and the
        generation-versioned pool exists precisely so it can stay there
        (a later refresh re-queues and re-evaluates it).  When the swap
        happens it is atomic (whole planes + ``page_gen`` in one host-side
        critical section): pages are immutable and independently coded, so
        every reader sees a consistent (planes, table) pair and decode
        stays bit-exact mid-refresh.  Returns True if swapped."""
        from repro.kernels import ref as _codec
        pool = self.pool
        if (self.verify_on_repack
                and int(self.page_crc[pid]) != self._plane_crc(pid)):
            self.traffic["kv_integrity_failures"] += 1
            self.traffic["kv_quarantined_pages"] += 1
            raise m.PageIntegrityError(
                f"PACKED page {pid} (layer {layer}) failed checksum before "
                "re-pack — planes corrupted in place; owning request must "
                "be failed", rid=self._owner_of(pid), layer=layer, pid=pid)
        old_gen = int(self.page_gen[pid])
        old_bytes = pool.page_bytes(pid)
        old_payload = int(pool.sym_bits[:, pid].sum()
                          + pool.ofs_bits[:, pid].sum())
        outs = []
        for kind in (0, 1):
            old_t = self._table_at(old_gen, layer, kind)
            # apack: allow-transfer(budgeted re-pack event: codec round-trip
            # over sealed PACKED pages, size-gated, never on the step path)
            vals = np.asarray(_codec.decode(
                jnp.asarray(pool.sym[kind, pid]),
                jnp.asarray(pool.ofs[kind, pid]),
                jnp.asarray(pool.stored[kind, pid]),
                _codec.TableArrays.from_table(old_t),
                pool.elems_per_stream, 8))
            ta = _codec.TableArrays.from_table(self.tables[layer][kind])
            planes = _codec.encode(jnp.asarray(vals.astype(np.int32)), ta,
                                   pool.elems_per_stream, 8)
            # apack: allow-transfer(budgeted re-pack event: pulls the
            # re-encoded planes for the host pool, off the step path)
            outs.append(tuple(np.asarray(p) for p in planes))
        # the decode read happened regardless of the gate's verdict
        self.traffic["kv_repack_read_bytes"] += old_bytes
        new_payload = int(sum(int(o[2].sum()) + int(o[3].sum())
                              for o in outs))
        if not force and new_payload >= old_payload:
            self.traffic["kv_repack_kept"] += 1
            return False
        pool.repack(pid, tuple(np.stack([o[i] for o in outs])
                               for i in range(5)))
        self.page_gen[pid] = int(self.table_gen[layer])
        self.page_crc[pid] = self._plane_crc(pid)
        self._mark_dirty(pid)
        # the re-pack write is off-chip traffic too — both legs accounted
        # under their own counters, never folded into the attention-read
        # stream ratios (see traffic init)
        self.traffic["kv_repack_write_bytes"] += pool.page_bytes(pid)
        self.traffic["kv_repack_pages"] += 1
        return True

    def refresh_step(self, budget: int | None = None) -> dict:
        """Engine decode-loop hook: check triggers, refresh due tables
        (one generation bump for the whole batch), re-pack up to
        ``budget`` stale pages, and push the results to the device mirror.
        Host-side only — no device_get; the steady-state zero-d2h
        invariant of the fused loop is preserved with refresh active."""
        refreshed = self.maybe_refresh()
        repacked = self.repack_pending(budget)
        if refreshed or repacked:
            self._flush_device()
        return {"refreshed_layers": refreshed, "repacked": repacked}

    # ------------------------------------------------- state snapshots
    def snapshot_state(self, rid: int) -> dict:
        """Engine checkpoint/preemption path: APack-compress the request's
        fixed-size recurrent/mLSTM/sLSTM states.  Bit-exact lossless — f32
        byte planes through the coder with *weight-mode* tables (the full
        state is profiled at snapshot time, so the §VI activation slack is
        unnecessary; same heuristic choice as ``compress_params`` for
        weights).  Attention KV needs no snapshotting: it already lives
        compressed in the page pool."""
        from repro.core import byteplane
        manifest: list[tuple[int, str, tuple[int, ...]]] = []
        parts: list[np.ndarray] = []
        for layer in self.state_layers:
            st = self.states[rid].get(layer)
            if st is None:
                raise RuntimeError(
                    f"request {rid} has no state for layer {layer} "
                    "(prefill not ingested?)")
            for f in sorted(st):
                arr = np.ascontiguousarray(st[f], np.float32)
                manifest.append((layer, f, arr.shape))
                parts.append(arr.reshape(-1))
        if not parts:
            return {"manifest": [], "planes": None}
        # one stream per snapshot, not one per (field, plane): the 298-byte
        # table overhead amortizes over the whole state, and every byte
        # that will ever be encoded is in the histogram (weight mode)
        flat = np.concatenate(parts)
        planes = byteplane.compress_float(flat, table_mode="weight")
        self.traffic["state_raw_bytes"] += flat.nbytes
        self.traffic["state_snapshot_bytes"] += planes.total_bits // 8
        self.traffic["state_snapshots"] += 1
        return {"manifest": manifest, "planes": planes}

    def restore_state(self, rid: int, snap: dict) -> None:
        """Decompress a ``snapshot_state`` blob back into the request's
        live state store (bit-exact: resumed decode == uninterrupted)."""
        from repro.core import byteplane
        if snap["planes"] is None:
            return
        flat = byteplane.decompress_float(snap["planes"])
        off = 0
        for layer, f, shape in snap["manifest"]:
            n = int(np.prod(shape))
            self.states[rid].setdefault(layer, {})[f] = \
                flat[off:off + n].reshape(shape).copy()
            off += n

    # --------------------------------------------------- host spill tier
    def _owner_of(self, pid: int) -> int | None:
        """Request owning a resident page (integrity-failure attribution;
        O(requests × pages) but only runs on a corruption path)."""
        for rid, layers in self.page_tables.items():
            for pids in layers:
                if pid in pids:
                    return rid
        return None

    def spilled_pages(self, rid: int) -> int:
        """SPILLED page-table entries of a request (kv_stats accounting)."""
        return sum(1 for pids in self.page_tables[rid]
                   for pid in pids if pid < 0)

    def request_last_read(self, rid: int) -> int:
        """Read-clock stamp of the request's most recently read page —
        the cold-LRU key for pressure victim selection (lower == colder)."""
        last = 0
        for layer in self.attn_layers:
            for pid in self.page_tables[rid][layer]:
                if pid >= 0:
                    last = max(last, int(self.page_last_read[pid]))
        return last

    def spill_request(self, rid: int) -> int:
        """Park every page of (a preempted) request ``rid`` in the host
        spill tier, compressed: PACKED pages move as their APack planes,
        COLD as page-requantized int8, partial HOT as per-token int8.
        Page-table entries become SPILLED (negative handle refs) and the
        pool slots return to the free list — this is what turns pool
        capacity into a cache under pressure.  Returns pages spilled.

        Never call for an *active* slot: the fused kernel reads every
        resident page each step (``step_meta`` raises on SPILLED
        entries)."""
        if self.dev is not None:
            self.sync_hot_to_host([rid])      # HOT payload truth -> host
        if self.faults is not None:
            d = self.faults.spill_delay()
            if d:
                time.sleep(d)
        n = 0
        for layer in self.attn_layers:
            pids = self.page_tables[rid][layer]
            for i, pid in enumerate(pids):
                if pid < 0:
                    continue                  # already spilled
                pids[i] = self._spill_page(rid, layer, pid)
                n += 1
        if n:
            self.traffic["kv_spill_calls"] += 1
        return n

    def _spill_page(self, rid: int, layer: int, pid: int) -> int:
        st, fill, payload, comp = self.pool.spill(pid)
        raw = self.pool.dense_bytes(fill if st == m.PAGE_HOT
                                    else self.page_size)
        rec = m.SpillRecord(state=st, fill=fill, layer=layer,
                            gen=int(self.page_gen[pid]), payload=payload,
                            comp_bytes=comp, raw_bytes=raw,
                            meta={"rid": rid, "pid": pid})
        handle = self.spill_tier.put(rec)
        self._cold[layer].discard(pid)
        self._packed[layer].discard(pid)
        self.page_gen[pid] = 0
        self.page_crc[pid] = 0
        self.traffic["kv_spill_bytes"] += comp
        self.traffic["kv_spill_raw_bytes"] += raw
        self.traffic["kv_spill_pages"] += 1
        return -handle - 1

    def unspill_request(self, rid: int) -> list[int]:
        """Readahead: restore every SPILLED page of ``rid`` into fresh
        pool slots ahead of the fused kernel's reads — checksum-verified,
        then pushed to the device mirror in ONE batched h2d flush.  Runs
        at resume/admission (an *event*), never inside the steady-state
        decode step, so the zero-``device_get`` invariant holds.

        A checksum mismatch quarantines the record in the tier and raises
        ``PageIntegrityError`` carrying ``rid`` — the engine fails only
        the owning request; already-restored pages stay consistent (their
        table entries were rewritten as they were adopted) so release
        cleans up normally and neighbors never see the corruption."""
        restored: list[int] = []
        try:
            for layer in self.attn_layers:
                pids = self.page_tables[rid][layer]
                for i, entry in enumerate(pids):
                    if entry >= 0:
                        continue
                    handle = -entry - 1
                    try:
                        rec = self.spill_tier.get(handle)
                    except m.PageIntegrityError as e:
                        self.traffic["kv_integrity_failures"] += 1
                        self.traffic["kv_quarantined_pages"] += 1
                        raise m.PageIntegrityError(
                            f"unspill of rid={rid} layer={layer} page {i}: "
                            f"{e}", rid=rid, layer=layer, handle=handle) from e
                    pid = self.pool.adopt(rec.state, rec.fill, rec.payload,
                                          shard=self.request_shard.get(rid, 0))
                    pids[i] = pid
                    self.page_gen[pid] = rec.gen
                    if rec.state == m.PAGE_PACKED:
                        self._packed[layer].add(pid)
                        self.page_crc[pid] = self._plane_crc(pid)
                        if rec.gen < int(self.table_gen[layer]):
                            # packed under a since-refreshed table: still
                            # decodable via its generation row; queue for the
                            # budgeted migration like any stale resident page
                            self._repack_queue.append((layer, pid))
                    elif rec.state == m.PAGE_COLD:
                        self._cold[layer].add(pid)
                        if self.tables[layer][0] is not None:
                            # table arrived while parked
                            self._pending[pid] = layer
                    self._mark_dirty(pid)
                    self.spill_tier.drop(handle)
                    self.traffic["kv_readahead_pages"] += 1
                    restored.append(pid)
        finally:
            # readahead bytes are the restored pages as they now stand,
            # packed where their table exists
            self._pack_pending()
            for pid in restored:
                self.traffic["kv_readahead_bytes"] += \
                    self.pool.page_bytes(pid)
        if restored:
            self.traffic["kv_readahead_calls"] += 1
            self._flush_device()              # one batched h2d, pre-kernel
        return restored

    # ---------------------------------------------- device-resident mode
    def _transfer_guard(self, direction: str) -> None:
        """Fault-injection hook on the host<->device boundary: a dropped
        transfer is retried up to ``transfer_retries`` times (each drop
        and retry accounted) before the failure propagates."""
        if self.faults is None:
            return
        for attempt in range(self.transfer_retries + 1):
            try:
                self.faults.check_transfer(direction)
                if attempt:
                    self.traffic["kv_transfer_retries"] += attempt
                return
            except m.TransferDropped:
                self.traffic["kv_transfer_drops"] += 1
                if attempt == self.transfer_retries:
                    raise

    # apack: allow-transfer(sole accounted d2h funnel: every KV pull rides
    # this wrapper so the bench ledger and the zero-device_get gates see it)
    def _fetch(self, tree):
        """``jax.device_get`` with transfer accounting (pytrees allowed,
        one call).  Every device->host byte the KV path moves goes
        through here — the decode bench and the steady-state
        zero-``device_get`` guard read these counters."""
        self._transfer_guard("d2h")
        out = jax.device_get(tree)
        self.transfers["d2h_calls"] += 1
        self.transfers["d2h_bytes"] += sum(
            np.asarray(x).nbytes for x in jax.tree.leaves(out))
        return out

    def _put(self, x):
        """host -> device with transfer accounting (counterpart of
        ``_fetch``)."""
        self._transfer_guard("h2d")
        arr = jnp.asarray(x)
        self.transfers["h2d_calls"] += 1
        self.transfers["h2d_bytes"] += int(arr.size) * arr.dtype.itemsize
        return arr

    def enable_device_pool(self, max_batch: int, mesh=None) -> None:
        """Switch to device-resident decode: mirror the pool planes on
        device (read by the fused kernel, written by the on-device
        append) and allocate the device state store for recurrent-kind
        layers.  Host numpy remains the seal/pack + invariant mirror.

        With ``mesh``: planes place under ``sharding.plane_pspecs`` (page
        shards over "data" matching the per-shard free lists).  The state
        store starts unplaced — the sharded step's out_specs pin it from
        the first step on."""
        self.dev = DevicePoolPlanes(self.pool, max(1, self.n_table_rows),
                                    mesh=mesh)
        self.dev_states = init_state_store(self.cfg, max_batch)
        self._sync_tables_to_device()
        # compile both chunk shapes of the seal's encode now, so that no
        # seal compiles while serving
        from repro.kernels import ref as _codec
        shape = (self.pool.n_streams, self.pool.elems_per_stream)
        for size in (SEAL_CHUNK_SMALL, SEAL_CHUNK_LARGE):
            jax.block_until_ready(_codec.encode_pages(
                jnp.zeros((size,) + shape, jnp.uint8),
                jnp.zeros((size, _codec.ENCODER_ROW), jnp.int32)))

    def _mark_dirty(self, pid: int) -> None:
        if self.dev is not None:
            self._dirty.add(pid)

    def _sync_tables_to_device(self) -> None:
        vm, ol, cm = self._tables_stacked()
        n = vm.shape[0]
        # a refresh past the current capacity reallocates the device table
        # planes (doubling -> O(log generations) decode-jit recompiles,
        # each at a refresh boundary, never in the steady-state loop)
        self.dev.ensure_table_capacity(n)
        d = self.dev.planes
        d["vm"] = d["vm"].at[:n].set(self._put(vm))
        d["ol"] = d["ol"].at[:n].set(self._put(ol))
        d["cum"] = d["cum"].at[:n].set(self._put(cm))
        self._tables_dirty = False

    def sync_pages_to_device(self, pids) -> None:
        """Push pages' current-state payloads into the device mirror —
        called at page *events* (seal, pack, prefill ingest), never in
        the steady-state decode loop.  Batched per lifecycle state: on a
        mesh, ONE fused scatter program per group (every plane of the
        state at once), not one eager dispatch per plane — each eager
        ``.at[].set`` there is a full SPMD dispatch, so a PACKED seal's
        8 plane writes would pay 8× the launch overhead; the page-id
        vector pads to a power-of-two bucket by repeating the last id
        (rewriting an identical payload row is idempotent), keeping the
        jit cache log-bounded in group size.  Without a mesh the planes
        stay on the eager per-plane path: single-device dispatch is
        ~100x cheaper than the fused program's one-off XLA compile, and
        that compile landing mid-serve would poison step-time baselines
        (the engine watchdog's trailing window)."""
        pool = self.pool
        groups: dict[int, list[int]] = {}
        for pid in pids:
            groups.setdefault(int(pool.state[pid]), []).append(pid)
        fused = self.dev.mesh is not None
        if fused and self._plane_push is None:
            def _push(d, idx, pay):
                return {k: d[k].at[idx].set(v) for k, v in pay.items()}
            self._plane_push = jax.jit(_push)
        for st, group in groups.items():
            if st == m.PAGE_FREE:
                continue
            if fused:
                b = 1 << max(len(group) - 1, 0).bit_length()
                group = group + [group[-1]] * (b - len(group))
            idx = jnp.asarray(np.asarray(group, np.int32))
            if st == m.PAGE_HOT:
                pay = {"tok_k": pool.tok_q[0, group],
                       "tok_v": pool.tok_q[1, group],
                       "tok_sk": pool.tok_scale[0, group],
                       "tok_sv": pool.tok_scale[1, group]}
            elif st == m.PAGE_COLD:
                pay = {"cold_k": pool.cold_q[0, group],
                       "cold_v": pool.cold_q[1, group]}
            elif st == m.PAGE_PACKED:
                pay = {"sym_k": pool.sym[0, group],
                       "sym_v": pool.sym[1, group],
                       "ofs_k": pool.ofs[0, group],
                       "ofs_v": pool.ofs[1, group],
                       "stored_k": pool.stored[0, group].astype(np.int32),
                       "stored_v": pool.stored[1, group].astype(np.int32)}
            if st in (m.PAGE_COLD, m.PAGE_PACKED):
                pay["pscale_k"] = pool.page_scale[0, group]
                pay["pscale_v"] = pool.page_scale[1, group]
            d = self.dev.planes
            if fused:
                self.dev.planes = dict(d, **self._plane_push(
                    {k: d[k] for k in pay}, idx,
                    {k: self._put(v) for k, v in pay.items()}))
            else:
                for k, v in pay.items():
                    d[k] = d[k].at[idx].set(self._put(v))

    def _flush_device(self) -> None:
        self._pack_pending()
        if self.dev is None:
            return
        with span("kv.flush"):
            changed = self._tables_dirty or bool(self._dirty)
            if self._tables_dirty:
                self._sync_tables_to_device()
            if self._dirty:
                self.sync_pages_to_device(sorted(self._dirty))
                self._dirty.clear()
            if changed:
                # mesh mode: eager event scatters can degrade plane
                # layouts; repin here (no-op without a mesh) so the next
                # sharded step sees canonical partitioning instead of an
                # implicit reshard
                self.dev.repin()

    def sync_request_to_device(self, rid: int) -> None:
        """Admission-time push: every page of a freshly-ingested request
        (HOT partials included) plus any pending seal/pack results."""
        if self.dev is None:
            return
        self._flush_device()
        self.sync_pages_to_device(sorted(
            {pid for layer in self.attn_layers
             for pid in self.page_tables[rid][layer] if pid >= 0}))

    def sync_hot_to_host(self, slot_rids=None) -> None:
        """Pull device-resident HOT page payloads back into the host pool
        mirror — the materialize/oracle path and state snapshots need the
        host view; a steady-state decode step never calls this."""
        if self.dev is None:
            return
        rids = [r for r in (slot_rids if slot_rids is not None
                            else list(self.page_tables)) if r is not None]
        pids = sorted({pid for rid in rids for layer in self.attn_layers
                       for pid in self.page_tables[rid][layer]
                       if pid >= 0
                       and self.pool.state[pid] == m.PAGE_HOT
                       and self.pool.fill[pid] > 0})
        if not pids:
            return
        d = self.dev.planes
        idx = jnp.asarray(np.asarray(pids, np.int32))
        kq, vq, ks, vs = self._fetch((d["tok_k"][idx], d["tok_v"][idx],
                                      d["tok_sk"][idx], d["tok_sv"][idx]))
        for i, pid in enumerate(pids):
            self.pool.tok_q[0, pid] = kq[i]
            self.pool.tok_q[1, pid] = vq[i]
            self.pool.tok_scale[0, pid] = ks[i]
            self.pool.tok_scale[1, pid] = vs[i]

    # ------------------------------------------- device-resident appends
    def claim_append_targets(self, slot_rids: list) -> dict:
        """Host-metadata half of the on-device append: allocate/locate the
        (page, offset) each attention layer's new token scatters into.
        Returns a pytree shaped like ``decode_step_paged``'s new-cache
        (``None`` at recurrent-kind positions); idle slots carry the
        out-of-range page sentinel, dropped by the scatter."""
        with span("kv.claim_append"):
            b = len(slot_rids)
            sentinel = self.pool.num_pages
            per_layer = {layer: (np.full(b, sentinel, np.int32),
                                 np.zeros(b, np.int32))
                         for layer in self.attn_layers}
            for slot, rid in enumerate(slot_rids):
                if rid is None:
                    continue
                t = self.seq_len[rid]
                for layer in self.attn_layers:
                    per_layer[layer][0][slot] = self._claim_page(rid, layer, t)
                    per_layer[layer][1][slot] = t % self.page_size
            prefix = [(self._put(per_layer[i][0]), self._put(per_layer[i][1]))
                      if kind in ATTN_KINDS else None
                      for i, kind in enumerate(self.cfg.prefix_pattern)]
            blocks = []
            for c, kind in enumerate(self.cfg.cycle):
                if kind not in ATTN_KINDS:
                    blocks.append(None)
                    continue
                layers = [self.n_prefix + j * self.n_cycle + c
                          for j in range(self.n_stack)]
                blocks.append((self._put(np.stack([per_layer[l][0]
                                                   for l in layers])),
                               self._put(np.stack([per_layer[l][1]
                                                   for l in layers]))))
            return {"prefix": prefix, "blocks": tuple(blocks)}

    def note_appended(self, slot_rids: list) -> None:
        """Metadata half of the on-device append (fused-path analogue of
        ``append_token``): advance fills and sequence lengths, seal pages
        that just filled (pulling their payload from the device mirror —
        the only steady-state d2h, amortized over ``page_size`` steps),
        evict rolled-out pages, and push freshly sealed/packed planes
        back to the device."""
        with span("kv.note_appended"):
            for slot, rid in enumerate(slot_rids):
                if rid is None:
                    continue
                for layer in self.attn_layers:
                    pid = self.page_tables[rid][layer][-1]
                    self.pool.note_device_write(pid)
                    if int(self.pool.fill[pid]) == self.page_size:
                        self._seal_from_device(layer, pid, rid)
                self.seq_len[rid] += 1
                self.evict_rolled(rid)
            self._flush_device()

    def _seal_from_device(self, layer: int, pid: int, rid: int) -> None:
        d = self.dev.planes
        if self.dev.mesh is None:
            # plain eager gather: compiles in microseconds per pid and the
            # single-device executables are trivial, so no jit is worth a
            # multi-second compile landing mid-serve (it would poison the
            # straggler watchdog's step-time baseline)
            with span("kv.seal.pull", rid=rid):
                kq, vq, ks, vs = self._fetch((d["tok_k"][pid],
                                              d["tok_v"][pid],
                                              d["tok_sk"][pid],
                                              d["tok_sv"][pid]))
        else:
            # on a sharded plane the page index must be a *traced* operand:
            # a static python index bakes the pid into the jaxpr, and every
            # distinct pid would pay a fresh SPMD partitioning compile (a
            # recompile storm that dwarfs the seal itself); one dynamic-slice
            # executable serves every page.  Only the four token staging
            # planes are operands — passing the whole planes dict would
            # recompile whenever ensure_table_capacity reallocates the
            # table planes
            if self._page_pull is None:
                self._page_pull = jax.jit(lambda tk, tv, sk, sv, i: (
                    tk[i], tv[i], sk[i], sv[i]))
            with span("kv.seal.pull", rid=rid):
                kq, vq, ks, vs = self._fetch(self._page_pull(
                    d["tok_k"], d["tok_v"], d["tok_sk"], d["tok_sv"],
                    jnp.asarray(pid, jnp.int32)))
        self.pool.tok_q[0, pid] = kq
        self.pool.tok_q[1, pid] = vq
        self.pool.tok_scale[0, pid] = ks
        self.pool.tok_scale[1, pid] = vs
        self._seal(layer, pid)

    # ------------------------------------------- device-resident states
    def read_state_slot(self, slot: int) -> dict:
        """Fetch one slot's recurrent-kind states from the device store
        (preemption/snapshot boundary — never the steady-state loop)."""
        picked = {}
        for layer in self.state_layers:
            leaf, j = self._layer_cache(self.dev_states, layer)
            picked[layer] = {f: (x[slot] if j is None else x[j, slot])
                             for f, x in leaf.items()}
        fetched = self._fetch(picked)
        return {layer: {f: np.asarray(v) for f, v in d.items()}
                for layer, d in fetched.items()}

    def write_state_slot(self, slot: int, rid: int) -> None:
        """Push ``self.states[rid]`` (prefill ingest / snapshot restore)
        into the device state store at ``slot``."""
        for layer in self.state_layers:
            st = self.states[rid].get(layer)
            if st is None:
                raise RuntimeError(
                    f"request {rid} has no state for layer {layer} "
                    "(prefill not ingested?)")
            leaf, j = self._layer_cache(self.dev_states, layer)
            for f, v in st.items():
                arr = self._put(np.ascontiguousarray(v))
                leaf[f] = (leaf[f].at[slot].set(arr) if j is None
                           else leaf[f].at[j, slot].set(arr))

    def _pull_states(self, slot_rids: list) -> None:
        if self.dev_states is None or not self.state_layers:
            return
        for slot, rid in enumerate(slot_rids):
            if rid is not None and rid in self.states:
                self.states[rid] = self.read_state_slot(slot)

    # --------------------------------------------------- step metadata
    def meta_pages(self, max_len: int, slot_rids: list | None = None) -> int:
        """Page-slot count of the fused kernel's grid.  Without
        ``slot_rids``: the static worst case for the full context.  With
        ``slot_rids``: the power-of-two bucket over the busiest active
        slot's *occupied* page count (``kernels.paged_decode.page_bucket``)
        capped at the worst case — a batch of mostly-short requests stops
        paying the max-pages grid.  Bit-exact either way: slots past a
        request's table mask via state == FREE, and a fully-masked page
        leaves the online-softmax accumulator unchanged.  Grid sizes
        bucket to powers of two so the decode jit compiles O(log pages)
        variants, with the same recompile-storm guard as the gather."""
        from repro.kernels.paged_decode import page_bucket
        pmax = max(1, self.pages_per_seq(max_len))
        if slot_rids is None:
            return pmax
        used = 1
        for rid in slot_rids:
            if rid is None or rid not in self.page_tables:
                continue
            for layer in self.attn_layers:
                used = max(used, len(self.page_tables[rid][layer]))
        return min(pmax, page_bucket(used))

    def step_meta(self, slot_rids: list, max_len: int) -> dict:
        """Per-step page-table metadata for ``decode_step_paged`` — the
        only per-step host->device upload of the fused path (a few i32
        per page slot).  Also accrues the read-traffic counters the
        materialize path would have charged (same pages are read, just
        decoded at point of use)."""
        with span("kv.step_meta"):
            b = len(slot_rids)
            pmax = self.meta_pages(max_len, slot_rids)
            ps = self.page_size
            per_layer = {}
            for layer in self.attn_layers:
                per_layer[layer] = {
                    "pid": np.zeros((b, pmax), np.int32),
                    "tid": np.full((b, pmax), 2 * layer, np.int32),
                    "state": np.zeros((b, pmax), np.int32),     # FREE: masked
                    "t0": np.zeros((b, pmax), np.int32),
                    "qw": np.zeros((b, 2), np.int32),
                }
            for slot, rid in enumerate(slot_rids):
                if rid is None:
                    continue
                qpos = self.seq_len[rid]
                for layer in self.attn_layers:
                    kind = self.layer_kinds[layer]
                    d = per_layer[layer]
                    base = self.page_base[rid][layer]
                    for k_, pid in enumerate(self.page_tables[rid][layer]):
                        d["pid"][slot, k_] = pid
                        # K-row of the (generation, layer, kind) table id the
                        # page was coded under (V row = +1 in-kernel); pages
                        # from different refresh generations coexist per step
                        d["tid"][slot, k_] = self._row(
                            self._checked_gen(pid, rid, layer), layer, 0)
                        d["state"][slot, k_] = int(self.pool.state[pid])
                        d["t0"][slot, k_] = (base + k_) * ps
                    d["qw"][slot] = (qpos, self._ring(max_len)
                                     if kind == "local" else 0)
            self._accrue_read_traffic(slot_rids, max_len)

            def pack(layer_arrs):
                return {k: self._put(v) for k, v in layer_arrs.items()}

            prefix = [pack(per_layer[i]) if kind in ATTN_KINDS else {}
                      for i, kind in enumerate(self.cfg.prefix_pattern)]
            blocks = []
            for c, kind in enumerate(self.cfg.cycle):
                if kind not in ATTN_KINDS:
                    blocks.append({})
                    continue
                layers = [self.n_prefix + j * self.n_cycle + c
                          for j in range(self.n_stack)]
                blocks.append({k: self._put(np.stack([per_layer[l][k]
                                                      for l in layers]))
                               for k in per_layer[layers[0]]})
            return {"prefix": prefix, "blocks": tuple(blocks)}

    def _accrue_read_traffic(self, slot_rids: list, max_len: int) -> None:
        """Charge the per-step KV read traffic (shared by materialize and
        the fused path — both read the same pages, the fused path just
        decodes them at point of use).  Partially-rolled-out pages of
        local layers charge only their *live token range* — the sub-page
        read accounting that reclaims the ``(ps-1)/window`` overhead
        (sub-page decode itself stays whole-page)."""
        pool, ps = self.pool, self.page_size
        raw = {"global": 0, "local": 0}
        read = {"global": 0, "local": 0}
        self._read_clock += 1
        for slot, rid in enumerate(slot_rids):
            if rid is None:
                continue
            qpos = self.seq_len[rid]
            for layer in self.attn_layers:
                kind = self.layer_kinds[layer]
                base = self.page_base[rid][layer]
                for k_, pid in enumerate(self.page_tables[rid][layer]):
                    if pid < 0:
                        raise m.PageIntegrityError(
                            f"active request {rid} layer {layer} page {k_} "
                            "is SPILLED at read time — readahead must "
                            "restore before decode", rid=rid, layer=layer)
                    self._checked_gen(pid, rid, layer)
                    self.page_last_read[pid] = self._read_clock
                    t0 = (base + k_) * ps
                    state = pool.state[pid]
                    n_tok = (int(pool.fill[pid]) if state == m.PAGE_HOT
                             else ps)
                    if kind == "local":
                        n_live = int(np.sum(np.arange(t0, t0 + n_tok)
                                            >= qpos - self._ring(max_len)))
                    else:
                        n_live = n_tok
                    raw[kind] += pool.dense_bytes(n_live)
                    charged = pool.page_bytes(pid)
                    if n_live < n_tok:
                        charged = -(-charged * n_live // n_tok)
                    read[kind] += charged
        for kind in ("global", "local"):
            self.traffic[f"kv_raw_bytes_{kind}"] += raw[kind]
            self.traffic[f"kv_read_bytes_{kind}"] += read[kind]
        self.traffic["kv_raw_bytes"] += raw["global"] + raw["local"]
        self.traffic["kv_read_bytes"] += read["global"] + read["local"]

    # -------------------------------------------------------- materialize
    def materialize(self, slot_rids: list, max_len: int) -> dict:
        """Rebuild the dense cache pytree for the active batch.

        Attention layers: HOT/COLD pages copy straight from the pool;
        PACKED pages decode in ONE batched Pallas gather-decode call per
        K/V kind (page-index + table-id vectors padded to a jit bucket),
        spanning every layer.  Global layers land at absolute positions,
        rolling layers in the ring slot ``pos % ring`` with dead positions
        skipped.  Recurrent-kind layers stitch the stored per-request
        states (init template for empty slots).  Also accrues the
        per-stream raw-vs-actual read-traffic counters."""
        from repro.core import quant
        from repro.kernels.paged_decode import gather_bucket, gather_decode
        pool = self.pool
        if self.dev is not None:
            # device-resident mode: HOT payloads + states live on device;
            # the materialize/oracle path needs the host mirror current
            self.sync_hot_to_host(slot_rids)
            self._pull_states(slot_rids)
        self._accrue_read_traffic(slot_rids, max_len)
        b = len(slot_rids)
        h, dh, ps = pool.kv_heads, pool.head_dim, self.page_size

        def span(kind):
            return max_len if kind == "global" else self._ring(max_len)

        kvq = {layer: np.zeros((2, b, span(self.layer_kinds[layer]), h, dh),
                               np.int8) for layer in self.attn_layers}
        kvs = {layer: np.zeros((2, b, span(self.layer_kinds[layer]), h),
                               np.float32) for layer in self.attn_layers}

        def place(layer, kind01, slot, t0, n_tok, q, sc, qpos):
            """q: [n_tok, H, dh], sc: [n_tok, H] -> dense-cache layout."""
            kind = self.layer_kinds[layer]
            if kind == "global":
                n_tok = min(n_tok, max_len - t0)
                kvq[layer][kind01, slot, t0:t0 + n_tok] = q[:n_tok]
                kvs[layer][kind01, slot, t0:t0 + n_tok] = sc[:n_tok]
            else:
                ring = kvq[layer].shape[2]
                a = np.arange(t0, t0 + n_tok)
                live = a >= qpos - ring
                if live.any():
                    kvq[layer][kind01, slot, a[live] % ring] = q[live]
                    kvs[layer][kind01, slot, a[live] % ring] = sc[live]

        jobs: list[tuple] = []           # (layer, pid, slot, t0, qpos)
        for slot, rid in enumerate(slot_rids):
            if rid is None:
                continue
            qpos = self.seq_len[rid]
            for layer in self.attn_layers:
                kind = self.layer_kinds[layer]
                base = self.page_base[rid][layer]
                for k_, pid in enumerate(self.page_tables[rid][layer]):
                    t0 = (base + k_) * ps
                    state = pool.state[pid]
                    n_tok = (int(pool.fill[pid]) if state == m.PAGE_HOT
                             else ps)
                    if state == m.PAGE_HOT:
                        for kind01 in (0, 1):
                            place(layer, kind01, slot, t0, n_tok,
                                  pool.tok_q[kind01, pid, :n_tok],
                                  pool.tok_scale[kind01, pid, :n_tok], qpos)
                    elif state == m.PAGE_COLD:
                        for kind01 in (0, 1):
                            place(layer, kind01, slot, t0, ps,
                                  pool.cold_q[kind01, pid],
                                  np.broadcast_to(
                                      pool.page_scale[kind01, pid][None],
                                      (ps, h)), qpos)
                    else:
                        jobs.append((layer, pid, slot, t0, qpos))
        if jobs:
            vm, ol, cm = self._tables_stacked()
            idx = np.asarray([pid for _, pid, _, _, _ in jobs], np.int32)
            g = gather_bucket(len(idx))
            pad = (0, g - len(idx))
            idx_p = self._put(np.pad(idx, pad, mode="edge"))
            for kind01 in (0, 1):
                tid = np.asarray([self._row(int(self.page_gen[pid]), layer,
                                            kind01)
                                  for layer, pid, *_ in jobs], np.int32)
                out = gather_decode(
                    self._put(pool.sym[kind01]),
                    self._put(pool.ofs[kind01]),
                    self._put(pool.stored[kind01]), idx_p,
                    self._put(vm), self._put(ol), self._put(cm),
                    n_steps=pool.elems_per_stream, backend=self.backend,
                    table_idx=self._put(np.pad(tid, pad, mode="edge")))
                vals = self._fetch(out)[:len(jobs)].astype(np.uint8)
                q = quant.from_unsigned(vals).reshape(len(jobs), ps, h, dh)
                for i, (layer, pid, slot, t0, qpos) in enumerate(jobs):
                    place(layer, kind01, slot, t0, ps, q[i],
                          np.broadcast_to(pool.page_scale[kind01, pid][None],
                                          (ps, h)), qpos)

        def attn_leaves(layer):
            return {"k": kvq[layer][0], "v": kvq[layer][1],
                    "k_scale": kvs[layer][0], "v_scale": kvs[layer][1]}

        def state_leaves(layer):
            tmpl = self._state_template(self.layer_kinds[layer])
            out = {}
            for f, t0_ in tmpl.items():
                rows = []
                for rid in slot_rids:
                    st = self.states[rid].get(layer) if rid is not None \
                        else None
                    rows.append(st[f] if st is not None else t0_)
                out[f] = np.stack(rows)
            return out

        prefix = []
        for i in range(self.n_prefix):
            leaves = (attn_leaves(i) if self.layer_kinds[i] in ATTN_KINDS
                      else state_leaves(i))
            prefix.append({f: self._put(x) for f, x in leaves.items()})
        blocks = []
        for c in range(self.n_cycle):
            layers = [self.n_prefix + j * self.n_cycle + c
                      for j in range(self.n_stack)]
            if self.cfg.cycle[c] in ATTN_KINDS:
                per = [attn_leaves(l) for l in layers]
            else:
                per = [state_leaves(l) for l in layers]
            blocks.append({f: self._put(np.stack([p[f] for p in per]))
                           for f in per[0]})
        return {"prefix": prefix, "blocks": tuple(blocks)}
