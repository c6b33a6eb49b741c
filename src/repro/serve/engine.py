"""Batched serving engine with APack-compressed weights.

Continuous batching over a fixed pool of decode slots; finished sequences
retire and waiting requests are admitted with a (jit-cached, power-of-two
bucketed) single-request prefill.  Weights arrive APack-compressed
(``compress_params``): the engine decompresses through the bit-exact codec
at load and keeps per-tensor traffic stats — on TPU the fused
``decompress_matmul`` kernel consumes the compressed planes directly
(kernels/decompress_matmul.py), which is the paper's Figure-1 integration;
this engine is the scheduling layer above it.

Two schedulers share every slot/pool/pressure mechanism:

* ``scheduler="sync"`` — the original loop: retire / admit / decode /
  host work, strictly serialized per step.
* ``scheduler="async"`` — the event-loop core (DESIGN.md §9): the fused
  decode is *dispatched* and left in flight while the next iteration's
  host work runs (seal pulls, sketch refresh + budgeted re-pack, chunked
  prefill ingest, spill-tier readahead staging), then collected one
  iteration later.  Greedy tokens are bit-identical to the sync engine —
  the same kernels see the same inputs, only the host work moved off the
  device critical path.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant, tables
from repro.kernels import fastpath, ops
from repro.kernels.decompress_matmul import DEFAULT_WEIGHT_MIN_SIZE
from repro.models import model as M
from repro.models import sharding as shd
from repro.models import modules as m
from repro.models.config import ModelConfig
from repro.runtime.spans import span
from repro.runtime.supervisor import StragglerWatchdog, WatchdogEvent

_log = logging.getLogger("repro.serve")

# Distinct jit prefill bucket sizes before the recompile-storm warning
# fires (same guard as kernels.paged_decode.gather_bucket).
PREFILL_BUCKET_WARN_THRESHOLD = 12
_seen_prefill_buckets: set[int] = set()


def prefill_bucket(s: int, max_len: int) -> int:
    """Power-of-two jit bucket for a prompt of length ``s``, capped at the
    context window (every admissible prompt fits it, so the cap keeps the
    bucket a valid cache length).  Varied-length traffic compiles one
    prefill per *bucket* instead of one per exact length; past
    ``PREFILL_BUCKET_WARN_THRESHOLD`` distinct buckets a warning fires
    once per new size — the same recompile-storm guard PR 4 added for
    ``gather_bucket``."""
    b = 1
    while b < s:
        b *= 2
    b = min(b, max_len)
    if b not in _seen_prefill_buckets:
        _seen_prefill_buckets.add(b)
        if len(_seen_prefill_buckets) > PREFILL_BUCKET_WARN_THRESHOLD:
            _log.warning(
                "prefill has compiled %d distinct jit bucket sizes "
                "(latest: %d): recompile storm — consider normalizing "
                "prompt lengths or growing the bucket threshold",
                len(_seen_prefill_buckets), b)
    return b


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # [S] int32
    max_new_tokens: int = 32
    eos_id: int | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # timestamps are time.perf_counter() — the monotonic clock.  A
    # wall-clock here (the old time.time()) races NTP slew against the
    # step loop's perf_counter and can report negative latencies.
    t_submit: float = 0.0
    t_admit: float = 0.0                # prefill dispatch (queue-wait end)
    t_first: float = 0.0                # first token appended
    t_done: float = 0.0
    # SLO: steps this request may hold a decode slot while others queue
    # (None: engine-level slot_deadline_steps, or no deadline at all)
    deadline_steps: int | None = None
    # SLO: target end-to-end latency.  Admission orders by earliest
    # deadline (t_submit + slo_ms); None sorts last, so traffic that sets
    # no SLOs keeps pure-FIFO admission exactly.
    slo_ms: float | None = None
    # structured failure (integrity quarantine): done=True + error set,
    # tokens truncated at the failure point — never silently wrong
    error: str | None = None


@dataclasses.dataclass
class _InFlight:
    """Dispatch-time record of one in-flight fused decode step (async
    scheduler).  Collect applies tokens against this snapshot of the
    slot binding — immune to any later rebinding, which by construction
    only happens post-collect."""
    slot_reqs: list                      # dispatch-time slot -> Request
    slot_rids: list                      # dispatch-time slot -> rid
    logits: Any                          # device future, [B, 1, V]


@dataclasses.dataclass
class _PendingPrefill:
    """A queued request whose prefill is being pumped in the background
    (async scheduler): the bucketed forward was dispatched (device
    future), its cache view is pulled once, and pages ingest chunk by
    chunk during the overlapped host phase — one long prompt no longer
    stalls the whole batch behind a monolithic prefill."""
    req: Request
    s: int                               # true prompt length
    logits: Any                          # [1, 1, V] device future
    caches: Any                          # forward caches until view pull
    view: dict | None = None             # host-side prefill view
    cursor: int = 0                      # tokens ingested so far
    tok: int | None = None               # first generated token when done

    @property
    def ready(self) -> bool:
        return self.tok is not None


class AdmissionImpossible(RuntimeError):
    """Admission can never succeed for the queue head — the structured
    replacement for ``run_until_drained`` silently spinning to
    ``max_steps``.  Names the request and its page reservation."""

    def __init__(self, req: Request, need: int, pool_pages: int, why: str):
        super().__init__(
            f"request {req.rid} can never be admitted: reserves {need} "
            f"pages worst-case against a pool of {pool_pages} ({why})")
        self.rid = req.rid
        self.pages_needed = need
        self.pool_pages = pool_pages


@dataclasses.dataclass
class CompressedParams:
    """APack-compressed int8 view of a param tree (large matrices only)."""
    containers: dict                     # path -> (CompressedTensor, QuantParams)
    passthrough: dict                    # path -> raw small leaves
    treedef: Any
    n_leaves: int
    original_bytes: int
    compressed_bytes: int

    @property
    def ratio(self) -> float:
        return self.original_bytes / max(self.compressed_bytes, 1)


def compress_params(params: Any,
                    min_size: int = DEFAULT_WEIGHT_MIN_SIZE
                    ) -> CompressedParams:
    """int8-quantize + APack-compress every large matrix in a param tree."""
    leaves, treedef = jax.tree.flatten(params)
    containers: dict = {}
    passthrough: dict = {}
    orig = comp = 0
    for i, leaf in enumerate(leaves):
        arr = np.asarray(jax.device_get(leaf))
        orig += arr.nbytes
        if arr.size >= min_size and arr.dtype.kind == "f" and arr.ndim >= 2:
            q, qp = quant.quantize_symmetric(jnp.asarray(arr, jnp.float32),
                                             axis=-1)
            u = quant.to_unsigned(np.asarray(q))
            # Weights are static, so the paper's weight-mode heuristic
            # applies: profile the full tensor (histogram is cheap) and do
            # NOT steal probability counts for empty ranges — that slack is
            # only needed for activations whose values aren't all profiled.
            # (tests/test_serve.py pins table.mode == "weight".)
            table = tables.table_for(u.reshape(-1), is_activation=False)
            ct = fastpath.compress_np(u, table)
            scale = np.asarray(qp.scale)
            containers[i] = (ct, scale, str(arr.dtype))
            # ceil-bytes, and the per-channel dequant scale ships with the
            # payload — flooring the bits and dropping the scale stream
            # (the old accounting) overstated the ratio
            comp += -(-ct.total_bits // 8) + scale.nbytes
        else:
            passthrough[i] = arr
            comp += arr.nbytes
    return CompressedParams(containers=containers, passthrough=passthrough,
                            treedef=treedef, n_leaves=len(leaves),
                            original_bytes=orig, compressed_bytes=comp)


def decompress_params(cp: CompressedParams) -> Any:
    leaves: list = [None] * cp.n_leaves
    for i, arr in cp.passthrough.items():
        leaves[i] = jnp.asarray(arr)
    for i, (ct, scale, dtype) in cp.containers.items():
        u = fastpath.decompress_np(ct)
        q = quant.from_unsigned(u, bits=ct.bits)
        leaves[i] = (jnp.asarray(q, jnp.float32)
                     * jnp.asarray(scale)).astype(jnp.dtype(dtype))
    return jax.tree.unflatten(cp.treedef, leaves)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params: Any, *, max_batch: int = 8,
                 max_len: int = 256, eos_id: int | None = None,
                 kv_pages: int | None = None, kv_page_size: int = 16,
                 kv_calib_pages: int = 4, kv_backend: str | None = None,
                 kv_fused: bool | None = None, kv_refresh: bool = False,
                 kv_refresh_every_pages: int | None = None,
                 kv_refresh_threshold: float = 0.15,
                 kv_refresh_min_pages: int = 4,
                 kv_repack_budget: int = 4,
                 kv_pressure: bool = False,
                 slot_deadline_steps: int | None = None,
                 pressure_backoff_max: int = 64,
                 watchdog_ratio: float | None = None,
                 watchdog_patience: int = 3,
                 kv_verify_on_repack: bool = False,
                 scheduler: str = "sync",
                 prefill_chunk_tokens: int | None = None,
                 mesh=None,
                 faults=None,
                 weights: str | None = None,
                 weight_min_size: int | None = None,
                 weight_tile_k: int | None = None):
        self.cfg = cfg
        self.params = params
        # packed weight store: ``weights="apack-int8"`` converts every
        # large projection/FFN matrix to CompressedLinear planes resident
        # in HBM (model.pack_weights) and the forward routes those sites
        # through the fused decompress-matmul — the weight-read stream at
        # decode becomes the compressed footprint, not the dense one.
        self.weights_mode = weights
        self._weight_stats: dict | None = None
        if weights is not None:
            if weights != "apack-int8":
                raise ValueError(f"unknown weights mode {weights!r}; "
                                 "expected 'apack-int8' or None")
            self.params, self._weight_stats = M.pack_weights(
                cfg, params, min_size=weight_min_size, tile_k=weight_tile_k)
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.queue: deque[Request] = deque()
        self.active: list[Request | None] = [None] * max_batch
        self.positions = np.zeros(max_batch, np.int32)
        self.last_tokens = np.zeros((max_batch, 1), np.int32)
        self.last_logits = None              # device array, step output
        self.stats = {"steps": 0, "generated": 0, "completed": 0,
                      "kv_admission_blocked": 0, "preempted": 0,
                      "resumed": 0, "kv_refreshes": 0,
                      "kv_pages_repacked": 0, "failed": 0,
                      "spilled_requests": 0, "admission_retries": 0,
                      "pressure_preempted": 0, "deadline_preempted": 0,
                      "watchdog_preempted": 0, "prefill_chunks": 0,
                      "staged_readahead": 0}
        # pressure policy: level 1 (always on) spills *preempted*
        # requests' idle pages to the host tier when admission blocks;
        # level 2 (kv_pressure opt-in) additionally preempts-with-spill
        # active slots under exponential backoff.  Without the opt-in,
        # blocked admission keeps today's FIFO-wait semantics.
        self.kv_pressure = kv_pressure
        self.slot_deadline_steps = slot_deadline_steps
        self.pressure_backoff_max = pressure_backoff_max
        self._pressure_backoff = 1
        self._next_pressure_admit = 0
        self._admit_clock = 0
        self._slot_steps = np.zeros(max_batch, np.int64)
        self._spilled: set[int] = set()
        # step-time watchdog (shared StragglerWatchdog code path with the
        # training Supervisor): a hung step preempts-with-spill the
        # longest-running slot so the rest of the batch keeps moving
        self.watchdog = (StragglerWatchdog(ratio=watchdog_ratio,
                                           patience=watchdog_patience)
                         if watchdog_ratio is not None else None)
        self.faults = faults
        # adaptive table refresh: when enabled, every decode step checks
        # the drift triggers and re-packs at most ``kv_repack_budget``
        # stale pages, so a refresh amortizes over steps instead of
        # stalling the batch (steady-state latency preserved; the re-pack
        # is host-side + h2d sync only — zero device_get)
        self.kv_refresh = kv_refresh
        self.kv_repack_budget = kv_repack_budget
        # paged, APack-compressed KV mode.  Default (fused=True): the pool
        # planes stay device-resident, attention reads pages through the
        # fused gather-decode kernel and the new token appends on-device —
        # no per-step host<->device payload traffic.  kv_fused=False keeps
        # the legacy materialize path (dense cache rebuilt from the pool
        # every step) as the parity oracle.
        self.paged = cfg.kv_cache_dtype == "apack-int8"
        self.fused = bool(kv_fused) if kv_fused is not None else self.paged
        # mesh-sharded serving (DESIGN.md §11): decode jobs data-parallel
        # over the mesh's "data" axis (slots, state store, page planes and
        # per-shard free lists all partition with their jobs), kv-heads
        # tensor-parallel over "model" inside the fused kernel.  Greedy
        # tokens are bit-identical to a one-device engine running the
        # same sharded step at one shard's batch; against the unsharded
        # engine at the full batch they can differ by rounding on a TPU
        # (DESIGN.md §11).
        mesh = shd.auto_mesh(mesh)
        self.mesh = mesh
        self._n_data = 1
        self._n_model = 1
        self._step_mesh = None
        if mesh is not None:
            if not (self.paged and self.fused):
                raise ValueError(
                    "mesh= requires the fused paged apack-int8 KV (the "
                    "sharded step is the combined decode+append program)")
            if scheduler != "sync":
                raise ValueError(
                    "mesh= requires scheduler='sync' (the async overlap "
                    "window is not shard-aware yet)")
            if "data" not in dict(mesh.shape):
                raise ValueError("serving mesh must name a 'data' axis")
            self._n_data, self._n_model = M.mesh_axis_sizes(mesh)
            if max_batch % self._n_data:
                raise ValueError(
                    f"max_batch={max_batch} must divide over the "
                    f"{self._n_data}-way data axis (whole slots per shard)")
            if self._n_model > 1 and cfg.num_kv_heads % self._n_model:
                raise ValueError(
                    f"num_kv_heads={cfg.num_kv_heads} must divide over "
                    f"the {self._n_model}-way model axis")
        if self.paged:
            if kv_pages is None:
                # enough for every slot at full context (slot-equivalent),
                # per layer kind: rolling layers cap at their window pages,
                # recurrent-kind layers take none
                kv_pages = max_batch * M.PagedKVCache.pages_for_config(
                    cfg, max_len, kv_page_size)
            if kv_pages % self._n_data:
                # whole pages per shard: round the pool up so every data
                # shard owns an equal contiguous range
                kv_pages += self._n_data - kv_pages % self._n_data
            self.kv = M.PagedKVCache(
                cfg, kv_pages, page_size=kv_page_size,
                calib_pages=kv_calib_pages, backend=kv_backend,
                refresh_every_pages=kv_refresh_every_pages,
                refresh_threshold=kv_refresh_threshold,
                refresh_min_pages=kv_refresh_min_pages,
                verify_on_repack=kv_verify_on_repack,
                n_shards=self._n_data)
            self.kv.faults = faults
            self._reserved: dict[int, int] = {}
            # per-shard reservation accounting — THE admission mechanism
            # (a single shard reduces it to the old global check, so the
            # single-device engine is the n_data=1 special case, not a
            # separate code path).  No global lock: each shard's admission
            # reserves against its own free-list-backed counter.
            self._rshard: dict[int, int] = {}
            self._shard_reserved: list[int] = [0] * self._n_data
            # rid -> (compressed state snapshot, position, last token):
            # preempted requests resume without re-prefill
            self._preempted: dict[int, tuple] = {}
            self.cache = None
            if self.fused:
                self.kv.enable_device_pool(max_batch, mesh=mesh)
                if mesh is not None:
                    self._step_mesh = M.build_sharded_step(
                        cfg, mesh, backend=kv_backend, params=self.params)
                self._decode_paged = jax.jit(
                    lambda p, pl, st, mt, t, pos: M.decode_step_paged(
                        cfg, p, pl, st, mt, t, pos, backend=kv_backend))
                self._append = jax.jit(
                    lambda pl, nc, tg: M.device_append(cfg, pl, nc, tg))
        else:
            self.fused = False
            self.kv = None
            self.cache = M.init_cache(cfg, max_batch, max_len)
        self._decode = jax.jit(
            lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))
        self._prefill_cache = {}
        # ---- event-loop scheduler state (DESIGN.md §9) ----
        if scheduler not in ("sync", "async"):
            raise ValueError(f"unknown scheduler {scheduler!r}")
        if scheduler == "async" and not (self.paged and self.fused):
            raise ValueError(
                "scheduler='async' requires the fused paged apack-int8 KV "
                "(the overlap window is the in-flight fused device step)")
        self.scheduler = scheduler
        # chunked-prefill ingest budget per overlapped host phase; the
        # default covers a few pages so short prompts still bind in one
        # step while long ones amortize over many
        self.prefill_chunk_tokens = (int(prefill_chunk_tokens)
                                     if prefill_chunk_tokens
                                     else kv_page_size * 4)
        self._inflight: _InFlight | None = None
        self._pump: dict[int, _PendingPrefill] = {}
        self._lat_wait: list[float] = []
        self._lat_e2e: list[float] = []
        self._lat_ttft: list[float] = []

    # -------------------------------------------------------- scheduling
    def submit(self, req: Request) -> None:
        if self.paged:
            need = self._pages_for(req)
            if need > self._shard_pages():
                # would head-of-line-block the queue forever otherwise
                # (a request lives entirely within one data shard's
                # page range, so the per-shard capacity is the limit)
                raise ValueError(
                    f"request {req.rid} needs {need} pages worst-case but "
                    f"each pool shard only has {self._shard_pages()}; "
                    "shorten the request or grow kv_pages")
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def _pages_for(self, req: Request) -> int:
        """Worst-case page reservation: prompt + generated tokens, capped at
        the context window (so ``append_token`` can never starve)."""
        toks = min(self.max_len, len(req.prompt) + req.max_new_tokens)
        return self.kv.pages_needed(toks)

    def _admission_order(self) -> list[Request]:
        """Queue snapshot in admission priority order: earliest SLO
        deadline first (EDF over ``t_submit + slo_ms``), submission order
        among requests without an SLO and as the tie-break — traffic that
        sets no SLOs keeps today's pure-FIFO admission exactly."""
        if not any(r.slo_ms is not None for r in self.queue):
            return list(self.queue)

        def key(ir):
            i, r = ir
            ddl = (r.t_submit + r.slo_ms / 1e3
                   if r.slo_ms is not None else float("inf"))
            return (ddl, i)

        return [r for _, r in sorted(enumerate(self.queue), key=key)]

    # ------------------------------------------ per-shard reservations
    # Admission accounting is per data shard: shard ``s`` owns pool pages
    # ``[s*pps, (s+1)*pps)`` (matching ``KVPagePool``'s free lists) and
    # the contiguous slot block ``[s*spb, (s+1)*spb)``.  There is no
    # global reservation lock — each shard's admission checks only its
    # own counter, so shards admit independently; the single-device
    # engine is the n_data=1 special case of the same mechanism.
    @property
    def _reserved_total(self) -> int:
        return sum(self._shard_reserved)

    @_reserved_total.setter
    def _reserved_total(self, v: int) -> None:
        # compatibility hook (tests poke this to simulate a full pool):
        # route the whole total to shard 0 — exact on a single shard
        self._shard_reserved = [int(v)] + [0] * (self._n_data - 1)

    def _slot_shard(self, slot: int) -> int:
        return slot // (self.max_batch // self._n_data)

    def _shard_pages(self) -> int:
        """Page capacity of ONE data shard (the whole pool at n_data=1)."""
        return self.kv.pool.num_pages // self._n_data

    def _reserve(self, rid: int, need: int, shard: int) -> None:
        self._reserved[rid] = need
        self._rshard[rid] = shard
        self._shard_reserved[shard] += need

    def _unreserve(self, rid: int) -> int:
        need = self._reserved.pop(rid)
        self._shard_reserved[self._rshard.pop(rid, 0)] -= need
        return need

    def _try_reserve(self, req: Request, shard: int = 0, *,
                     allow_relief: bool) -> int | None:
        """Reservation headroom check for one admission candidate against
        ONE data shard's page budget.  Returns the page count to reserve
        (0 when the request still holds its reservation), or None while
        it stays blocked.  Only the priority head may trigger pressure
        relief (``allow_relief``) — other candidates admit into existing
        headroom only, so continuous batching never spills victims on
        behalf of a request that jumped the queue."""
        need = 0 if req.rid in self._reserved else self._pages_for(req)
        if self._shard_reserved[shard] + need <= self._shard_pages():
            if allow_relief:
                self._pressure_backoff = 1    # clean head admission
            return need
        if not allow_relief:
            return None
        self.stats["kv_admission_blocked"] += 1
        if not self._relieve_pressure(req, need, shard):
            return None                       # request waits
        # Recompute after relief: the victim scan can change this very
        # request's standing (an L2 preemption requeues an active
        # request's pages).  Trusting the stale pre-relief ``need`` was
        # the pool over-commit bug — a head whose own reservation was
        # released by relief would resume with need=0 and under-count
        # the shard counter forever after.
        need = 0 if req.rid in self._reserved else self._pages_for(req)
        if self._shard_reserved[shard] + need > self._shard_pages():
            return None                       # partial relief; retry later
        self.stats["admission_retries"] += 1
        return need

    def _resume_request(self, slot: int, req: Request, need: int,
                        shard: int = 0) -> None:
        if need:
            self._reserve(req.rid, need, shard)
        # spilled requests re-adopt into fresh pages and are shard-free
        # until here; resident preempted requests only reach this with
        # their own shard (the _admit candidate scan guarantees it), so
        # the rebind is a no-op for them
        self.kv.request_shard[req.rid] = shard
        try:
            self._resume_into_slot(slot, req)
        except m.PageIntegrityError as e:
            # quarantined on unspill: fail ONLY this request
            self._fail_request(req, e)

    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self.active[slot] is not None or not self.queue:
                continue
            if not self.paged:
                self._prefill_into_slot(slot, self.queue.popleft())
                continue
            self._admit_clock += 1
            shard = self._slot_shard(slot)
            head = None
            for r in self._admission_order():
                # a preempted-but-resident request's pages are pinned to
                # the shard range they were allocated from: it can only
                # resume into that shard's slots.  Spilled requests
                # re-adopt into fresh pages, so they bind to any shard.
                if (r.rid in self._preempted
                        and r.rid not in self._spilled
                        and self.kv.request_shard.get(r.rid, shard)
                        != shard):
                    continue
                head = r
                break
            if head is None:
                continue                   # nothing eligible for this shard
            need = self._try_reserve(head, shard, allow_relief=True)
            if need is None:
                if self._n_data > 1:
                    continue               # other shards admit independently
                break                      # head waits (FIFO)
            self.queue.remove(head)
            if head.rid in self._preempted:
                self._resume_request(slot, head, need, shard)
                continue
            self._prefill_into_slot(slot, head)

    def _relieve_pressure(self, head: Request, need: int,
                          shard: int = 0) -> bool:
        """Bounded spill -> retry -> preempt escalation under pool
        exhaustion of ONE data shard.  Returns True when reservation
        headroom was freed on that shard (the caller re-checks and
        admits); False means wait.

        Level 1 (always on): spill the *coldest* preempted request still
        holding a reservation on this shard — its pages sit idle in the
        pool, so parking them compressed in the host tier frees a whole
        reservation without touching any active slot.  Level 2
        (``kv_pressure`` opt-in): preempt-with-spill the longest-running
        active slot of this shard, gated by exponential backoff so a pool
        that is simply too small degrades to FIFO instead of livelocking
        on preempt/resume churn."""
        # The head itself can be parked (preempted, reservation held) —
        # it must never be its own victim: spilling it would release the
        # reservation the caller's ``need`` math was computed against
        # (the other half of the over-commit bug `_try_reserve` guards).
        parked = [rid for rid in self._preempted
                  if rid in self._reserved and rid not in self._spilled
                  and rid != head.rid
                  and self._rshard.get(rid, 0) == shard]
        if parked:
            rid = min(parked, key=self.kv.request_last_read)
            self._spill_reserved(rid)
            return True
        if not self.kv_pressure:
            return False
        if self._admit_clock < self._next_pressure_admit:
            return False                  # backing off
        victims = [s for s, r in enumerate(self.active)
                   if r is not None and self._slot_shard(s) == shard]
        if not victims:
            if self._pump:
                # pumped prefills hold reservations and will bind, serve
                # and retire — admission is delayed, not impossible
                return False
            if self._n_data > 1 and any(r is not None for r in self.active):
                # other shards still serve; this shard just waits (a
                # retire elsewhere can't help it, but a spill-free wait
                # is not impossibility — the caller keeps FIFO order)
                return False
            # nothing active and nothing left to spill: no future retire
            # or spill can ever free pages for this reservation
            raise AdmissionImpossible(
                head, need, self._shard_pages(),
                "no active slots to retire and no spillable reservations")
        slot = max(victims, key=lambda s: int(self._slot_steps[s]))
        self.preempt(slot, spill=True, requeue="tail")
        self.stats["pressure_preempted"] += 1
        self._next_pressure_admit = self._admit_clock + self._pressure_backoff
        self._pressure_backoff = min(2 * self._pressure_backoff,
                                     self.pressure_backoff_max)
        return True

    def _spill_reserved(self, rid: int) -> None:
        """Park a preempted request's pages compressed in the host spill
        tier and release its pool reservation (resume re-reserves and
        runs the checksum-verified readahead)."""
        self.kv.spill_request(rid)
        self._unreserve(rid)
        self._spilled.add(rid)
        self.stats["spilled_requests"] += 1

    def _fail_request(self, req: Request, err: Exception) -> None:
        """Structured failure of ONE request (the integrity-quarantine
        recovery path): surface the error on the request, release its
        pages/reservation/snapshot, and leave every other slot untouched
        — corruption never poisons neighbors."""
        req.done = True
        req.error = str(err)
        req.t_done = time.perf_counter()
        self.stats["failed"] += 1
        rid = req.rid
        self._pump.pop(rid, None)
        for s, r in enumerate(self.active):
            if r is req:
                # apack: allow-phase(overlap-reachable only via readahead
                # staging, which fails parked/spilled requests; a request
                # bound to an in-flight slot never takes this path)
                self.active[s] = None
        try:
            self.queue.remove(req)
        except ValueError:
            pass
        if self.paged:
            if rid in self.kv.page_tables:
                # apack: allow-phase(releases a parked request's SPILLED refs
                # and residual pages; the in-flight step's page tables were
                # snapshotted at dispatch and cannot reference this rid)
                self.kv.release(rid)
            if rid in self._reserved:
                self._unreserve(rid)
        self._preempted.pop(rid, None)
        self._spilled.discard(rid)

    def _prefill_forward(self, prompt) -> tuple:
        """Single-request prefill, jit-cached per power-of-two *bucket*
        rather than per exact prompt length — the recompile-storm fix.
        Prompts shorter than their bucket are zero-padded and the model
        masks the pads (``true_len``): pad positions drop out of
        attention, freeze out of the recurrent/mLSTM/sLSTM scans, and the
        returned last-token logits are sliced at the true position.  A
        prompt that lands exactly on its bucket skips the mask entirely
        (bit-identical to the legacy exact-length path)."""
        with span("engine.prefill"):
            s = len(prompt)
            bucket = prefill_bucket(s, self.max_len)
            exact = s == bucket
            key = (bucket, exact)
            fn = self._prefill_cache.get(key)
            if fn is None:
                if exact:
                    fn = jax.jit(
                        lambda p, t: M.forward(self.cfg, p, {"tokens": t},
                                               remat=False, collect_cache=True,
                                               last_only=True)[:2])
                else:
                    fn = jax.jit(
                        lambda p, t, n: M.forward(self.cfg, p, {"tokens": t},
                                                  remat=False,
                                                  collect_cache=True,
                                                  last_only=True,
                                                  true_len=n)[:2])
                self._prefill_cache[key] = fn
            if exact:
                return fn(self.params, jnp.asarray(np.asarray(prompt)[None]))
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :s] = np.asarray(prompt)
            return fn(self.params, jnp.asarray(toks),
                      jnp.asarray(s, jnp.int32))

    def _prefill_into_slot(self, slot: int, req: Request) -> None:
        with span("engine.admit", rid=req.rid):
            s = len(req.prompt)
            req.t_admit = time.perf_counter()
            logits, caches = self._prefill_forward(req.prompt)
            if self.paged:
                # chop the prefill cache into pages instead of a batch write;
                # the request binds to its slot's data shard — page claims
                # come from that shard's free list from here on
                shard = self._slot_shard(slot)
                self.kv.add_request(req.rid, shard=shard)
                self._reserve(req.rid, self._pages_for(req), shard)
                self.kv.ingest_prefill(req.rid, caches, s)
                if self.fused:
                    # admission-time device sync: pages (HOT partials
                    # included) + recurrent-kind states move once, here — the
                    # decode loop itself never uploads payloads
                    self.kv.sync_request_to_device(req.rid)
                    if self.kv.state_layers:
                        self.kv.write_state_slot(slot, req.rid)
            else:
                self._write_prefill_cache(slot, caches)
            # apack: allow-transfer(admission event: first-token pick after a
            # prefill forward; not in the steady-state decode loop)
            next_tok = int(jnp.argmax(logits[0, -1]))
            req.tokens.append(next_tok)
            req.t_first = time.perf_counter()
            self.active[slot] = req
            self.positions[slot] = s
            self.last_tokens[slot, 0] = next_tok
            self._slot_steps[slot] = 0

    def _write_prefill_cache(self, slot: int, caches) -> None:
        # write this sequence's prefill cache into the batch cache at `slot`
        caches = M.extend_caches(self.cfg, caches, self.max_len)

        def put(batch_leaf, one_leaf):
            # both trees have identical ndim (init_cache vs forward caches
            # stacked the same way); find the batch axis by shape matching
            rank = one_leaf.ndim
            # find batch axis: the axis where one_leaf has size 1 and
            # batch_leaf has size max_batch
            for ax in range(rank):
                if one_leaf.shape[ax] == 1 and batch_leaf.shape[ax] == self.max_batch:
                    idx = [slice(None)] * rank
                    idx[ax] = slice(slot, slot + 1)
                    return batch_leaf.at[tuple(idx)].set(
                        one_leaf.astype(batch_leaf.dtype))
            return batch_leaf                          # scalar stats etc.

        self.cache = jax.tree.map(put, self.cache, caches)

    def preempt(self, slot: int, *, spill: bool = False,
                requeue: str = "head") -> dict:
        """Checkpoint/preemption path (paged mode): kick an in-flight
        request out of its decode slot and back to the queue.

        Default (``spill=False``, ``requeue="head"``): its attention KV
        stays where it is — already APack-compressed in the page pool,
        reservation held — while the dense recurrent/mLSTM/sLSTM
        hot-path states are snapshot-compressed
        (``PagedKVCache.snapshot_state``, weight-mode tables, bit-exact).
        Re-admission restores the snapshot and resumes decoding at the
        same position: no re-prefill, byte-identical continuation.

        ``spill=True`` (pressure/deadline/watchdog path) additionally
        parks the pages compressed in the host spill tier and releases
        the pool reservation — resume re-reserves and readahead restores
        them, still byte-identical.  ``requeue="tail"`` avoids the
        head-of-line livelock when the preemption was *caused by* the
        head waiting.  Returns the compressed snapshot (also kept
        internally)."""
        if not self.paged:
            raise RuntimeError("preempt requires the paged apack-int8 KV")
        self._drain()      # async: the in-flight step must land first
        req = self.active[slot]
        if req is None:
            raise ValueError(f"slot {slot} is idle, nothing to preempt")
        if self.fused and self.kv.state_layers:
            # states live on device in fused mode; pull this slot's copy
            # into the host store the snapshot reads (boundary transfer)
            self.kv.states[req.rid] = self.kv.read_state_slot(slot)
        snap = self.kv.snapshot_state(req.rid)
        # drop the dense copy: the compressed snapshot is now the only
        # home of the state, so preemption actually reclaims the memory
        # (and the restore path is load-bearing, not a formality)
        self.kv.states[req.rid] = {}
        self._preempted[req.rid] = (snap, int(self.positions[slot]),
                                    int(self.last_tokens[slot, 0]))
        self.active[slot] = None
        self._slot_steps[slot] = 0
        if requeue == "tail":
            self.queue.append(req)
        else:
            self.queue.appendleft(req)
        self.stats["preempted"] += 1
        if spill:
            self._spill_reserved(req.rid)
        return snap

    def _resume_into_slot(self, slot: int, req: Request) -> None:
        snap, pos, last = self._preempted[req.rid]
        if req.rid in self._spilled:
            # readahead: checksum-verified restore of every SPILLED page
            # into fresh pool slots + ONE batched h2d flush, all before
            # the fused kernel's next read (an admission event — the
            # steady-state zero-device_get invariant is untouched).
            # PageIntegrityError propagates to _admit, which fails only
            # this request (reservation was already re-taken; _fail_request
            # unwinds it).
            self.kv.unspill_request(req.rid)
            self._spilled.discard(req.rid)
        del self._preempted[req.rid]
        self.kv.restore_state(req.rid, snap)
        if self.fused and self.kv.state_layers:
            self.kv.write_state_slot(slot, req.rid)
        self.active[slot] = req
        self.positions[slot] = pos
        self.last_tokens[slot, 0] = last
        self._slot_steps[slot] = 0
        self.stats["resumed"] += 1

    def _retire(self) -> None:
        with span("engine.retire"):
            for slot, req in enumerate(self.active):
                if req is None:
                    continue
                eos = self.eos_id if req.eos_id is None else req.eos_id
                if (len(req.tokens) >= req.max_new_tokens
                        or (eos is not None and req.tokens
                            and req.tokens[-1] == eos)
                        or self.positions[slot] >= self.max_len - 1):
                    req.done = True
                    req.t_done = time.perf_counter()
                    self._log_latency(req)
                    self.stats["completed"] += 1
                    self.active[slot] = None
                    if self.paged:
                        self.kv.release(req.rid)
                        self._unreserve(req.rid)

    def _log_latency(self, req: Request) -> None:
        if req.t_submit <= 0.0:
            return            # directly-constructed request (tests)
        t_admit = req.t_admit if req.t_admit > 0.0 else req.t_done
        self._lat_wait.append(max(t_admit - req.t_submit, 0.0))
        self._lat_e2e.append(max(req.t_done - req.t_submit, 0.0))
        if req.t_first > 0.0:
            self._lat_ttft.append(max(req.t_first - req.t_submit, 0.0))

    def latency_stats(self) -> dict:
        """Queue-wait, time-to-first-token and end-to-end latency
        percentiles (seconds) over every completed request,
        monotonic-clock based (perf_counter) so NTP slew can never report
        a negative latency.  The serving bench and ``launch/serve``
        consume this."""
        out: dict = {"n": len(self._lat_e2e)}
        for name, vals in (("queue_wait", self._lat_wait),
                           ("ttft", self._lat_ttft),
                           ("e2e", self._lat_e2e)):
            if vals:
                out[f"{name}_p50"] = float(np.percentile(vals, 50))
                out[f"{name}_p99"] = float(np.percentile(vals, 99))
                out[f"{name}_mean"] = float(np.mean(vals))
        return out

    def _check_deadlines(self) -> None:
        """Per-request SLO deadlines: a slot that has held the GPU past
        its ``deadline_steps`` (or the engine-wide
        ``slot_deadline_steps``) while other requests queue is
        preempted-with-spill to the queue tail — stuck or SLO-violating
        slots stop starving the batch.  With an empty queue there is
        nothing to yield to, so deadlines don't fire."""
        if not self.queue:
            return
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            ddl = (req.deadline_steps if req.deadline_steps is not None
                   else self.slot_deadline_steps)
            if ddl is not None and int(self._slot_steps[slot]) >= ddl:
                self.preempt(slot, spill=True, requeue="tail")
                self.stats["deadline_preempted"] += 1

    def _on_hung(self, ev: WatchdogEvent) -> None:
        """Watchdog escalation (shared StragglerWatchdog event): the step
        loop is persistently slow — preempt-with-spill the longest-running
        slot (tail requeue) and widen the pressure backoff so recovery
        doesn't immediately re-trigger the stall."""
        victims = [s for s, r in enumerate(self.active) if r is not None]
        if not victims:
            return
        slot = max(victims, key=lambda s: int(self._slot_steps[s]))
        self.preempt(slot, spill=True, requeue="tail")
        self.stats["watchdog_preempted"] += 1
        self.watchdog.reset()
        self._next_pressure_admit = self._admit_clock + self._pressure_backoff
        self._pressure_backoff = min(2 * self._pressure_backoff,
                                     self.pressure_backoff_max)

    def _handle_integrity_failure(self, e: m.PageIntegrityError) -> None:
        """Quarantine recovery: attribute the corruption to its owning
        request and fail exactly that one.  Unattributable corruption
        re-raises — swallowing it would serve wrong tokens."""
        req = None
        if e.rid is not None:
            for r in list(self.active) + list(self.queue):
                if r is not None and r.rid == e.rid:
                    req = r
                    break
        if req is None:
            raise e
        self._fail_request(req, e)

    # ------------------------------------------------------------- step
    # apack: hot-path-root
    def step(self) -> int:
        """One engine iteration.  Returns number of active sequences."""
        with span("engine.step"):
            if self.scheduler == "async":
                return self._step_async()
            return self._step_sync()

    def _step_sync(self) -> int:
        t0 = time.perf_counter()
        if self.faults is not None:
            d = self.faults.step_delay()
            if d:
                time.sleep(d)
        self._retire()
        if self.paged:
            self._check_deadlines()
        self._admit()
        n_active = sum(r is not None for r in self.active)
        if n_active == 0:
            return 0
        # per-slot positions: every sequence advances at its own offset
        # (attention_step takes a [B] position vector)
        slot_rids = [r.rid if r is not None else None for r in self.active]
        try:
            n_active = self._step_decode(slot_rids, n_active)
        except m.PageIntegrityError as e:
            # the guards fire before any page/seq mutation (step_meta /
            # materialize read guards, pre-swap repack verify), so failing
            # the owner here leaves every other slot consistent
            self._handle_integrity_failure(e)
            n_active = sum(r is not None for r in self.active)
        if self.watchdog is not None:
            ev = self.watchdog.observe(time.perf_counter() - t0)
            if ev is not None and ev.kind == "hung":
                self._on_hung(ev)
        return n_active

    def _step_decode(self, slot_rids: list, n_active: int) -> int:
        if self.fused and self._step_mesh is not None:
            # mesh-sharded hot path: decode + append + state re-bind run
            # as ONE jit(shard_map) program, each data shard reading and
            # scattering only its own page range.  Targets are claimed
            # BEFORE step_meta — the claim is host metadata only, and a
            # freshly claimed HOT page has fill 0, so every key slot it
            # could cover is masked and the online-softmax accumulator is
            # bit-exactly unchanged: same tokens as the single-device
            # meta->decode->claim->append order.
            targets = self.kv.claim_append_targets(slot_rids)
            meta = self.kv.step_meta(slot_rids, self.max_len)
            with span("engine.decode_dispatch"):
                logits, toks_dev, self.kv.dev.planes, self.kv.dev_states = \
                    self._step_mesh(
                        self.params, self.kv.dev.planes, self.kv.dev_states,
                        meta, jnp.asarray(self.last_tokens),
                        jnp.asarray(self.positions), targets)
            self.kv.note_appended(slot_rids)
            with span("engine.token_pull"):
                # apack: allow-transfer(the step's one sanctioned sync: token
                # ids must reach the host for EOS/retire — the greedy argmax
                # runs inside the sharded program, so this pulls batch
                # int32s, not the [batch, vocab] logits)
                toks = np.asarray(toks_dev, np.int32)
        elif self.fused:
            # device-resident hot path: pages stay on device, attention
            # gather-decodes them in the fused kernel, and the new token's
            # K/V scatters into the pool planes on-device — the only
            # per-step host<->device traffic is the i32 page-table meta
            # up and the sampled logits down
            meta = self.kv.step_meta(slot_rids, self.max_len)
            with span("engine.decode_dispatch"):
                logits, new_cache = self._decode_paged(
                    self.params, self.kv.dev.planes, self.kv.dev_states,
                    meta, jnp.asarray(self.last_tokens),
                    jnp.asarray(self.positions))
            targets = self.kv.claim_append_targets(slot_rids)
            self.kv.dev.planes = self._append(self.kv.dev.planes,
                                              new_cache, targets)
            self.kv.dev_states = M.states_from_step(self.cfg, new_cache)
            self.kv.note_appended(slot_rids)
            with span("engine.token_pull"):
                # apack: allow-transfer(the step's one sanctioned sync: token
                # ids must reach the host for EOS/retire; the d2h ledger and
                # the zero-device_get gates account for exactly this pull)
                toks = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
        else:
            if self.paged:
                # attention read: rebuild the dense int8 cache from the
                # page pool (compressed pages decode through the Pallas
                # kernel)
                self.cache = self.kv.materialize(slot_rids, self.max_len)
            logits, new_cache = self._decode(self.params, self.cache,
                                             jnp.asarray(self.last_tokens),
                                             jnp.asarray(self.positions))
            # apack: allow-transfer(materialize parity oracle: same sanctioned
            # token-id pull as the fused branch)
            toks = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
            if self.paged:
                # the decode wrote each slot's quantized K/V at its
                # position; extract into the paged store and drop the
                # dense view (re-materialized from pages next step)
                self.kv.append_step_tokens(new_cache, slot_rids,
                                           self.positions)
                self.cache = None
            else:
                self.cache = new_cache
        if self.paged and self.kv_refresh:
            # drift check + budgeted re-pack ride the decode loop: all
            # host-side (sketches were fed at seal time), so the fused
            # path's zero-device_get steady state survives refresh
            rs = self.kv.refresh_step(self.kv_repack_budget)
            self.stats["kv_refreshes"] += len(rs["refreshed_layers"])
            self.stats["kv_pages_repacked"] += rs["repacked"]
        self.last_logits = logits
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            req.tokens.append(int(toks[slot]))
            self.last_tokens[slot, 0] = toks[slot]
            self.positions[slot] += 1
            self._slot_steps[slot] += 1
            self.stats["generated"] += 1
        self.stats["steps"] += 1
        return n_active

    # ------------------------------------------- async event-loop core
    def _step_async(self) -> int:
        """One iteration of the event-loop scheduler.  Phase order *is*
        the design (DESIGN.md §9):

        1. overlapped host work — while the previous iteration's fused
           decode is still in flight on device, run the host work the
           sync engine serializes around the kernel: injected host
           delays, adaptive refresh + budgeted re-pack, chunked prefill
           ingest, spill-tier readahead staging.  Safe because jax
           arrays are immutable and the host pool is truth only for
           sealed pages — nothing here mutates state the in-flight step
           reads, and plane/state re-binds only chain futures for the
           *next* dispatch.
        2. collect — block on the in-flight logits (the loop's only
           blocking device read) and apply tokens against the
           dispatch-time slot map.
        3. retire / deadlines / admit — every slot-binding mutation runs
           here, strictly post-collect; a bind during flight would point
           the dispatch-time ``states_from_step`` slot re-bind at the
           wrong request.
        4. dispatch — fire the next fused step and return without
           blocking on it.

        Greedy tokens are bit-identical to the sync engine: the same
        kernels see the same per-slot inputs, only host work moved."""
        t0 = time.perf_counter()
        with span("engine.overlap_host"):
            if self.faults is not None:
                d = self.faults.step_delay()
                if d:
                    time.sleep(d)
            self._overlap_host_work()
        with span("engine.collect"):
            self._collect()
        with span("engine.schedule_dispatch"):
            self._retire()
            self._check_deadlines()
            self._admit_async()
            n_active = sum(r is not None for r in self.active)
            if n_active:
                try:
                    self._dispatch()
                except m.PageIntegrityError as e:
                    # step_meta read guards fire before any page mutation;
                    # fail the owner and re-dispatch for the survivors
                    self._handle_integrity_failure(e)
                    n_active = sum(r is not None for r in self.active)
                    if n_active:
                        self._dispatch()
        if self.watchdog is not None:
            ev = self.watchdog.observe(time.perf_counter() - t0)
            if ev is not None and ev.kind == "hung":
                self._on_hung(ev)
        return n_active

    def _overlap_host_work(self) -> None:
        """Host-side work overlapped with the in-flight device step —
        everything the sync engine runs serially between kernels."""
        if self.faults is not None:
            d = self.faults.host_delay()
            if d:
                time.sleep(d)
        if self.kv_refresh and self._inflight is not None:
            # drift check + budgeted re-pack (host sketches + one h2d
            # flush chained onto the pending plane futures) — same
            # cadence as the sync engine: once per decode step
            # apack: allow-phase(refresh mutates only sealed PACKED pages
            # with whole-page plane+gen swaps; the in-flight kernel reads the
            # device planes snapshotted at dispatch, so it never observes a
            # half-swapped page)
            rs = self.kv.refresh_step(self.kv_repack_budget)
            self.stats["kv_refreshes"] += len(rs["refreshed_layers"])
            self.stats["kv_pages_repacked"] += rs["repacked"]
        for p in list(self._pump.values()):
            while not p.ready:
                self._pump_chunk(p)
                if self._inflight is not None:
                    break      # paced: one chunk per overlapped step
                # nothing in flight — chunk pacing would be pure added
                # latency, so drain the pump like a sync prefill
        self._stage_readahead()

    def _pump_chunk(self, p: _PendingPrefill) -> None:
        if p.view is None:
            # one d2h pull of the prefill caches — the forward was
            # dispatched at pump start and has been computing since
            p.view = self.kv.prefill_host_view(p.caches)
            p.caches = None
        t1 = min(p.cursor + self.prefill_chunk_tokens, p.s)
        # apack: allow-phase(pending request's pages only: the rid has no
        # slot until admission completes post-collect, so the in-flight
        # step cannot reference these page tables)
        self.kv.ingest_prefill_chunk(p.req.rid, p.view, p.cursor, t1, p.s)
        p.cursor = t1
        self.stats["prefill_chunks"] += 1
        if p.cursor >= p.s:
            # apack: allow-phase(same pending-request argument as the chunk
            # ingest above: no slot binding exists yet for this rid)
            self.kv.finish_prefill(p.req.rid, p.view, p.s)
            # apack: allow-transfer(prefill-completion event in the overlap
            # window: the wait rides the in-flight decode step)
            p.tok = int(jnp.argmax(p.logits[0, -1]))
            p.view = None

    def _stage_readahead(self) -> None:
        """Spill-tier readahead staging: re-reserve and restore the
        highest-priority spilled request during the overlap window, so
        its batched h2d + checksum verify ride the in-flight step
        instead of stalling the admission that resumes it."""
        for req in self._admission_order():
            rid = req.rid
            if rid in self._preempted and rid in self._spilled:
                need = self._pages_for(req)
                # async scheduler is single-shard (mesh rejects it)
                if self._shard_reserved[0] + need > self._shard_pages():
                    return                 # no headroom this step
                self._reserve(rid, need, 0)
                try:
                    # apack: allow-phase(restores a parked spilled request into
                    # fresh pool slots; the in-flight step was dispatched
                    # without this rid and cannot read the new pages)
                    self.kv.unspill_request(rid)
                except m.PageIntegrityError as e:
                    self._fail_request(req, e)
                    return
                self._spilled.discard(rid)
                self.stats["staged_readahead"] += 1
                return                     # one staging per step
            if rid not in self._reserved and rid not in self._pump:
                # a higher-priority request claims the headroom first
                return

    def _start_pump(self, req: Request, need: int) -> None:
        """Reserve pages and dispatch the bucketed prefill forward for a
        queued request; it keeps queueing while the overlapped host phase
        ingests its pages chunk by chunk."""
        req.t_admit = time.perf_counter()
        logits, caches = self._prefill_forward(req.prompt)
        self.kv.add_request(req.rid)
        self._reserve(req.rid, need, 0)     # async is single-shard
        self._pump[req.rid] = _PendingPrefill(
            req=req, s=len(req.prompt), logits=logits, caches=caches)

    def _bind_prefilled(self, slot: int, p: _PendingPrefill) -> None:
        """Slot-bind a fully-ingested pumped prefill.  The only
        device-touching part of admission (page h2d sync + state-slot
        write) — runs post-collect, where it chains cleanly onto the
        pending plane/state futures."""
        req = p.req
        self.kv.sync_request_to_device(req.rid)
        if self.kv.state_layers:
            self.kv.write_state_slot(slot, req.rid)
        req.tokens.append(p.tok)
        req.t_first = time.perf_counter()
        self.active[slot] = req
        self.positions[slot] = p.s
        self.last_tokens[slot, 0] = p.tok
        self._slot_steps[slot] = 0

    def _admit_async(self) -> None:
        """Continuous admission (post-collect): bind ready pumped
        prefills and resume preempted requests into free slots; start
        prefill pumps for queued requests that can reserve pages now.
        EDF-over-FIFO priority; a blocked higher-priority request stops
        lower-priority candidates from taking NEW reservations (no
        headroom stealing), but zero-cost binds of already-reserved work
        still proceed — that is the continuous-batching part."""
        if not self.queue:
            return
        self._admit_clock += 1
        free = [s for s in range(self.max_batch)
                if self.active[s] is None]
        blocked = False
        for i, req in enumerate(self._admission_order()):
            rid = req.rid
            if rid in self._preempted:
                if not free:
                    # still claims headroom while it waits for a slot
                    blocked = blocked or rid not in self._reserved
                    continue
                if blocked and rid not in self._reserved:
                    continue
                need = self._try_reserve(req, allow_relief=(i == 0))
                if need is None:
                    blocked = True
                    continue
                self.queue.remove(req)
                self._resume_request(free.pop(0), req, need)
                continue
            p = self._pump.get(rid)
            if p is None:
                if blocked or len(self._pump) >= self.max_batch:
                    blocked = True
                    continue
                need = self._try_reserve(req, allow_relief=(i == 0))
                if need is None:
                    blocked = True
                    continue
                self._start_pump(req, need)
                if free and not any(r is not None for r in self.active):
                    # idle engine: no decode to overlap the chunked
                    # ingest with, so admit like a sync prefill — drain
                    # the pump and bind in this very step
                    p = self._pump.pop(rid)
                    while not p.ready:
                        self._pump_chunk(p)
                    self.queue.remove(req)
                    self._bind_prefilled(free.pop(0), p)
                continue
            if p.ready and free:
                self.queue.remove(req)
                del self._pump[rid]
                self._bind_prefilled(free.pop(0), p)
            # pump still ingesting: it binds on a later step

    # apack: hot-path-root
    def _dispatch(self) -> None:
        """Fire the fused decode for the current binding WITHOUT blocking
        on the result: jit dispatch is async, so the logits / plane
        append / state re-bind land on device while the next iteration's
        host phase runs.  The dispatch-time slot map is recorded in
        ``_InFlight`` for collect."""
        slot_rids = [r.rid if r is not None else None for r in self.active]
        meta = self.kv.step_meta(slot_rids, self.max_len)
        with span("engine.decode_dispatch"):
            logits, new_cache = self._decode_paged(
                self.params, self.kv.dev.planes, self.kv.dev_states, meta,
                jnp.asarray(self.last_tokens), jnp.asarray(self.positions))
        targets = self.kv.claim_append_targets(slot_rids)
        self.kv.dev.planes = self._append(self.kv.dev.planes,
                                          new_cache, targets)
        self.kv.dev_states = M.states_from_step(self.cfg, new_cache)
        self._inflight = _InFlight(slot_reqs=list(self.active),
                                   slot_rids=slot_rids, logits=logits)

    # apack: hot-path-root
    def _collect(self) -> None:
        """Land the in-flight device step: block on its logits, account
        the appends, and apply per-slot token updates against the
        dispatch-time slot map — bindings cannot have changed mid-flight
        because every binding mutation runs post-collect (external
        ``preempt`` drains first)."""
        inf = self._inflight
        if inf is None:
            return
        self._inflight = None
        with span("engine.token_pull"):
            # apack: allow-transfer(collect IS the sync point: the async
            # loop's one sanctioned token-id pull, after the step finished
            # computing)
            toks = np.asarray(jnp.argmax(inf.logits[:, 0], axis=-1),
                              np.int32)
        self.kv.note_appended(inf.slot_rids)
        self.last_logits = inf.logits
        for slot, req in enumerate(inf.slot_reqs):
            if req is None:
                continue
            tok = int(toks[slot])
            req.tokens.append(tok)
            self.last_tokens[slot, 0] = tok
            self.positions[slot] += 1
            self._slot_steps[slot] += 1
            self.stats["generated"] += 1
        self.stats["steps"] += 1

    def _drain(self) -> None:
        """Synchronize the pipeline: land the in-flight step (if any) so
        external mutations — ``preempt``, ``sync_host_mirror``, state
        snapshots — observe a consistent post-step engine.  No-op on the
        sync scheduler."""
        if self._inflight is not None:
            self._collect()

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        stalled = 0
        for _ in range(max_steps):
            # an idle step that still advanced a pumped prefill is
            # progress (the async scheduler ingests chunks before the
            # first slot binds)
            if self.step() > 0 or self._pump:
                stalled = 0
                continue
            if not self.queue:
                break
            # idle step with work still queued: admission is blocked and
            # nothing is in flight to unblock it.  Bounded patience (the
            # pressure backoff can legitimately hold a few retries), then
            # a structured error instead of silently burning max_steps.
            stalled += 1
            if stalled > 2 * self.pressure_backoff_max:
                head = self.queue[0]
                need = self._pages_for(head) if self.paged else 0
                pool = self._shard_pages() if self.paged else 0
                raise AdmissionImpossible(
                    head, need, pool,
                    f"{stalled} consecutive no-progress steps with zero "
                    "active slots")

    def weight_stats(self) -> dict:
        """Weight-store accounting for the packed serving path.

        With ``weights="apack-int8"`` every decode step streams the
        compressed planes (APack payload + the per-channel dequant scale)
        where the dense engine streams the full weight matrices —
        ``weight_ratio`` is that per-step read ratio against the int8
        dense baseline (the quantization is shared by both stores;
        ``native_ratio`` additionally credits the fp32->int8 narrowing).
        Cumulative totals scale with ``stats["steps"]``: weights are read
        once per step regardless of batch size."""
        if self._weight_stats is None:
            return {"weights": "dense"}
        s = dict(self._weight_stats)
        comp = s["payload_bytes"] + s["scale_bytes"]
        s["weights"] = "apack-int8"
        s["compressed_read_bytes_per_step"] = comp
        s["dense_read_bytes_per_step"] = s["int8_bytes"]
        s["weight_ratio"] = comp / max(s["int8_bytes"], 1)
        s["native_ratio"] = comp / max(s["native_bytes"], 1)
        steps = self.stats["steps"]
        s["compressed_read_bytes_total"] = comp * steps
        s["dense_read_bytes_total"] = s["int8_bytes"] * steps
        return s

    def kv_stats(self) -> dict:
        """Raw-vs-compressed KV traffic + pool occupancy (paged mode).

        ``kv_ratio`` is ``None`` until a read has actually moved bytes —
        an engine that has served nothing must not report break-even.
        ``kv_streams`` splits the accounting into the three stream kinds
        (global KV, rolling/local KV, recurrent-state snapshots)."""
        if not self.paged:
            return {}
        out = dict(self.kv.traffic)
        out["kv_ratio"] = self.kv.kv_ratio()
        out["kv_streams"] = self.kv.stream_stats()
        out["kv_repack"] = out["kv_streams"]["repack"]
        out["kv_pool_pages"] = self.kv.pool.num_pages
        out["kv_pages_allocated"] = self.kv.pool.alloc_count
        out["kv_pages_high_water"] = self.kv.pool.high_water
        out["kv_pages_evicted"] = self.kv.pool.evict_count
        out["kv_fused"] = self.fused
        out["transfers"] = dict(self.kv.transfers)
        if self._n_data > 1:
            # per-shard accounting (mesh mode): free-list depth and live
            # reservations per data shard — the invariants tests gate on
            out["kv_shard_free"] = [self.kv.pool.free_count_shard(s)
                                    for s in range(self._n_data)]
            out["kv_shard_reserved"] = list(self._shard_reserved)
        # spill tier: own stream (never folded into read ratios) + the
        # per-request accounting of what is parked on host right now
        out["kv_spill"] = out["kv_streams"]["spill"]
        out["kv_pages_spilled"] = self.kv.pool.spill_count
        out["kv_pages_unspilled"] = self.kv.pool.unspill_count
        out["kv_spilled_requests"] = {
            rid: self.kv.spilled_pages(rid)
            for rid in sorted(self._spilled) if rid in self.kv.page_tables}
        return out

    def sync_host_mirror(self) -> None:
        """Fused mode: pull device-resident HOT pages and recurrent states
        into the host mirror so ``kv.materialize`` / snapshots see the
        live data (tests + oracle path; never called by ``step``)."""
        if not self.fused:
            return
        self._drain()
        slot_rids = [r.rid if r is not None else None for r in self.active]
        self.kv.sync_hot_to_host(slot_rids)
        self.kv._pull_states(slot_rids)
