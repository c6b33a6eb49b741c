"""Bring-up smoke run: serve qwen3-1.7b at its published widths on a TPU.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the mesh paths, on four chips

One chip.  ``configs.get_config("qwen3-1.7b")`` (28 layers, d_model 2048,
16 query / 8 kv heads, head_dim 128, d_ff 6144, vocab 151936, tied
embeddings) with random weights from ``--seed`` serves 4 requests (128-token
prompts, 32 new tokens, ``max_batch`` 4, 16-token KV pages) through
``ServeEngine`` with APack-packed weights and the fused apack-int8 paged KV
cache: the construction ``python -m repro.launch.serve --weights apack-int8
--kv apack-int8`` uses.  It then checks, and fails on any miss:

* the device is a TPU;
* every request finished without error, ``failed == 0``, nothing was
  quarantined, PACKED pages were written and read (``kv_ratio`` measured);
* the compiled kernels decode a real-width packed weight (layer 0
  ``w_up``, 2048 x 6144) and every PACKED KV page of layer 0 bit for bit
  like the reference decoder run on the host CPU, and
  ``compressed_matmul`` of the identity reproduces that weight bit for bit;
* the served path's logits (after prefill, and after the first
  ``N_COMPARE`` cached decode steps) agree within ``LOGIT_TOL`` with the
  plain path: the same requests, teacher-forced with the served tokens,
  with dense weights dequantized from the same int8 quantization and the
  KV cache read through the jnp reference backend (``kv_backend="ref"``),
  over ``N_SETS`` prompt sets;
* a planted fault (layer 0 ``w_up`` read with its K-tiles in reverse
  order) moves the plain path's logits by more than ``LOGIT_TOL``, so the
  bound can see a fault of that size;
* the decode step program holds compiled Pallas calls
  (``tpu_custom_call``) named ``compressed_matmul`` and
  ``fused_page_attention``.

Four chips (``--chips 4``) run only the mesh paths and what they are
compared with, at full width and ``MESH_LAYERS`` layers: the one-chip
engine; the same requests on one chip through a 1x1 mesh at a data
shard's batch (``N_REQUESTS // 4``); on a 4x1 ``data`` mesh (greedy
tokens identical to the 1x1 mesh engine, teacher-forced logits within
``LOGIT_TOL`` of the one-chip engine); and on a 2x2 ``data`` x ``model``
mesh with the packed weights K-split over ``model`` (teacher-forced
logits within ``LOGIT_TOL`` of the one-chip engine).

Every check runs and prints its reading before the script fails on the
first miss.  Printed times are one cold run's smoke timings, not metrics.  With no TPU,
or when a check fails, the script exits non-zero and prints no result.  The
last line of stdout is ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-1.7b"
N_REQUESTS = 4
PROMPT_LEN = 128
MAX_NEW = 32
PAGE_SIZE = 16
N_COMPARE = 4          # cached decode steps whose logits are compared
N_SETS = 3             # prompt sets compared: the served one + 2 forced
# Largest |logit difference| admitted between two paths that hold the same
# int8 weight codes and KV values.  The residual stream is bf16, so any
# difference in f32 summation order, or the dense path's bf16 rounding of
# each dequantized weight (the packed kernel multiplies the exact codes),
# flips bf16 roundings that grow over the layers.  On a v5e at seed 0 the
# largest served-vs-plain gap over the N_SETS prompt sets was 0.158 and
# the planted fault checked below moved the logits by 5.9 (PERF.md).
LOGIT_TOL = 0.25
# Depth of the --chips 4 run (PERF.md lists the cut and the 28-layer
# per-chip memory from a compile for a described v5e 2x2).
MESH_LAYERS = 2


FAILED: list[str] = []


def check(ok: bool, what: str) -> bool:
    """Record a miss and go on, so every reading is printed; ``main``
    fails the run when any check missed."""
    if not ok:
        FAILED.append(what)
        log(f"CHECK FAILED: {what}")
    return ok


def log(msg: str) -> None:
    print(msg, flush=True)


def requests(cfg, seed: int, *, rid0: int = 0, max_new: int = MAX_NEW):
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=rid0 + i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        PROMPT_LEN).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(N_REQUESTS)]


def forced_requests(cfg, seed: int, k: int, rid0: int):
    """Prompt set ``k`` for a teacher-forced comparison: requests that
    finish at the last compared step, so the next set finds free slots."""
    return requests(cfg, seed + k, rid0=rid0 + 100 * k,
                    max_new=N_COMPARE + 1)


def random_fed(cfg, seed: int, k: int) -> np.ndarray:
    """[N_COMPARE, B] tokens fed to the extra prompt set ``k``."""
    rng = np.random.default_rng((seed, k))
    return rng.integers(0, cfg.vocab_size,
                        (N_COMPARE, N_REQUESTS)).astype(np.int32)


def engine(cfg, params, max_batch: int = N_REQUESTS, **kw):
    from repro.serve import ServeEngine
    return ServeEngine(cfg, params, max_batch=max_batch,
                       max_len=PROMPT_LEN + MAX_NEW + 8,
                       kv_page_size=PAGE_SIZE, **kw)


def prefill_logits(eng, reqs) -> np.ndarray:
    """Last-position prefill logits [B, V] through the engine's own jitted
    prefill (the program admission runs)."""
    return np.stack([np.asarray(eng._prefill_forward(r.prompt)[0][0, -1])
                     for r in reqs])


def serve(eng, reqs, on_first_step=None) -> tuple[np.ndarray, float, float]:
    """Serve ``reqs`` to completion; returns the logits [N_COMPARE, B, V]
    of the first decode steps, the first step's seconds (admission,
    prefill and compiles) and the remaining steps' seconds."""
    for r in reqs:
        eng.submit(r)
    logits = []
    t0 = time.perf_counter()
    eng.step()
    logits.append(np.asarray(eng.last_logits[:, 0]))
    t1 = time.perf_counter()
    if on_first_step is not None:
        on_first_step(eng)
    t2 = time.perf_counter()
    while eng.step():
        if len(logits) < N_COMPARE:
            logits.append(np.asarray(eng.last_logits[:, 0]))
    t3 = time.perf_counter()
    failed = [(r.rid, r.error) for r in reqs
              if not r.done or r.error is not None]
    check(not failed, f"requests failed: {failed}")
    check(eng.stats["failed"] == 0, f"engine failed {eng.stats['failed']}")
    return np.stack(logits), t1 - t0, t3 - t2


def forced_logits(eng, reqs, fed: np.ndarray) -> np.ndarray:
    """Admit ``reqs`` (prefill into slots 0..B-1), then decode
    ``len(fed)`` steps feeding ``fed[i]`` ([B] tokens) instead of the
    engine's own picks: the teacher-forced comparison path."""
    for r in reqs:
        eng.submit(r)
    eng._retire()                      # the previous set's finished requests
    eng._admit()
    slots = [None if r is None else r.rid for r in eng.active]
    check(slots == [r.rid for r in reqs],
          f"forced requests {[r.rid for r in reqs]} landed as {slots}")
    out = []
    for toks in fed:
        eng.last_tokens[:, 0] = toks
        eng.step()
        out.append(np.asarray(eng.last_logits[:, 0]))
    return np.stack(out)


def fed_tokens(reqs) -> np.ndarray:
    """[N_COMPARE, B]: the token each request consumed at decode step i
    (``tokens[0]`` is the prefill's pick)."""
    return np.array([[r.tokens[i] for r in reqs] for i in range(N_COMPARE)],
                    np.int32)


def logit_gap(name: str, got: np.ndarray, want: np.ndarray) -> float:
    """Print and return max |got - want|.  Argmax agreement is printed
    only: where |diff| <= LOGIT_TOL everywhere, the two picks are within
    2 * LOGIT_TOL of each other in both paths (near-ties of random
    weights)."""
    diff = float(np.max(np.abs(got - want)))
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
    log(f"logits {name}: max |diff| {diff:.6g} (tolerance {LOGIT_TOL}), "
        f"max |logit| {float(np.max(np.abs(want))):.4g}, "
        f"argmax agreement {agree:.3f}")
    check(bool(np.all(np.isfinite(got))), f"{name}: non-finite logits")
    return diff


def compare_logits(name: str, got: np.ndarray, want: np.ndarray) -> float:
    diff = logit_gap(name, got, want)
    check(diff <= LOGIT_TOL, f"{name}: logits differ by {diff} > {LOGIT_TOL}")
    return diff


def packed_weights(params):
    """(path, PackedWeight) for every packed site of a param tree."""
    import jax
    from repro.models.modules import PackedWeight
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, PackedWeight))
    return [(jax.tree_util.keystr(p), x) for p, x in flat
            if isinstance(x, PackedWeight)]


def host_device():
    import jax
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def check_weight_codec(cfg, params) -> None:
    """Layer 0 ``w_up``: the compiled decoder vs the reference decoder on
    the host, bit for bit; ``compressed_matmul`` of the identity vs the
    host-dequantized weight, bit for bit (the kernel multiplies exact int8
    codes and scales the sum, so each output is one exact product)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import decompress_matmul as dm
    from repro.kernels import ref
    from repro.kernels.apack_decode import decode_pallas
    path, pw = next((p, w) for p, w in packed_weights(params)
                    if p.endswith("['w_up']"))
    cw = jax.tree.map(lambda a: a[0], pw.cw)             # layer 0
    check((cw.k, cw.n) == (cfg.d_model, cfg.d_ff), f"w_up is {cw.k}x{cw.n}")
    got = np.asarray(decode_pallas(cw.sym_plane, cw.ofs_plane, cw.stored,
                                   cw.v_min, cw.ol, cw.cum,
                                   n_steps=cw.tile_k))
    host = host_device()
    host_cw = jax.device_put(cw, host)
    with jax.default_device(host):
        want = np.asarray(ref.decode(
            host_cw.sym_plane, host_cw.ofs_plane,
            host_cw.stored.astype(bool),
            ref.TableArrays(host_cw.v_min, host_cw.ol, host_cw.cum),
            cw.tile_k, 8))
    n_bad = int(np.sum(got != want))
    log(f"weight codec {path}[0] ({cw.k}x{cw.n}, {want.size} values): "
        f"{n_bad} mismatches vs the host reference decoder")
    check(n_bad == 0, "packed weight decodes differently on the chip")
    nk, nn = cw.k_pad // cw.tile_k, cw.n_pad // dm.TILE_N
    w = (want.reshape(nk, nn, dm.TILE_N, cw.tile_k).transpose(0, 3, 1, 2)
         .reshape(cw.k_pad, cw.n_pad)).astype(np.int64)
    w = (np.where(w >= 128, w - 256, w).astype(np.float32)
         * np.asarray(cw.scale)[None, :])[:cw.k, :cw.n]
    y = np.asarray(dm.compressed_matmul(jnp.eye(cw.k, dtype=jnp.float32),
                                        cw, block_m=256))
    n_bad = int(np.sum(y != w))
    log(f"compressed_matmul(I) vs host-decoded weight: {n_bad} of {w.size} "
        f"values differ, max |diff| {float(np.max(np.abs(y - w))):.3g} "
        f"(max |w| {float(np.max(np.abs(w))):.3g})")
    check(n_bad == 0, "compressed_matmul(I) does not reproduce the weight")


def check_kv_codec(eng, layer: int = 0) -> int:
    """Every PACKED page of ``layer``: the compiled gather-decode kernel on
    the device planes vs the reference decoder on the host pool's planes
    and tables, bit for bit.  Returns the number of pages checked."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.paged_decode import gather_decode_pallas, table_row
    from repro.models.modules import PAGE_PACKED
    kv, pool = eng.kv, eng.kv.pool
    pids = sorted(p for tables in kv.page_tables.values()
                  for p in tables[layer] if pool.state[p] == PAGE_PACKED)
    if not check(bool(pids), f"no PACKED page in layer {layer}"):
        return 0
    planes = kv.dev.planes
    e = pool.elems_per_stream
    host = host_device()
    for kind, name in enumerate("kv"):
        rows = [table_row(kv.gen_rows[int(kv.page_gen[p])], layer, kind,
                          kv.n_layers) for p in pids]
        got = np.asarray(gather_decode_pallas(
            planes[f"sym_{name}"], planes[f"ofs_{name}"],
            planes[f"stored_{name}"], jnp.asarray(pids, jnp.int32),
            planes["vm"], planes["ol"], planes["cum"], n_steps=e,
            table_idx=jnp.asarray(rows, jnp.int32)))
        table = ref.TableArrays.from_table(kv.tables[layer][kind])
        with jax.default_device(host):
            table = jax.device_put(table, host)
            want = np.stack([np.asarray(ref.decode(
                jnp.asarray(pool.sym[kind, p]), jnp.asarray(pool.ofs[kind, p]),
                jnp.asarray(pool.stored[kind, p]), table, e, 8))
                for p in pids])
        n_bad = int(np.sum(got != want))
        log(f"kv codec layer {layer} {name.upper()}: {len(pids)} PACKED "
            f"pages, {want.size} values, {n_bad} mismatches vs the host "
            f"reference decoder")
        check(n_bad == 0, f"PACKED {name.upper()} pages decode differently")
    return len(pids)


def step_program_text(eng, args) -> str:
    import jax
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.result_type(a)), args)
    return eng._decode_paged.lower(*shapes).as_text()


def record_step_args(eng) -> dict:
    """Keep the shapes of the first decode step's arguments."""
    seen: dict = {}
    inner = eng._decode_paged

    def recording(*args):
        seen.setdefault("args", args)
        return inner(*args)

    recording.lower = inner.lower
    eng._decode_paged = recording
    return seen


def plain_params(host_params, packed_paths):
    """The plain path's weights, from the host copy: every packed site
    dense again, dequantized from the same per-channel int8 quantization
    the packer applies (per layer for stacked sites)."""
    import jax
    import jax.numpy as jnp
    from repro.core import quant

    def deq(a):
        q, qp = quant.quantize_symmetric(jnp.asarray(a, jnp.float32), axis=-1)
        return np.asarray(q, np.float32) * np.asarray(qp.scale, np.float32)

    def one(path, arr):
        shape = packed_paths.get(jax.tree_util.keystr(path))
        if shape is None:
            return jnp.asarray(arr)
        if arr.ndim > len(shape):                       # layer stack
            return jnp.asarray(np.stack([deq(a) for a in arr]).astype(
                arr.dtype))
        return jnp.asarray(deq(arr).astype(arr.dtype))

    return jax.tree_util.tree_map_with_path(one, host_params)


def planted_fault(params):
    """The plain path's weights with a decoder-layout fault: layer 0
    ``w_up`` read with its K-tiles (``DEFAULT_TILE_K`` rows each) in
    reverse order."""
    import jax
    from repro.kernels import decompress_matmul as dm

    def one(path, a):
        if not jax.tree_util.keystr(path).endswith("['ffn']['w_up']"):
            return a
        nk = a.shape[-2] // dm.DEFAULT_TILE_K
        tiles = a[0].reshape(nk, dm.DEFAULT_TILE_K, a.shape[-1])
        return a.at[0].set(tiles[::-1].reshape(a.shape[1:]))

    return jax.tree_util.tree_map_with_path(one, params)


def peak_bytes():
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def one_chip(cfg, seed: int) -> None:
    import jax
    from repro.models import model as M

    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    host_params = jax.device_get(params)
    t0 = time.perf_counter()
    eng = engine(cfg, params, weights="apack-int8")
    log(f"weight packing: {time.perf_counter() - t0:.1f} s")
    del params
    ws = eng.weight_stats()
    log(f"weights: {ws['packed_tensors']} packed tensors, int8 "
        f"{ws['int8_bytes']} B, payload {ws['payload_bytes']} B, slots "
        f"{ws['slotted_bytes']} B, scales {ws['scale_bytes']} B "
        f"(payload ratio {ws['weight_ratio']:.4f} vs int8)")
    check_weight_codec(cfg, eng.params)

    reqs = requests(cfg, seed)
    t0 = time.perf_counter()
    served_prefill = prefill_logits(eng, reqs)
    log(f"prefill compile + 4 prefills: {time.perf_counter() - t0:.1f} s")
    seen = record_step_args(eng)
    kv_pages = {}
    served, first_s, rest_s = serve(
        eng, reqs, on_first_step=lambda e: kv_pages.update(
            n=check_kv_codec(e)))
    log(f"first step (admission, prefill, decode compile): {first_s:.1f} s;"
        f" remaining {MAX_NEW - 1} decode steps: {rest_s:.2f} s wall "
        f"(smoke timing, not a metric)")

    t0 = time.perf_counter()
    text = step_program_text(eng, seen["args"])
    log(f"decode step lowering: {time.perf_counter() - t0:.1f} s; "
        f"{text.count('tpu_custom_call')} tpu_custom_call sites")
    for kernel in ("compressed_matmul", "fused_page_attention"):
        check("tpu_custom_call" in text and kernel in text,
              f"no compiled {kernel} in the decode step program")

    ks = eng.kv_stats()
    log(f"kv: {ks['kv_pages_packed']} pages packed, read "
        f"{ks['kv_read_bytes']} B vs raw {ks['kv_raw_bytes']} B (payload "
        f"ratio {ks['kv_ratio']}), pool {ks['kv_pages_high_water']}/"
        f"{ks['kv_pool_pages']} pages of {eng.kv.pool.sym_words}+"
        f"{eng.kv.pool.ofs_words} words x {eng.kv.pool.n_streams} streams "
        f"per kind")
    check(ks["kv_pages_packed"] > 0, "no KV page was packed")
    check(ks["kv_ratio"] is not None, "no KV read was measured")
    check(ks["kv_spill"]["quarantined"] == 0, "quarantined KV pages")

    # the extra prompt sets, teacher-forced through the served engine
    fed = [fed_tokens(reqs)] + [random_fed(cfg, seed, k)
                                for k in range(1, N_SETS)]
    served = [(served_prefill, served)]
    t0 = time.perf_counter()
    for k in range(1, N_SETS):
        extra = forced_requests(cfg, seed, k, rid0=1000)
        served.append((prefill_logits(eng, extra),
                       forced_logits(eng, extra, fed[k])))
    log(f"{N_SETS - 1} more prompt sets, prefill + {N_COMPARE} forced "
        f"steps each: {time.perf_counter() - t0:.1f} s")
    check(eng.stats["failed"] == 0, f"engine failed {eng.stats['failed']}")
    log(f"peak device memory, packing and serving: {peak_bytes()} B")
    packed_paths = {p: w.shape for p, w in packed_weights(eng.params)}
    del eng, seen                      # free the packed planes and pool

    plain = engine(cfg, plain_params(host_params, packed_paths),
                   kv_backend="ref")
    del host_params
    gaps = []
    for k in range(N_SETS):
        reqs = forced_requests(cfg, seed, k, rid0=2000)
        gaps.append(compare_logits(f"set {k} after prefill",
                                   prefill_logits(plain, reqs),
                                   served[k][0]))
        gaps.append(compare_logits(f"set {k} over {N_COMPARE} decode steps",
                                   forced_logits(plain, reqs, fed[k]),
                                   served[k][1]))
    log(f"largest served-vs-plain gap over {N_SETS} prompt sets: "
        f"{max(gaps):.6g} (tolerance {LOGIT_TOL})")

    plain.params = planted_fault(plain.params)
    reqs = forced_requests(cfg, seed, 0, rid0=3000)
    fault = max(
        logit_gap("planted fault after prefill",
                  prefill_logits(plain, reqs), served[0][0]),
        logit_gap(f"planted fault over {N_COMPARE} decode steps",
                  forced_logits(plain, reqs, fed[0]), served[0][1]))
    check(fault > LOGIT_TOL,
          f"a planted fault moves the logits by only {fault} <= {LOGIT_TOL}")
    check(plain.stats["failed"] == 0,
          f"plain engine failed {plain.stats['failed']}")
    log(f"peak device memory, whole run: {peak_bytes()} B")


def one_chip_mesh():
    """A 1x1 mesh on the first chip: the sharded step program at one data
    shard's batch."""
    import jax
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def four_chips(cfg, seed: int) -> None:
    import jax
    from jax.sharding import PartitionSpec as P
    from repro import configs
    from repro.launch.mesh import make_debug_mesh
    from repro.models import model as M

    if not check(len(jax.devices()) >= 4,
                 f"{len(jax.devices())} devices < 4"):
        return
    cfg = dataclasses.replace(cfg, num_layers=MESH_LAYERS)
    log(f"mesh paths at full width, {cfg.num_layers} of "
        f"{configs.get_config(ARCH).num_layers} layers")
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    one = engine(cfg, params, weights="apack-int8")
    del params
    reqs = requests(cfg, seed)
    logits, first_s, rest_s = serve(one, reqs)
    tokens = [list(r.tokens) for r in reqs]
    fed = fed_tokens(reqs)
    packed = one.params
    del one
    log(f"one chip: {first_s:.1f} s first step, {rest_s:.2f} s decode "
        f"(smoke timing)")

    per_shard = N_REQUESTS // 4
    eng = engine(cfg, packed, max_batch=per_shard, mesh=one_chip_mesh())
    reqs = requests(cfg, seed)
    _, first_s, rest_s = serve(eng, reqs)
    shard_tokens = [list(r.tokens) for r in reqs]
    log(f"1x1 mesh, batch {per_shard}: tokens identical to one chip at "
        f"batch {N_REQUESTS}: {shard_tokens == tokens}; {first_s:.1f} s "
        f"first step, {rest_s:.2f} s decode (smoke timing)")
    del eng

    mesh = make_debug_mesh(4, 1)
    eng = engine(cfg, packed, mesh=mesh)
    reqs = requests(cfg, seed)
    _, first_s, rest_s = serve(eng, reqs)
    mesh_tokens = [list(r.tokens) for r in reqs]
    log(f"4x1 mesh {dict(mesh.shape)}: tokens identical to one chip at "
        f"batch {N_REQUESTS}: {mesh_tokens == tokens}, to the 1x1 mesh at "
        f"batch {per_shard}: {mesh_tokens == shard_tokens}; {first_s:.1f} s "
        f"first step, {rest_s:.2f} s decode (smoke timing)")
    check(mesh_tokens == shard_tokens,
          "4x1 mesh tokens differ from one chip at the shard's batch")
    compare_logits("4x1 mesh vs one chip",
                   forced_logits(eng, forced_requests(cfg, seed, 0, 1000),
                                 fed), logits)
    del eng

    mesh = make_debug_mesh(2, 2)
    eng = engine(cfg, packed, mesh=mesh)
    check(eng._n_model == 2, "2x2 mesh has no model axis")
    specs = jax.tree.leaves(M.packed_param_specs(eng.params, eng._n_model),
                            is_leaf=lambda s: isinstance(s, P))
    split = sum(s != P() for s in specs)
    log(f"2x2 mesh {dict(mesh.shape)}: {split} packed weight leaves "
        f"K-split over 'model' in the step program")
    check(split > 0, "no packed weight is split over 'model'")
    compare_logits("2x2 mesh vs one chip",
                   forced_logits(eng, forced_requests(cfg, seed, 0, 1000),
                                 fed), logits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    FAILED.clear()

    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {device.platform})",
              file=sys.stderr)
        return 1
    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {device.device_kind} x{len(jax.devices())}; compile "
        f"cache: {enable_compile_cache()}")
    cfg = dataclasses.replace(configs.get_config(ARCH),
                              kv_cache_dtype="apack-int8")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(cfg, args.seed)
    log(f"total: {time.perf_counter() - t0:.1f} s")
    if FAILED:
        print(f"chip_smoke: FAILED: {'; '.join(FAILED)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
