"""Paged APack-compressed KV cache tests: activation-mode tables, the page
pool, the Pallas gather-decode kernel, decode parity with the raw int8-KV
path, and ServeEngine scheduling edge cases."""
import dataclasses
import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.core import format as fmt, quant, tables
from repro.kernels import fastpath, ref as _ref
from repro.kernels.paged_decode import (gather_bucket, gather_decode,
                                        gather_decode_pallas)
from repro.models import model as M
from repro.models import modules as m
from repro.runtime import spans
from repro.serve import Request, ServeEngine

KEY = jax.random.PRNGKey(0)


def apack_cfg(**kw):
    return dataclasses.replace(configs.get_smoke_config("qwen3-1.7b"),
                               kv_cache_dtype="apack-int8", **kw)


# ------------------------------------------------------ activation tables
class TestActivationTables:
    @settings(max_examples=25)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(1, 40))
    def test_every_range_has_nonzero_probability(self, seed, spread):
        """Activation-mode tables must keep every value space encodable:
        no range — however empty during profiling — may get a zero count
        (a zero-count group would brick any unprofiled value landing in
        it, paper §VI "Final Adjustment for Activations")."""
        rng = np.random.default_rng(seed)
        # heavily clustered sample: most of the 256-value space unseen
        vals = (rng.normal(128, spread, 4096).astype(np.int64)) & 0xFF
        t = tables.table_for(vals, is_activation=True)
        counts = np.diff(np.asarray(t.cum))
        assert t.mode == "activation"
        assert counts.shape == (16,)
        assert (counts > 0).all(), counts
        assert counts.sum() == 1024

    @settings(max_examples=10)
    @given(st.integers(0, 2 ** 31 - 1))
    def test_roundtrip_outside_calibration_sample(self, seed):
        """Values never seen while profiling must round-trip bit-exactly
        through the codec (lossless even for unprofiled symbols)."""
        rng = np.random.default_rng(seed)
        calib = (rng.normal(100, 10, 2048).astype(np.int64)) & 0xFF
        t = tables.table_for(calib, is_activation=True)
        # full value space, including everything the table never saw
        unseen = np.setdiff1d(np.arange(256), np.unique(calib))
        assert unseen.size > 0, "calibration sample unexpectedly covered 0..255"
        payload = np.concatenate([np.arange(256), unseen, unseen])
        ct = fastpath.compress_np(payload.astype(np.uint8), t)
        out = fastpath.decompress_np(ct)
        assert np.array_equal(out.astype(np.int64), payload)

    def test_weight_mode_can_brick_unseen_values(self):
        """Contrast case documenting why activations need the slack: a
        weight-mode table may assign empty ranges zero counts."""
        calib = np.full(1024, 7, np.int64)
        t = tables.table_for(calib, is_activation=False)
        counts = np.diff(np.asarray(t.cum))
        assert (counts == 0).any()


# ----------------------------------------------------------- page pool
class TestKVPagePool:
    def make(self, num_pages=6, page_size=4, h=2, dh=8):
        return m.KVPagePool(num_pages, page_size, h, dh, elems_per_stream=16)

    def test_alloc_free_reuse(self):
        pool = self.make()
        pids = [pool.alloc() for _ in range(6)]
        assert sorted(pids) == list(range(6))
        assert pool.alloc() is None                    # exhausted
        for pid in pids[:3]:
            pool.free(pid)
        again = [pool.alloc() for _ in range(3)]
        assert sorted(again) == sorted(pids[:3])       # ids recycled
        assert pool.alloc_count == 9
        assert pool.high_water == 6

    def test_lifecycle_and_accounting(self):
        pool = self.make()
        pid = pool.alloc()
        k = np.ones((2, 8), np.int8)
        s = np.ones(2, np.float32)
        for _ in range(4):
            pool.write_token(pid, k, k, s, s)
        assert pool.state[pid] == m.PAGE_HOT
        hot_bytes = pool.page_bytes(pid)
        assert hot_bytes == pool.dense_bytes(4)
        q2 = np.ones((2, 4, 2, 8), np.int8)
        pool.seal(pid, q2, np.ones((2, 2), np.float32))
        assert pool.state[pid] == m.PAGE_COLD
        # scale amortization alone shrinks the page
        assert pool.page_bytes(pid) < hot_bytes
        assert (pool.tok_q[:, pid] == 0).all()         # hot copy dropped

    def test_overfull_page_rejected(self):
        pool = self.make()
        pid = pool.alloc()
        k = np.zeros((2, 8), np.int8)
        s = np.zeros(2, np.float32)
        for _ in range(4):
            pool.write_token(pid, k, k, s, s)
        # real exceptions, not bare asserts: -O must not strip the guard
        with pytest.raises(RuntimeError, match="overfull"):
            pool.write_token(pid, k, k, s, s)

    def test_double_free_and_bad_seal_rejected(self):
        pool = self.make()
        pid = pool.alloc()
        k = np.zeros((2, 8), np.int8)
        s = np.zeros(2, np.float32)
        pool.write_token(pid, k, k, s, s)
        with pytest.raises(ValueError, match="non-full or non-HOT"):
            pool.seal(pid, np.zeros((2, 4, 2, 8), np.int8),
                      np.zeros((2, 2), np.float32))
        pool.free(pid)
        with pytest.raises(ValueError, match="double free"):
            pool.free(pid)


# ------------------------------------------------- gather-decode kernel
def _pack_pages(pages: np.ndarray, table: tables.ApackTable):
    """Encode [P, S, E] pages into pooled fixed-capacity planes."""
    p, s, e = pages.shape
    ta = _ref.TableArrays.from_table(table)
    outs = [tuple(np.asarray(x) for x in
                  _ref.encode(jnp.asarray(pages[i]), ta, e, 8))
            for i in range(p)]
    return tuple(np.stack([o[i] for o in outs]) for i in range(5))


class TestGatherDecode:
    def setup_method(self):
        rng = np.random.default_rng(7)
        self.E, self.S, self.P = 32, 4, 5
        self.pages = (rng.normal(40, 25, (self.P, self.S, self.E))
                      .astype(np.int64) & 0xFF)
        self.table = tables.table_for(self.pages[:2].reshape(-1),
                                      is_activation=True)
        self.planes = _pack_pages(self.pages, self.table)

    def test_matches_decompress_np_per_page(self):
        """Interpret-mode kernel output == fastpath.decompress_np of the
        same per-page container."""
        sym, ofs, sb, ob, stored = self.planes
        ta = _ref.TableArrays.from_table(self.table)
        idx = np.asarray([3, 0, 2], np.int32)
        out = np.asarray(gather_decode_pallas(
            jnp.asarray(sym), jnp.asarray(ofs), jnp.asarray(stored),
            jnp.asarray(idx), ta.v_min, ta.ol, ta.cum,
            n_steps=self.E, interpret=True))
        for g, pid in enumerate(idx):
            ws = int(np.max(np.where(stored[pid], 0,
                                     (sb[pid] + 31) // 32), initial=0))
            wo = int(np.max((ob[pid] + 31) // 32, initial=0))
            ct = fmt.CompressedTensor(
                shape=(self.S, self.E), bits=8, table=self.table,
                elems_per_stream=self.E, n_valid=self.S * self.E,
                sym_plane=sym[pid][:ws], ofs_plane=ofs[pid][:wo],
                sym_bits=sb[pid], ofs_bits=ob[pid], stored=stored[pid])
            want = fastpath.decompress_np(ct).astype(np.int64)
            assert np.array_equal(out[g], want)
            assert np.array_equal(out[g], self.pages[pid])

    def test_ref_and_pallas_backends_agree(self):
        sym, ofs, sb, ob, stored = self.planes
        ta = _ref.TableArrays.from_table(self.table)
        idx = jnp.asarray(np.asarray([1, 1, 4, 0], np.int32))
        outs = [np.asarray(gather_decode(
            jnp.asarray(sym), jnp.asarray(ofs), jnp.asarray(stored), idx,
            ta.v_min, ta.ol, ta.cum, n_steps=self.E, backend=b))
            for b in ("ref", "pallas_interpret")]
        assert np.array_equal(outs[0], outs[1])

    def test_gather_bucket(self):
        assert gather_bucket(1) == 1
        assert gather_bucket(3) == 4
        assert gather_bucket(129) == 256
        assert gather_bucket(5000) % 1024 == 0 and gather_bucket(5000) >= 5000


# ------------------------------------------- decode parity vs raw int8 KV
class TestCompressedKVDecodeParity:
    def test_logits_within_int8_bound(self):
        """Teacher-forced decode: the paged/compressed KV path must stay
        within the raw-int8-KV error envelope of tests/test_kv_int8.py
        (0.35 vs bf16), and close to the raw int8 path itself."""
        cfg16 = configs.get_smoke_config("qwen3-1.7b")
        cfg8 = dataclasses.replace(cfg16, kv_cache_dtype="int8")
        cfga = apack_cfg()
        params = M.init_params(cfg16, KEY)
        b, s = 2, 12
        rng = np.random.default_rng(1)
        tokens = jnp.asarray(rng.integers(0, cfg16.vocab_size, (b, s)))
        n_layers = cfga.n_cycles * len(cfga.cycle)
        kv = M.PagedKVCache(cfga, num_pages=n_layers * b * 4, page_size=4,
                            calib_pages=2)
        rids = list(range(b))
        for rid in rids:
            kv.add_request(rid)
        cache16 = M.init_cache(cfg16, b, s)
        cache8 = M.init_cache(cfg8, b, s)
        l16s, l8s, las = [], [], []
        for t in range(s):
            tok = tokens[:, t:t + 1]
            l16, cache16 = M.decode_step(cfg16, params, cache16, tok,
                                         jnp.asarray(t))
            l8, cache8 = M.decode_step(cfg8, params, cache8, tok,
                                       jnp.asarray(t))
            la, new_a = M.decode_step(cfga, params,
                                      kv.materialize(rids, s), tok,
                                      jnp.asarray(t))
            kv.append_step_tokens(new_a, rids, [t] * b)
            l16s.append(l16)
            l8s.append(l8)
            las.append(la)
        d16 = np.asarray(jnp.concatenate(l16s, 1), np.float32)
        d8 = np.asarray(jnp.concatenate(l8s, 1), np.float32)
        da = np.asarray(jnp.concatenate(las, 1), np.float32)
        # compression actually ran (pages sealed + packed, lossless reads)
        assert kv.traffic["kv_pages_packed"] > 0
        assert kv.kv_ratio() < 1.0
        # paged path vs raw int8 path: same quantization family, the only
        # extra error is the page-granular re-quantization of cold pages
        assert np.abs(da - d8).max() < 0.35, np.abs(da - d8).max()
        # and the absolute envelope vs bf16 from test_kv_int8.py holds
        assert np.abs(da - d16).max() < 0.35, np.abs(da - d16).max()

    def test_materialize_is_lossless_for_packed_pages(self):
        """Round-trip through seal+pack+gather-decode reproduces the COLD
        int8 payload bit-exactly (APack is lossless; only the page
        re-quantization is lossy, and that happens before packing)."""
        cfg = apack_cfg()
        n_layers = cfg.n_cycles * len(cfg.cycle)
        kv = M.PagedKVCache(cfg, num_pages=n_layers * 8, page_size=4,
                            calib_pages=1)
        kv.add_request(0)
        rng = np.random.default_rng(3)
        h, dh = cfg.num_kv_heads, cfg.head_dim
        toks = 8                                     # two full pages
        kq = rng.integers(-127, 128, (toks, n_layers, h, dh)).astype(np.int8)
        vq = rng.integers(-127, 128, (toks, n_layers, h, dh)).astype(np.int8)
        ks = rng.uniform(0.01, 0.02, (toks, n_layers, h)).astype(np.float32)
        vs = rng.uniform(0.01, 0.02, (toks, n_layers, h)).astype(np.float32)
        for t in range(toks):
            kv.append_token(0, kq[t], vq[t], ks[t], vs[t])
        assert kv.traffic["kv_pages_packed"] == n_layers * 2
        # reference: what seal() stored before packing scrubbed it
        cache = kv.materialize([0], toks)
        for layer in range(n_layers):
            c, j = layer % len(cfg.cycle), layer // len(cfg.cycle)
            got_k = np.asarray(cache["blocks"][c]["k"])[j, 0]
            got_s = np.asarray(cache["blocks"][c]["k_scale"])[j, 0]
            f = kq[:, layer].astype(np.float32) * ks[:, layer][..., None]
            for pno in range(2):
                page = f[pno * 4:(pno + 1) * 4]
                sc = np.maximum(np.abs(page).max(axis=(0, 2)), 1e-8) / 127.0
                want = np.clip(np.round(page / sc[None, :, None]),
                               -127, 127).astype(np.int8)
                assert np.array_equal(got_k[pno * 4:(pno + 1) * 4], want)
                assert np.allclose(got_s[pno * 4:(pno + 1) * 4],
                                   np.broadcast_to(sc, (4, h)))


# ------------------------------------------------ engine scheduling edges
def _mk_engine(max_batch=2, max_len=32, **kw):
    cfg = apack_cfg()
    params = M.init_params(configs.get_smoke_config("qwen3-1.7b"), KEY)
    return cfg, ServeEngine(cfg, params, max_batch=max_batch,
                            max_len=max_len, kv_page_size=4,
                            kv_calib_pages=2, **kw)


class TestPagedEngineScheduling:
    def test_paged_generation_drains_and_frees_all_pages(self):
        cfg, eng = _mk_engine(max_batch=3, max_len=32)
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 9)
                        .astype(np.int32), max_new_tokens=5)
                for i in range(5)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained(max_steps=200)
        assert all(r.done for r in reqs)
        assert all(len(r.tokens) >= 5 for r in reqs)
        assert eng.kv.pool.free_count == eng.kv.pool.num_pages
        assert eng._reserved_total == 0
        ks = eng.kv_stats()
        assert ks["kv_pages_packed"] > 0
        assert ks["kv_ratio"] < 1.0

    def test_eos_mid_batch_retires_slot_early(self):
        """A request hitting EOS mid-flight retires (frees its pages) while
        its batchmates keep decoding."""
        cfg, eng = _mk_engine(max_batch=2, max_len=48)
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
                   for _ in range(2)]
        # dry run to learn a token request 0 will emit mid-stream
        probe = [Request(rid=i, prompt=p.copy(), max_new_tokens=10)
                 for i, p in enumerate(prompts)]
        for r in probe:
            eng.submit(r)
        eng.run_until_drained(max_steps=200)
        eos = probe[0].tokens[2]                      # emitted at step 3
        cfg2, eng2 = _mk_engine(max_batch=2, max_len=48)
        reqs = [Request(rid=10 + i, prompt=p.copy(), max_new_tokens=10,
                        eos_id=(eos if i == 0 else None))
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng2.submit(r)
        eng2.run_until_drained(max_steps=200)
        assert all(r.done for r in reqs)
        assert len(reqs[0].tokens) <= 4               # retired early on eos
        assert len(reqs[1].tokens) >= 10              # batchmate unaffected
        assert eng2.kv.pool.free_count == eng2.kv.pool.num_pages

    def test_admission_blocks_when_pool_exhausted_then_recovers(self):
        """Free slots but no free pages: requests queue until a retire
        returns pages, and page ids are recycled across waves."""
        cfg = apack_cfg()
        params = M.init_params(configs.get_smoke_config("qwen3-1.7b"), KEY)
        n_layers = cfg.n_cycles * len(cfg.cycle)
        # pool sized for exactly ONE in-flight request (4 pages/layer)
        eng = ServeEngine(cfg, params, max_batch=4, max_len=16,
                          kv_page_size=4, kv_calib_pages=2,
                          kv_pages=n_layers * 4)
        rng = np.random.default_rng(5)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8)
                        .astype(np.int32), max_new_tokens=4)
                for i in range(3)]
        for r in reqs:
            eng.submit(r)
        # first admit: only one request fits despite 4 free slots
        eng._retire()
        eng._admit()
        assert sum(r is not None for r in eng.active) == 1
        assert len(eng.queue) == 2
        assert eng.stats["kv_admission_blocked"] > 0
        eng.run_until_drained(max_steps=300)
        assert all(r.done for r in reqs)
        # serialized waves reused the same page ids: lifetime allocs exceed
        # the pool high-water mark
        assert eng.kv.pool.alloc_count > eng.kv.pool.high_water
        assert eng.kv.pool.free_count == eng.kv.pool.num_pages

    def test_oversized_request_rejected_at_submit(self):
        """A request whose worst-case reservation exceeds the whole pool
        can never be admitted — fail fast instead of spinning forever."""
        cfg = apack_cfg()
        params = M.init_params(configs.get_smoke_config("qwen3-1.7b"), KEY)
        n_layers = cfg.n_cycles * len(cfg.cycle)
        eng = ServeEngine(cfg, params, max_batch=2, max_len=64,
                          kv_page_size=4, kv_pages=n_layers * 2)
        with pytest.raises(ValueError, match="pages worst-case"):
            eng.submit(Request(rid=0,
                               prompt=np.arange(12, dtype=np.int32),
                               max_new_tokens=8))

    def test_slot_reuse_after_retire_keeps_outputs_correct(self):
        """Batched paged engine == one-at-a-time paged engine (greedy),
        exercising slot+page reuse across admissions."""
        cfg = apack_cfg()
        params = M.init_params(configs.get_smoke_config("qwen3-1.7b"), KEY)
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
                   for _ in range(3)]
        seq_out = []
        for p in prompts:
            eng = ServeEngine(cfg, params, max_batch=1, max_len=24,
                              kv_page_size=4, kv_calib_pages=2)
            r = Request(rid=0, prompt=p, max_new_tokens=4)
            eng.submit(r)
            eng.run_until_drained(max_steps=100)
            seq_out.append(r.tokens[:4])
        eng = ServeEngine(cfg, params, max_batch=2, max_len=24,
                          kv_page_size=4, kv_calib_pages=2)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained(max_steps=200)
        for r, ref_toks in zip(reqs, seq_out):
            assert r.tokens[:4] == ref_toks, (r.tokens, ref_toks)


# ---------------------------------------------------- batched page seal
def _record_packs(kv):
    """Wrap ``kv.pool.pack`` to keep each page's COLD payload as it was
    when the page was packed (``pack`` scrubs it)."""
    seen = {}
    inner = kv.pool.pack

    def pack(pid, planes):
        seen[pid] = kv.pool.cold_q[:, pid].copy()
        inner(pid, planes)

    kv.pool.pack = pack
    return seen


def _assert_packed_alone(kv, seen):
    """Every recorded page holds the planes, CRC and table generation that
    packing it alone with ``ref.encode`` gives."""
    pool = kv.pool
    e = pool.elems_per_stream
    layer_of = {pid: layer for layer in kv.attn_layers
                for pid in kv._packed[layer]}
    assert seen and set(seen) <= set(layer_of)
    for pid, cold in seen.items():
        layer = layer_of[pid]
        outs = []
        for kind in (0, 1):
            vals = quant.to_unsigned(cold[kind]).reshape(pool.n_streams, e)
            ta = _ref.TableArrays.from_table(kv.tables[layer][kind])
            outs.append([np.asarray(x) for x in _ref.encode(
                jnp.asarray(vals.astype(np.int32)), ta, e, 8)])
        want = {name: np.stack([o[i] for o in outs]).astype(
                    getattr(pool, name).dtype)
                for i, name in enumerate(("sym", "ofs", "sym_bits",
                                          "ofs_bits", "stored"))}
        for name, w in want.items():
            assert np.array_equal(getattr(pool, name)[:, pid], w), (pid, name)
        want["page_scale"] = pool.page_scale[:, pid]
        assert int(kv.page_crc[pid]) == m.payload_crc(want)
        assert int(kv.page_gen[pid]) == int(kv.table_gen[layer])
        assert pool.state[pid] == m.PAGE_PACKED


class TestBatchedPageSeal:
    def test_admission_and_multi_slot_step_match_packing_alone(self):
        """One step admits two prompts (every prompt page sealed) and
        decodes a token that fills a page of both slots: the cache holds
        what packing each page alone gives, in few encode calls."""
        cfg, eng = _mk_engine(max_batch=2, max_len=32)
        kv = eng.kv
        seen = _record_packs(kv)
        sealed_by = []
        inner = kv._seal_from_device

        def seal_from_device(layer, pid, rid):
            sealed_by.append(rid)
            inner(layer, pid, rid)

        kv._seal_from_device = seal_from_device
        rng = np.random.default_rng(4)
        for rid, n in enumerate((7, 11)):      # both 3 past a page boundary
            eng.submit(Request(rid=rid, prompt=rng.integers(
                0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=3))
        eng.step()
        assert sorted(set(sealed_by)) == [0, 1]
        assert len(sealed_by) == 2 * len(kv.attn_layers)
        _assert_packed_alone(kv, seen)
        ks = eng.kv_stats()
        assert ks["kv_pages_packed"] == len(seen) \
            == (1 + 2 + 2) * len(kv.attn_layers)
        # the second admission (the first seals before calibration) and
        # the decode-path flush
        assert 2 <= ks["kv_seal_encode_calls"] < ks["kv_pages_packed"]
        assert not kv._pending

    def test_calibration_mid_ingest_packs_once_without_nesting(self):
        """A prompt long enough to calibrate its layers mid-ingest: the
        pages sealed before and after calibration pack in one flush at
        the end of the ingest, and no ``kv.seal`` span nests in another
        (``admit.seal_ms`` sums them at any depth)."""
        cfg, eng = _mk_engine(max_batch=1, max_len=32)
        kv = eng.kv
        seen = _record_packs(kv)
        rng = np.random.default_rng(5)
        eng.submit(Request(rid=0, prompt=rng.integers(
            0, cfg.vocab_size, 13).astype(np.int32), max_new_tokens=2))
        t0 = time.perf_counter()
        calls0 = kv.traffic["kv_seal_encode_calls"]
        eng.step()
        win = spans.recorded(t0, time.perf_counter())
        assert win.complete
        by_id = {s.id: s for s in win.spans}
        seals = [s for s in win.spans if s.name == "kv.seal"]
        for s in seals:
            p = s.parent
            while p is not None and p in by_id:
                assert by_id[p].name != "kv.seal"
                p = by_id[p].parent
        flushes = [s for s in seals if any(
            c.parent == s.id and c.name == "kv.seal.encode"
            for c in win.spans)]
        assert len(flushes) == 1                 # the ingest's, none nested
        assert kv.traffic["kv_seal_encode_calls"] - calls0 \
            == len(M.seal_chunks(2 * 3 * len(kv.attn_layers)))
        assert all(len(kv._packed[layer]) == 3 for layer in kv.attn_layers)
        _assert_packed_alone(kv, seen)

    def test_readahead_restore_packs_a_cold_page(self):
        """A page spilled COLD, whose layer calibrates while it is
        parked, comes back PACKED at the restore, as packing it alone
        would make it, with readahead bytes of the packed page."""
        cfg = apack_cfg()
        kv = M.PagedKVCache(cfg, num_pages=64, page_size=4, calib_pages=2)
        rng = np.random.default_rng(6)
        h, dh, n = kv.pool.kv_heads, kv.pool.head_dim, kv.n_layers

        def tok():
            return (rng.integers(-127, 128, (n, h, dh)).astype(np.int8),
                    rng.integers(-127, 128, (n, h, dh)).astype(np.int8),
                    rng.uniform(0.01, 0.02, (n, h)).astype(np.float32),
                    rng.uniform(0.01, 0.02, (n, h)).astype(np.float32))

        kv.add_request(0)
        for _ in range(4):
            kv.append_token(0, *tok())
        assert all(len(kv._cold[layer]) == 1 for layer in kv.attn_layers)
        assert kv.spill_request(0) == len(kv.attn_layers)
        kv.add_request(1)
        for _ in range(4):                       # calibrates every layer
            kv.append_token(1, *tok())
        packed0 = kv.traffic["kv_pages_packed"]
        seen = _record_packs(kv)
        restored = kv.unspill_request(0)
        assert not kv._pending
        assert sorted(seen) == sorted(restored)
        assert kv.traffic["kv_pages_packed"] - packed0 == len(restored)
        _assert_packed_alone(kv, seen)
        assert kv.traffic["kv_readahead_bytes"] == sum(
            kv.pool.page_bytes(pid) for pid in restored)

    @pytest.mark.parametrize("scheduler", ["sync", "async"])
    def test_pending_queue_empty_after_every_public_call(self, scheduler):
        """No caller of the cache sees a queued page: the queue is empty
        whenever a public method returns to code outside the cache (a
        public method it calls itself, such as ``evict_rolled``, may run
        while pages wait for the flush)."""
        cfg, eng = _mk_engine(max_batch=2, max_len=32, scheduler=scheduler)
        kv = eng.kv
        returned = []
        depth = [0]
        for name, _ in inspect.getmembers(M.PagedKVCache,
                                          inspect.isfunction):
            if name.startswith("_"):
                continue
            inner = getattr(kv, name)

            def checked(*a, _inner=inner, _name=name, **k):
                depth[0] += 1
                try:
                    return _inner(*a, **k)
                finally:
                    depth[0] -= 1
                    if depth[0] == 0:
                        assert not kv._pending, _name
                        returned.append(_name)

            setattr(kv, name, checked)
        rng = np.random.default_rng(7)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n)
                        .astype(np.int32), max_new_tokens=6)
                for i, n in enumerate((9, 6, 13))]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained(max_steps=200)
        assert all(r.done for r in reqs)
        assert {"note_appended", "step_meta", "release"} <= set(returned)
        assert eng.kv_stats()["kv_pages_packed"] > 0
