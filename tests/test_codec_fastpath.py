"""Fast-path coverage for the multi-bit-renormalization codec and the
decode-once fused matmul.

The multi-bit renorm (kernels/ref.py ``renorm_counts``) replaces the per-bit
WNC loop with closed-form bit arithmetic; these tests pin it against (a) a
direct Python transcription of the per-bit loop and (b) the golden codec's
full streams, across bit-widths, stored-mode fallbacks, and stream counts
that don't tile the 128-lane kernel block.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ac_golden, distributions, format as fmt, quant, tables
from repro.core.ac_golden import HALF, QUARTER, THREEQ, TOP
from repro.kernels import ops, ref
from repro.kernels import decompress_matmul as dm
from repro.models import model as M


def _wnc_renorm(low: int, high: int):
    """Per-bit reference of one post-update renormalization run."""
    m = u = 0
    bits = []
    while True:
        if high < HALF:
            bits.append(0)
            m += 1
        elif low >= HALF:
            bits.append(1)
            low -= HALF
            high -= HALF
            m += 1
        elif low >= QUARTER and high < THREEQ:
            u += 1
            low -= QUARTER
            high -= QUARTER
        else:
            break
        low = low * 2
        high = high * 2 + 1
    return m, u, low, high, bits


class TestRenormCounts:
    @settings(max_examples=200, deadline=None)
    @given(st.integers(0, TOP), st.integers(0, TOP))
    def test_matches_per_bit_loop(self, a, b):
        low, high = min(a, b), max(a, b)
        if low == high:
            high = min(high + 1, TOP)
            low = high - 1
        em, eu, elo, ehi, ebits = _wnc_renorm(low, high)
        m, u, lo, hi = ref.renorm_counts(jnp.asarray([low], jnp.int32),
                                         jnp.asarray([high], jnp.int32))
        assert (int(m[0]), int(u[0])) == (em, eu), (low, high)
        assert (int(lo[0]), int(hi[0])) == (elo, ehi), (low, high)
        # emitted bits are the m matched leading bits of low, MSB-first
        prefix = [(low >> (15 - i)) & 1 for i in range(em)]
        assert prefix == ebits

    def test_interval_invariant_restored(self):
        # after renorm the range must exceed QUARTER (WNC invariant)
        rng = np.random.default_rng(0)
        lows = rng.integers(0, TOP, 4096)
        highs = np.minimum(lows + rng.integers(16, TOP, 4096), TOP)
        m, u, lo, hi = ref.renorm_counts(jnp.asarray(lows, jnp.int32),
                                         jnp.asarray(highs, jnp.int32))
        assert bool(jnp.all(hi - lo + 1 > QUARTER))

    def test_bit_helpers(self):
        x = jnp.asarray([0, 1, 2, 0x8000, 0xFFFF], jnp.int32)
        assert np.asarray(ref.bitlen16(x)).tolist() == [0, 1, 2, 16, 16]
        w = jnp.asarray([0x0001, 0x8000, 0x1234], jnp.uint32)
        assert np.asarray(ref.rev16(w)).tolist() == [0x8000, 0x0001, 0x2C48]


def _golden_stream_check(v, table, e):
    """Encode with the jnp kernels and golden; assert bit-identical planes."""
    ct = fmt.compress(v, table, bits=table.bits, elems_per_stream=e)  # golden
    for backend in ("ref", "pallas_interpret"):
        ca = ops.apack_encode(v, table, elems_per_stream=e, backend=backend)
        assert np.array_equal(np.asarray(ca.sym_bits), ct.sym_bits), backend
        assert np.array_equal(np.asarray(ca.ofs_bits), ct.ofs_bits), backend
        assert np.array_equal(np.asarray(ca.stored), ct.stored), backend
        ws, wo = ct.sym_plane.shape[0], ct.ofs_plane.shape[0]
        assert np.array_equal(
            np.asarray(ca.sym_plane[:ws]).astype(np.uint32), ct.sym_plane)
        assert np.array_equal(
            np.asarray(ca.ofs_plane[:wo]).astype(np.uint32), ct.ofs_plane)
        out = ops.apack_decode(ca, backend=backend)
        assert np.array_equal(np.asarray(out).astype(np.int64), v), backend


class TestBitExactVsGolden:
    @pytest.mark.parametrize("bits", [4, 8, 16])
    def test_bitwidth_sweep(self, bits):
        rng = np.random.default_rng(bits)
        base = distributions.gaussian_weights(3000, seed=bits).astype(np.int64)
        v = (base * (1 if bits <= 8 else 257)) & ((1 << bits) - 1)
        t = tables.table_for(v, bits=bits, is_activation=True)
        _golden_stream_check(v, t, e=128)

    def test_stored_mode_fallback_streams(self):
        # uniform values under a uniform table inflate -> stored fallback
        rng = np.random.default_rng(1)
        v = rng.integers(0, 256, 1024).astype(np.int64)
        t = tables.uniform_table()
        ca = ops.apack_encode(v, t, elems_per_stream=128,
                              backend="pallas_interpret")
        assert bool(np.asarray(ca.stored).all())
        _golden_stream_check(v, t, e=128)

    def test_mixed_stored_and_ac_streams(self):
        # half gaussian (compresses), half uniform (stored) in one tensor
        rng = np.random.default_rng(2)
        g = distributions.gaussian_weights(512, seed=3).astype(np.int64)
        u = rng.integers(0, 256, 512).astype(np.int64)
        v = np.concatenate([g, u]) & 0xFF
        t = tables.table_for(g, is_activation=True)
        ca = ops.apack_encode(v, t, elems_per_stream=128,
                              backend="pallas_interpret")
        stored = np.asarray(ca.stored)
        assert stored.any() and not stored.all()
        _golden_stream_check(v, t, e=128)

    @pytest.mark.parametrize("n", [1, 100, 129 * 64, 5000])
    def test_non_multiple_of_128_streams(self, n):
        # stream counts that don't tile BLOCK_STREAMS exercise the padding
        # lanes (garbage-in, discarded-out) around the multi-bit fast path
        v = distributions.gaussian_weights(max(n, 1), seed=n).astype(np.int64) & 0xFF
        t = tables.table_for(v, is_activation=True)
        ca = ops.apack_encode(v, t, elems_per_stream=64,
                              backend="pallas_interpret")
        assert ca.sym_bits.shape[0] == -(-n // 64)
        out = ops.apack_decode(ca, backend="pallas_interpret")
        assert np.array_equal(np.asarray(out).astype(np.int64), v)


class TestDecodeOnceMatmul:
    @pytest.mark.parametrize("block_m", [8, 16, 32])
    def test_output_invariant_to_block_m(self, block_m):
        # m_pad // block_m > 1 for every setting: the decode-under-
        # pl.when(i == 0) + VMEM scratch path must give identical results
        # no matter how many row-blocks reuse the decoded tile.
        rng = np.random.default_rng(7)
        m, k, n = 64, 256, 128
        w = rng.normal(0, 0.05, (k, n)).astype(np.float32)
        x = rng.normal(0, 1, (m, k)).astype(np.float32)
        cw = dm.compress_linear(w, tile_k=128)
        assert m // block_m > 1
        fused = np.asarray(dm.compressed_matmul(jnp.asarray(x), cw,
                                                block_m=block_m))
        oracle = np.asarray(dm.reference_matmul(jnp.asarray(x), cw))
        np.testing.assert_allclose(fused, oracle, rtol=1e-5, atol=1e-5)

    def test_multi_tile_grid(self):
        # nk > 1 and nn > 1 and row-blocks > 1 simultaneously: scratch must
        # be refilled at each (j, kt) tile and reused across i only.
        rng = np.random.default_rng(8)
        m, k, n = 32, 256, 256
        w = rng.normal(0, 0.05, (k, n)).astype(np.float32)
        x = rng.normal(0, 1, (m, k)).astype(np.float32)
        cw = dm.compress_linear(w, tile_k=128)
        fused = np.asarray(dm.compressed_matmul(jnp.asarray(x), cw, block_m=16))
        oracle = np.asarray(dm.reference_matmul(jnp.asarray(x), cw))
        np.testing.assert_allclose(fused, oracle, rtol=1e-5, atol=1e-5)


class TestTableMode:
    def test_find_table_records_mode(self):
        v = distributions.gaussian_weights(4096, seed=0).astype(np.int64) & 0xFF
        assert tables.table_for(v, is_activation=False).mode == "weight"
        assert tables.table_for(v, is_activation=True).mode == "activation"

    def test_weight_mode_gives_empty_ranges_zero_counts(self):
        # only low values present: weight mode must not steal counts for
        # the empty upper ranges, activation mode must
        v = np.zeros(4096, np.int64)
        v[:100] = np.arange(100) % 16
        tw = tables.table_for(v, is_activation=False)
        ta = tables.table_for(v, is_activation=True)
        cw = np.diff(np.asarray(tw.cum))
        ca = np.diff(np.asarray(ta.cum))
        assert (cw == 0).any()
        assert (ca > 0).all()


# ------------------------------------------------- batched page encoder
def _overflow_table():
    """16 ranges of 16 values; range 1 owns counts [256, 768), so a run
    of its values keeps the interval on the midpoint and every step adds
    a pending underflow bit until the encoder's cap trips."""
    cum = [0, 256, 768] + [768 + 18 * i for i in range(1, 14)] + [1024]
    return tables.ApackTable(v_min=tuple(range(0, 257, 16)),
                             ol=(4,) * 16, cum=tuple(cum), bits=8,
                             mode="activation")


def _page_kinds(n, s, e, seed):
    """``n`` page-kinds of ``s`` x ``e`` 8-bit values under four tables
    (as layers and kinds have): two fitted, a uniform one whose streams
    inflate to stored mode, and one on which one stream's pending run
    overflows."""
    rng = np.random.default_rng(seed)
    fitted = [tables.table_for(
        quant.to_unsigned(np.clip(np.round(rng.normal(0, sp, 4096)),
                                  -127, 127).astype(np.int8)).astype(np.int64),
        is_activation=True) for sp in (3, 40)]
    ts = [fitted[0], fitted[1], tables.uniform_table(), _overflow_table()]
    pages, pts = [], []
    for i in range(n):
        k = i % 4
        if k < 2:
            sp = (3, 40)[k]
            q = np.clip(np.round(rng.normal(0, sp, (s, e))), -127, 127)
            u = quant.to_unsigned(q.astype(np.int8))
        elif k == 2:
            u = rng.integers(0, 256, (s, e))
        else:
            u = rng.integers(0, 256, (s, e))
            u[0] = rng.integers(16, 32, e)            # the overflow stream
        pages.append(u.astype(np.uint8))
        pts.append(ts[k])
    return np.stack(pages), pts


def _encode_chunk(pages, pts, size):
    """``pages`` in one chunk of ``size`` page-kinds, padded with zero
    pages as ``PagedKVCache._pack_pending`` pads its last chunk."""
    n = len(pages)
    v = np.zeros((size,) + pages.shape[1:], np.uint8)
    v[:n] = pages
    rows = np.stack([ref.encoder_row(t) for t in pts])
    r = np.concatenate([rows, np.repeat(rows[:1], size - n, axis=0)])
    out = ref.encode_pages(jnp.asarray(v), jnp.asarray(r))
    return [np.asarray(o)[:n] for o in out]


class TestBatchedPageEncoder:
    @pytest.mark.parametrize("size", [M.SEAL_CHUNK_SMALL, M.SEAL_CHUNK_LARGE])
    def test_matches_per_page_encode_and_golden(self, size):
        """A chunk at each compiled shape, its last slots zero padding:
        every page equals ``ref.encode`` and ``format.compress`` of that
        page alone, stored-mode and overflow streams included."""
        s, e = 2, 128
        pages, pts = _page_kinds(size - 3, s, e, seed=size)
        out = _encode_chunk(pages, pts, size)
        assert out[4].any() and not out[4].all()
        overflowed = 0
        for p, (vals, t) in enumerate(zip(pages, pts)):
            want = ref.encode(jnp.asarray(vals.astype(np.int32)),
                              ref.TableArrays.from_table(t), e, 8)
            for got, w in zip(out, want):
                w = np.asarray(w)
                assert got[p].dtype == w.dtype
                assert np.array_equal(got[p], w), p
            ct = fmt.compress(vals.reshape(-1).astype(np.int64), t, bits=8,
                              elems_per_stream=e)
            assert np.array_equal(out[2][p], ct.sym_bits)
            assert np.array_equal(out[3][p], ct.ofs_bits)
            assert np.array_equal(out[4][p], ct.stored)
            ws, wo = ct.sym_plane.shape[0], ct.ofs_plane.shape[0]
            assert np.array_equal(out[0][p][:ws], ct.sym_plane)
            assert not out[0][p][ws:].any()
            assert np.array_equal(out[1][p][:wo], ct.ofs_plane)
            assert not out[1][p][wo:].any()
            overflowed += t == pts[3] and bool(out[4][p][0])
        assert overflowed > 0

    def test_page_geometry_of_the_served_cache(self):
        """128 streams of 128 values (a 16-token page of 8 heads x 128):
        a small chunk of mixed tables against ``ref.encode``."""
        s = e = 128
        pages, pts = _page_kinds(M.SEAL_CHUNK_SMALL - 1, s, e, seed=9)
        out = _encode_chunk(pages, pts, M.SEAL_CHUNK_SMALL)
        for p, (vals, t) in enumerate(zip(pages, pts)):
            want = ref.encode(jnp.asarray(vals.astype(np.int32)),
                              ref.TableArrays.from_table(t), e, 8)
            for got, w in zip(out, want):
                assert np.array_equal(got[p], np.asarray(w)), p

    def test_scan_body_has_no_scatter_or_gather(self):
        """The serial step's cost must not grow with the lane count the
        way a scatter's or a gather's does: its body holds neither."""
        v = jnp.zeros((4, 2, 128), jnp.uint8)
        r = jnp.zeros((4, ref.ENCODER_ROW), jnp.int32)
        jaxpr = jax.make_jaxpr(ref.encode_pages)(v, r).jaxpr

        def eqns(j):
            for eq in j.eqns:
                yield eq
                for v in eq.params.values():
                    for sub in (v if isinstance(v, (tuple, list)) else [v]):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            yield from eqns(sub)

        scans = [eq for eq in eqns(jaxpr) if eq.primitive.name == "scan"]
        assert len(scans) == 1
        body = {eq.primitive.name
                for eq in eqns(scans[0].params["jaxpr"].jaxpr)}
        assert not {p for p in body if "scatter" in p or "gather" in p}, body
        assert "select_n" in body

    def test_seal_chunks_cover_with_two_shapes(self):
        small, large = M.SEAL_CHUNK_SMALL, M.SEAL_CHUNK_LARGE
        assert M.seal_chunks(0) == []
        assert M.seal_chunks(1) == [small]
        assert small % 2 == 0 and large % 2 == 0   # K and V share a chunk
        for n in range(1, 3 * large + 5):
            plan = M.seal_chunks(n)
            assert set(plan) <= {small, large}
            assert sum(plan) >= n
            # a chunk is all padding only if it is the single one
            assert sum(plan) - plan[-1] < n
            assert plan.count(small) < M.SEAL_SMALL_PER_LARGE
