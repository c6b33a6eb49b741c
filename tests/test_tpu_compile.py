"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

Nothing runs on a chip: each test lowers and compiles a kernel with
``interpret=False`` for a described (not attached) v5e at qwen3-1.7b
widths — d_model 2048, d_ff 6144, 16 query / 8 kv heads, head_dim 128,
16-token KV pages — so whatever the chip's compiler refuses (an
unsupported gather, a misaligned block, too much VMEM) fails here.  Plane
shapes come from the layout functions; no data is encoded.

The topology is described only inside the module fixture below: describing
it loads the TPU compiler library, which one process at a time may hold.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decompress_matmul as dm
from repro.kernels.fused_page_attention import fused_page_attention_pallas
from repro.kernels.paged_decode import gather_decode_pallas
from repro.kernels.ref import (ENCODER_ROW, encode_pages,
                               ofs_capacity_words, sym_capacity_words)
from repro.models.model import SEAL_CHUNK_LARGE, SEAL_CHUNK_SMALL

I32, U32, F32 = jnp.int32, jnp.uint32, jnp.float32
D_MODEL, D_FF = 2048, 6144
HQ, HKV, DH, PAGE = 16, 8, 128, 16
E = 128                                  # values per KV stream
N_STREAMS = PAGE * HKV * DH // E
N_POOL, N_TABLES, BATCH, P_SLOTS = 512, 56, 4, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_kernel(lowered) -> None:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_compressed_matmul_decode_compiles(one_chip, k, n):
    tile_k = dm.DEFAULT_TILE_K
    nk, nn = k // tile_k, n // dm.TILE_N
    s = nk * nn * dm.TILE_N
    a = lambda shape, dtype: _shape(one_chip, shape, dtype)  # noqa: E731
    cw = dm.CompressedLinear(
        a((sym_capacity_words(tile_k), s), U32),
        a((ofs_capacity_words(tile_k, 8), s), U32), a((s,), I32),
        a((17,), I32), a((16,), I32), a((17,), I32), a((n,), F32),
        k=k, n=n, tile_k=tile_k, payload_bits=0)
    _compiled_kernel(dm.compressed_matmul.lower(
        a((8, k), F32), cw, interpret=False, block_m=8))


def _pool_planes(one_chip):
    a = lambda shape, dtype: _shape(one_chip, shape, dtype)  # noqa: E731
    ws, wo = sym_capacity_words(E), ofs_capacity_words(E, 8)
    dense = a((N_POOL, PAGE, HKV, DH), jnp.int8)
    tok_s = a((N_POOL, PAGE, HKV), F32)
    return dict(
        tok_k=dense, tok_sk=tok_s, tok_v=dense, tok_sv=tok_s,
        cold_k=dense, cold_v=dense, pscale_k=a((N_POOL, HKV), F32),
        pscale_v=a((N_POOL, HKV), F32),
        sym_k=a((N_POOL, ws, N_STREAMS), U32),
        ofs_k=a((N_POOL, wo, N_STREAMS), U32),
        stored_k=a((N_POOL, N_STREAMS), I32),
        sym_v=a((N_POOL, ws, N_STREAMS), U32),
        ofs_v=a((N_POOL, wo, N_STREAMS), U32),
        stored_v=a((N_POOL, N_STREAMS), I32),
        vm=a((N_TABLES, 17), I32), ol=a((N_TABLES, 16), I32),
        cum=a((N_TABLES, 17), I32))


def test_fused_page_attention_compiles(one_chip):
    a = lambda shape, dtype: _shape(one_chip, shape, dtype)  # noqa: E731
    jobs = a((BATCH, P_SLOTS), I32)
    _compiled_kernel(fused_page_attention_pallas.lower(
        a((BATCH, HQ, DH), F32), jobs, jobs, a((BATCH, P_SLOTS, 2), I32),
        a((BATCH, 3), I32), **_pool_planes(one_chip), n_steps=E,
        num_heads=HQ, interpret=False))


def test_paged_decode_compiles(one_chip):
    a = lambda shape, dtype: _shape(one_chip, shape, dtype)  # noqa: E731
    pl = _pool_planes(one_chip)
    pages = a((BATCH * P_SLOTS,), I32)
    _compiled_kernel(gather_decode_pallas.lower(
        pl["sym_k"], pl["ofs_k"], pl["stored_k"], pages, pl["vm"],
        pl["ol"], pl["cum"], n_steps=E, interpret=False, table_idx=pages))


@pytest.mark.parametrize("size", [SEAL_CHUNK_SMALL, SEAL_CHUNK_LARGE])
def test_page_seal_encoder_compiles(one_chip, size):
    """The page seal's batched encoder (a plain XLA program) at both of
    its chunk shapes."""
    a = lambda shape, dtype: _shape(one_chip, shape, dtype)  # noqa: E731
    encode_pages.lower(a((size, N_STREAMS, E), jnp.uint8),
                       a((size, ENCODER_ROW), I32)).compile()
