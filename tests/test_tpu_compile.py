"""Compile the main-path Pallas kernels for a TPU v5e at published widths.

Interpret mode (every other kernel test) runs the kernel bodies on the CPU
and never applies Mosaic's rules: block shapes must tile (8, 128), dynamic
DMA row offsets must be provably tile-aligned, and scratch must fit VMEM.
These tests hand the TPU compiler a *described* v5e chip (no device
attached) and fail on exactly what a first chip run would crash on. Widths
are DBRX's (d=6144, 48 q / 8 kv heads of 128, expert hidden 10752, bf16);
the fused FFN is also compiled at d=4096 (Mixtral-8x7B: hidden 14336).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers all import
this file.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_decode.flash_decode import flash_decode
from repro.kernels.flash_decode.paged import flash_decode_paged
from repro.kernels.gmm import ops
from repro.kernels.registry import FUSED_FFN_MAX_DOWN_DIM

D, H, KV, HD, F = 6144, 48, 8, 128, 10752     # DBRX-132B widths
BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip compile can be written to a persistent cache but not
    # read back without the chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_hlo(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo: str) -> None:
    assert "tpu_custom_call" in hlo


# One chip's share at DBRX widths: 20 expert slots (16 experts + 4 spare),
# 128 rows per bucket.
G, CAP = 20, 128


# Bucket heights the cells run through the ragged pair: 128 (decode) and
# 256 (the prefill lane) on the closed-loop cell, 32 and 256 on the open.
@pytest.mark.parametrize("cap", [32, 128, 256])
def test_gmm_ragged_pair_compiles(one_chip, cap):
    fn = functools.partial(ops.expert_ffn_ragged, interpret=False)
    hlo = _compile_hlo(
        fn, one_chip,
        ((G, cap, D), BF16), ((G, D, F), BF16), ((G, D, F), BF16),
        ((G, F, D), BF16), ((G,), I32),
    )
    _assert_kernel(hlo)


def test_gmm_gather_scatter_pair_compiles(one_chip):
    fn = functools.partial(
        ops.expert_ffn_gather_compact, capacity=CAP, interpret=False
    )
    hlo = _compile_hlo(
        fn, one_chip,
        ((G * CAP, D), BF16), ((G, D, F), BF16), ((G, D, F), BF16),
        ((G, F, D), BF16), ((G,), I32), ((G,), I32),
    )
    _assert_kernel(hlo)


@pytest.mark.parametrize("d,f", [(4096, 14336), (D, F)], ids=["d4096", "d6144"])
def test_gmm_fused_ffn_compiles(one_chip, d, f):
    assert d <= FUSED_FFN_MAX_DOWN_DIM  # the registry sends these widths here
    fn = functools.partial(ops.expert_ffn_fused, capacity=CAP, interpret=False)
    hlo = _compile_hlo(
        fn, one_chip,
        ((G * CAP, d), BF16), ((G, d, f), BF16), ((G, d, f), BF16),
        ((G, f, d), BF16), ((G,), I32), ((G,), I32),
    )
    _assert_kernel(hlo)


def test_flash_decode_paged_compiles(one_chip):
    b, page, nb = 8, 128, 8                   # 8 slots x 1024 context
    pages = b * nb + 1
    fn = functools.partial(flash_decode_paged, interpret=False)
    hlo = _compile_hlo(
        fn, one_chip,
        ((b, H, HD), BF16), ((pages, page, KV, HD), BF16),
        ((pages, page, KV, HD), BF16), ((b, nb), I32), ((b,), I32),
    )
    _assert_kernel(hlo)


def test_flash_decode_dense_compiles(one_chip):
    b, t = 8, 1024
    fn = functools.partial(flash_decode, interpret=False)
    hlo = _compile_hlo(
        fn, one_chip,
        ((b, H, HD), BF16), ((b, t, KV, HD), BF16), ((b, t, KV, HD), BF16),
        ((b, t), I32),
    )
    _assert_kernel(hlo)


def test_flash_attention_compiles(one_chip):
    b, s = 1, 512
    fn = functools.partial(flash_attention, interpret=False)
    hlo = _compile_hlo(
        fn, one_chip,
        ((b, s, H, HD), BF16), ((b, s, KV, HD), BF16), ((b, s, KV, HD), BF16),
    )
    _assert_kernel(hlo)
