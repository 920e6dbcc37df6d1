"""Compile every Pallas kernel for a described (not attached) TPU v5e chip at
the widths of the models that use it.

Interpret mode, which ``test_kernels.py`` runs, checks the maths but not the
Mosaic rules: block tiling, lowerable primitives and VMEM limits.  These
tests hand the TPU compiler shapes only, so they need no chip and run no
kernel; a compile that passes here is not a run on the chip.

The topology is described inside a fixture, never at import, so that only
the worker that runs this file loads the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.rglru_scan.kernel import linear_scan_pallas
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas

# llama3.2-3b attention geometry: 24 q heads padded to 32, 8 kv heads, D 128
HQ, HKV, HD = 32, 8, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_paged_attention_compiles(one_chip):
    B, NP, page, maxp = 4, 64, 16, 16
    _assert_kernel(
        paged_decode_attention_pallas,
        _spec((B, HQ, HD), jnp.bfloat16, one_chip),
        _spec((NP, HKV, page, HD), jnp.bfloat16, one_chip),
        _spec((NP, HKV, page, HD), jnp.bfloat16, one_chip),
        _spec((B, maxp), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip))


def test_decode_attention_compiles(one_chip):
    B, T = 4, 2048
    _assert_kernel(
        decode_attention_pallas,
        _spec((B, HQ, HD), jnp.bfloat16, one_chip),
        _spec((B, HKV, T, HD), jnp.bfloat16, one_chip),
        _spec((B, HKV, T, HD), jnp.bfloat16, one_chip),
        _spec((B,), jnp.int32, one_chip))


def test_flash_attention_compiles(one_chip):
    B, S = 1, 2048
    _assert_kernel(
        flash_attention_pallas,
        _spec((B, HQ, S, HD), jnp.bfloat16, one_chip),
        _spec((B, HKV, S, HD), jnp.bfloat16, one_chip),
        _spec((B, HKV, S, HD), jnp.bfloat16, one_chip))


def test_ssd_scan_compiles(one_chip):
    # mamba2-130m: d_inner 1536 = 24 heads x 64, state 128, chunk 256
    B, S, H, P, N = 2, 1024, 24, 64, 128
    _assert_kernel(
        ssd_scan_pallas,
        _spec((B, S, H, P), jnp.bfloat16, one_chip),
        _spec((B, S, H), jnp.float32, one_chip),
        _spec((H,), jnp.float32, one_chip),
        _spec((B, S, N), jnp.bfloat16, one_chip),
        _spec((B, S, N), jnp.bfloat16, one_chip),
        _spec((H,), jnp.float32, one_chip))


def test_rglru_scan_compiles(one_chip):
    # recurrentgemma-9b: lru_width 4096
    B, S, W = 2, 1024, 4096
    _assert_kernel(
        linear_scan_pallas,
        _spec((B, S, W), jnp.float32, one_chip),
        _spec((B, S, W), jnp.bfloat16, one_chip))
