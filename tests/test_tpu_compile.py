"""Compile every Pallas kernel for a described (not attached) TPU v5e chip at
the widths of the models that use it.

Interpret mode, which ``test_kernels.py`` runs, checks the maths but not the
Mosaic rules: block tiling, lowerable primitives and VMEM limits.  These
tests hand the TPU compiler shapes only, so they need no chip and run no
kernel; a compile that passes here is not a run on the chip.

The topology is described inside a fixture, never at import, so that only
the worker that runs this file loads the TPU compiler library.
"""
import dataclasses
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.latent_attention.kernel import (
    latent_paged_decode_attention_pallas)
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.rglru_scan.kernel import linear_scan_pallas
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.models.common import merge_params
from repro.models.model_zoo import build_model
from repro.serving import kvcache
from repro.serving.engine import paged_decode_step

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
    # 6 layers of row-interleaved pages, read in place at a traced layer
    L, B, NP, page, maxp = 6, 4, 64, 16, 16
    text = paged_decode_attention_pallas.lower(
        _spec((B, HQ, HD), jnp.bfloat16, one_chip),
        _spec((L, NP, page * HKV, HD), jnp.bfloat16, one_chip),
        _spec((L, NP, page * HKV, HD), jnp.bfloat16, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((B, maxp), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        page_size=page).compile().as_text()
    assert "tpu_custom_call" in text


#: an HLO instruction's name, result array type, layout and opcode
_HLO_OP = re.compile(r"%(\S+) = \w+\[([\d,]*)\](\S*) ([\w-]+)\(([^)]*)\)")


def test_serve_step_reads_pages_in_place(one_chip, monkeypatch):
    """The ``serve-batch`` cell's step (6 layers of Qwen2.5-14B, 32 slots of
    2,048, pages of 16), compiled with the Pallas kernel for the chip: no
    op slices, copies or relayouts one layer's pages out of the cache.
    The only ops that make a whole cache are the two token writes (each a
    fusion around a scatter) and at most the two undonated entry copies of
    the arguments, in one layout throughout."""
    monkeypatch.setattr(paged_ops, "use_pallas", lambda: True)
    monkeypatch.setattr(paged_ops, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(get_config("qwen2.5-14b"), num_layers=6,
                              head_pad_multiple=1, norm_eps=1e-5)
    slots = 32
    specs, axes = build_model(cfg).param_specs()
    kv = jax.eval_shape(lambda: kvcache.paged_cache_init(
        cfg, slots, 2048, page_size=16))
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    def step(values, kv, tokens, active):
        return paged_decode_step(merge_params(values, axes), kv, tokens,
                                 active, cfg)

    text = jax.jit(step).lower(
        placed(specs), placed(kv), _spec((slots,), jnp.int32, one_chip),
        _spec((slots,), jnp.bool_, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text

    cache = kv.k_pages.shape
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    args = {m[0] for m in _HLO_OP.findall(entry) if m[3] == "parameter"}
    ops, layouts, copied = Counter(), set(), []
    for name, dims, layout, opcode, operands in _HLO_OP.findall(text):
        shape = tuple(int(d) for d in dims.split(",") if d)
        if shape[1 - len(cache):] != cache[1:]:
            continue
        assert shape == cache, (name, shape, opcode)    # no layer's pages
        ops[opcode] += 1
        layouts.add(layout)
        if opcode == "copy":
            copied.append(operands.lstrip("%"))
    assert set(ops) <= {"parameter", "get-tuple-element", "fusion",
                        "scatter", "copy"}, ops
    assert ops["scatter"] == 2 and ops["fusion"] == 2, ops
    assert len(copied) <= 2 and set(copied) <= args, copied
    assert len(layouts) == 1, layouts


def test_latent_attention_compiles(one_chip):
    # DeepSeek-V2: 128 heads over 576-wide latent rows stored 640 wide,
    # 512-wide values; 6 layers of pages, 8 pages a grid step
    L, B, NP, page, maxp = 6, 4, 64, 16, 16
    text = latent_paged_decode_attention_pallas.lower(
        _spec((B, 128, 640), jnp.bfloat16, one_chip),
        _spec((L, NP, page, 640), jnp.bfloat16, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((B, maxp), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        rank=512, scale=0.1).compile().as_text()
    assert "tpu_custom_call" in text


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
