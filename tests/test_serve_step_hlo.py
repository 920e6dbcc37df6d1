"""The GQA serve step's program is pinned: adding an attention kind to the
engine (MLA's latent pages) leaves the step that dense GQA models compile
unchanged.  The fingerprints are of the lowered StableHLO without debug
information (no source lines, no scope names), of ``paged_decode_step``
for Qwen2.5-14B's layer at a tiny width and at the ``serve-batch`` cell's
size (6 layers, 32 slots of 2,048, pages of 16; shapes only, nothing is
allocated).  A change that means to alter that step updates them."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models.common import merge_params
from repro.models.model_zoo import build_model
from repro.serving import kvcache
from repro.serving.engine import paged_decode_step

QWEN = get_config("qwen2.5-14b")
TINY = dataclasses.replace(
    QWEN, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=512, head_pad_multiple=1, norm_eps=1e-6)
CELL = dataclasses.replace(QWEN, num_layers=6, head_pad_multiple=1,
                           norm_eps=1e-5)


def _fingerprint(cfg, slots, max_len, page):
    specs, axes = build_model(cfg).param_specs()
    kv = jax.eval_shape(lambda: kvcache.paged_cache_init(
        cfg, slots, max_len, page_size=page))

    def step(values, kv, tokens, active):
        return paged_decode_step(merge_params(values, axes), kv, tokens,
                                 active, cfg)

    text = jax.jit(step).lower(
        specs, kv, jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.bool_)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("cfg,slots,max_len,digest", [
    (TINY, 4, 128,
     "df03ae3744b9b5f551538604a4c223eef50c550a76a2ab2174208edebc3eaa6b"),
    (CELL, 32, 2048,
     "248341a5d5cebbb1bb8d1751fac31d54a61a79c7715f63831edb089869bd17cb"),
], ids=["tiny", "serve-batch"])
def test_gqa_serve_step_program_is_unchanged(cfg, slots, max_len, digest):
    assert _fingerprint(cfg, slots, max_len, 16) == digest
