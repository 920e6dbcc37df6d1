"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle, swept
over shapes and dtypes, plus sequential-scan ground truths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import (
    attention_reference, attention_reference_chunked)
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_reference
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.paged_attention.ref import paged_decode_attention_reference
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_scan_reference, ssd_decode_reference
from repro.kernels.rglru_scan.kernel import linear_scan_pallas
from repro.kernels.rglru_scan.ref import linear_scan_reference


def _qkv(key, B, Sq, Sk, Hq, Hkv, D, dtype):
    """Head-major q (B, Hq, Sq, D) and k/v (B, Hkv, Sk, D)."""
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, D), dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


@pytest.mark.parametrize("B,S,Hq,Hkv,D,blk", [
    (1, 128, 4, 4, 32, 64),
    (2, 256, 4, 2, 64, 64),
    (2, 128, 8, 1, 16, 32),      # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 40),
                                           (False, None)])
def test_flash_attention_vs_ref(rng, B, S, Hq, Hkv, D, blk, dtype, causal,
                                window):
    q, k, v = _qkv(rng, B, S, S, Hq, Hkv, D, dtype)
    ref = attention_reference(q, k, v, causal=causal, window=window)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 blk_q=blk, blk_k=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_q_offset(rng):
    q, k, v = _qkv(rng, 1, 64, 128, 2, 2, 16, jnp.float32)
    ref = attention_reference(q, k, v, causal=True, q_offset=64)
    out = flash_attention_pallas(q, k, v, causal=True, q_offset=64,
                                 blk_q=32, blk_k=32, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kw", [dict(causal=True), dict(causal=True, window=37),
                                dict(causal=False),
                                dict(causal=True, q_offset=64)])
def test_chunked_ref_vs_dense_ref(rng, kw):
    q, k, v = _qkv(rng, 2, 256, 256, 4, 2, 16, jnp.float32)
    ref = attention_reference(q, k, v, **kw)
    out = attention_reference_chunked(q, k, v, blk_q=64, blk_k=64, **kw)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,T,Hq,Hkv,D", [(3, 256, 4, 2, 32), (2, 128, 8, 8, 16),
                                          (2, 64, 4, 1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_vs_ref(rng, B, T, Hq, Hkv, D, dtype):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, T, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, T, D), dtype)
    lengths = jnp.asarray([T, max(T // 3, 1), 7][:B], jnp.int32)
    ref = decode_attention_reference(q, k, v, lengths)
    out = decode_attention_pallas(q, k, v, lengths, blk_t=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_decode_attention_window(rng):
    ks = jax.random.split(rng, 3)
    B, T, Hq, Hkv, D = 2, 128, 4, 2, 16
    q = jax.random.normal(ks[0], (B, Hq, D))
    k = jax.random.normal(ks[1], (B, Hkv, T, D))
    v = jax.random.normal(ks[2], (B, Hkv, T, D))
    lengths = jnp.asarray([100, 33], jnp.int32)
    ref = decode_attention_reference(q, k, v, lengths, window=24)
    out = decode_attention_pallas(q, k, v, lengths, window=24, blk_t=32,
                                  interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("Hkv,G,lengths,window", [
    (1, 4, (1, 16, 320), None),       # MQA; one token, a page boundary, all
    (2, 2, (320, 17, 129), None),     # 129: one past a grid step's pages
    (4, 2, (0, 33, 200), None),       # an inactive slot
    (8, 5, (319, 1, 128), None),      # Qwen2.5's 40 / 8 heads
    (2, 3, (320, 150, 5), 40),        # sliding window across a grid step
], ids=["hkv1", "hkv2", "hkv4-inactive", "hkv8", "hkv2-window"])
def test_paged_attention_vs_ref(rng, Hkv, G, lengths, window):
    """Interpret-mode kernel vs the paged reference and vs dense attention
    over the gathered cache, at a layer other than the first; 20 page slots
    a slot, so the walk takes three grid steps, the last one partial."""
    B, NP, page, D, maxp, L, layer = 3, 64, 16, 32, 20, 3, 2
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, Hkv * G, D))
    k_pages = jax.random.normal(ks[1], (L, NP, page * Hkv, D))
    v_pages = jax.random.normal(ks[2], (L, NP, page * Hkv, D))
    page_table = jax.random.permutation(ks[3], NP)[:B * maxp].reshape(B, maxp)
    lengths = jnp.asarray(lengths, jnp.int32)
    ref = paged_decode_attention_reference(
        q, k_pages, v_pages, layer, page_table, lengths, page_size=page,
        window=window)
    out = paged_decode_attention_pallas(
        q, k_pages, v_pages, jnp.int32(layer), page_table, lengths,
        page_size=page, window=window, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    # row t * Hkv + h of page p is token t of head h: dense attention over
    # the pages the table names, unpacked one token at a time
    def dense(pages):
        g = np.asarray(pages[layer])[np.asarray(page_table)]
        return np.stack([g[:, :, h::Hkv].reshape(B, maxp * page, D)
                         for h in range(Hkv)], axis=1)

    want = decode_attention_reference(q, dense(k_pages), dense(v_pages),
                                      lengths, window=window)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(out)[~live], 0.0)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

def _ssd_inputs(key, B, S, H, P, N, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    Dm = jax.random.normal(ks[5], (H,))
    return x, dt, A, Bm, Cm, Dm


def _ssd_sequential(x, dt, A, Bm, Cm, Dm):
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    st = np.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        st = np.exp(np.asarray(dt[:, t]) * np.asarray(A))[..., None, None] * st \
            + np.einsum("bh,bhp,bn->bhpn", np.asarray(dt[:, t]),
                        np.asarray(x[:, t]), np.asarray(Bm[:, t]))
        ys.append(np.einsum("bhpn,bn->bhp", st, np.asarray(Cm[:, t]))
                  + np.asarray(Dm)[None, :, None] * np.asarray(x[:, t]))
    return np.stack(ys, 1), st


@pytest.mark.parametrize("B,S,H,P,N,chunk", [(2, 64, 3, 8, 16, 16),
                                             (1, 32, 2, 4, 8, 8),
                                             (2, 48, 4, 16, 16, 16)])
def test_ssd_ref_vs_sequential(rng, B, S, H, P, N, chunk):
    args = _ssd_inputs(rng, B, S, H, P, N)
    y, fs = ssd_scan_reference(*args, chunk=chunk)
    y_seq, fs_seq = _ssd_sequential(*args)
    np.testing.assert_allclose(y, y_seq, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(fs, fs_seq, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [(2, 64, 3, 8, 16, 16),
                                             (1, 32, 2, 4, 8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_vs_ref(rng, B, S, H, P, N, chunk, dtype):
    args = _ssd_inputs(rng, B, S, H, P, N, dtype)
    y_ref, fs_ref = ssd_scan_reference(*args, chunk=chunk)
    y, fs = ssd_scan_pallas(*args, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    np.testing.assert_allclose(fs, fs_ref, atol=TOL[dtype], rtol=TOL[dtype])


def test_ssd_decode_matches_scan(rng):
    B, S, H, P, N = 2, 16, 3, 8, 8
    x, dt, A, Bm, Cm, Dm = _ssd_inputs(rng, B, S, H, P, N)
    y_full, _ = ssd_scan_reference(x, dt, A, Bm, Cm, Dm, chunk=8)
    st = jnp.zeros((B, H, P, N))
    for t in range(S):
        y_t, st = ssd_decode_reference(x[:, t], dt[:, t], A, Bm[:, t],
                                       Cm[:, t], Dm, st)
        np.testing.assert_allclose(y_t, y_full[:, t], atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# RG-LRU linear scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,W,blk", [(2, 128, 64, 32), (1, 64, 16, 16),
                                       (3, 96, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linear_scan_pallas_vs_ref(rng, B, S, W, blk, dtype):
    ks = jax.random.split(rng, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))).astype(dtype)
    b = jax.random.normal(ks[1], (B, S, W), dtype)
    h_ref, hl_ref = linear_scan_reference(a, b)
    h, hl = linear_scan_pallas(a, b, blk=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h_ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    np.testing.assert_allclose(hl, hl_ref, atol=TOL[dtype], rtol=TOL[dtype])


def test_linear_scan_vs_sequential(rng):
    B, S, W = 2, 33, 8
    ks = jax.random.split(rng, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W)))
    b = jax.random.normal(ks[1], (B, S, W))
    h, hl = linear_scan_reference(a, b)
    hs = np.zeros((B, W))
    for t in range(S):
        hs = np.asarray(a[:, t]) * hs + np.asarray(b[:, t])
        np.testing.assert_allclose(h[:, t], hs, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(hl, hs, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Scan ops under a mesh: Mosaic kernels cannot be partitioned by GSPMD, so
# the ops run them per shard (shard_map over logical axes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["ssd_scan", "linear_scan"])
def test_scan_op_per_shard_under_mesh(rng, monkeypatch, op):
    from repro.distributed.sharding import ShardingCtx
    from repro.kernels.rglru_scan import linear_scan
    from repro.kernels.ssd_scan import ssd_scan
    from repro.launch.mesh import make_mesh

    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    if op == "ssd_scan":
        args = _ssd_inputs(rng, 2, 32, 4, 8, 16)
        fn, ref = (lambda *a: ssd_scan(*a, chunk=8),
                   lambda *a: ssd_scan_reference(*a, chunk=8))
    else:
        ks = jax.random.split(rng, 2)
        args = (jax.nn.sigmoid(jax.random.normal(ks[0], (2, 64, 16))),
                jax.random.normal(ks[1], (2, 64, 16)))
        fn, ref = linear_scan, linear_scan_reference
    with ShardingCtx(make_mesh((1, 1), ("data", "model"))):
        out = jax.jit(fn)(*args)
    for got, want in zip(out, ref(*args)):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
