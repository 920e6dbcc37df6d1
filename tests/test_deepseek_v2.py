"""DeepSeek-V2's mechanisms at a tiny size on the CPU: group-limited routing,
the expert layer that holds a share of the experts, absorbed against naive
MLA, the latent paged kernel, and the engine's decode through the latent
cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.latent_attention.kernel import (
    latent_paged_decode_attention_pallas)
from repro.kernels.latent_attention.ref import (
    latent_paged_decode_attention_reference)
from repro.models import mla, moe
from repro.models.model_zoo import build_model
from repro.serving.engine import ServingEngine

CFG = get_config("deepseek-v2").reduced()        # 8 experts in 4 groups


def _tiny(**kw):
    return dataclasses.replace(CFG, **kw)


def _np_route(x, router, cfg):
    """NumPy group-limited greedy routing: (weights (T, E), ids (T, k))."""
    logits = x.astype(np.float64) @ router.astype(np.float64)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    scores = e / e.sum(-1, keepdims=True)
    T, E = scores.shape
    G = cfg.n_group
    best = scores.reshape(T, G, E // G).max(-1)
    groups = np.argsort(-best, axis=1, kind="stable")[:, :cfg.topk_group]
    keep = np.zeros((T, E), bool)
    for t in range(T):
        for g in groups[t]:
            keep[t, g * (E // G):(g + 1) * (E // G)] = True
    masked = np.where(keep, scores, 0.0)
    ids = np.argsort(-masked, axis=1, kind="stable")[:, :cfg.experts_per_token]
    w = np.take_along_axis(masked, ids, 1)
    if cfg.norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    dense = np.zeros((T, E))
    np.put_along_axis(dense, ids, w * cfg.routed_scaling_factor, 1)
    return dense, ids


@pytest.mark.parametrize("norm", [False, True])
def test_group_limited_routing_matches_numpy(norm):
    cfg = _tiny(num_experts=16, n_group=4, topk_group=2, experts_per_token=3,
                norm_topk_prob=norm)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (64, cfg.d_model))
    router = jax.random.normal(k2, (cfg.d_model, 16)) * 0.3
    w, _ = moe.group_limited_route(x, router, cfg)
    want, ids = _np_route(np.asarray(x), np.asarray(router), cfg)
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-5, atol=1e-6)
    # every chosen expert lies in one of the token's kept groups
    groups = ids // (16 // cfg.n_group)
    assert all(len(set(row)) <= cfg.topk_group for row in groups)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Over the 8 group shares of the routed experts (one routing group
    each, as each chip of DeepSeek-V2's 8-way expert parallelism holds),
    what each share's layer gives adds up to the layer that holds them all,
    with the shared experts (computed alike by every share) counted once."""
    shares, per = 8, 2
    cfg = _tiny(num_experts=shares * per, n_group=shares, topk_group=3,
                experts_per_token=4)
    p = moe.moe_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model))
    whole, _ = moe.moe_share_apply(p, x, cfg)
    shared = moe.mlp_apply(p["shared"], x)
    total = -(shares - 1) * shared
    for g in range(shares):
        part = dataclasses.replace(cfg, experts_held=per,
                                   first_expert=per * g)
        held = dict(p)
        for name in ("wi_gate", "wi_up", "wo"):
            held[name] = dataclasses.replace(
                p[name], value=p[name].value[per * g:per * (g + 1)])
        y, _ = moe.moe_share_apply(held, x, part)
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


def test_absorbed_decode_equals_naive_mla():
    cfg = CFG
    p = mla.mla_init(jax.random.PRNGKey(3), cfg)
    S = 9
    x = jax.random.normal(jax.random.PRNGKey(4), (2, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (2, S))
    naive = mla.mla_apply(p, x, cfg, angles=mla.rope_angles(pos, cfg))
    cache = jnp.zeros((2, S + 3, cfg.latent_width))
    for t in range(S):
        lengths = jnp.full((2,), t, jnp.int32)
        out, cache = mla.mla_decode(
            p, x[:, t:t + 1], cfg, cache=cache, lengths=lengths,
            angles=mla.rope_angles(lengths[:, None], cfg))
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(naive[:, t]),
                                   rtol=1e-4, atol=1e-5)


def test_yarn_frequencies_and_scale():
    cfg = get_config("deepseek-v2")
    inv = mla.yarn_inv_freq(cfg)
    base = 1e4 ** -(np.arange(0, 64, 2) / 64)
    # dims below the high-frequency correction dim keep their frequency,
    # those above the low one are divided by the factor, a ramp between
    assert np.allclose(inv[:10], base[:10], rtol=1e-6)
    assert np.allclose(inv[-8:], base[-8:] / 40, rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    m = 0.1 * 0.707 * np.log(40) + 1
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert mla.rope_scale(cfg) == 1.0


@pytest.mark.parametrize("lengths", [[1, 17, 40, 160], [16, 33, 128, 2]])
def test_latent_kernel_matches_reference(lengths):
    L, B, H, W, R, page, maxp = 2, 4, 8, 256, 128, 16, 10
    NP = B * maxp
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(k[0], (B, H, W), jnp.float32)
    pages = jax.random.normal(k[1], (L, NP, page, W), jnp.float32)
    table = jax.random.permutation(k[2], NP).reshape(B, maxp)
    lens = jnp.asarray(lengths, jnp.int32)
    for layer in range(L):
        got = latent_paged_decode_attention_pallas(
            q, pages, jnp.int32(layer), table, lens, rank=R, scale=0.1,
            interpret=True)
        want = latent_paged_decode_attention_reference(
            q, pages, layer, table, lens, rank=R, scale=0.1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


def _engine_and_model(held=4, first=4):
    cfg = _tiny(experts_held=held, first_expert=first)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(6))
    return cfg, model, params


PROMPTS = [[5, 17, 30, 2, 9], [44, 101, 3], list(range(1, 20)), [7]]


def test_engine_latent_decode_matches_forward():
    """Prompts fed a token a tick, then greedy decoding through the latent
    pages: every served token is the one the full forward pass puts first."""
    cfg, model, params = _engine_and_model()
    eng = ServingEngine(model, params, batch_slots=4, max_len=64, page_size=16)
    assert eng.kv.is_latent and eng.kv.k_pages is None
    for p in PROMPTS:
        eng.submit(p, max_new=6)
    fin = eng.run_until_drained()
    for rid, p in enumerate(PROMPTS):
        seq = p + fin[rid][:-1]
        logits, _ = model.forward(params, {"tokens": jnp.asarray([seq])})
        want = np.argmax(np.asarray(logits[0, len(p) - 1:]), -1)
        assert fin[rid] == want.tolist()


def test_latent_engine_artifact_round_trip(tmp_path):
    """The exported step carries the latent cache kind: a cold-started
    engine serves the same tokens."""
    cfg, model, params = _engine_and_model()
    eng = ServingEngine(model, params, batch_slots=4, max_len=64, page_size=16)
    for p in PROMPTS:
        eng.submit(p, max_new=4)
    want = eng.run_until_drained()
    eng2 = ServingEngine(model, params, batch_slots=4, max_len=64,
                         page_size=16)
    eng2.export_artifact(str(tmp_path))
    cold = ServingEngine.from_artifact(str(tmp_path), cfg)
    assert cold.kv.is_latent
    for p in PROMPTS:
        cold.submit(p, max_new=4)
    assert cold.run_until_drained() == want
