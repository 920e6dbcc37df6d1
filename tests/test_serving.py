"""Serving engine: paged KV on the balanced allocator, continuous batching."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS
from repro.core import events
from repro.models import build_model
from repro.serving import engine as engine_mod
from repro.serving import kvcache
from repro.serving.engine import ServingEngine


@pytest.fixture(scope="module")
def dense_model():
    cfg = CONFIGS["llama3.2-3b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _greedy_reference(model, params, prompt, max_new):
    cache, _ = model.init_cache(1, 128)
    for t in prompt[:-1]:
        _, cache = model.decode_step(params, cache,
                                     jnp.asarray([t], jnp.int32))
    out, cur = [], prompt[-1]
    for _ in range(max_new):
        lg, cache = model.decode_step(params, cache,
                                      jnp.asarray([cur], jnp.int32))
        cur = int(jnp.argmax(lg[0]))
        out.append(cur)
    return out


def test_engine_matches_reference_decode(dense_model):
    cfg, model, params = dense_model
    prompt = [5, 17, 42, 7]
    ref = _greedy_reference(model, params, prompt, 6)
    eng = ServingEngine(model, params, batch_slots=3, max_len=64, page_size=8)
    r1 = eng.submit(prompt, max_new=6)
    r2 = eng.submit([9, 3], max_new=4)
    res = eng.run_until_drained()
    assert res[r1] == ref
    assert len(res[r2]) == 4


def test_engine_slot_reuse_is_clean(dense_model):
    """A released slot must not leak KV into the next request (O(1) chunk
    reclaim must actually reset visibility)."""
    cfg, model, params = dense_model
    prompt = [11, 23, 4]
    ref = _greedy_reference(model, params, prompt, 5)
    eng = ServingEngine(model, params, batch_slots=1, max_len=64, page_size=8)
    a = eng.submit([7, 7, 7, 7, 7], max_new=3)     # dirties slot 0
    b = eng.submit(prompt, max_new=5)               # reuses slot 0
    res = eng.run_until_drained()
    assert res[b] == ref


def test_engine_spill_sink_receives_page_ids(dense_model):
    """Host-side page spill (transport v3): every retiring request ships
    its page-id list as ONE batched payload RPC before its slot is
    released — ids are the slot's live page-table prefix, distinct, and
    consistent with the request's token count."""
    cfg, model, params = dense_model
    spilled = []

    def sink(rid, n_tokens, pages):
        spilled.append((int(rid), int(n_tokens), pages.tolist()))

    eng = ServingEngine(model, params, batch_slots=2, max_len=64,
                        page_size=8, spill_sink=sink)
    r1 = eng.submit([5, 17, 42, 7], max_new=6)
    r2 = eng.submit([9, 3], max_new=13)
    res = eng.run_until_drained()
    assert len(res) == 2 and len(spilled) == 2
    by_rid = {rid: (n, pages) for rid, n, pages in spilled}
    assert set(by_rid) == {r1, r2}
    for rid, (n_tokens, pages) in by_rid.items():
        # one page per started page_size window, all ids distinct
        assert len(pages) == -(-n_tokens // 8)
        assert len(set(pages)) == len(pages)
    # cache holds prompt + generated - 1 tokens (the final sampled token is
    # harvested without ever being fed back)
    # r1: 4 prompt + 6 generated -> 9 written tokens -> 2 pages of 8
    assert by_rid[r1][0] == 9 and len(by_rid[r1][1]) == 2
    # r2: 2 prompt + 13 generated -> 14 written tokens -> 2 pages
    assert by_rid[r2][0] == 14 and len(by_rid[r2][1]) == 2
    # v4: every spill is ACKED through the reply arena — the sink returned
    # None, so the ack defaults to the page count it was handed
    assert eng.spill_acks == {rid: len(pages)
                              for rid, (_, pages) in by_rid.items()}


def test_engine_spill_ack_carries_sink_return(dense_model):
    """A spill sink that RETURNS a value sees that value come back as the
    ack (the reply arena round-trip through the engine's flush)."""
    cfg, model, params = dense_model

    def sink(rid, n_tokens, pages):
        return 1000 + int(rid)

    eng = ServingEngine(model, params, batch_slots=1, max_len=32,
                        page_size=8, spill_sink=sink)
    r1 = eng.submit([4, 2], max_new=3)
    eng.run_until_drained()
    assert eng.spill_acks == {r1: 1000 + r1}

    # a sink written against the pre-ack contract may return non-scalars
    # (here: the page list itself) — the flush must not crash, and the ack
    # is the drain's 1-word coercion (first element)
    spilled = []

    def page_sink(rid, n_tokens, pages):
        spilled.append(pages.tolist())
        return pages

    eng2 = ServingEngine(model, params, batch_slots=1, max_len=32,
                         page_size=8, spill_sink=page_sink)
    r2 = eng2.submit([4, 2], max_new=3)
    eng2.run_until_drained()
    assert spilled and eng2.spill_acks == {r2: spilled[0][0]}


def test_engine_spill_flaky_sink_is_retried(dense_model):
    """PR 9: a sink that fails its first delivery is re-enqueued in a
    fresh epoch (application-level retry, spill_retries rounds) — the
    ack still lands and nothing degrades."""
    cfg, model, params = dense_model
    calls = {}

    def flaky(rid, n_tokens, pages):
        calls[int(rid)] = calls.get(int(rid), 0) + 1
        if calls[int(rid)] == 1:
            raise RuntimeError("transient spill-store hiccup")
        return int(rid) + 500

    import warnings
    eng = ServingEngine(model, params, batch_slots=1, max_len=32,
                        page_size=8, spill_sink=flaky, spill_retries=2)
    r1 = eng.submit([4, 2], max_new=3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        eng.run_until_drained()
    assert calls[r1] == 2
    assert eng.spill_acks == {r1: r1 + 500}
    assert eng.recompute_on_readmit == set()


def test_engine_spill_dead_sink_degrades_to_recompute(dense_model):
    """PR 9: a sink that fails EVERY attempt exhausts the retry budget —
    the engine records the failed ack as None, marks the request for
    recompute-on-readmit, and the tick completes (no wedge, no raise)."""
    cfg, model, params = dense_model

    def dead(rid, n_tokens, pages):
        raise RuntimeError("spill store down")

    import warnings
    eng = ServingEngine(model, params, batch_slots=1, max_len=32,
                        page_size=8, spill_sink=dead, spill_retries=1)
    r1 = eng.submit([4, 2], max_new=3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        res = eng.run_until_drained()
    assert len(res[r1]) == 3               # decode itself unaffected
    assert eng.spill_acks == {r1: None}
    assert eng.recompute_on_readmit == {r1}


def test_engine_spill_disabled_by_default(dense_model):
    cfg, model, params = dense_model
    eng = ServingEngine(model, params, batch_slots=1, max_len=32,
                        page_size=8)
    assert eng.spill_q is None
    eng.submit([3, 1], max_new=2)
    eng.run_until_drained()        # no spill machinery touched


def test_engine_mixed_lengths_continuous_batching(dense_model):
    cfg, model, params = dense_model
    eng = ServingEngine(model, params, batch_slots=2, max_len=64, page_size=8)
    rids, refs = [], []
    for i, (prompt, n) in enumerate([([3, 1], 7), ([9, 9, 9, 2], 3),
                                     ([5], 5), ([8, 2, 4], 6)]):
        rids.append(eng.submit(prompt, max_new=n))
        refs.append(_greedy_reference(model, params, prompt, n))
    res = eng.run_until_drained()
    for rid, ref in zip(rids, refs):
        assert res[rid] == ref, rid


#: mixed prompt and output lengths; the first four prompts (>= 3 tokens)
#: fill every slot, so the first ticks have no slot reading a token
_MIXED = [([4, 8, 15], 2), ([16, 23, 42, 3], 4), ([7, 1, 9], 3),
          ([2, 6, 5, 3, 1], 2), ([11], 5), ([12, 14], 1), ([19, 5, 2], 3)]


def test_engine_one_host_transfer_per_decoding_tick(dense_model):
    """Each tick moves every slot's next token to the host in one transfer
    when some slot decodes, and in none when every slot is still feeding
    its prompt; ``engine.host_reads`` still counts one per decoding slot."""
    cfg, model, params = dense_model
    eng = ServingEngine(model, params, batch_slots=4, max_len=64,
                        page_size=8)
    for prompt, n in _MIXED:
        eng.submit(prompt, max_new=n)
    seen = set()
    while eng.queue or any(s.request_id >= 0 for s in eng.slots):
        free = sum(1 for s in eng.slots if s.request_id < 0)
        decoding = sum(1 for s in eng.slots if s.request_id >= 0
                       and s.fed + 1 >= len(s.prompt))
        decoding += sum(1 for _, p, _ in eng.queue[:free] if len(p) <= 1)
        with events.recording() as rec:
            eng.step()
        assert rec.deltas.get("engine.host_transfers", 0) == \
            (1 if decoding else 0)
        assert rec.deltas.get("engine.host_reads", 0) == decoding
        seen.add(bool(decoding))
    assert seen == {False, True} and len(eng.finished) == len(_MIXED)


class _PerSlotReads:
    """The per-slot harvest: each decoding slot's token read to the host
    alone, as an eager slice and its own transfer."""

    def __init__(self, nxt):
        self.nxt = nxt

    def __getitem__(self, i):
        return int(self.nxt[i])


@pytest.mark.parametrize("mesh", [None, 2])
def test_engine_batched_harvest_matches_per_slot_reads(dense_model,
                                                       monkeypatch, mesh):
    """The one-transfer harvest serves, token for token, what per-slot
    reads serve on the same submissions, and every token is a Python int."""
    cfg, model, params = dense_model
    results = []
    for read in (None, _PerSlotReads):
        if read is not None:
            monkeypatch.setattr(engine_mod, "_read_tokens", read)
        eng = ServingEngine(model, params, batch_slots=4, max_len=64,
                            page_size=8, mesh=mesh)
        rids = [eng.submit(p, max_new=n) for p, n in _MIXED]
        res = eng.run_until_drained()
        results.append([res[r] for r in rids])
    assert results[0] == results[1]
    assert [len(out) for out in results[0]] == [n for _, n in _MIXED]
    assert all(type(t) is int for out in results[0] for t in out)


def test_engine_sharded_page_heap_matches(dense_model):
    """ISSUE 3: an engine on the sharded page heap (mesh=2: one heap shard
    per device block of slots) decodes exactly what the single-heap engine
    decodes — page ids move, token streams don't."""
    from repro.core.allocator import ShardedHeap
    cfg, model, params = dense_model
    prompts = [([5, 17, 42, 7], 6), ([9, 3], 4)]
    engines = [
        ServingEngine(model, params, batch_slots=2, max_len=64, page_size=8),
        ServingEngine(model, params, batch_slots=2, max_len=64, page_size=8,
                      mesh=2),
    ]
    assert isinstance(engines[1].kv.alloc, ShardedHeap)
    results = []
    for eng in engines:
        rids = [eng.submit(p, max_new=n) for p, n in prompts]
        res = eng.run_until_drained()
        results.append([res[r] for r in rids])
    assert results[0] == results[1]


def test_paged_cache_allocator_lifecycle(dense_model):
    cfg, _, _ = dense_model
    kv = kvcache.paged_cache_init(cfg, batch_slots=2, max_len=64, page_size=8)
    active = jnp.asarray([True, True])
    # first token allocates page 0 of each slot's chunk
    kv = kvcache.ensure_pages(kv, active)
    assert int(kv.alloc.count[0]) == 1 and int(kv.alloc.count[1]) == 1
    # advancing within a page allocates nothing
    kv = kvcache.advance(kv, active)
    kv = kvcache.ensure_pages(kv, active)
    assert int(kv.alloc.count[0]) == 1
    # crossing the boundary allocates one more
    for _ in range(7):
        kv = kvcache.advance(kv, active)
    kv = kvcache.ensure_pages(kv, active)
    assert int(kv.alloc.count[0]) == 2
    # release reclaims the whole chunk in O(1)
    kv = kvcache.release_slot(kv, 0)
    assert int(kv.alloc.count[0]) == 0 and int(kv.alloc.watermark[0]) == 0
    assert int(kv.lengths[0]) == 0
