"""Ring KV-cache + decode-step batching (inference/kv_cache.py): slot
admission/eviction under the deadline-aware gate, ONE compiled step
shared across in-flight sequences of different lengths, per-slot
bitwise isolation (no cross-sequence bleed), and ring-wraparound
sliding-window attention. Synchronization is via condition waits and
observable counters — never bare sleeps."""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.inference.kv_cache import DecodeStepBatcher, RingKVCache

SLOTS, MAX_LEN, HEADS, DIM = 3, 8, 1, 4
VOCAB, EMBED = 11, HEADS * DIM


def _toy_weights(seed=7):
    rng = np.random.RandomState(seed)
    return {
        "E": rng.randn(VOCAB, EMBED).astype("float32"),
        "Wq": rng.randn(EMBED, EMBED).astype("float32"),
        "Wk": rng.randn(EMBED, EMBED).astype("float32"),
        "Wv": rng.randn(EMBED, EMBED).astype("float32"),
        "Wo": rng.randn(EMBED, VOCAB).astype("float32"),
    }


def _make_step(max_len, trace_counter=None, seed=7):
    """A complete masked ring-attention decode step over the full slot
    axis: embed the token, append K/V at the ring position (writes
    gated on active_mask), attend over the valid window, project to
    logits. Lengths and the mask are DATA — shapes never change.
    Distinct `seed`s yield distinct model weights (the multi-model
    shared-pool tests drive two of these over one PagedKVCache)."""
    w = {k: jnp.asarray(v) for k, v in _toy_weights(seed).items()}

    def step(tokens, k, v, lengths, active_mask):
        if trace_counter is not None:
            trace_counter.append(1)  # runs at TRACE time only
        S, L = k.shape[0], k.shape[1]
        x = w["E"][tokens]  # [S, E]
        q = (x @ w["Wq"]).reshape(S, HEADS, DIM)
        k_t = (x @ w["Wk"]).reshape(S, HEADS, DIM)
        v_t = (x @ w["Wv"]).reshape(S, HEADS, DIM)
        pos = lengths % L  # ring write position per slot
        gate = active_mask[:, None, None]
        rows = jnp.arange(S)
        k = k.at[rows, pos].set(jnp.where(gate, k_t, k[rows, pos]))
        v = v.at[rows, pos].set(jnp.where(gate, v_t, v[rows, pos]))
        # valid ring positions AFTER this append: min(length+1, L)
        valid = jnp.minimum(lengths + 1, L)  # [S]
        scores = jnp.einsum("shd,slhd->shl", q, k) / np.sqrt(DIM)
        col = jnp.arange(L)[None, None, :]
        scores = jnp.where(col < valid[:, None, None], scores, -jnp.inf)
        attn = jnp.exp(scores - scores.max(-1, keepdims=True))
        attn = attn / attn.sum(-1, keepdims=True)
        ctx = jnp.einsum("shl,slhd->shd", attn, v).reshape(S, EMBED)
        logits = ctx @ w["Wo"]
        return logits, k, v

    return step


def _decode(cache, batcher, streams, steps):
    """Drive `steps` batched decode steps; `streams[slot]` yields the
    token fed to that slot each step. Returns {slot: [logits...]}."""
    outs = {s: [] for s in streams}
    for i in range(steps):
        tokens = np.zeros((cache.num_slots,), np.int32)
        for slot, toks in streams.items():
            tokens[slot] = toks[i]
        logits = batcher.step(tokens)
        for slot in streams:
            outs[slot].append(logits[slot].copy())
    return outs


# ------------------------------------------------------- admission gate


def test_slot_admission_eviction_and_counters():
    cache = RingKVCache(2, MAX_LEN, HEADS, DIM)
    a = cache.acquire("seq-a")
    b = cache.acquire("seq-b")
    assert {a, b} == {0, 1}
    c = cache.counters.snapshot()
    assert c["kv_slots_inflight"] == 2 and c["kv_slot_acquires"] == 2

    # full + nothing evictable + zero window -> immediate shed
    assert cache.acquire("seq-c") is None
    assert cache.counters.snapshot()["kv_admission_sheds"] == 1

    # a finished-but-resident sequence stays readable... until
    # admission pressure evicts the least-recently-finished one
    cache.mark_finished(a)
    assert cache.seq_id(a) == "seq-a"
    assert cache.counters.snapshot()["kv_slots_inflight"] == 1
    d = cache.acquire("seq-d")
    assert d == a  # evicted the LRU finished slot
    c = cache.counters.snapshot()
    assert c["kv_evictions"] == 1 and c["kv_slots_inflight"] == 2

    cache.release(b)
    cache.release(d)
    c = cache.counters.snapshot()
    assert c["kv_slot_releases"] == 2 and c["kv_slots_inflight"] == 0
    with pytest.raises(KeyError):
        cache.release(b)  # double-release is a caller bug, loudly


def test_admission_window_waits_for_release_and_deadline_sheds():
    """The coalescer's deadline-vs-window contract, on slot admission:
    a waiter inside its budget blocks until a release hands it the
    slot; a caller whose deadline cannot afford the window sheds
    immediately (counter-observable, no sleep-based sync)."""
    cache = RingKVCache(1, MAX_LEN, HEADS, DIM, admission_window_s=30.0)
    s0 = cache.acquire("holder")
    assert s0 == 0

    # deadline tighter than the window: immediate None, no 30 s wait
    t0 = time.monotonic()
    assert cache.acquire("tight", deadline=t0 + 0.05) is None
    assert cache.counters.snapshot()["kv_admission_sheds"] == 1
    assert time.monotonic() - t0 < 5.0  # never sat out the window

    got = {}

    def waiter():
        got["slot"] = cache.acquire("patient",
                                    deadline=time.monotonic() + 120.0)

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    # the waiter is parked on the admission condition; the release is
    # the synchronization event that wakes it
    deadline = time.monotonic() + 20.0
    while not cache._cv._waiters and time.monotonic() < deadline:
        time.sleep(0.005)
    cache.release(s0)
    t.join(timeout=20)
    assert got.get("slot") == 0
    assert cache.counters.snapshot()["kv_slots_inflight"] == 1


# ------------------------------------------- shared step, slot isolation


def test_one_compiled_step_shared_across_lengths_bitwise():
    """Sequences admitted at different times (so different lengths) all
    ride ONE traced executable, and each slot's logits are bitwise-
    identical to decoding that sequence alone — no cross-slot bleed,
    no per-length recompile."""
    rng = np.random.RandomState(3)
    toks = {s: rng.randint(0, VOCAB, 10).tolist() for s in range(3)}

    traces = []
    cache = RingKVCache(SLOTS, MAX_LEN, HEADS, DIM)
    batcher = DecodeStepBatcher(cache, _make_step(MAX_LEN, traces))

    # staggered admission: slot 0 decodes 2 steps alone, then slot 1
    # joins, then slot 2 — lengths stay skewed throughout
    s0 = cache.acquire("s0")
    out = {0: [], 1: [], 2: []}
    for i in range(2):
        step_out = batcher.step(
            np.array([toks[0][i], 0, 0], np.int32))
        out[0].append(step_out[s0].copy())
    s1 = cache.acquire("s1")
    for i in range(2):
        step_out = batcher.step(
            np.array([toks[0][2 + i], toks[1][i], 0], np.int32))
        out[0].append(step_out[s0].copy())
        out[1].append(step_out[s1].copy())
    s2 = cache.acquire("s2")
    for i in range(4):
        step_out = batcher.step(np.array(
            [toks[0][4 + i], toks[1][2 + i], toks[2][i]], np.int32))
        for sl, j in ((s0, 0), (s1, 1), (s2, 2)):
            out[j].append(step_out[sl].copy())
    assert list(cache.lengths) == [8, 6, 4]
    assert sum(traces) == 1, "admissions/length skew must not retrace"
    assert cache.counters.snapshot()["kv_decode_steps"] == 8

    # solo reference: same step function, fresh cache, one active slot
    for seq in range(3):
        ref_cache = RingKVCache(SLOTS, MAX_LEN, HEADS, DIM)
        ref_batcher = DecodeStepBatcher(ref_cache, _make_step(MAX_LEN))
        slot = ref_cache.acquire(f"ref-{seq}")
        n = len(out[seq])
        for i in range(n):
            tokens = np.zeros((SLOTS,), np.int32)
            tokens[slot] = toks[seq][i]
            logits = ref_batcher.step(tokens)
            np.testing.assert_array_equal(
                logits[slot], out[seq][i],
                err_msg=f"seq {seq} step {i}: batched decode diverged "
                        "from solo decode")


def test_finished_resident_slot_survives_neighbor_steps():
    """mark_finished freezes a slot's cache rows bit-for-bit while the
    other slots keep decoding over it (write gating on active_mask)."""
    cache = RingKVCache(2, MAX_LEN, HEADS, DIM)
    batcher = DecodeStepBatcher(cache, _make_step(MAX_LEN))
    a = cache.acquire("a")
    b = cache.acquire("b")
    rng = np.random.RandomState(0)
    for _ in range(3):
        batcher.step(rng.randint(0, VOCAB, 2).astype(np.int32))
    cache.mark_finished(a)
    k_frozen = np.asarray(cache.k[a]).copy()
    v_frozen = np.asarray(cache.v[a]).copy()
    len_frozen = int(cache.lengths[a])
    for _ in range(4):
        batcher.step(rng.randint(0, VOCAB, 2).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(cache.k[a]), k_frozen)
    np.testing.assert_array_equal(np.asarray(cache.v[a]), v_frozen)
    assert int(cache.lengths[a]) == len_frozen
    assert int(cache.lengths[b]) == 7
    cache.release(a)
    cache.release(b)


# ------------------------------------------------------ ring wraparound


def test_ring_wraparound_attends_over_sliding_window():
    """Past max_len the ring overwrites the oldest position: the step
    keeps attending over exactly max_len entries (all columns valid),
    and the stored K rows equal the projections of the LAST max_len
    tokens — verified against a host-side numpy replay."""
    short = 4
    cache = RingKVCache(1, short, HEADS, DIM)
    batcher = DecodeStepBatcher(cache, _make_step(short))
    slot = cache.acquire("w")
    rng = np.random.RandomState(5)
    toks = rng.randint(0, VOCAB, 7)
    for t in toks:
        batcher.step(np.array([t], np.int32))
    assert int(cache.lengths[slot]) == 7
    assert int(cache.valid_counts()[slot]) == short

    w = _toy_weights()
    k_rows = np.asarray(cache.k[slot]).reshape(short, EMBED)
    # after 7 appends into a 4-ring: position p holds the newest token
    # whose write position was p — tokens 4,5,6 wrapped onto 0,1,2
    expected_tok = [toks[4], toks[5], toks[6], toks[3]]
    for pos, tok in enumerate(expected_tok):
        np.testing.assert_allclose(
            k_rows[pos], w["E"][tok] @ w["Wk"], rtol=1e-5, atol=1e-5)


# -------------------------------------------- paged pool (round 19)


def _paged(num_pages=16, page_len=4, pages_per_seq=2, streams=3, **kw):
    from paddle_tpu.inference.kv_cache import PagedKVCache

    return PagedKVCache(num_pages, page_len, pages_per_seq, HEADS, DIM,
                        max_streams=streams, **kw)


def test_paged_decode_bitwise_equals_ring():
    """THE tentpole pin: the same step function driven through the
    paged pool (gather in table order -> step -> scatter the appended
    row back through the table) produces logits bitwise-equal to the
    ring cache, across staggered admission AND ring wraparound."""
    from paddle_tpu.inference.kv_cache import (PagedDecodeStepBatcher,
                                               PagedKVCache)

    rng = np.random.RandomState(11)
    toks = {s: rng.randint(0, VOCAB, 12).tolist() for s in range(3)}

    ring = RingKVCache(SLOTS, MAX_LEN, HEADS, DIM)
    ring_b = DecodeStepBatcher(ring, _make_step(MAX_LEN))
    paged = PagedKVCache(16, 4, MAX_LEN // 4, HEADS, DIM, max_streams=SLOTS)
    assert paged.max_len == MAX_LEN
    paged_b = PagedDecodeStepBatcher(paged, _make_step(MAX_LEN))

    rs = {0: ring.acquire("s0")}
    ps = {0: paged.acquire("s0", total_len=12)}
    # 12 > max_len 8: both caches wrap their rings mid-run
    for i in range(12):
        if i == 2:
            rs[1] = ring.acquire("s1")
            ps[1] = paged.acquire("s1", total_len=10)
        if i == 5:
            rs[2] = ring.acquire("s2")
            ps[2] = paged.acquire("s2", total_len=7)
        r_toks = np.zeros((SLOTS,), np.int32)
        p_toks = np.zeros((SLOTS,), np.int32)
        for seq, slot in rs.items():
            r_toks[slot] = toks[seq][i]
        for seq, slot in ps.items():
            p_toks[slot] = toks[seq][i]
        r_out = ring_b.step(r_toks)
        p_out = paged_b.step(p_toks)
        for seq in rs:
            np.testing.assert_array_equal(
                np.asarray(r_out[rs[seq]]), np.asarray(p_out[ps[seq]]),
                err_msg=f"seq {seq} step {i}: paged diverged from ring")
    assert list(paged.lengths[:3]) == list(ring.lengths)


def test_paged_admit_prefill_rows_matches_sequential_decode():
    """admit() placing chronological prefilled rows through the page
    table lands every row exactly where sequential decode would have
    written it — the property the prefill->decode handoff rests on."""
    from paddle_tpu.inference.kv_cache import (PagedDecodeStepBatcher,
                                               PagedKVCache)

    rng = np.random.RandomState(13)
    toks = rng.randint(0, VOCAB, 6)
    w = _toy_weights()

    # sequential: feed all 6 tokens one at a time
    seq_cache = PagedKVCache(8, 4, 2, HEADS, DIM, max_streams=2)
    seq_b = PagedDecodeStepBatcher(seq_cache, _make_step(8))
    slot = seq_cache.acquire("seq", total_len=8)
    for t in toks:
        m = np.zeros((2,), bool)
        m[slot] = True
        seq_b.step(np.array([t, 0], np.int32), mask=m)

    # admitted: project the first 5 rows host-side, admit, then decode
    # one step with token 5 — cache contents must match bitwise
    x = w["E"][toks[:5]]
    k_rows = (x @ w["Wk"]).reshape(5, HEADS, DIM)
    v_rows = (x @ w["Wv"]).reshape(5, HEADS, DIM)
    adm_cache = PagedKVCache(8, 4, 2, HEADS, DIM, max_streams=2)
    adm_b = PagedDecodeStepBatcher(adm_cache, _make_step(8))
    slot2 = adm_cache.acquire("adm", total_len=8)
    adm_cache.admit(slot2, k_rows, v_rows, 5)
    m = np.zeros((2,), bool)
    m[slot2] = True
    adm_b.step(np.array([toks[5], 0], np.int32), mask=m)

    sk, sv = seq_cache.gather(slot)
    ak, av = adm_cache.gather(slot2)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(ak),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sv), np.asarray(av),
                               rtol=1e-5, atol=1e-6)
    assert int(seq_cache.lengths[slot]) == int(adm_cache.lengths[slot2])


def test_paged_capacity_eviction_and_counters():
    """Page-granular admission: short streams reserve ceil(len/page_len)
    pages, not a whole max_len slot — the pool admits where the ring
    sheds; LRU-finished residents are evicted page-by-page under
    pressure and the gauges track pool occupancy."""
    cache = _paged(num_pages=4, page_len=4, pages_per_seq=2, streams=8)
    # 4 pages / total_len 4 -> 1 page each: four short streams fit
    slots = [cache.acquire(f"s{i}", total_len=4) for i in range(4)]
    assert None not in slots
    assert cache.free_pages() == 0
    c = cache.counters.snapshot()
    assert c["kv_pages_in_use"] == 4 and c["kv_page_allocs"] == 4

    # full + nothing finished -> shed
    assert cache.acquire("s4", total_len=4) is None
    assert cache.counters.snapshot()["kv_admission_sheds"] == 1

    # finishing one stream makes its page reclaimable: the next
    # admission evicts the LRU finished resident
    cache.mark_finished(slots[1])
    s5 = cache.acquire("s5", total_len=4)
    assert s5 is not None
    c = cache.counters.snapshot()
    assert c["kv_page_evictions"] == 1 and c["kv_evictions"] == 1
    assert c["kv_pages_in_use"] == 4

    # a 2-page request under 1 free page: evict as many LRU-finished
    # residents as it takes
    cache.mark_finished(slots[0])
    cache.mark_finished(slots[2])
    s6 = cache.acquire("s6", total_len=8)
    assert s6 is not None
    assert cache.counters.snapshot()["kv_page_evictions"] == 3
    for s in (slots[3], s5, s6):
        cache.release(s)
    c = cache.counters.snapshot()
    assert c["kv_pages_in_use"] == 0 and cache.free_pages() == 4
    with pytest.raises(KeyError):
        cache.release(s6)


def test_paged_release_then_reacquire_bitwise_isolation():
    """A page freed by one stream and reallocated to another must not
    leak the old rows: the new owner's gather sees only its own
    writes (acquire zeroes the reserved pages)."""
    from paddle_tpu.inference.kv_cache import PagedDecodeStepBatcher

    cache = _paged(num_pages=2, page_len=4, pages_per_seq=1, streams=2)
    b = PagedDecodeStepBatcher(cache, _make_step(4))
    a = cache.acquire("a", total_len=4)
    rng = np.random.RandomState(2)
    for t in rng.randint(0, VOCAB, 3):
        m = np.zeros((2,), bool)
        m[a] = True
        b.step(np.array([t, 0], np.int32)
               if a == 0 else np.array([0, t], np.int32), mask=m)
    cache.release(a)
    a2 = cache.acquire("a2", total_len=4)
    k2, v2 = cache.gather(a2)
    assert not np.asarray(k2).any() and not np.asarray(v2).any()


# ------------------------------------- ring slot lifecycle edges (r19)


def test_ring_release_then_reacquire_bitwise_isolation():
    """A released ring slot handed to a new sequence starts from
    zeroed rows and length 0 — no bleed from the previous resident."""
    cache = RingKVCache(1, MAX_LEN, HEADS, DIM)
    batcher = DecodeStepBatcher(cache, _make_step(MAX_LEN))
    a = cache.acquire("first")
    rng = np.random.RandomState(4)
    for t in rng.randint(0, VOCAB, 5):
        batcher.step(np.array([t], np.int32))
    assert np.asarray(cache.k[a]).any()
    cache.release(a)
    a2 = cache.acquire("second")
    assert a2 == a
    assert int(cache.lengths[a2]) == 0
    assert not np.asarray(cache.k[a2]).any()
    assert not np.asarray(cache.v[a2]).any()
    # and the reborn slot decodes bitwise-equal to a fresh cache
    out = batcher.step(np.array([3], np.int32))
    ref_cache = RingKVCache(1, MAX_LEN, HEADS, DIM)
    ref_b = DecodeStepBatcher(ref_cache, _make_step(MAX_LEN))
    ref_cache.acquire("ref")
    ref = ref_b.step(np.array([3], np.int32))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_ring_mark_finished_under_full_ring():
    """mark_finished on a slot whose ring already wrapped keeps it
    readable (seq_id, frozen rows) and reclaimable — the full-ring
    state must not wedge the finished-LRU bookkeeping."""
    short = 4
    cache = RingKVCache(1, short, HEADS, DIM)
    batcher = DecodeStepBatcher(cache, _make_step(short))
    a = cache.acquire("wrapped")
    rng = np.random.RandomState(6)
    for t in rng.randint(0, VOCAB, 6):  # 6 > max_len: wrapped
        batcher.step(np.array([t], np.int32))
    assert int(cache.lengths[a]) == 6
    cache.mark_finished(a)
    assert cache.seq_id(a) == "wrapped"
    assert int(cache.valid_counts()[a]) == short
    frozen = np.asarray(cache.k[a]).copy()
    # admission pressure evicts it; the new resident starts clean
    b = cache.acquire("next")
    assert b == a
    assert cache.counters.snapshot()["kv_evictions"] == 1
    assert int(cache.lengths[b]) == 0
    assert not np.asarray(cache.k[b]).any()
    del frozen
    cache.release(b)


def test_ring_deadline_expired_acquire_sheds_immediately():
    """An acquire whose deadline has ALREADY passed never blocks on the
    admission window, even when a release could eventually serve it."""
    cache = RingKVCache(1, MAX_LEN, HEADS, DIM, admission_window_s=30.0)
    cache.acquire("holder")
    t0 = time.monotonic()
    assert cache.acquire("late", deadline=t0 - 1.0) is None
    assert time.monotonic() - t0 < 5.0
    assert cache.counters.snapshot()["kv_admission_sheds"] == 1


# -------------------------------------------- multi-model shared pool


def _interleave(pools, toks, probe=None):
    """Drive the fixed two-model admission/eviction/decode schedule
    against whichever models are present in ``pools`` ({tag: (pool,
    batcher)}). Streams of absent models are skipped, so the SAME
    script yields both the shared run (two models, one pool) and the
    solo references (each model alone on a private pool of half the
    pages). ``probe`` fires at the fully-subscribed point. Returns
    {stream: [per-step logits]}."""
    slots, outs = {}, {}

    def tag_of(name):
        return "A" if name.startswith("a") else "B"

    def acq(name, total_len):
        if tag_of(name) not in pools:
            return
        pool, _ = pools[tag_of(name)]
        s = pool.acquire(name, total_len=total_len)
        assert s is not None
        slots[name] = s
        outs[name] = []

    def step(tag, feed):  # feed: {stream name: token}
        if tag not in pools:
            return
        pool, batcher = pools[tag]
        tokens = np.zeros((pool.max_streams,), np.int32)
        mask = np.zeros((pool.max_streams,), bool)
        for name, tok in feed.items():
            tokens[slots[name]] = tok
            mask[slots[name]] = True
        logits = batcher.step(tokens, mask=mask)
        for name in feed:
            outs[name].append(logits[slots[name]].copy())

    def fin(name):
        if tag_of(name) in pools:
            pools[tag_of(name)][0].mark_finished(slots[name])

    acq("a0", 8), acq("b0", 8)  # 2 pages each
    for i in range(4):
        step("A", {"a0": toks["a0"][i]})
        step("B", {"b0": toks["b0"][i]})
    acq("a1", 4), acq("b1", 4)  # 1 page each: pool fully subscribed
    if probe is not None:
        probe()
    for i in range(4):
        step("A", {"a0": toks["a0"][4 + i], "a1": toks["a1"][i]})
        step("B", {"b0": toks["b0"][4 + i], "b1": toks["b1"][i]})
    fin("a0"), fin("b0")
    # under full-pool pressure each admission evicts the LRU finished
    # resident — B lands on the pages (and slot) model A just vacated,
    # then A takes B's: cross-model page handoff in both directions
    acq("b2", 8), acq("a2", 8)
    for i in range(4):
        step("A", {"a2": toks["a2"][i]})
        step("B", {"b2": toks["b2"][i]})
    for name in ("a1", "a2", "b1", "b2"):  # a0/b0 went by eviction
        if tag_of(name) in pools:
            pools[tag_of(name)][0].release(slots[name])
    return outs


def test_paged_pool_shared_across_models_bitwise_and_accounting():
    """ONE PagedKVCache pool serves TWO models (distinct-weight step
    fns, one batcher each) with interleaved admissions, decode steps
    and pressure evictions — the multi-model registry's shared-pool
    contract. Every stream's logits are bitwise-identical to a solo
    run of its model on a private pool (slot isolation: the other
    model's traffic, including cross-model reuse of evicted pages and
    the shared scratch page, perturbs nothing), and page/stream
    accounting returns to baseline once the streams drain."""
    rng = np.random.RandomState(21)
    toks = {n: rng.randint(0, VOCAB, size=8 if n.endswith("0") else 4)
            for n in ("a0", "b0", "a1", "b1", "a2", "b2")}

    from paddle_tpu.inference.kv_cache import PagedDecodeStepBatcher

    shared = _paged(num_pages=6, streams=4)
    pools = {
        "A": (shared, PagedDecodeStepBatcher(shared, _make_step(MAX_LEN))),
        "B": (shared, PagedDecodeStepBatcher(shared,
                                             _make_step(MAX_LEN, seed=11))),
    }

    def probe():  # both models admitted: pool fully subscribed
        assert shared.free_pages() == 0
        assert shared.counters.snapshot()["kv_pages_in_use"] == 6

    outs = _interleave(pools, toks, probe=probe)

    c = shared.counters.snapshot()
    assert shared.free_pages() == 6
    assert c["kv_pages_in_use"] == 0 and c["kv_slots_inflight"] == 0
    assert c["kv_slot_acquires"] == 6 and c["kv_slot_releases"] == 4
    assert c["kv_evictions"] == 2 and c["kv_page_evictions"] == 4
    assert c["kv_page_allocs"] == 10  # 2+2 + 1+1 + 2+2

    # solo references: each model alone on a private half-size pool
    # (3 pages — the same per-model pressure, so the same evictions)
    for tag, seed, names in (("A", 7, ("a0", "a1", "a2")),
                             ("B", 11, ("b0", "b1", "b2"))):
        solo_pool = _paged(num_pages=3, streams=4)
        solo = _interleave(
            {tag: (solo_pool,
                   PagedDecodeStepBatcher(solo_pool,
                                          _make_step(MAX_LEN, seed=seed)))},
            toks)
        assert solo_pool.counters.snapshot()["kv_evictions"] == 1
        for n in names:
            assert len(outs[n]) == len(solo[n])
            for got, want in zip(outs[n], solo[n]):
                np.testing.assert_array_equal(got, want)

    # the two models really are different models: same token, same
    # fresh stream position, different logits
    np.testing.assert_array_equal(toks["a0"][0], toks["a0"][0])
    assert not np.array_equal(outs["a0"][0], outs["b0"][0]) or \
        toks["a0"][0] != toks["b0"][0]


@pytest.mark.parametrize("kind", ["ring", "paged"])
def test_step_hands_the_compiled_step_copies_of_the_host_mirrors(kind):
    """`lengths` (and the paged cache's `page_table`) are numpy mirrors
    the batcher writes right after it dispatches the step, and on the CPU
    backend jnp.asarray of an aligned numpy array shares its buffer: what
    the step was given must still read as it did at dispatch once step()
    has returned and the cache has moved on. Whether one array aliases
    depends on how numpy aligned it, so sixteen caches are tried, all kept
    alive so that no two have their mirrors at one address."""
    from paddle_tpu.inference.kv_cache import PagedDecodeStepBatcher

    def step_fn(tokens, k, v, lengths, active):
        return tokens, k, v

    caches = []
    for _ in range(16):
        if kind == "ring":
            cache = RingKVCache(num_slots=3, max_len=8, num_heads=HEADS,
                                head_dim=DIM)
            batcher = DecodeStepBatcher(cache, step_fn)
            slot = cache.acquire("a")
        else:
            cache = _paged()
            batcher = PagedDecodeStepBatcher(cache, step_fn)
            slot = cache.acquire("a", total_len=8)
        caches.append(cache)
        kept = {}

        def recording(tokens, k, v, *mirrors, kept=kept):
            kept["mirrors"] = mirrors[:-1]  # the last one is the mask
            return tokens, k, v

        batcher._fn = recording
        before = [cache.lengths.copy()]
        if kind == "paged":
            before.insert(0, cache.page_table.copy())
        batcher.step(np.zeros((len(cache.lengths),), np.int32))
        assert cache.lengths[slot] == before[-1][slot] + 1
        cache.release(slot)  # the paged cache clears the table's row
        assert len(kept["mirrors"]) == len(before)
        for given, was in zip(kept["mirrors"], before):
            np.testing.assert_array_equal(np.asarray(given), was)
