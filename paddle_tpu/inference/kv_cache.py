"""Preallocated ring KV-cache + decode-step batching for autoregressive
serving (the second half of the round-14 continuous-batching tentpole).

The request coalescer (inference/server.py) batches ONE-shot predicts;
autoregressive models instead hold per-sequence state (attention K/V)
across many tiny decode steps, and naive serving compiles one executable
per (sequence length, batch) pair and dispatches per sequence. This
module fixes both:

- **RingKVCache** preallocates the K/V blocks once —
  ``[num_slots, max_len, num_heads, head_dim]`` — so cache geometry
  (and therefore every decode-step shape) is FIXED for the server's
  lifetime. Each in-flight sequence owns a slot; its per-token writes
  land at ``length % max_len`` (a ring: sequences longer than max_len
  keep a sliding window instead of reallocating). Slot admission uses
  the SAME deadline-aware bounded-window gate semantics as the request
  coalescer: ``acquire`` takes a free slot immediately when one exists,
  waits at most ``admission_window_s`` when none does, sheds (returns
  None) without waiting when the caller's deadline cannot afford the
  window, and evicts the least-recently-finished resident sequence
  under admission pressure.

- **DecodeStepBatcher** drives ONE jitted step function over the whole
  slot axis. In-flight sequences of DIFFERENT lengths share that single
  compiled executable because lengths and the active-slot mask ride as
  data arguments, never as shapes — admitting a new sequence or
  finishing an old one never recompiles. Slots are independent rows of
  every batched op, so a slot's outputs are bitwise-identical whether
  it decodes alone or next to seven strangers (the same no-cross-
  request-bleed property the coalescer guarantees, proven in
  tests/test_kv_cache.py).

- **PagedKVCache** (the round-19 disaggregated-serving tier) replaces
  fixed-slot residency with page-granular admission: one preallocated
  page pool ``[num_pages, page_len, H, D]`` plus a per-stream page
  table. A short stream holds only the pages its window touches
  (``ceil(min(total_len, max_len) / page_len)``) instead of a full
  ``max_len`` slot, so at equal KV memory the pool admits
  ``page_len``-fold more short concurrent streams than the ring's
  ``num_slots``. Admission keeps the ring's exact gate contract
  (free-now / evict-LRU-finished / bounded wait / deadline shed) but
  reserves ALL of a stream's pages up front from its declared
  ``total_len`` — mid-decode page allocation can then never deadlock
  or shed a half-decoded stream. ``PagedDecodeStepBatcher`` wraps the
  SAME ``step_fn`` contract as the ring batcher: it gathers each
  stream's pages through the page table into the ``[S, max_len, H, D]``
  view the step already expects, runs the one compiled step, and
  scatters only the appended ring position back into the pool —
  decode outputs are bitwise-equal to the ring cache (pinned in
  tests/test_kv_cache.py). Inactive rows write to a dedicated scratch
  page (index ``num_pages``) so duplicate scatter indices always carry
  identical values (deterministic under XLA's unordered scatter).

Always-on profiler counters (instance CounterSet rolled up globally,
like the server's): kv_slots_inflight (gauge), kv_slot_acquires,
kv_slot_releases, kv_evictions, kv_admission_sheds, kv_decode_steps;
the paged cache adds kv_pages_in_use / kv_decode_streams (gauges),
kv_page_allocs and kv_page_evictions (pages reclaimed from
finished-LRU residents under admission pressure).
"""

from __future__ import annotations

import math
import threading
import time
from collections import OrderedDict

import numpy as np

__all__ = ["RingKVCache", "DecodeStepBatcher", "PagedKVCache",
           "PagedDecodeStepBatcher"]


class RingKVCache:
    """Fixed-geometry slot-sharded K/V storage with gated admission.

    The jax arrays ``k``/``v`` are functional values: the batcher (or a
    caller using ``write``) REPLACES them each step; the cache object
    owns slot bookkeeping — lengths (host mirror), the free list, the
    active set, and the finished-LRU eviction order.
    """

    def __init__(self, num_slots, max_len, num_heads, head_dim,
                 dtype="float32", admission_window_s=0.0):
        import jax.numpy as jnp

        if num_slots < 1 or max_len < 1:
            raise ValueError("num_slots and max_len must be >= 1")
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.shape = (self.num_slots, self.max_len,
                      int(num_heads), int(head_dim))
        self.k = jnp.zeros(self.shape, dtype)
        self.v = jnp.zeros(self.shape, dtype)
        self.lengths = np.zeros((self.num_slots,), np.int32)
        self.admission_window_s = float(admission_window_s)

        self._cv = threading.Condition()
        # serializes every k/v array replacement (acquire's slot
        # zeroing, write(), the batcher's donate-and-replace step):
        # without it an acquire racing a step either reads a DONATED
        # buffer or has its zeroing overwritten by the step's writeback
        self._array_lock = threading.Lock()
        self._free = list(range(self.num_slots - 1, -1, -1))
        self._active = {}  # slot -> seq_id
        self._finished = OrderedDict()  # slot -> seq_id, LRU-evictable
        from .. import profiler

        self.counters = profiler.CounterSet()

    # -- admission gate ---------------------------------------------------
    def acquire(self, seq_id=None, deadline=None):
        """Claim a slot for a new sequence. Returns the slot index, or
        None (shed). Order of preference: a free slot NOW; evict the
        least-recently-finished resident; otherwise wait out the
        admission window for a release — unless the caller's deadline
        cannot afford the window, which sheds immediately (the same
        deadline-vs-window contract as the request coalescer)."""
        window = self.admission_window_s
        wait_until = time.monotonic() + window
        with self._cv:
            while True:
                slot = self._claim_locked()
                if slot is not None:
                    self._activate_locked(slot, seq_id)
                    break
                # tight deadline: a budget that cannot afford the
                # admission window sheds NOW, it never waits it out
                if deadline is not None and deadline < wait_until:
                    self.counters.bump("kv_admission_sheds")
                    return None
                left = wait_until - time.monotonic()
                if left <= 0:
                    self.counters.bump("kv_admission_sheds")
                    return None
                self._cv.wait(left)
        # zero the slot outside the admission condition (a long device
        # op must not block waiters) but under the ARRAY lock: stale
        # rows from the previous occupant must never alias into the new
        # sequence's window, and the zeroing must neither read a buffer
        # the batcher just donated nor be overwritten by its writeback
        with self._array_lock:
            self.k = self.k.at[slot].set(0)
            self.v = self.v.at[slot].set(0)
        return slot

    def _claim_locked(self):
        if self._free:
            return self._free.pop()
        if self._finished:
            slot, _ = self._finished.popitem(last=False)  # LRU
            self.counters.bump("kv_evictions")
            return slot
        return None

    def _activate_locked(self, slot, seq_id):
        self.lengths[slot] = 0
        self._active[slot] = seq_id
        self.counters.bump("kv_slot_acquires")
        self.counters.gauge("kv_slots_inflight", len(self._active))

    def mark_finished(self, slot):
        """The sequence is done decoding but its cache stays resident
        (readable for reply assembly) until released — or evicted when
        admission pressure needs the slot."""
        with self._cv:
            seq = self._active.pop(slot, None)
            if seq is None and slot not in self._finished:
                raise KeyError(f"slot {slot} is not active")
            if seq is not None:
                self._finished[slot] = seq
            self.counters.gauge("kv_slots_inflight", len(self._active))
            self._cv.notify_all()

    def release(self, slot):
        """Free the slot entirely (active or finished-resident)."""
        with self._cv:
            was_active = self._active.pop(slot, None) is not None
            was_finished = self._finished.pop(slot, None) is not None
            if not (was_active or was_finished):
                raise KeyError(f"slot {slot} is not in use")
            self._free.append(slot)
            self.counters.bump("kv_slot_releases")
            self.counters.gauge("kv_slots_inflight", len(self._active))
            self._cv.notify_all()

    # -- slot state -------------------------------------------------------
    def active_slots(self):
        with self._cv:
            return sorted(self._active)

    def active_mask(self):
        mask = np.zeros((self.num_slots,), bool)
        mask[self.active_slots()] = True
        return mask

    def seq_id(self, slot):
        with self._cv:
            return self._active.get(slot, self._finished.get(slot))

    def write(self, slot, k_t, v_t):
        """Host-driven single-token append (tests / non-batched paths):
        writes at the ring position and advances the slot's length. The
        batched path does the equivalent update INSIDE the compiled
        step; this is the semantic reference for it."""
        with self._array_lock:
            pos = int(self.lengths[slot]) % self.max_len
            self.k = self.k.at[slot, pos].set(k_t)
            self.v = self.v.at[slot, pos].set(v_t)
            self.lengths[slot] += 1

    def valid_counts(self):
        """Per-slot count of ring positions holding real tokens —
        min(length, max_len); the attention mask derives from this."""
        return np.minimum(self.lengths, self.max_len)


class DecodeStepBatcher:
    """One compiled decode step shared by every in-flight sequence.

    ``step_fn(tokens, k, v, lengths, active_mask) -> (out, k_new,
    v_new)`` operates on the FULL slot axis: tokens ``[S]``, the cache
    blocks ``[S, L, H, D]``, lengths ``[S]`` int32, active_mask ``[S]``
    bool. It must gate its cache writes on ``active_mask`` (inactive
    slots keep their stored rows bit-for-bit — a finished-but-resident
    sequence must not be corrupted by its neighbors' steps) and mask
    its attention by position validity derived from ``lengths``.

    The batcher jits the step once (donating the cache blocks so the
    ring update is in-place), writes the returned blocks back into the
    cache, and advances the host-side length mirror for active slots
    only. Shapes never change across steps, so admission, completion,
    and length skew never retrace — ``kv_decode_steps`` counts
    dispatches against ONE executable.
    """

    def __init__(self, cache: RingKVCache, step_fn, donate=True):
        import jax

        self._cache = cache
        self._fn = jax.jit(step_fn,
                           donate_argnums=(1, 2) if donate else ())

    def step(self, tokens):
        """Advance every ACTIVE slot by one token. `tokens` is the full
        [num_slots] vector (inactive entries are ignored by the masked
        step). Returns the step output as numpy ([num_slots, ...])."""
        import jax.numpy as jnp

        c = self._cache
        # the whole read -> donate -> replace cycle holds the cache's
        # array lock: a concurrent acquire() zeroing a freshly claimed
        # slot must interleave BETWEEN steps, never mid-donation
        with c._array_lock:
            mask = c.active_mask()
            # `lengths` goes in as a copy: on the CPU backend jnp.asarray
            # of an aligned numpy array aliases its buffer, the dispatch is
            # asynchronous, and the increment below would then reach a
            # step still in flight (one ring position too many admitted)
            out, k_new, v_new = self._fn(
                jnp.asarray(np.asarray(tokens)),
                c.k, c.v,
                jnp.array(c.lengths, copy=True),
                jnp.asarray(mask),
            )
            c.k, c.v = k_new, v_new
            c.lengths[mask] += 1
        c.counters.bump("kv_decode_steps")
        return np.asarray(out)


class PagedKVCache:
    """Page-granular K/V storage: a preallocated pool
    ``[num_pages + 1, page_len, H, D]`` (the +1 row is the scratch page
    inactive-stream writes target) and a per-stream page table
    ``[max_streams, pages_per_seq]``. A stream's logical window is the
    SAME ring the RingKVCache keeps — logical position ``p`` lives at
    ``page_table[s, p // page_len][p % page_len]`` with
    ``p = global_index % max_len`` — so gathering a stream's pages in
    table order reconstructs exactly the ``[max_len, H, D]`` block the
    ring cache would hold, and the shared step function produces
    bitwise-identical logits.

    Admission (``acquire``) reserves the stream's FULL page need up
    front from its declared ``total_len`` (prompt + max new tokens):
    under pressure it first evicts least-recently-finished residents
    page-by-page, then waits out the admission window, and sheds
    immediately when the caller's deadline cannot afford the window —
    the ring cache's exact gate contract, at page granularity.
    """

    def __init__(self, num_pages, page_len, pages_per_seq, num_heads,
                 head_dim, dtype="float32", max_streams=None,
                 admission_window_s=0.0):
        import jax.numpy as jnp

        if num_pages < 1 or page_len < 1 or pages_per_seq < 1:
            raise ValueError(
                "num_pages, page_len and pages_per_seq must be >= 1")
        self.num_pages = int(num_pages)
        self.page_len = int(page_len)
        self.pages_per_seq = int(pages_per_seq)
        self.max_len = self.page_len * self.pages_per_seq
        self.max_streams = int(max_streams or num_pages)
        self.scratch_page = self.num_pages  # never allocated
        self.shape = (self.num_pages + 1, self.page_len,
                      int(num_heads), int(head_dim))
        self.k = jnp.zeros(self.shape, dtype)
        self.v = jnp.zeros(self.shape, dtype)
        # host mirrors, mutated under _array_lock like the ring's
        self.page_table = np.full((self.max_streams, self.pages_per_seq),
                                  self.scratch_page, np.int32)
        self.lengths = np.zeros((self.max_streams,), np.int32)
        self.admission_window_s = float(admission_window_s)

        self._cv = threading.Condition()
        self._array_lock = threading.Lock()
        self._free_pages = list(range(self.num_pages - 1, -1, -1))
        self._free_slots = list(range(self.max_streams - 1, -1, -1))
        self._active = {}  # stream slot -> seq_id
        self._finished = OrderedDict()  # slot -> seq_id, LRU-evictable
        self._pages_of = {}  # slot -> [page ids], reserved at acquire
        from .. import profiler

        self.counters = profiler.CounterSet()

    # -- geometry ---------------------------------------------------------
    def pages_needed(self, total_len):
        """Pages a stream of final length `total_len` reserves: its
        sliding window is min(total_len, max_len) positions."""
        window = min(max(int(total_len), 1), self.max_len)
        return int(math.ceil(window / self.page_len))

    def free_pages(self):
        with self._cv:
            return len(self._free_pages)

    # -- admission gate ---------------------------------------------------
    def acquire(self, seq_id=None, total_len=1, deadline=None):
        """Claim a stream slot plus its full page reservation. Returns
        the slot index, or None (shed). Same preference order as the
        ring: satisfiable NOW (evicting LRU-finished residents if their
        pages cover the shortfall); else wait out the admission window
        for a release — unless the caller's deadline cannot afford the
        window, which sheds immediately."""
        need = self.pages_needed(total_len)
        window = self.admission_window_s
        wait_until = time.monotonic() + window
        with self._cv:
            while True:
                slot = self._claim_locked(need)
                if slot is not None:
                    self._activate_locked(slot, seq_id)
                    pages = self._pages_of[slot]
                    break
                if deadline is not None and deadline < wait_until:
                    self.counters.bump("kv_admission_sheds")
                    return None
                left = wait_until - time.monotonic()
                if left <= 0:
                    self.counters.bump("kv_admission_sheds")
                    return None
                self._cv.wait(left)
        # zero the reserved pages outside the admission condition but
        # under the array lock (same stale-rows / donation-race contract
        # as the ring's slot zeroing)
        import jax.numpy as jnp

        with self._array_lock:
            idx = jnp.asarray(np.asarray(pages, np.int32))
            self.k = self.k.at[idx].set(0)
            self.v = self.v.at[idx].set(0)
        return slot

    def _claim_locked(self, need):
        if not self._free_slots:
            # a finished resident also frees its STREAM slot on eviction
            if not self._finished:
                return None
        while len(self._free_pages) < need and self._finished:
            fslot, _ = self._finished.popitem(last=False)  # LRU
            freed = self._release_pages_locked(fslot)
            self._free_slots.append(fslot)
            self.counters.bump("kv_evictions")
            self.counters.bump("kv_page_evictions", freed)
        if not self._free_slots or len(self._free_pages) < need:
            return None
        slot = self._free_slots.pop()
        pages = [self._free_pages.pop() for _ in range(need)]
        self._pages_of[slot] = pages
        self.page_table[slot, :] = self.scratch_page
        self.page_table[slot, :need] = pages
        self.counters.bump("kv_page_allocs", need)
        self._note_pages_locked()
        return slot

    def _release_pages_locked(self, slot):
        pages = self._pages_of.pop(slot, [])
        self._free_pages.extend(pages)
        self.page_table[slot, :] = self.scratch_page
        self._note_pages_locked()
        return len(pages)

    def _note_pages_locked(self):
        self.counters.gauge("kv_pages_in_use",
                            self.num_pages - len(self._free_pages))

    def _activate_locked(self, slot, seq_id):
        self.lengths[slot] = 0
        self._active[slot] = seq_id
        self.counters.bump("kv_slot_acquires")
        self.counters.gauge("kv_slots_inflight", len(self._active))

    def admit(self, slot, k_rows, v_rows, length):
        """Land a prefilled K/V history into the stream's reserved
        pages: `k_rows`/`v_rows` are the projections of the prompt's
        first `length` tokens in CHRONOLOGICAL order ([length, H, D] —
        the handoff wire layout); rows beyond the sliding window are
        dropped and the kept rows land at their ring positions
        (global index % max_len), exactly where sequential decode
        writes would have put them."""
        import jax.numpy as jnp

        k_rows = np.asarray(k_rows)
        v_rows = np.asarray(v_rows)
        length = int(length)
        if k_rows.shape[0] != length or v_rows.shape[0] != length:
            raise ValueError(
                f"admit: got {k_rows.shape[0]} K rows / "
                f"{v_rows.shape[0]} V rows for length {length}")
        window = min(length, self.max_len)
        with self._array_lock:
            if window:
                g = np.arange(length - window, length)
                pos = g % self.max_len
                pages = self.page_table[slot][pos // self.page_len]
                if int(pages.max(initial=-1)) >= self.scratch_page:
                    raise RuntimeError(
                        f"admit: stream {slot} reserved too few pages "
                        f"for length {length} (acquire with a larger "
                        "total_len)")
                offs = pos % self.page_len
                idx = (jnp.asarray(pages.astype(np.int32)),
                       jnp.asarray(offs.astype(np.int32)))
                self.k = self.k.at[idx].set(
                    jnp.asarray(k_rows[length - window:]))
                self.v = self.v.at[idx].set(
                    jnp.asarray(v_rows[length - window:]))
            self.lengths[slot] = length

    def mark_finished(self, slot):
        """Done decoding but resident (readable) until released — or
        evicted page-by-page when admission pressure needs the pool."""
        with self._cv:
            if slot in self._active:
                self._finished[slot] = self._active.pop(slot)
            elif slot not in self._finished:
                raise KeyError(f"stream {slot} is not active")
            self.counters.gauge("kv_slots_inflight", len(self._active))
            self._cv.notify_all()

    def release(self, slot):
        """Free the stream's slot and every reserved page."""
        with self._cv:
            if slot in self._active:
                del self._active[slot]
            elif slot in self._finished:
                del self._finished[slot]
            else:
                raise KeyError(f"stream {slot} is not in use")
            self._release_pages_locked(slot)
            self._free_slots.append(slot)
            self.counters.bump("kv_slot_releases")
            self.counters.gauge("kv_slots_inflight", len(self._active))
            self._cv.notify_all()

    # -- slot state (ring-compatible surface) -----------------------------
    def active_slots(self):
        with self._cv:
            return sorted(self._active)

    def active_mask(self):
        mask = np.zeros((self.max_streams,), bool)
        mask[self.active_slots()] = True
        return mask

    def seq_id(self, slot):
        with self._cv:
            return self._active.get(slot, self._finished.get(slot))

    def write(self, slot, k_t, v_t):
        """Host-driven single-token append (the semantic reference for
        the batched path): resolves the ring position through the page
        table and advances the length mirror."""
        import jax.numpy as jnp

        with self._array_lock:
            pos = int(self.lengths[slot]) % self.max_len
            page = int(self.page_table[slot, pos // self.page_len])
            if page >= self.scratch_page:
                raise RuntimeError(
                    f"write: stream {slot} has no page reserved for "
                    f"position {pos} (acquire with a larger total_len)")
            off = pos % self.page_len
            self.k = self.k.at[page, off].set(jnp.asarray(k_t))
            self.v = self.v.at[page, off].set(jnp.asarray(v_t))
            self.lengths[slot] += 1

    def gather(self, slot):
        """This stream's logical ``[max_len, H, D]`` K/V view (host
        numpy) — the block a ring cache of the same geometry would
        hold. Unreserved positions read the scratch page (masked by
        valid_counts in any attention over them)."""
        k = np.asarray(self.k)
        v = np.asarray(self.v)
        table = self.page_table[slot]
        return (k[table].reshape(self.max_len, *self.shape[2:]),
                v[table].reshape(self.max_len, *self.shape[2:]))

    def valid_counts(self):
        return np.minimum(self.lengths, self.max_len)


class PagedDecodeStepBatcher:
    """The ring batcher's contract on a PagedKVCache: ONE jitted
    executable advances every active stream a token. The user-supplied
    ``step_fn(tokens, k, v, lengths, active_mask) -> (out, k_new,
    v_new)`` is UNCHANGED from DecodeStepBatcher — inside the compiled
    program the pool is gathered through the page table into the
    ``[S, max_len, H, D]`` view the step expects, and after the step
    only the appended ring position is scattered back into the pool
    (the one row the step actually wrote). Page tables, lengths and the
    mask ride as data, so admission/eviction/handoff never retrace.

    ``step(tokens, mask=None)`` takes an explicit active mask so a
    decode driver can step exactly the streams it has registered —
    a stream admitted between mask snapshot and dispatch joins the
    NEXT step (its pages are untouched: unmasked rows scatter to the
    scratch page)."""

    def __init__(self, cache: PagedKVCache, step_fn, donate=True):
        import jax
        import jax.numpy as jnp

        self._cache = cache
        S = cache.max_streams
        page_len = cache.page_len
        max_len = cache.max_len
        scratch = cache.scratch_page
        hd = cache.shape[2:]

        def paged_step(tokens, k_pool, v_pool, table, lengths, active):
            kg = k_pool[table].reshape((S, max_len) + hd)
            vg = v_pool[table].reshape((S, max_len) + hd)
            out, k_new, v_new = step_fn(tokens, kg, vg, lengths, active)
            rows = jnp.arange(S)
            pos = lengths % max_len
            # inactive rows scatter to the scratch page; duplicates
            # there all write the pool's current value (deterministic)
            page = jnp.where(active, table[rows, pos // page_len],
                             scratch)
            off = pos % page_len
            gate = active.reshape((S,) + (1,) * len(hd))
            k_pool = k_pool.at[page, off].set(
                jnp.where(gate, k_new[rows, pos], k_pool[page, off]))
            v_pool = v_pool.at[page, off].set(
                jnp.where(gate, v_new[rows, pos], v_pool[page, off]))
            return out, k_pool, v_pool

        self._fn = jax.jit(paged_step,
                           donate_argnums=(1, 2) if donate else ())

    def step(self, tokens, mask=None):
        """Advance the masked streams one token (default: every active
        stream). Returns the step output as numpy ([max_streams, ...])."""
        import jax.numpy as jnp

        c = self._cache
        with c._array_lock:
            m = (c.active_mask() if mask is None
                 else np.asarray(mask, bool))
            # copies, as in DecodeStepBatcher.step: the host mirrors are
            # written (the increment below, an acquire or a release on
            # another thread) while the step that aliased them may still
            # be in flight
            out, k_new, v_new = self._fn(
                jnp.asarray(np.asarray(tokens)),
                c.k, c.v,
                jnp.array(c.page_table, copy=True),
                jnp.array(c.lengths, copy=True),
                jnp.asarray(m),
            )
            c.k, c.v = k_new, v_new
            c.lengths[m] += 1
        c.counters.bump("kv_decode_steps")
        return np.asarray(out)
