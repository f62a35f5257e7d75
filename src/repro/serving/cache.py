"""Paged serving state: a block-pool KV allocator over a shared device page
pool, plus per-slot recurrent-state slabs for SSM/hybrid layers.

Dense serving gives every request a (max_seq, K, Dh) slab per layer — memory
scales with the *worst-case* context. The paged cache instead carves the
device KV buffers into fixed-size pages (``models.attention.init_paged_kv_cache``)
and hands each serving slot just the pages its context actually occupies,
vLLM-style. The allocator here is host-side bookkeeping (free list, page
table, per-slot lengths); the jitted decode step consumes snapshots of the
table as device arrays, so the step stays shape-stable while occupancy churns.

Page 0 is reserved: inactive slots' writes and fully-masked reads land there,
so the jitted step never needs a branch on slot liveness.

Pages are *refcounted* (``ref``): a private page has count 1 (its owning
slot); shared-prefix serving (serving.prefix.PrefixTree, attached with
``prefix_pages > 0``) raises counts — one per slot mapping the page
read-only plus one while the tree holds it. Every free path — retirement,
preemption, speculative rollback via ``truncate_slot``, deadline
cancellation, tree eviction — routes through the single refcount-aware
``_release``: a page returns to the free list only when its last
reference drops. A slot's first write into a page it doesn't exclusively
own is a copy-on-write split (``cow_page``); allocation under pressure
evicts unreferenced tree pages (LRU) before reporting OOM, so the prefix
cache yields memory ahead of the engine's stall ladder.

SSM/hybrid layers carry state that is per-slot and CONSTANT-SIZE (an SSD
state matrix plus a conv tail), not per-token — pages are the wrong shape
for it. ``RecurrentStatePool`` holds those slabs beside the page pool, one
row per slot plus a reserved scratch row 0 that mirrors the page pool's
scratch page: packed-prefill padding rows read and write row 0, so the
jitted step never branches on row liveness either.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .prefix import PrefixTree


@dataclasses.dataclass
class CacheStats:
    num_pages: int = 0            # allocatable pages (excl. reserved page 0)
    page_size: int = 0
    pages_in_use: int = 0
    high_water_pages: int = 0     # max pages_in_use over the session
    allocs: int = 0               # slot admissions
    appends: int = 0              # decode-time page extensions
    oom_denials: int = 0          # admissions/extensions refused for space
    truncations: int = 0          # pages released by truncate_slot (rollback)
    shared_pages: int = 0         # pages with refcount > 1 right now
    high_water_shared: int = 0    # max shared_pages over the session
    cow_splits: int = 0           # copy-on-write page copies performed

    @property
    def high_water_tokens(self) -> int:
        return self.high_water_pages * self.page_size


class RecurrentStatePool:
    """Per-slot recurrent-state slabs for SSM/hybrid serving.

    ``bundle.init_recurrent_state(n_rows)`` builds the device pytree (every
    leaf has a leading row axis of ``n_slots + 1``); the engine reassigns
    ``self.state`` with the jit step's updated (donated) arrays each step,
    exactly like ``PagedKVCache.pool``.

    Row convention: row 0 is the reserved scratch row — packed-prefill
    padding rows gather and scatter it so the jitted step needs no
    liveness branch — and slot ``s`` owns row ``s + 1`` (``rows``). No
    host-side reset exists or is needed on slot reuse: the model's chunked
    prefill re-enters a row from zero state whenever its chunk starts at
    position 0 (a fresh prompt), and the decode step freezes rows whose
    slot is not active, so a retired slot's stale state is dead the moment
    its successor admits.
    """

    def __init__(self, bundle, n_slots: int):
        if bundle.init_recurrent_state is None:
            raise ValueError(f"{bundle.cfg.name}: architecture keeps no "
                             "recurrent serving state")
        self.n_slots = n_slots
        self.state = bundle.init_recurrent_state(n_slots + 1)

    def rows(self, slots) -> np.ndarray:
        """State-pool row ids for ``slots`` (np.int32); pad with 0 (the
        scratch row) for packed-batch padding rows."""
        return np.asarray(slots, np.int32) + 1

    @property
    def state_bytes(self) -> int:
        """Device bytes held by the state slabs (all rows, scratch
        included) — constant for the engine's lifetime, the recurrent
        analogue of the KV pool's capacity."""
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(self.state))


class PagedKVCache:
    """Block-pool KV cache for one model's serving slots.

    ``bundle.init_paged_cache`` builds the device pool; this class owns the
    host-side page table (n_slots, max_pages_per_slot), per-slot lengths,
    and the free list. The engine reassigns ``self.pool`` with the jit
    step's updated pool arrays each step.
    """

    def __init__(self, bundle, n_slots: int, num_pages: int, page_size: int,
                 max_pages_per_slot: int, prefix_pages: int = 0):
        if bundle.init_paged_cache is None:
            raise ValueError(f"{bundle.cfg.name}: architecture does not "
                             "support the paged KV cache layout")
        self.pool = bundle.init_paged_cache(num_pages, page_size)
        self.n_slots = n_slots
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_pages_per_slot = max_pages_per_slot
        self.page_table = np.zeros((n_slots, max_pages_per_slot), np.int32)
        self.seq_lens = np.zeros((n_slots,), np.int32)
        self._free = list(range(num_pages - 1, 0, -1))  # pop() -> 1, 2, ...
        self._owned: dict[int, list[int]] = {s: [] for s in range(n_slots)}
        self.held_pages = 0      # pages held externally via hold_pages
        # per-page reference counts: 0 = free/held, 1 = exclusively owned
        # (or tree-only resident), > 1 = shared. All frees go through
        # _release, which returns a page to the free list only at zero.
        self.ref = np.zeros((num_pages,), np.int32)
        # shared-prefix radix tree (serving.prefix); prefix_pages caps its
        # resident footprint, 0 disables sharing entirely
        self.prefix = PrefixTree(self, prefix_pages) if prefix_pages else None
        self.stats = CacheStats(num_pages=num_pages - 1, page_size=page_size)

    # ------------------------------------------------------------- allocation
    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` tokens (ceil division by
        ``page_size``)."""
        return -(-n_tokens // self.page_size)

    def can_admit(self, n_tokens: int, reserve: int = 0,
                  hit_pages: int = 0) -> bool:
        """Can a fresh request of ``n_tokens`` be admitted now? ``reserve``
        discounts pages promised to slots still mid-prefill (chunked
        admission allocates incrementally, so their remaining prompt pages
        are not yet in ``pages_in_use``); ``hit_pages`` discounts full
        pages a prefix-tree walk would map shared instead of allocating.
        Evictable tree pages count as available — allocation reclaims them
        on demand."""
        n = self.pages_for(max(n_tokens, 1))
        avail = len(self._free) - reserve
        if self.prefix is not None:
            avail += self.prefix.evictable()
        return n - hit_pages <= avail and n <= self.max_pages_per_slot

    # ----------------------------------------------------- page-level plumbing
    def _take(self, n: int):
        """Pop ``n`` fresh pages off the free list at refcount 1, evicting
        unreferenced prefix-tree pages (LRU) to cover a shortfall — memory
        pressure reclaims the prefix cache before anything stalls. Returns
        the page list or None (nothing taken) when even eviction can't
        cover ``n``."""
        if n > len(self._free) and self.prefix is not None:
            self.prefix.evict(n - len(self._free))
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.ref[p] = 1
        return pages

    def _release(self, pages) -> list:
        """THE refcount-aware free path: every page release — slot
        retirement, preemption, ``truncate_slot`` rollback, deadline
        cancellation, prefix-tree eviction — decrements here, and a page
        rejoins the free list only when its last reference drops. Returns
        the pages actually freed."""
        freed = []
        for p in pages:
            p = int(p)
            r = int(self.ref[p]) - 1
            if r < 0:
                raise AssertionError(f"page {p}: released below zero "
                                     "references — double free")
            self.ref[p] = r
            if r == 0:
                freed.append(p)
        self._free.extend(reversed(freed))
        return freed

    def owned_pages(self, slot: int) -> int:
        """Pages currently allocated to ``slot`` (0 for a free slot)."""
        return len(self._owned[slot])

    @property
    def free_pages(self) -> int:
        """Pages currently on the free list. The engine's speculative-round
        selection budgets several slots' growth against one pool snapshot
        (cumulative arithmetic ``can_admit`` can't express)."""
        return len(self._free)

    def alloc_slot(self, slot: int, n_tokens: int):
        """Allocate pages covering ``n_tokens`` for an empty slot. Returns the
        page ids (np.int32) or None if the pool can't satisfy the request."""
        assert not self._owned[slot], f"slot {slot} already owns pages"
        n = self.pages_for(max(n_tokens, 1))
        pages = self._take(n) if n <= self.max_pages_per_slot else None
        if pages is None:
            self.stats.oom_denials += 1
            return None
        self._owned[slot] = pages
        self.page_table[slot, :] = 0
        self.page_table[slot, :n] = pages
        self.seq_lens[slot] = n_tokens
        self.stats.allocs += 1
        self._mark_usage()
        return np.asarray(pages, np.int32)

    def map_shared(self, slot: int, pages, n_tokens: int) -> None:
        """Map already-resident (prefix-tree) pages read-only into an empty
        slot: each gains one reference, the slot's table points at them,
        and ``seq_lens`` jumps to ``n_tokens`` — the matched prefix is
        resident without a single prefill chunk. The final mapped page may
        be partially matched (a mid-page fork); the slot's first write
        into any page it doesn't exclusively own must ``cow_page`` first."""
        assert not self._owned[slot], f"slot {slot} already owns pages"
        pages = [int(p) for p in pages]
        assert len(pages) <= self.max_pages_per_slot
        for i, p in enumerate(pages):
            self.ref[p] += 1
            self.page_table[slot, i] = p
        self.page_table[slot, len(pages):] = 0
        self._owned[slot] = pages
        self.seq_lens[slot] = n_tokens
        self.stats.allocs += 1
        self._mark_usage()

    def page_is_shared(self, slot: int, pos: int) -> bool:
        """Is the page holding token position ``pos`` of ``slot`` shared
        (referenced beyond this slot)? Writing it requires ``cow_page``."""
        idx = pos // self.page_size
        owned = self._owned[slot]
        return idx < len(owned) and int(self.ref[owned[idx]]) > 1

    def cow_page(self, slot: int, pos: int):
        """Copy-on-write split of the shared page holding position ``pos``:
        allocate a private replacement, repoint the slot's table entry, and
        drop the shared reference (other readers keep the original). The
        caller must device-copy the page contents src -> dst before any
        write lands. Returns ``(src, dst)`` page ids, or None when the pool
        can't supply the copy's page (nothing changed — a prefill stall)."""
        idx = pos // self.page_size
        src = self._owned[slot][idx]
        assert int(self.ref[src]) > 1, f"page {src} is not shared"
        got = self._take(1)
        if got is None:
            self.stats.oom_denials += 1
            return None
        dst = got[0]
        self._owned[slot][idx] = dst
        self.page_table[slot, idx] = dst
        self._release([src])
        self.stats.cow_splits += 1
        self._mark_usage()
        return src, dst

    def prefix_publish(self, slot: int, tokens, upto: int) -> int:
        """Publish ``slot``'s completed full pages covering
        ``tokens[:upto]`` into the prefix tree (dedup against resident
        prefixes). Call sites: after each prefill chunk lands (intra-batch
        fan-out sharing) and just before retirement/preemption frees the
        slot (multi-turn and resume sharing)."""
        if self.prefix is None:
            return 0
        n_full = upto // self.page_size
        if n_full == 0:
            return 0
        return self.prefix.publish(tokens[:n_full * self.page_size],
                                   self._owned[slot][:n_full])

    def drop_prefix(self) -> None:
        """Detach the prefix tree, releasing every tree reference (pages
        slots still map survive until those slots free)."""
        if self.prefix is not None:
            self.prefix.clear()
            self.prefix = None
            self._mark_usage()

    def extend_slot(self, slot: int, n_new: int):
        """Extend ``slot`` by ``n_new`` tokens (one chunked-prefill step):
        allocate whatever pages are needed to cover ``seq_lens + n_new`` and
        advance ``seq_lens``. Works on an empty slot too (first chunk).
        Returns the newly allocated page ids (possibly empty) or None if the
        pool / the slot's page cap can't satisfy the extension — in which
        case nothing is allocated and ``seq_lens`` is unchanged."""
        owned = self._owned[slot]
        need = self.pages_for(int(self.seq_lens[slot]) + n_new)
        fresh = need - len(owned)
        pages = self._take(fresh) if need <= self.max_pages_per_slot else None
        if pages is None:
            self.stats.oom_denials += 1
            return None
        self.page_table[slot, len(owned):need] = pages
        if not owned:
            self.stats.allocs += 1
        else:
            self.stats.appends += fresh
        owned.extend(pages)
        self.seq_lens[slot] += n_new
        self._mark_usage()
        return np.asarray(pages, np.int32)

    def extend_slots(self, slots, n_news):
        """Batched ``extend_slot`` for packed multi-slot prefill: attempt
        each (slot, n_new) extension independently, in order, with per-row
        stall fallback — a row the pool can't satisfy gets None while the
        rest proceed, so one slot's page stall never blocks its bucket.
        Returns a list aligned with ``slots`` of fresh page-id arrays
        (possibly empty) or None per stalled row."""
        return [self.extend_slot(s, n) for s, n in zip(slots, n_news)]

    def truncate_slot(self, slot: int, n_tokens: int):
        """Roll ``slot`` back to ``n_tokens`` resident tokens — the inverse
        of ``extend_slot``, for speculative-decoding rollback: a rejected
        draft suffix rewinds ``seq_lens`` and releases the tail pages past
        ``pages_for(n_tokens)`` (their table entries return to 0, the
        reserved scratch page). Refcount-aware: a tail page another holder
        still references — the prefix tree, or a sibling slot sharing it —
        only drops this slot's reference and stays resident for its other
        readers; the same contract protects a speculative draft mirror's
        rollback from freeing pages its target still maps. A no-op when
        the slot already sits at or below the page boundary ``n_tokens``
        needs. Returns the tail page ids released from this slot
        (np.int32, possibly empty — they may outlive the release)."""
        cur = int(self.seq_lens[slot])
        if not 0 <= n_tokens <= cur:
            raise ValueError(f"truncate_slot(slot={slot}, "
                             f"n_tokens={n_tokens}): slot holds {cur} tokens"
                             f" — truncation can only rewind, never extend")
        owned = self._owned[slot]
        keep = self.pages_for(n_tokens)
        tail = owned[keep:]
        self._release(tail)
        del owned[keep:]
        self.page_table[slot, keep:] = 0
        self.seq_lens[slot] = n_tokens
        self.stats.truncations += len(tail)
        self._mark_usage()
        return np.asarray(tail, np.int32)

    def ensure_append(self, slot: int, reserve: int = 0) -> bool:
        """Guarantee room for one more token in ``slot`` (the next decode
        step's write). Allocates a fresh page at a page boundary. Returns
        False when the pool is exhausted or the slot hit its page cap — the
        engine then skips the slot this step (admission-control stall).
        ``reserve`` discounts pages promised to mid-prefill slots, so decode
        growth can't strand a half-admitted prompt."""
        used = int(self.seq_lens[slot])
        owned = self._owned[slot]
        if used < len(owned) * self.page_size:
            return True
        avail = len(self._free)
        if self.prefix is not None:
            avail += self.prefix.evictable()
        if len(owned) >= self.max_pages_per_slot or avail - reserve < 1:
            self.stats.oom_denials += 1
            return False
        page = self._take(1)[0]
        self.page_table[slot, len(owned)] = page
        owned.append(page)
        self.stats.appends += 1
        self._mark_usage()
        return True

    def free_slot(self, slot: int):
        """Release the slot's pages (refcount-aware: shared pages stay
        resident for the prefix tree / sibling slots still mapping them)."""
        self._release(self._owned[slot])
        self._owned[slot] = []
        self.page_table[slot, :] = 0
        self.seq_lens[slot] = 0
        self._mark_usage()

    # ------------------------------------------------------- external holds
    def hold_pages(self, n: int) -> np.ndarray:
        """Take up to ``n`` free pages out of circulation — the
        fault-injection / ops hook for page-pool pressure (a co-tenant, a
        defrag pass, a shrinking quota). Held pages count as in use, shrink
        every admission/extension decision, and must be given back with
        ``release_pages``; the engine treats a stall with pages held
        externally as transient back-pressure (it waits) rather than a
        deadlock (it would otherwise preempt, shed, or raise). Returns the
        held page ids."""
        take = [self._free.pop() for _ in range(min(n, len(self._free)))]
        self.held_pages += len(take)
        self._mark_usage()
        return np.asarray(take, np.int32)

    def release_pages(self, pages) -> None:
        """Return pages taken by ``hold_pages`` to the free list."""
        pages = [int(p) for p in np.asarray(pages).reshape(-1)]
        if len(pages) > self.held_pages:
            raise ValueError(f"releasing {len(pages)} pages but only "
                             f"{self.held_pages} are held")
        self._free.extend(reversed(pages))
        self.held_pages -= len(pages)
        self._mark_usage()

    # ------------------------------------------------------------------ views
    def device_tables(self):
        """(page_table, seq_lens) as device arrays for the jitted step.

        Host copies, not views: the CPU backend may read a numpy buffer
        zero-copy after the dispatch returns (``jnp.array`` of a numpy
        array makes no host copy), and the allocator mutates these arrays
        while the dispatched step is still reading them asynchronously."""
        return (jnp.asarray(self.page_table.copy()),
                jnp.asarray(self.seq_lens.copy()))

    # ------------------------------------------------------------------ stats
    def _mark_usage(self):
        in_use = self.stats.num_pages - len(self._free)
        self.stats.pages_in_use = in_use
        self.stats.high_water_pages = max(self.stats.high_water_pages, in_use)
        shared = int((self.ref > 1).sum())
        self.stats.shared_pages = shared
        self.stats.high_water_shared = max(self.stats.high_water_shared,
                                           shared)

    def check_refcounts(self) -> list:
        """Zero-leak reference audit; returns human-readable violations
        (empty = consistent). Every page's refcount must equal the number
        of slots mapping it plus one if the prefix tree holds it; every
        zero-reference page must be on the free list or externally held;
        and free + held + referenced must account for the whole pool."""
        bad: list = []
        expect = np.zeros((self.num_pages,), np.int64)
        for slot, owned in self._owned.items():
            for p in owned:
                expect[p] += 1
        if self.prefix is not None:
            for p in self.prefix.resident_page_ids():
                expect[p] += 1
        free_set = set(self._free)
        unaccounted = 0
        for p in range(1, self.num_pages):
            r = int(self.ref[p])
            if r != int(expect[p]):
                bad.append(f"page {p}: refcount {r} but {int(expect[p])} "
                           "live references (slots + prefix tree)")
            if r > 0 and p in free_set:
                bad.append(f"page {p}: on the free list with refcount {r}")
            if r == 0 and p not in free_set:
                unaccounted += 1
        if unaccounted != self.held_pages:
            bad.append(f"{unaccounted} zero-reference pages off the free "
                       f"list but {self.held_pages} held externally")
        return bad

    @property
    def bytes_per_page(self) -> int:
        """Device bytes one page costs across every attention layer (K and
        V both). 0 for attention-free stacks, whose pools have zero
        layers."""
        k = self.pool["k_pages"]  # (L, P, K, ps, Dh) x2 for k and v
        return 2 * (k.size // k.shape[1]) * k.dtype.itemsize

    @property
    def high_water_bytes(self) -> int:
        """Worst-moment KV footprint of the session, in bytes (high-water
        pages x bytes_per_page) — the column benchmarks compare against the
        dense engine's slab."""
        return self.stats.high_water_pages * self.bytes_per_page

    @property
    def fragmentation(self) -> float:
        """Fraction of allocated token slots not holding a token (tail waste
        of partially-filled last pages). Dense serving's analogue is the
        entire (max_seq - len) tail."""
        alloc = sum(len(p) for p in self._owned.values()) * self.page_size
        used = int(self.seq_lens.sum())
        return (alloc - used) / alloc if alloc else 0.0
