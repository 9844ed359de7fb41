"""Paged HBM KV cache: fixed-size page pool + per-slot page tables.

The full realisation of BASELINE.json's north star for the reference's
``src/kvstore.py`` ("repurposed as an HBM-resident paged KV cache with LRU
eviction"): instead of one contiguous ``max_seq_len`` row per slot
(``SlotKVCache``), attention state lives in a shared pool of
``page_size``-token pages. Short sequences hold few pages, long ones many;
freeing a sequence returns its pages to the pool immediately (the recycling
that LRU-evicting whole rows only approximates).

Split of responsibilities:

- **Host (this class):** page accounting — free list, per-slot page lists,
  capacity reservations. Pure Python, mirrors the reference's free-list slot
  discipline (``src/kvstore.py:82-102``'s eviction loop becomes page
  recycling).
- **Device:** ``k_pages``/``v_pages`` ``[L, num_pages, page_size, Hkv*Dh]``
  and an int32 ``page_table`` ``[max_slots, max_pages_per_seq]`` that jitted
  decode indexes through (``ops/paged_attention.py``). The table is rebuilt
  on device only when host accounting changes (admission / page growth), so
  steady-state decode does zero host→device traffic for metadata.

**Two page lifetimes under one allocator** (a per-layer spec with
sliding-window layers, ``models/mellum.py``): the full-attention layers'
pages are the pool above, held while the sequence lives; the window
layers' pages are a second, small pool (``state["window_pages"]``,
``max_slots * window_pages_per_slot`` pages) with a free list and a table
of its own. A slot holds window pages only for the rows a later step can
still see: ``release_behind_window`` hands back every page the window has
wholly passed, ``alloc_slot`` / ``ensure_capacity`` take pages for the rows
ahead, and admission reckons the two kinds separately (a slot needs its
pages of BOTH). The table rides to the device inside ``state``.

Chunked-decode contract: callers must ``reserve(slot, n_tokens)`` the whole
chunk before launching it — the table is static while the chunk runs, so page
boundaries crossed mid-chunk already have physical pages behind them.
"""

from __future__ import annotations

import collections
import functools
import hashlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import ModelSpec, layered_family


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_pages(k_pages, v_pages, ids, k_vals, v_vals):
    """Write whole pages back into the (donated) pools: the host-tier
    upload's one dispatch. ``ids`` may repeat (pow2 padding duplicates the
    last entry) — duplicate scatter writes carry identical values, so the
    undefined write order is harmless."""
    return k_pages.at[:, ids].set(k_vals), v_pages.at[:, ids].set(v_vals)


class OutOfPagesError(RuntimeError):
    """Pool exhausted — the scheduler must queue or preempt."""


def _stage_value(val, dtype):
    """Coerce one staged page value to a device array for the upload
    scatter: plain host/device arrays pass through; per-layer-chunk lists
    (the layer-wise prefetch staging in ``HostKVOffload.start_upload``)
    concatenate on device — ordered slices of one array concatenated back
    are bit-identical to the whole array."""
    if isinstance(val, (list, tuple)):
        return jnp.concatenate([jnp.asarray(c, dtype) for c in val], axis=0)
    return jnp.asarray(val, dtype)


def _value_nbytes(val) -> int:
    """Byte size of one staged page value (array or per-layer-chunk list)."""
    if isinstance(val, (list, tuple)):
        return sum(int(c.nbytes) for c in val)
    return int(val.nbytes)


def _host_page(val) -> np.ndarray:
    """One page value → contiguous host array (KV-fabric export). Accepts
    host arrays, staged device arrays, or per-layer-chunk lists."""
    if isinstance(val, (list, tuple)):
        # graftlint: ok[host-sync-hot-path] fabric export (drain/pre-warm RPC), never the decode hot path
        return np.concatenate([np.asarray(c) for c in val], axis=0)
    # graftlint: ok[host-sync-hot-path] fabric export (drain/pre-warm RPC), never the decode hot path
    return np.ascontiguousarray(np.asarray(val))


def page_chain_hashes(tokens, n_pages: int, page_size: int) -> List[bytes]:
    """Chain hashes for the first ``n_pages`` FULL pages of ``tokens``:
    hash_i commits to tokens[0 : (i+1)·P], so a hit is an exact-prefix
    match, never a content collision across different prefixes.

    Module-level so a REMOTE party (the disaggregated prefill worker) can
    compute the same chain and probe a decode pool's prefix cache without
    shipping the prompt twice (``WorkerServer._rpc_prefix_probe``)."""
    out: List[bytes] = []
    h = b""
    for i in range(n_pages):
        # graftlint: ok[host-sync-hot-path] tokens is the host prompt list (never a device array) — host→host conversion
        chunk = np.asarray(tokens[i * page_size: (i + 1) * page_size],
                           np.int64).tobytes()
        h = hashlib.blake2b(h + chunk, digest_size=16).digest()
        out.append(h)
    return out


class PagedKVCache:
    """Host-side page allocator + device-side page pool for one model."""

    def __init__(
        self,
        spec: ModelSpec,
        max_slots: int,
        page_size: int = 128,
        num_pages: int = 512,
        max_seq_len: Optional[int] = None,
        dtype: Optional[str] = None,
        sharding=None,   # NamedSharding over [L, N, P, fused] (tp serving)
        offload=None,    # HostKVOffload: host-RAM second tier (optional)
    ) -> None:
        fused = spec.cache_row_width
        if fused % 128 and not spec.layer_kinds:
            raise ValueError(
                f"n_kv_heads*head_dim = {fused} must be a multiple of 128 "
                "for the paged layout (TPU lane alignment)"
            )
        if spec.layer_kinds and (sharding is not None
                                 or offload is not None):
            kind = "K|V" if spec.kv_row_lanes else "latent"
            raise ValueError(
                f"a per-layer spec keeps ONE {kind} pool (and, with "
                "recurrent layers, per-slot state beside it): a sharded "
                "pool and the host tier (kv_offload) move K/V page pairs "
                "and would resume a sequence on what they do not carry")
        self.spec = spec
        self.max_slots = max_slots
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_seq_len = max_seq_len or spec.max_seq_len
        self.max_pages_per_seq = -(-self.max_seq_len // page_size)
        self.dtype = jnp.dtype(dtype) if dtype else spec.jnp_dtype

        shape = (spec.paged_layers, num_pages, page_size, fused)
        self.state = None       # per-slot recurrent state (layered specs)
        if spec.layer_kinds:
            # two kinds of storage under one allocator: ``k_pages`` is the
            # pool of the layers that grow by the token (one row a token,
            # a latent row or K|V side by side, its width the layer's own,
            # no V pool); ``state`` is the per-SLOT state of the recurrent
            # layers (the family's ``init_state``: S float32 and the conv
            # tail). ``pools`` is the pair the programs donate.
            self.k_pages = jnp.zeros(shape, dtype=self.dtype)
            self.v_pages = None
            fam = layered_family(spec)
            # a third kind: pages of the window layers, bounded a slot
            self.window_pages_per_slot = (
                fam.window_pages_per_slot(spec, page_size)
                if spec.window_layers else 0)
            self.num_window_pages = max_slots * self.window_pages_per_slot
            # ONE call for every family: each takes of the pool's sizes what
            # its own storage needs (a per-slot state none; the window
            # layers' pool ``window_pages``; the index keys' pool, a second
            # cache of another WIDTH on this pool's own table and lifetimes,
            # ``num_pages``)
            self.state = fam.init_state(
                spec, max_slots, page_size=page_size, num_pages=num_pages,
                window_pages=self.num_window_pages,
                max_pages_per_seq=self.max_pages_per_seq)
        elif sharding is not None:
            # tp serving: each chip's pool holds only its heads' lanes.
            # Allocate DIRECTLY sharded — zeros-then-device_put would
            # materialise the global pool on one chip first (OOM at exactly
            # the large-pool sizes tp serving exists for) and cannot target
            # non-addressable devices on a multi-host mesh
            alloc = jax.jit(lambda: jnp.zeros(shape, dtype=self.dtype),
                            out_shardings=sharding)
            self.k_pages = alloc()
            self.v_pages = alloc()
        else:
            self.k_pages = jnp.zeros(shape, dtype=self.dtype)
            self.v_pages = jnp.zeros(shape, dtype=self.dtype)

        self._free: List[int] = list(range(num_pages))
        self._slot_pages: Dict[int, List[int]] = {}   # slot -> physical pages
        self._slot_len: Dict[int, int] = {}           # slot -> reserved tokens
        self._free_slots: List[int] = list(range(max_slots))
        self._table = np.zeros((max_slots, self.max_pages_per_seq), dtype=np.int32)
        self._table_dirty = True
        self._table_dev: Optional[jnp.ndarray] = None
        self._peak_pages_used = 0
        # ---- the window layers' pages (0 pages: the spec has none): which
        # physical page backs each logical page a slot still holds, the
        # host mirror of ``state["window_table"]``, and what went back
        self.window = spec.sliding_window if spec.window_layers else 0
        if not self.window:
            self.num_window_pages = self.window_pages_per_slot = 0
        self._wfree: List[int] = list(range(self.num_window_pages))
        self._slot_wpages: Dict[int, Dict[int, int]] = {}
        self._wtable = np.zeros_like(self._table)
        self._wtable_dirty = False
        self._window_pages_released = 0
        self._peak_window_pages_used = 0
        # sums over decode dispatches (``tally_window``): window pages held,
        # and the pages the same contexts hold in the full layers' pool
        self._window_pages_held_sum = 0
        self._window_pages_uncut_sum = 0

        # ---- prefix cache (vLLM-style shared full pages; SURVEY.md §3.5's
        # kvstore north-star taken one level deeper: the unit of reuse is a
        # KV page keyed by its token-prefix hash, not a whole response)
        self._page_ref: Dict[int, int] = {}            # live page -> refcount
        self._prefix_index: Dict[bytes, int] = {}      # chain hash -> page
        self._page_key: Dict[int, bytes] = {}          # page -> chain hash
        # registered pages with refcount 0: reusable immediately on a hash
        # hit, reclaimable (oldest first) when the free list runs dry
        self._reclaimable: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict()
        )
        self._prefix_hits_pages = 0
        self._prefix_hits_tokens = 0
        self._prefix_queries = 0
        self._prefix_reclaimed = 0

        # ---- host tier (engine/kv_offload.py). Transfers are QUEUED here
        # and flushed by sync_tiers() — one batched device_get / one scatter
        # dispatch per flush, called by the engine immediately before any
        # program that writes the pools (so queued reads see pre-write
        # contents and queued writes land before being read).
        self.offload = offload
        self._pending_offload: List[Tuple[bytes, int]] = []   # (key, page)
        self._pending_upload: Dict[int, Tuple[object, object]] = {}
        self._host_hit_pages = 0
        self._host_hit_tokens = 0
        self._upload_pages = 0
        self._upload_bytes = 0

    # ------------------------------------------------------- page sourcing

    @property
    def available_pages(self) -> int:
        """Pages obtainable right now: free + reclaimable cached."""
        return len(self._free) + len(self._reclaimable)

    def _take_free(self, n: int) -> Optional[List[int]]:
        """Source ``n`` writable pages (each returned with refcount 1):
        free list first, then reclaim the oldest cached-but-unreferenced
        prefix pages (evicting their index entries)."""
        if n <= 0:
            return []
        if self.available_pages < n:
            return None
        out: List[int] = []
        while len(out) < n and self._free:
            out.append(self._free.pop(0))
        while len(out) < n:
            page, _ = self._reclaimable.popitem(last=False)   # oldest
            key = self._page_key.pop(page)
            self._prefix_index.pop(key, None)
            self._prefix_reclaimed += 1
            if self.offload is not None:
                if page in self._pending_upload:
                    # host-hit landing page reclaimed before its upload
                    # flushed: the DEVICE copy is stale (never written) and
                    # the store still holds the authoritative bytes — drop
                    # the upload, never offload the stale contents
                    self._pending_upload.pop(page)
                elif self.offload.admit(key):
                    # contents stay intact until the next pool-writing
                    # dispatch, and sync_tiers flushes this queue before
                    # any such dispatch — deferred read is safe
                    self._pending_offload.append((key, page))
            out.append(page)
        for p in out:
            self._page_ref[p] = 1
        used = self.num_pages - len(self._free) - len(self._reclaimable)
        self._peak_pages_used = max(self._peak_pages_used, used)
        return out

    def _unref(self, page: int) -> None:
        self._page_ref[page] -= 1
        if self._page_ref[page] > 0:
            return
        del self._page_ref[page]
        if page in self._page_key:
            # registered prefix page: stays warm for future hash hits,
            # reclaimed LRU-last when the pool needs writable pages
            self._reclaimable[page] = None
            self._reclaimable.move_to_end(page)
        else:
            self._free.append(page)

    # ------------------------------------------------------------ slots

    def alloc_slot(self, n_tokens: int) -> Optional[int]:
        """Claim a slot with capacity for ``n_tokens``; None if no slot or
        not enough pages (caller queues the request)."""
        if not self._free_slots:
            return None
        # the two kinds apart: the window pages of the rows a later step
        # can still see, then the full layers' pages of every row
        if self.window and len(self._window_span(n_tokens)) > len(
                self._wfree):
            return None
        pages = self._take_free(self._pages_for(n_tokens))
        if pages is None:
            return None
        slot = self._install_slot_pages(pages, n_tokens)
        if self.window:
            self._slot_wpages[slot] = {}
            self._hold_window(slot, self._window_span(n_tokens))
        return slot

    def _install_slot_pages(self, pages: List[int], n_tokens: int) -> int:
        """Shared tail of slot allocation: claim a slot id and point its
        table row at ``pages`` (each already refcounted by the caller)."""
        slot = self._free_slots.pop(0)
        self._slot_pages[slot] = pages
        self._slot_len[slot] = n_tokens
        self._table[slot, : len(pages)] = pages
        self._table[slot, len(pages):] = 0
        self._table_dirty = True
        return slot

    def reserve(self, slot: int, n_tokens: int) -> int:
        """Grow the slot by up to ``n_tokens`` more tokens of capacity.

        Returns the number of tokens actually granted — less than
        ``n_tokens`` when ``max_seq_len`` truncates the request, ``0`` when
        the page pool can't cover it. Callers running a decode chunk must
        bound the chunk's steps by the grant (SURVEY.md §7 hard-part #2:
        positions past the grant would index past the page table's width)."""
        if slot not in self._slot_pages:
            raise KeyError(f"slot {slot} not live")
        total = min(self._slot_len[slot] + n_tokens, self.max_seq_len)
        granted = total - self._slot_len[slot]
        if granted <= 0:
            return 0
        need = self._pages_for(total) - len(self._slot_pages[slot])
        if need <= 0:
            self._slot_len[slot] = total
            return granted
        pages = self._take_free(need)
        if pages is None:
            return 0
        cur = self._slot_pages[slot]
        self._table[slot, len(cur): len(cur) + len(pages)] = pages
        cur.extend(pages)
        self._slot_len[slot] = total
        self._table_dirty = True
        return granted

    def ensure_capacity(self, slot: int, total_tokens: int) -> int:
        """Best-effort growth toward ``total_tokens`` of total capacity.

        Unlike ``reserve`` (all-or-nothing increments), this takes as many
        pages as the pool can spare and returns the slot's resulting token
        capacity (clamped to ``max_seq_len``) — the continuous engine bounds
        its decode chunk by this, so pool pressure shortens chunks instead
        of failing them."""
        if slot not in self._slot_pages:
            raise KeyError(f"slot {slot} not live")
        target = min(total_tokens, self.max_seq_len)
        pages = self._slot_pages[slot]
        need = self._pages_for(target) - len(pages)
        take = min(max(need, 0), self.available_pages)
        if self.window:
            # a page of the full layers comes with one of the window
            # layers, as far as their pool can spare them: it backs every
            # slot's window and one chunk, and a slot granted a chunk AHEAD
            # of the one in flight (its pages behind the window freed a
            # chunk late) may ask for a page more than that
            take = min(take, len(self._wfree))
        if take > 0:
            fresh = self._take_free(take)
            assert fresh is not None
            self._table[slot, len(pages): len(pages) + take] = fresh
            pages.extend(fresh)
            self._table_dirty = True
        cap = min(len(pages) * self.page_size, self.max_seq_len)
        self._slot_len[slot] = max(self._slot_len[slot], min(target, cap))
        if self.window:
            held = self._slot_wpages[slot]
            self._hold_window(slot, range(max(held, default=len(pages) - 1) + 1,
                                          len(pages)))
        return cap

    def free_slot(self, slot: int) -> None:
        pages = self._slot_pages.pop(slot, None)
        if pages is None:
            return
        if self.state is not None:
            # a slot is handed out on a ZERO state: alloc_slot finds what
            # the last free left (and the pool starts zeroed)
            self.state = layered_family(self.spec).zero_state_slot(
                self.state, jnp.int32(slot))
        for p in pages:
            self._unref(p)
        if self.window:
            self._wfree.extend(self._slot_wpages.pop(slot).values())
        del self._slot_len[slot]
        self._free_slots.append(slot)
        self._table[slot, :] = 0
        self._table_dirty = True

    def _pages_for(self, n_tokens: int) -> int:
        return max(1, -(-n_tokens // self.page_size))

    # ------------------------------------------------ window layers' pages

    def _window_span(self, n_tokens: int) -> range:
        """Logical pages of a prompt of ``n_tokens`` that hold a row a later
        step can see: from the page of row ``n_tokens - window + 1`` to the
        prompt's last."""
        first = max(n_tokens - self.window + 1, 0) // self.page_size
        return range(first, self._pages_for(n_tokens))

    def _hold_window(self, slot: int, logical_pages: range) -> None:
        held = self._slot_wpages[slot]
        for lp in logical_pages:
            if not self._wfree:
                raise OutOfPagesError(
                    "window pool exhausted: a slot asked for more than "
                    f"its {self.window_pages_per_slot} pages")
            held[lp] = self._wfree.pop(0)
            self._wtable[slot, lp] = held[lp]
            self._wtable_dirty = True
        self._peak_window_pages_used = max(self._peak_window_pages_used,
                                           self.window_pages_used)

    def release_behind_window(self, slot: int, cur: int) -> int:
        """Free the slot's window pages that lie wholly before the first row
        a step at position >= ``cur`` can see (``cur - window + 1``); they
        go to the back of the free list and to whoever asks next. The
        table's entries for them stay as they are: no program reads a row
        behind its window. Returns the pages freed (0 without a window)."""
        if not self.window:
            return 0
        held = self._slot_wpages[slot]
        first = max(cur - self.window + 1, 0) // self.page_size
        gone = [lp for lp in held if lp < first]
        for lp in gone:
            self._wfree.append(held.pop(lp))
        self._window_pages_released += len(gone)
        return len(gone)

    def window_pages_held(self, slot: int) -> int:
        return len(self._slot_wpages.get(slot, ()))

    @property
    def window_pages_used(self) -> int:
        return self.num_window_pages - len(self._wfree)

    def tally_window(self) -> None:
        """One sample a decode dispatch: window pages held by the live
        slots, and the pages their contexts hold where nothing is cut (the
        full layers' pool, the same slots)."""
        if self.window:
            self._window_pages_held_sum += self.window_pages_used
            self._window_pages_uncut_sum += sum(
                len(p) for p in self._slot_pages.values())

    # ----------------------------------------------------- prefix caching

    def _page_hashes(self, tokens, n_pages: int) -> List[bytes]:
        return page_chain_hashes(tokens, n_pages, self.page_size)

    def probe_prefix(self, hashes: List[bytes]) -> int:
        """How many LEADING chain hashes are currently indexed — the page
        count a prefix-aware handoff may omit. Advisory: pages can be
        reclaimed between probe and admission; ``alloc_slot_prefix`` at
        admission is authoritative and a shortfall surfaces as the typed
        ``stale_prefix`` outcome (the sender re-ships the full KV).

        Falls through to the host tier: a page evicted from the device
        index but still resident in host RAM counts as cached — admission
        will upload it rather than recompute it."""
        n = 0
        for h in hashes:
            if h in self._prefix_index:
                n += 1
            elif self.offload is not None and self.offload.probe(h):
                n += 1
            else:
                break
        return n

    def prefetch_chain(self, hashes: List[bytes]) -> int:
        """Async-prefetch hook (serving pump, on enqueue): for each leading
        chain hash resident ONLY in the host tier, start its host→device
        copy now, so by the time admission runs the transfer is already in
        flight and the upload scatter consumes staged device arrays instead
        of blocking on PCIe. Returns how many uploads were started."""
        if self.offload is None:
            return 0
        started = 0
        for h in hashes:
            if h in self._prefix_index:
                continue
            if not self.offload.start_upload(h):
                break
            started += 1
        return started

    def first_page_hash(self, tokens,
                        registerable: bool = False) -> Optional[bytes]:
        """Chain hash of the prompt's first full page, or None when the
        prompt has none. Any prefix sharing between two prompts implies
        sharing this hash — the batched-admission loop uses it to detect
        intra-round overlap cheaply.

        ``registerable=True`` uses the register bound (``len // P``: the
        pages ``register_prefix`` WILL index) — the adding side of the
        dedup set; the default uses the match bound (``(len-1) // P``:
        what ``alloc_slot_prefix`` can reuse) — the checking side.
        """
        n_full = (len(tokens) if registerable
                  else len(tokens) - 1) // self.page_size
        if n_full < 1:
            return None
        return self._page_hashes(tokens, 1)[0]

    def alloc_slot_prefix(self, tokens) -> Optional[Tuple[int, int]]:
        """Claim a slot for a prompt, reusing cached KV pages for its
        longest indexed full-page prefix. Returns (slot, n_cached_tokens),
        or None when slots/pages are exhausted.

        At most ``len(tokens) - 1`` tokens come from cache: the engine
        always needs ≥1 suffix position to produce the first-token logits.
        Shared pages are read-only by construction — decode writes land at
        positions ≥ the prompt length, past every full prefix page.
        """
        if not self._free_slots:
            return None
        n_tokens = len(tokens)
        self._prefix_queries += 1
        matchable = (n_tokens - 1) // self.page_size
        hashes = self._page_hashes(tokens, matchable)
        shared: List[int] = []
        for h in hashes:
            page = self._prefix_index.get(h)
            if page is None:
                break
            shared.append(page)
        # continue the chain through the host tier: hashes past the device
        # match whose pages still live in host RAM get fresh device pages
        # with a staged upload instead of a recompute
        host_hits: List[Tuple[bytes, object, object]] = []
        if self.offload is not None:
            for h in hashes[len(shared):]:
                if h in self._prefix_index:
                    # chain re-enters the device index mid-stream (the key
                    # was re-registered after its offload): staging a host
                    # upload here would double-index h — stop the chain
                    break
                got = self.offload.get(h)
                if got is None:
                    break
                host_hits.append((h, got[0], got[1]))
        # PIN the shared pages BEFORE sourcing fresh ones: a ref-0 cached
        # page sits in _reclaimable, and an unpinned _take_free under pool
        # pressure could reclaim one of THESE pages as this slot's own
        # writable suffix page — same physical page twice in the table, and
        # the suffix prefill would clobber the cached prefix KV
        for p in shared:
            self._page_ref[p] = self._page_ref.get(p, 0) + 1
            self._reclaimable.pop(p, None)       # in use again
        fresh = self._take_free(self._pages_for(n_tokens) - len(shared))
        if fresh is None:
            for p in shared:                     # roll the pins back
                self._unref(p)
            return None
        slot = self._install_slot_pages(shared + fresh, n_tokens)
        # host-hit pages land in the slot's leading fresh pages; index them
        # NOW (pre-flush) so same-round siblings pin and share them — the
        # upload scatter lands before any program reads the pool
        for i, (h, k_arr, v_arr) in enumerate(host_hits):
            page = fresh[i]
            self._pending_upload[page] = (k_arr, v_arr)
            self._prefix_index[h] = page
            self._page_key[page] = h
            self._upload_pages += 1
        n_cached = (len(shared) + len(host_hits)) * self.page_size
        self._prefix_hits_pages += len(shared)
        self._prefix_hits_tokens += len(shared) * self.page_size
        self._host_hit_pages += len(host_hits)
        self._host_hit_tokens += len(host_hits) * self.page_size
        return slot, n_cached

    def holds_prefix_page(self, h: bytes) -> bool:
        """Is this chain hash resident locally (device index or host
        tier)? No recency touch — advisory, for import dedup."""
        return (h in self._prefix_index
                or (self.offload is not None and self.offload.probe(h)))

    def export_prefix_pages(self, hashes: List[bytes]
                            ) -> List[Tuple[bytes, np.ndarray, np.ndarray]]:
        """Host copies of the longest LEADING run of resident pages, in
        chain order — the KV-fabric export reader. Pages are sourced from
        wherever the authoritative bytes live: a pending-upload staged
        value (device copy not yet scattered), the device pool (one
        batched read for all such pages), or the host tier (``peek``: no
        recency touch, so an export never perturbs the serving LRU).
        Returns ``[(hash, k, v), ...]`` with ``[L, page_size, fused]``
        host arrays."""
        spec: List[Tuple[bytes, object, Optional[int]]] = []
        for h in hashes:
            page = self._prefix_index.get(h)
            if page is not None:
                spec.append((h, self._pending_upload.get(page), page))
                continue
            if self.offload is not None:
                got = self.offload.peek(h)
                if got is not None:
                    spec.append((h, got, None))
                    continue
            break
        dev = [page for _, pend, page in spec
               if pend is None and page is not None]
        dev_map: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        if dev:
            ks, vs = self.read_pages(dev)
            dev_map = {p: (k, v) for p, k, v in zip(dev, ks, vs)}
        return [(h,
                 _host_page(pend[0] if pend is not None else dev_map[page][0]),
                 _host_page(pend[1] if pend is not None else dev_map[page][1]))
                for h, pend, page in spec]

    def register_prefix(self, slot: int, tokens) -> int:
        """Index this slot's full prompt pages for future reuse; returns
        how many pages were newly registered. Call after the prompt KV is
        in the pages (post-prefill). Pages covering decode positions (the
        partial tail) are never registered."""
        pages = self._slot_pages.get(slot)
        if pages is None:
            raise KeyError(f"slot {slot} not live")
        n_full = len(tokens) // self.page_size
        hashes = self._page_hashes(tokens, n_full)
        fresh = 0
        for i, h in enumerate(hashes):
            if h in self._prefix_index:
                continue
            page = pages[i]
            if page in self._page_key:
                # page already indexed under a different hash (shouldn't
                # happen: shared pages match the same chain) — skip
                continue
            self._prefix_index[h] = page
            self._page_key[page] = h
            fresh += 1
        return fresh

    # ----------------------------------------------------------- device

    @property
    def page_table(self) -> jnp.ndarray:
        """Device copy of the table; re-uploaded only after host changes.
        From a private host copy that nothing else holds: the engine frees
        and re-issues slots while the chunk that took this snapshot is
        still in flight, and a snapshot must not track those mutations
        (``asarray`` of the table itself may zero-copy-alias it on a CPU
        backend; ``jnp.array`` of it raced with them under async dispatch,
        jax 0.9.0, PR 43: the chunk read a zeroed row)."""
        if self._table_dirty or self._table_dev is None:
            self._table_dev = jnp.asarray(self._table.copy())
            self._table_dirty = False
        return self._table_dev

    @property
    def pools(self):
        """The pair a donating program takes and returns: K and V pages,
        or the latent pages and the per-slot state of a layered spec (with
        the window layers' page table as the host has it now)."""
        if self._wtable_dirty:
            self.state = dict(self.state,
                              window_table=jnp.asarray(self._wtable.copy()))
            self._wtable_dirty = False
        return self.k_pages, (self.v_pages if self.state is None
                              else self.state)

    def swap(self, new_k: jnp.ndarray, new_v) -> None:
        """Adopt the ``pools`` pair returned by a jitted (donating) step."""
        self.k_pages = new_k
        if self.state is None:
            self.v_pages = new_v
        else:
            self.state = new_v

    # ------------------------------------------------- host-tier transfers

    @property
    def page_bytes(self) -> int:
        """Host bytes one page's K+V occupy (all layers); one latent row
        pool for a per-layer spec."""
        l, _, p, fused = self.k_pages.shape
        pools = 1 if self.spec.layer_kinds else 2
        return pools * l * p * fused * self.k_pages.dtype.itemsize

    def _gather_pages(self, pages: List[int]) -> Tuple[np.ndarray, np.ndarray]:
        """One batched device→host read of whole pages → numpy
        ``[L, n, page_size, fused]`` pair. The id vector pads to a pow2
        bucket (repeating the last page) so the gather compiles
        O(log max-batch) programs, not one per count."""
        n = len(pages)
        bucket = 1 << max(0, n - 1).bit_length()
        ids = np.asarray(pages + [pages[-1]] * (bucket - n), np.int32)
        ids = jnp.asarray(ids)
        # graftlint: ok[host-sync-hot-path] swap-out export: ONE batched whole-page read per swap event, not per step
        k = np.asarray(jax.device_get(self.k_pages[:, ids]))[:, :n]
        # graftlint: ok[host-sync-hot-path] second half of the same batched swap-out read
        v = np.asarray(jax.device_get(self.v_pages[:, ids]))[:, :n]
        return k, v

    def read_pages(self, pages: List[int]):
        """Batched read of physical pages as per-page contiguous host
        arrays — the swap-out path's device→host copy."""
        k, v = self._gather_pages(list(pages))
        return ([np.ascontiguousarray(k[:, i]) for i in range(len(pages))],
                [np.ascontiguousarray(v[:, i]) for i in range(len(pages))])

    def stage_uploads(self, pages: List[int], ks, vs) -> None:
        """Queue host→device page writes (swap-in resume). Target pages
        must be refcounted to the caller's slot; applied at the next
        ``sync_tiers``."""
        for p, k_arr, v_arr in zip(pages, ks, vs):
            self._pending_upload[int(p)] = (k_arr, v_arr)

    def sync_tiers(self) -> None:
        """Flush queued host↔device page traffic. The engine calls this
        immediately before dispatching ANY program that writes the pools
        (admission prefill, suffix prefill, handoff page write, decode
        chunk) — the single ordering point of the two-tier design:

        1. pending offloads first — a device→host read of reclaimed pages,
           whose contents are intact exactly until the next pool write;
        2. THEN staged uploads — one donating scatter; an upload's target
           page may itself be queued for offload (reclaimed and reissued
           in the same round), so reads must precede writes.
        """
        if self.offload is None:
            return
        if self._pending_offload:
            pend, self._pending_offload = self._pending_offload, []
            k, v = self._gather_pages([p for _, p in pend])
            for i, (key, _page) in enumerate(pend):
                self.offload.put(key,
                                 np.ascontiguousarray(k[:, i]),
                                 np.ascontiguousarray(v[:, i]))
        if self._pending_upload:
            items = list(self._pending_upload.items())
            self._pending_upload.clear()
            n = len(items)
            self._upload_bytes += sum(
                _value_nbytes(k_arr) + _value_nbytes(v_arr)
                for _, (k_arr, v_arr) in items)
            bucket = 1 << max(0, n - 1).bit_length()
            items.extend([items[-1]] * (bucket - n))  # identical dup writes
            ids = jnp.asarray(np.asarray([p for p, _ in items], np.int32))
            k_vals = jnp.stack(
                [_stage_value(kv[0], self.dtype) for _, kv in items], axis=1)
            v_vals = jnp.stack(
                [_stage_value(kv[1], self.dtype) for _, kv in items], axis=1)
            self.k_pages, self.v_pages = _scatter_pages(
                self.k_pages, self.v_pages, ids, k_vals, v_vals)

    # ------------------------------------------------------------ stats

    @property
    def n_free_pages(self) -> int:
        return len(self._free)

    @property
    def n_free_slots(self) -> int:
        return len(self._free_slots)

    def slot_capacity(self, slot: int) -> int:
        return len(self._slot_pages[slot]) * self.page_size

    def get_stats(self) -> Dict[str, float]:
        itemsize = self.k_pages.dtype.itemsize
        state_bytes = 0
        if self.spec.layer_kinds:
            bytes_total = self.k_pages.size * itemsize
            state_bytes = sum(int(a.nbytes) for a in self.state.values())
        else:
            bytes_total = 2 * self.k_pages.size * itemsize
        used = self.num_pages - len(self._free) - len(self._reclaimable)
        if self.offload is not None:
            host = dict(self.offload.get_stats())
            host.update({
                "host_hit_pages_admit": self._host_hit_pages,
                "host_hit_tokens": self._host_hit_tokens,
                "uploaded_pages": self._upload_pages,
                "uploaded_bytes": self._upload_bytes,
                "pending_offload": len(self._pending_offload),
                "pending_upload": len(self._pending_upload),
            })
        else:
            host = None
        return {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "pages_used": used,
            "pages_free": len(self._free),
            "pages_cached": len(self._reclaimable),
            "peak_pages_used": self._peak_pages_used,
            "utilization": used / self.num_pages if self.num_pages else 0.0,
            "live_slots": len(self._slot_pages),
            "free_slots": len(self._free_slots),
            "prefix_queries": self._prefix_queries,
            "prefix_hit_pages": self._prefix_hits_pages,
            "prefix_hit_tokens": self._prefix_hits_tokens,
            "prefix_reclaimed": self._prefix_reclaimed,
            "prefix_indexed": len(self._prefix_index),
            "hbm_bytes": bytes_total,
            "hbm_gib": bytes_total / (1 << 30),
            # what the cache holds by kind: pages for the layers that grow
            # by the token, a fixed state per slot for the recurrent ones
            "paged_layers": self.spec.paged_layers,
            "state_layers": self.spec.state_layers,
            # the window layers' pool beside the keys above, which keep
            # their meaning (the layers that keep every row)
            **({"window_layers": self.spec.window_layers,
                "window_num_pages": self.num_window_pages,
                "window_pages_per_slot": self.window_pages_per_slot,
                "window_pages_used": self.window_pages_used,
                "peak_window_pages_used": self._peak_window_pages_used,
                "window_pages_released": self._window_pages_released,
                "window_pages_held_sum": self._window_pages_held_sum,
                "window_pages_uncut_sum": self._window_pages_uncut_sum,
                "window_hbm_bytes": int(
                    self.state["window_pages"].nbytes)}
               if self.window else {}),
            "state_bytes": state_bytes,
            "latent_bytes_per_token": (
                self.spec.paged_layers * self.k_pages.shape[-1] * itemsize
                if self.spec.layer_kinds else 0),
            **({"index_bytes_per_token": self.spec.paged_layers
                * self.spec.index_head_dim * itemsize}
               if self.spec.index_topk else {}),
            **({"host_tier": host} if host is not None else {}),
        }
