"""Paged KV memory for the serving pool: page allocator + cache helpers.

Since PR 6 the pool cache for the attention families
(``registry.PAGED_FAMILIES``) is **block-table paged** instead of one
contiguous ``max_slots x max_len`` block per leaf:

    k/v   (L, num_pages+1, page, KV, hd)   physical page store
    pos   (num_pages+1, page)              global position per physical slot
    len   (max_slots,)                     per-slot sequence length
    table (max_slots, pages_per_slot)      logical page -> physical page

A slot's logical cache row is reassembled inside the jitted step bodies
by gathering ``k[table[slot]]`` — a fixed-shape gather, so
``decode_step``/``chunk_step`` stay memoized; only the (tiny, int32)
table contents change between steps.  Attention reduces over the same
(position, value) pairs whatever the physical page layout, which is why
pool-vs-solo bit-identity survives every page size (the conformance
suite pins it for page = span and small pages alike).

Two sentinel page ids make dead state self-masking:

* physical page ``num_pages`` is the **null page**: never written, its
  ``pos`` stays -1 forever, so any gather that lands there is masked out
  by the attention position mask;
* table entries of unallocated / retired slots hold ``num_pages + 1``
  (:func:`drop_id`) — out of bounds, so scatters through them are
  dropped (jit OOB-scatter semantics) and gathers clamp onto the null
  page.  A retired slot can therefore keep "decoding" garbage without
  ever touching a live page.

:class:`PageAllocator` is the host-side bookkeeping: free list,
refcounts, per-slot tables, a shared-prefix cache (prompt-content keyed,
LRU-evicted) and copy-on-write when a slot must append into a shared
page.  It owns no arrays — the engine mirrors its tables/page resets
into the device cache once per admission.

The pre-PR-6 helpers (``lift_cache``/``reset_slot``/``write_slot``) are
kept for the non-attention families (ssm/hybrid recurrent state is O(1)
in sequence length — nothing to page) and now dispatch on the cache
layout, so direct callers (solo conformance references, tests) keep
working on either.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compress
from repro.core.policy import KVQuantSpec


# ---------------------------------------------------------------------------
# Legacy slot-row layout (ssm / hybrid, and any unpaged pool cache)
# ---------------------------------------------------------------------------


def lift_cache(cache, max_slots: int):
    """Lift a fresh ``registry.init_cache(cfg, max_slots, ...)`` tree to the
    slot-pooled layout (per-slot ``pos``/``len``)."""

    def one(path, x):
        key = str(getattr(path[-1], "key", "")) if path else ""
        if key == "len":
            return jnp.zeros((max_slots,), x.dtype)
        if key == "pos":
            return jnp.tile(x[None], (max_slots,) + (1,) * x.ndim)
        return x

    return jax.tree_util.tree_map_with_path(one, cache)


# ---------------------------------------------------------------------------
# Paged layout
# ---------------------------------------------------------------------------


def is_paged(pool) -> bool:
    return isinstance(pool, dict) and "table" in pool


def num_pages_of(pool) -> int:
    """Usable page count (the +1 null page excluded)."""
    return pool["pos"].shape[0] - 1


def drop_id(pool_or_num_pages) -> int:
    """Sentinel table entry: out of bounds, so scatters through it drop
    and gathers clamp onto the null page (``num_pages``, pos -1)."""
    n = (pool_or_num_pages if isinstance(pool_or_num_pages, int)
         else num_pages_of(pool_or_num_pages))
    return n + 1


def page_pool_cache(cache, max_slots: int, page_size: int,
                    num_pages: Optional[int] = None,
                    kv_quant: Optional[KVQuantSpec] = None):
    """Turn a fresh ``registry.init_cache(cfg, max_slots, max_len)`` tree
    into the paged pool layout.

    ``k``/``v`` (L, B, span, KV, hd) become physical page stores
    (L, num_pages+1, page, KV, hd); ``pos`` is lifted per physical slot;
    ``len`` per pool slot; a ``table`` leaf maps (slot, logical page) ->
    physical page.  Slot-rowed leaves (encdec's cross ``ck``/``cv``) are
    left alone — they are written once per admission and never shared.

    With ``kv_quant`` the K/V stores hold the PoT wire format instead
    (core/compress.py): ``k``/``v`` become int code pages
    (L, num_pages+1, page, KV, hd[/2]) plus per-token scale leaves
    ``k_beta``/``v_beta`` of shape (L, num_pages+1, page) — page-shaped,
    so a page's scales travel with it through COW/eviction/prefix-sharing
    with zero extra bookkeeping.  Cross ``ck``/``cv`` stay raw fp.

    With the default ``num_pages = max_slots * pages_per_slot`` the table
    is initialized to the identity mapping (slot i owns pages
    [i*n, (i+1)*n)), so a fresh paged pool behaves exactly like the old
    contiguous layout for direct callers that never retire slots (solo
    conformance references, unit tests).  Engine-managed pools overwrite
    tables at admission regardless.
    """
    span = None

    def spanof(x):  # k/v: (L, B, span, KV, hd)
        return x.shape[2]

    for path, x in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if str(getattr(path[-1], "key", "")) == "k":
            span = spanof(x)
    assert span is not None, "page_pool_cache needs a k/v attention cache"
    if span % page_size != 0 or page_size < 1:
        raise ValueError(
            f"page_size={page_size} must divide the cache span {span}"
        )
    n = span // page_size
    if num_pages is None:
        num_pages = max_slots * n
    if num_pages < n:
        raise ValueError(
            f"num_pages={num_pages} < pages_per_slot={n}: no single "
            "request could ever be admitted"
        )

    def one(path, x):
        key = str(getattr(path[-1], "key", "")) if path else ""
        if key in ("k", "v"):
            L, _, _, kv, hd = x.shape
            if kv_quant is not None:
                hdw = compress.kv_code_width(kv_quant, hd)
                return jnp.zeros(
                    (L, num_pages + 1, page_size, kv, hdw),
                    compress.kv_code_dtype(kv_quant),
                )
            return jnp.zeros((L, num_pages + 1, page_size, kv, hd), x.dtype)
        if key == "pos":
            return jnp.full((num_pages + 1, page_size), -1, jnp.int32)
        if key == "len":
            return jnp.zeros((max_slots,), jnp.int32)
        return x

    out = dict(jax.tree_util.tree_map_with_path(one, cache))
    if kv_quant is not None:
        L = out["k"].shape[0]
        for key in ("k_beta", "v_beta"):
            out[key] = jnp.zeros((L, num_pages + 1, page_size), jnp.int32)
    if num_pages == max_slots * n:
        table = np.arange(max_slots * n, dtype=np.int32).reshape(max_slots, n)
    else:
        table = np.full((max_slots, n), drop_id(num_pages), np.int32)
    out["table"] = jnp.asarray(table)
    return out


def reset_slot(pool, slot: int):
    """Rewind one slot for chunked-prefill admission: ``len`` -> 0 and its
    position bookkeeping to -1 (the not-yet-written sentinel the attention
    mask keys on).  K/V bytes are left as-is — unreachable with ``pos``
    rewound.  On a paged pool this resets the ``pos`` rows of the pages
    the slot's table currently maps (engine-managed slots get their
    tables — and page resets — from the allocator instead)."""
    if is_paged(pool):
        pool = dict(pool)
        pids = pool["table"][slot]
        pool["pos"] = pool["pos"].at[pids].set(-1, mode="drop")
        pool["len"] = pool["len"].at[slot].set(0)
        return pool

    def one(path, x):
        key = str(getattr(path[-1], "key", "")) if path else ""
        if key == "len":
            return x.at[slot].set(0)
        if key == "pos":
            return x.at[slot].set(-1)
        return x

    return jax.tree_util.tree_map_with_path(one, pool)


def write_slot(pool, mini, slot: int, *, pages: Optional[Sequence[int]] = None,
               kv_quant: Optional[KVQuantSpec] = None):
    """Copy a batch-1 cache (``registry.init_cache(cfg, 1, max_len)`` after
    a solo prefill) into ``slot`` of the pool cache.

    Paged pools scatter the mini cache's span into the slot's pages —
    ``pages`` (length ``pages_per_slot``, drop_id-padded) overrides the
    slot's current table row (engine admission passes freshly allocated
    pages; direct callers default to the existing row, which a fresh
    default pool initializes to the identity mapping).  Slot-rowed leaves
    (encdec ``ck``/``cv``) are row-assigned as before.

    A quantized pool (``kv_quant`` — must match the pool's wire format)
    encodes the raw fp mini K/V per written token on the way in; the
    per-token betas land in the slot's page rows of ``k_beta``/``v_beta``.
    """
    if is_paged(pool):
        return _write_slot_paged(pool, mini, slot, pages, kv_quant)

    def one(p, m):
        m = m.astype(p.dtype)
        if m.ndim == p.ndim - 1:  # lifted per-slot leaf (pos / len)
            return p.at[slot].set(m)
        if p.shape == m.shape:  # max_slots == 1: the row IS the pool
            return m
        diffs = [
            d for d, (ps, ms) in enumerate(zip(p.shape, m.shape)) if ps != ms
        ]
        assert len(diffs) == 1 and m.shape[diffs[0]] == 1, (p.shape, m.shape)
        idx = [0] * p.ndim
        idx[diffs[0]] = slot
        return jax.lax.dynamic_update_slice(p, m, tuple(idx))

    return jax.tree_util.tree_map(one, pool, mini)


def _write_slot_paged(pool, mini, slot, pages, kv_quant=None):
    page = pool["pos"].shape[1]
    n = pool["table"].shape[1]
    if ("k_beta" in pool) != (kv_quant is not None):
        raise ValueError(
            "write_slot kv_quant must be given exactly when the pool holds "
            "quantized K/V pages"
        )
    if pages is None:
        pids = pool["table"][slot]
    else:
        assert len(pages) == n, (len(pages), n)
        pids = jnp.asarray(np.asarray(pages, np.int32))
    out = dict(pool)
    out["table"] = pool["table"].at[slot].set(pids)
    for key in ("k", "v"):
        m = mini[key]  # (L, 1, span, KV, hd)
        L, _, span, kv, hd = m.shape
        if kv_quant is not None:
            codes, beta = compress.kv_page_encode(m, kv_quant)
            mp = codes.reshape((L, n, page, kv) + codes.shape[4:])
            bp = beta.reshape(L, n, page)
            bkey = f"{key}_beta"
            out[bkey] = pool[bkey].at[:, pids].set(bp, mode="drop")
        else:
            mp = m.astype(pool[key].dtype).reshape(L, n, page, kv, hd)
        out[key] = pool[key].at[:, pids].set(mp, mode="drop")
    mpos = mini["pos"].reshape(n, page)  # (span,) -> per-page rows
    out["pos"] = pool["pos"].at[pids].set(mpos, mode="drop")
    out["len"] = pool["len"].at[slot].set(mini["len"].astype(jnp.int32))
    for key in ("ck", "cv"):  # encdec cross K/V stay slot-rowed
        if key in pool:
            out[key] = jax.lax.dynamic_update_slice(
                pool[key], mini[key].astype(pool[key].dtype),
                (0, slot, 0, 0, 0),
            )
    return out


# ---------------------------------------------------------------------------
# Host-side page allocator with shared-prefix cache
# ---------------------------------------------------------------------------


class PageAllocatorError(RuntimeError):
    """An allocator invariant was violated (double free, bad refcount)."""


@dataclasses.dataclass
class AdmissionPlan:
    """What :meth:`PageAllocator.plan_admission` decided for one request.

    ``shared`` pages are mapped straight from the prefix cache (ref
    bumped); ``cow`` pages are prefix hits the slot will append into, so
    they need a fresh copy (src physical page recorded for the engine's
    device-side content copy); ``fresh`` is the count of brand-new pages.
    ``resume`` is the prompt position streaming restarts from (a multiple
    of lcm(page, chunk); everything before it is served from the cache).
    """

    shared: List[int]
    cow: List[Tuple[int, int]]  # (src physical page, logical index)
    fresh: int
    resume: int
    hit_tokens: int


class PageAllocator:
    """Free-list page allocator with refcounts, per-slot tables, a
    shared-prefix cache and copy-on-write — the host half of the paged
    pool (device half: :func:`page_pool_cache` + the step bodies).

    Pages are admitted **worst-case up front**: a request gets every page
    it could ever touch (``ceil((plen + max_new) / page)``, or the full
    ring span for windowed archs) at admission, so a mid-flight step can
    never run out — "preemption" is admission deferral, counted by the
    engine.  The prefix cache keeps a page alive after its last slot
    retires (one cache ref) until LRU eviction makes room for a new
    admission.

    Determinism: the free list is a sorted structure and eviction is
    strictly LRU on an engine-step clock, so for a fixed trace the
    physical page assignment — and every counter — is exactly
    reproducible (benchmarks/compare.py gates on that).
    """

    def __init__(self, num_pages: int, page_size: int, pages_per_slot: int,
                 max_slots: int):
        self.num_pages = num_pages
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        self.max_slots = max_slots
        self._free: List[int] = list(range(num_pages - 1, -1, -1))  # stack
        self.refcount = np.zeros((num_pages,), np.int64)
        self.tables: List[List[int]] = [[] for _ in range(max_slots)]
        # prefix cache: chain key -> physical page; key = (logical index,
        # prompt bytes through the page's covering chunk) so a hit is
        # exact token equality, never a hash collision.
        self._prefix: Dict[Tuple, int] = {}
        self._prefix_of: Dict[int, Tuple] = {}  # physical page -> key
        self._lru: Dict[int, int] = {}  # physical page -> last-hit clock
        self._clock = 0
        # counters (engine folds these into ServeStats)
        self.cow_copies = 0
        self.evictions = 0

    # -- invariant-checked primitives ---------------------------------------
    def free_pages(self) -> int:
        return len(self._free)

    def evictable_pages(self, protect=()) -> int:
        """Prefix-cached pages whose only ref is the cache itself."""
        protect = set(protect)
        return sum(
            1 for pid in self._prefix_of
            if self.refcount[pid] == 1 and pid not in protect
        )

    def can_admit(self, fresh_needed: int, protect=()) -> bool:
        return self.free_pages() + self.evictable_pages(protect) >= fresh_needed

    def alloc(self, count: int, protect=()) -> List[int]:
        """Pop ``count`` pages, LRU-evicting idle prefix pages if the free
        list runs short.  Raises if the pool genuinely cannot supply them
        (the engine checks ``can_admit`` first)."""
        while len(self._free) < count:
            self._evict_one(protect)
        out = [self._free.pop() for _ in range(count)]
        for pid in out:
            if self.refcount[pid] != 0:  # pragma: no cover - internal
                raise PageAllocatorError(f"page {pid} allocated while live")
            self.refcount[pid] = 1
        return out

    def _evict_one(self, protect=()):
        protect = set(protect)
        victims = [
            pid for pid in self._prefix_of
            if self.refcount[pid] == 1 and pid not in protect
        ]
        if not victims:
            raise PageAllocatorError("out of pages: nothing evictable")
        victim = min(victims, key=lambda pid: (self._lru.get(pid, -1), pid))
        self._unregister(victim)
        self.evictions += 1

    def _unregister(self, pid: int):
        key = self._prefix_of.pop(pid)
        del self._prefix[key]
        self._lru.pop(pid, None)
        self._unref(pid)

    def _unref(self, pid: int):
        if self.refcount[pid] <= 0:
            raise PageAllocatorError(f"double free of page {pid}")
        self.refcount[pid] -= 1
        if self.refcount[pid] == 0:
            self._free.append(pid)
            self._free.sort(reverse=True)  # deterministic: lowest pid first

    # -- prefix cache --------------------------------------------------------
    @staticmethod
    def chunk_dep(logical_page: int, page_size: int, chunk: int) -> int:
        """Prompt length page ``logical_page``'s content depends on: the
        end of the chunk that wrote the page's last position.  Chunked
        prefill's activation-scale groups cover a whole chunk, so a page
        is only shareable between prompts that agree through this bound."""
        end = (logical_page + 1) * page_size
        return -(-end // chunk) * chunk  # ceil(end / chunk) * chunk

    def _key(self, prompt: np.ndarray, k: int, chunk: int) -> Tuple:
        dep = self.chunk_dep(k, self.page_size, chunk)
        return (k, prompt[:dep].tobytes())

    def prefix_lookup(self, prompt: np.ndarray, chunk: int) -> List[int]:
        """Longest chain of registered pages matching ``prompt``'s head.
        Full-prompt-covered pages only (dep(k) <= plen)."""
        plen = len(prompt)
        hits: List[int] = []
        k = 0
        while (k + 1) * self.page_size <= plen:
            if self.chunk_dep(k, self.page_size, chunk) > plen:
                break
            pid = self._prefix.get(self._key(prompt, k, chunk))
            if pid is None:
                break
            hits.append(pid)
            k += 1
        return hits

    def register_prefix(self, slot: int, prompt: np.ndarray, chunk: int):
        """After a slot finishes (or skips) prefill, publish its full,
        chunk-complete prompt pages into the prefix cache (one cache ref
        each; already-registered keys just get an LRU touch)."""
        plen = len(prompt)
        table = self.tables[slot]
        for k in range(plen // self.page_size):
            if self.chunk_dep(k, self.page_size, chunk) > plen:
                break
            key = self._key(prompt, k, chunk)
            pid = self._prefix.get(key)
            if pid is not None:
                self._lru[pid] = self._clock
                continue
            pid = table[k]
            self._prefix[key] = pid
            self._prefix_of[pid] = key
            self.refcount[pid] += 1
            self._lru[pid] = self._clock

    def tick(self, clock: int):
        self._clock = clock

    # -- admission / retirement ---------------------------------------------
    def plan_admission(self, prompt: Optional[np.ndarray], need_tokens: int,
                       chunk: Optional[int]) -> AdmissionPlan:
        """Pages for one request: prefix hits (shared / copy-on-write
        split) + fresh count.  ``prompt=None`` (or no chunking) disables
        prefix reuse — solo prefill's activation-scale groups cover the
        whole prompt, so its pages are never content-shareable."""
        npages = min(-(-need_tokens // self.page_size), self.pages_per_slot)
        if prompt is None or chunk is None:
            return AdmissionPlan([], [], npages, 0, 0)
        hits = self.prefix_lookup(prompt, chunk)
        plen = len(prompt)
        share_tok = len(hits) * self.page_size
        # streaming must resume on a chunk boundary, with >= 1 prompt
        # token left to stream (the resumed chunk emits the first token)
        resume = (min(share_tok, plen - 1) // chunk) * chunk
        if resume == 0:  # hits too short to skip even one chunk
            return AdmissionPlan([], [], npages, 0, 0)
        first_stream_page = resume // self.page_size
        shared = hits[:first_stream_page]
        cow = [(pid, k) for k, pid in enumerate(hits) if k >= first_stream_page]
        return AdmissionPlan(
            shared=shared, cow=cow, fresh=npages - len(hits),
            resume=resume, hit_tokens=resume,
        )

    def fresh_needed(self, plan: AdmissionPlan) -> int:
        return plan.fresh + len(plan.cow)

    def reserve(self, plan: AdmissionPlan) -> Dict:
        """Commit an admission plan's pages *before* a slot is known:
        allocate fresh/COW pages and bump shared refs, so back-to-back
        ``can_admit`` checks within one scheduler call can never hand the
        same free pages to two requests.  Returns {'table': full table
        row, 'new': cow-dst + fresh pids, 'copies': [(src, dst)]} for the
        engine's device-side mirror; pass it to :meth:`bind` immediately
        (a held, unbound reservation fails ``check_conservation``)."""
        protect = set(plan.shared) | {pid for pid, _ in plan.cow}
        new = self.alloc(self.fresh_needed(plan), protect)
        copies = []
        table: List[int] = []
        for pid in plan.shared:
            self.refcount[pid] += 1
            self._lru[pid] = self._clock
            table.append(pid)
        for src, _ in plan.cow:
            dst = new.pop(0)
            self._lru[src] = self._clock
            copies.append((src, dst))
            table.append(dst)
            self.cow_copies += 1
        table.extend(new)
        return {"table": table, "new": [d for _, d in copies] + new,
                "copies": copies}

    def bind(self, slot: int, hold: Dict) -> None:
        """Attach a :meth:`reserve` result to its assigned slot."""
        if self.tables[slot]:
            raise PageAllocatorError(f"slot {slot} already holds pages")
        self.tables[slot] = list(hold["table"])

    def admit(self, slot: int, plan: AdmissionPlan) -> Dict:
        """reserve + bind in one call (direct/test use; the engine splits
        them around the scheduler's slot assignment)."""
        if self.tables[slot]:
            raise PageAllocatorError(f"slot {slot} already holds pages")
        hold = self.reserve(plan)
        self.bind(slot, hold)
        return hold

    def release_slot(self, slot: int):
        """Page-granular free on retirement: unref every page the slot
        maps; prefix-registered pages stay alive on their cache ref."""
        for pid in self.tables[slot]:
            self._unref(pid)
        self.tables[slot] = []

    # -- accounting ----------------------------------------------------------
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def check_conservation(self):
        """free + live == num_pages, refcounts consistent, no aliasing
        between the free list and any table / the prefix cache."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise PageAllocatorError("duplicate page on the free list")
        refs = np.zeros_like(self.refcount)
        for t in self.tables:
            for pid in t:
                refs[pid] += 1
        for pid in self._prefix_of:
            refs[pid] += 1
        if not np.array_equal(refs, self.refcount):
            bad = np.nonzero(refs != self.refcount)[0]
            raise PageAllocatorError(
                f"refcount drift on pages {bad.tolist()}: "
                f"counted {refs[bad].tolist()}, "
                f"stored {self.refcount[bad].tolist()}"
            )
        for pid in range(self.num_pages):
            if (self.refcount[pid] == 0) != (pid in free):
                raise PageAllocatorError(
                    f"page {pid}: refcount {self.refcount[pid]} vs "
                    f"free-list membership {pid in free}"
                )
        if np.any(self.refcount < 0):
            raise PageAllocatorError("negative refcount")


# ---------------------------------------------------------------------------
# Speculative-decoding snapshot / rollback (serve/spec.py)
# ---------------------------------------------------------------------------
#
# A spec round (draft + verify) may write the C cache entries at global
# positions [len, len + C) of every slot: the low-bit self-draft runs
# C - 1 real decode_steps on the shared cache, and the verify step writes
# each slot's n_new valid positions.  Rollback is snapshot/restore:
# ``spec_snapshot`` gathers the (k, v, pos) state of exactly those C
# entries (plus ``len``) *before* the round, and ``spec_restore`` scatters
# the snapshot back at positions >= keep[b] — used twice per round, with
# keep = 0 to erase the draft's pollution before the verify pass (so the
# verifier sees the pristine pre-round cache and stays bit-identical to
# sequential decode even on windowed rings, where a draft write evicts a
# key later positions still need), and with keep = accepted + 1 after
# acceptance to roll back the rejected tail.  On a non-windowed cache the
# restored entries always held pos = -1 (the slot was never written —
# no wrap can occur), so the restore is exactly the "pos clamped to -1"
# rollback rule; on windowed rings it additionally restores the evicted
# old keys.  Addressing mirrors the step bodies: slot (len + i) % span,
# routed through the page table when paged; entries of dead slots
# (drop_id tables) gather from the null page and scatter-drop.


def _spec_addr(cache, c: int, pos0):
    """Physical addresses of the C spec-round entries per slot.  Returns
    ``(dest, loff)`` (B, C) page addressing for paged pools or
    ``(None, sidx)`` for slot-rowed pools."""
    offs = jax.lax.iota(jnp.int32, c)
    gpos = pos0[:, None] + offs[None, :]  # (B, C)
    if "table" in cache:
        table = cache["table"]
        page = cache["pos"].shape[1]
        span = table.shape[1] * page
        lo = gpos % span
        dest = jnp.take_along_axis(table, lo // page, axis=1)  # (B, C)
        return dest, lo % page
    span = cache["k"].shape[2]
    return None, gpos % span


def spec_snapshot(cache, c: int):
    """Gather the pre-round state of the C cache entries a spec round can
    touch: ``{"k": (L, B, C, KV, hd), "v": ..., "pos": (B, C),
    "len": (B,)}``.  jit-friendly (fixed-shape gathers); dead slots read
    the null page (restored values are scatter-dropped anyway)."""
    pos0 = cache["len"]
    dest, off = _spec_addr(cache, c, pos0)
    if dest is not None:  # paged: k (L, P+1, page, KV, hd)
        snap = {
            "k": cache["k"][:, dest, off],
            "v": cache["v"][:, dest, off],
            "pos": cache["pos"][dest, off],
            "len": pos0,
        }
        for key in ("k_beta", "v_beta"):  # quantized: per-token scales
            if key in cache:
                snap[key] = cache[key][:, dest, off]
        return snap
    rows = jnp.arange(off.shape[0])[:, None]
    return {
        "k": cache["k"][:, rows, off],
        "v": cache["v"][:, rows, off],
        "pos": cache["pos"][rows, off],
        "len": pos0,
    }


def spec_restore(cache, snap, keep):
    """Scatter the snapshot back at positions >= ``keep[b]`` and set
    ``len = snap["len"] + keep``.  ``keep`` (B,) int32 in [0, C]: 0 erases
    the whole round for that slot (draft-pollution cleanup / idle rows),
    ``accepted + 1`` keeps the accepted prefix + bonus token.  Kept
    positions are routed out of bounds so their scatters drop; dead slots'
    addresses are drop_id-OOB already.  jit-friendly."""
    c = snap["pos"].shape[1]
    pos0 = snap["len"]
    offs = jax.lax.iota(jnp.int32, c)
    rej = offs[None, :] >= keep[:, None]  # (B, C) -> restore these
    dest, off = _spec_addr(cache, c, pos0)
    out = dict(cache)
    if dest is not None:
        oob = jnp.asarray(cache["pos"].shape[0], dest.dtype)  # P+1: drops
        dest = jnp.where(rej, dest, oob)
        out["k"] = cache["k"].at[:, dest, off].set(snap["k"], mode="drop")
        out["v"] = cache["v"].at[:, dest, off].set(snap["v"], mode="drop")
        out["pos"] = cache["pos"].at[dest, off].set(snap["pos"], mode="drop")
        for key in ("k_beta", "v_beta"):
            if key in cache:
                out[key] = cache[key].at[:, dest, off].set(
                    snap[key], mode="drop"
                )
    else:
        span = cache["k"].shape[2]
        rows = jnp.arange(off.shape[0])[:, None]
        sidx = jnp.where(rej, off, span)  # OOB -> drop kept positions
        out["k"] = cache["k"].at[:, rows, sidx].set(snap["k"], mode="drop")
        out["v"] = cache["v"].at[:, rows, sidx].set(snap["v"], mode="drop")
        out["pos"] = cache["pos"].at[rows, sidx].set(snap["pos"],
                                                     mode="drop")
    out["len"] = pos0 + keep
    return out
