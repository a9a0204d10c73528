"""Shared-prefix page cache for the decode slot pool: refcounted physical
pages with a quantized host tier.

At millions of clients most prompts share a head — a system prompt, a
few-shot header — so the K/V pages that head prefills into are identical
across admissions (pad-masked bucketed prefill is causal and
width-independent for real positions, so a page's values depend only on
the token prefix up to its end).  This module deduplicates that work the
same way AgileNN moves online cost into offline structure: pages are
content-addressed by a *chain hash* over the full token prefix, an
admission that finds its leading pages resident seeds them into its slot
and prefills only the suffix, and every page a live slot was built from
is pinned by refcount until the slot is released.

Ownership model (the scheduler's side of the contract is in
`serve.scheduler`):

  * **page table** — ``key -> _Entry``; the key of page p is
    ``H(key_{p-1} || tokens[p*page : (p+1)*page])``, so two prompts share
    page p only when they agree on *every* token before it (position
    matters: causal K/V is a function of the whole prefix, not the page's
    own tokens).  The page holding a prompt's final token is never
    shareable — the admission must compute at least the last position
    itself to produce its first-token logits.
  * **copy-on-write, hoisted to inject** — slots never alias pages: the
    pool's dense layout means a slot's first (and only) write below its
    prompt length is the inject scatter, so the "first divergent write"
    copy happens exactly once, at admission, by seeding private copies of
    the shared pages.  Decode then appends strictly above the prompt, so
    a slot can never mutate a shared page and readers need no fault path.
  * **refcounts** — ``pin(slot, ...)`` takes a reference on every
    shareable page of the slot's prompt (inserting pages the slot just
    prefilled); ``release(slot)`` drops them.  Pages with live references
    are never demoted or dropped, so a fetch for an occupied slot can
    always be served from the hot tier.
  * **two tiers** — hot pages are device arrays sliced per page; when the
    hot tier exceeds ``hot_pages``, cold (refcount-zero) pages demote LRU
    to a host tier compressed with the repo's transmission codec
    (`compress.quantize` uniform codebook + `compress.lzw` bit-packing) —
    the device->gateway payload machinery turned inward.  A hit on a cold
    page decompresses it back to the device.  The tier is *lossy* by
    design (``2**bits`` centers spanning the page's own value range), the
    same accuracy-for-bytes trade the paper makes on the link; runs that
    need bit-exact replay size ``hot_pages`` to their working set or set
    ``cold_bytes=0`` so cold pages drop instead of degrade.

A page holds every token-axis leaf of the pool's cache by name ("k" and
"v", or latent attention's "latent"), each sliced on its token axis, so
the cache stays agnostic of what a row of K/V state holds.

Everything here is host-side bookkeeping plus whole-page device
slices/concats — no compiled program changes shape because of sharing,
which is what lets the scheduler's one-program-per-bucket discipline
survive intact.
"""
from __future__ import annotations

import hashlib

import jax.numpy as jnp
import numpy as np

from repro.compress.lzw import pack_indices, unpack_indices
from repro.compress.quantize import dequantize, hard_indices, quantizer_init


def page_keys(tokens, page_size: int) -> list[bytes]:
    """Chain-hash keys for every *shareable* page of a prompt.

    Key p digests the whole token prefix through page p (each digest
    extends the previous hash state), so equal keys imply equal prefixes
    — a page is only reusable where causal attention guarantees its K/V
    match.  Pages at or past the final token are excluded: the admission
    owns its last position (first-token logits come from it).
    """
    toks = np.ascontiguousarray(np.asarray(tokens, np.int64))
    n = max(0, (len(toks) - 1) // page_size)
    h = hashlib.sha1(np.int64(page_size).tobytes())
    keys = []
    for p in range(n):
        h.update(toks[p * page_size:(p + 1) * page_size].tobytes())
        keys.append(h.digest())
    return keys


class _Entry:
    """One physical page: device-resident K/V and/or a compressed host
    blob, pinned by the slots built from it."""

    __slots__ = ("key", "refs", "hot", "cold", "stamp")

    def __init__(self, key: bytes):
        self.key = key
        self.refs = 0        # live slots whose cache was seeded/built here
        self.hot = None      # {leaf: (n_sb, n_attn, page, ...)} on the device
        self.cold = None     # {leaf: (payload, lo, hi)} packed indices
        self.stamp = 0       # LRU tick


class PrefixCache:
    """Refcounted page table over prompt-prefix K/V, with a hot device
    tier and a quantized cold host tier.

    hot_pages:  device-resident page budget; referenced pages are pinned
                and may transiently overflow it.
    cold_bytes: host-tier payload budget for demoted pages (0: demotion
                drops the page outright).
    bits:       codebook bits per element in the cold tier (<= 8, the
                bit-packer's framing).
    """

    def __init__(self, page_size: int, *, hot_pages: int = 512,
                 cold_bytes: int = 0, bits: int = 8):
        assert page_size >= 1
        assert 1 <= bits <= 8, "cold tier packs <= 8 bits per element"
        self.page_size = page_size
        self.hot_pages = hot_pages
        self.cold_bytes = cold_bytes
        self.bits = bits
        self._index: dict[bytes, _Entry] = {}
        self._slot_keys: dict[int, list[bytes]] = {}
        self._parked: dict[object, list[bytes]] = {}
        self._tick = 0
        self._cold_used = 0
        self._page_shape = {}        # leaf -> (n_sb, n_attn, page, ...)
        self._dtype = {}
        self.stats = {"page_lookups": 0, "page_hits": 0, "inserts": 0,
                      "demotions": 0, "promotions": 0, "hot_drops": 0,
                      "cold_drops": 0}

    # ------------------------------------------------------------ queries --

    @property
    def hit_rate(self) -> float:
        """Pages served from the cache / shareable pages of admitted
        prompts (deterministic for a fixed workload + schedule)."""
        return self.stats["page_hits"] / max(1, self.stats["page_lookups"])

    @property
    def n_hot(self) -> int:
        return sum(1 for e in self._index.values() if e.hot is not None)

    @property
    def n_cold(self) -> int:
        return sum(1 for e in self._index.values() if e.cold is not None)

    @property
    def cold_used_bytes(self) -> int:
        return self._cold_used

    def lookup(self, tokens) -> tuple[list[bytes], int]:
        """(page keys of the prompt, length of the leading resident run).
        Pure query — admission stats are recorded by `record` only when a
        request is actually admitted, so re-planning the same queue head
        across rounds does not inflate the hit rate."""
        keys = page_keys(tokens, self.page_size)
        n = 0
        for k in keys:
            if k not in self._index:
                break
            n += 1
        return keys, n

    def record(self, n_pages: int, n_seeded: int) -> None:
        """Account one admission: n_pages shareable pages looked up,
        n_seeded of them served from the cache."""
        self.stats["page_lookups"] += n_pages
        self.stats["page_hits"] += n_seeded

    # ----------------------------------------------------------- transfer --

    def fetch(self, keys: list[bytes]) -> dict:
        """Concatenated device rows of each leaf for a resident run of
        pages (token axis 2), promoting cold pages back to the device on
        the way."""
        parts = []
        for key in keys:
            e = self._index[key]
            self._touch(e)
            if e.hot is None:
                e.hot = {n: self._decompress(n, b) for n, b in e.cold.items()}
                self.stats["promotions"] += 1
            parts.append(e.hot)
        if len(parts) == 1:
            return dict(parts[0])
        return {n: jnp.concatenate([p[n] for p in parts], axis=2)
                for n in parts[0]}

    def pin(self, slot: int, keys: list[bytes], rows: dict) -> None:
        """Reference every shareable page of a freshly admitted slot,
        inserting the ones it prefilled itself.  rows holds the slot's
        cache rows by leaf, each (n_sb, n_attn, W, ...) with
        W >= len(keys) * page_size; per-page slices are device copies, so
        entries never alias (or pin) a slot's cache buffer."""
        assert slot not in self._slot_keys, f"slot {slot} already pinned"
        page = self.page_size
        for p, key in enumerate(keys):
            e = self._index.get(key)
            sl = (slice(None), slice(None), slice(p * page, (p + 1) * page))
            if e is None:
                e = _Entry(key)
                e.hot = {n: jnp.copy(r[sl]) for n, r in rows.items()}
                self._register_shape(e.hot)
                self._index[key] = e
                self.stats["inserts"] += 1
            elif e.hot is None:
                # resident only as a cold blob: the slot's own rows hold
                # the bytes it was seeded from — rehydrate for free
                e.hot = {n: jnp.copy(r[sl]) for n, r in rows.items()}
            e.refs += 1
            self._touch(e)
        self._slot_keys[slot] = list(keys)
        self._enforce_budgets()

    def release(self, slot: int) -> None:
        """Drop the slot's references; unpinned pages become demotion
        candidates.  Unknown slots are a no-op (staging admissions that
        abort before finishing were never pinned)."""
        for key in self._slot_keys.pop(slot, []):
            e = self._index.get(key)
            if e is not None:
                e.refs -= 1
                assert e.refs >= 0, "refcount underflow"
        self._enforce_budgets()

    def park(self, slot: int, token) -> "object | None":
        """Transfer a slot's pins to a parked handle: the references move
        from the slot to ``token`` without ever dropping, so a suspended
        request's pages stay resident (never demoted — the fetch contract
        holds) while it waits to resume.  Returns the handle, or None when
        the slot held no pins.  The slot itself is left unpinned and free
        to re-admit."""
        keys = self._slot_keys.pop(slot, None)
        if not keys:
            return None
        assert token not in self._parked, f"park handle {token!r} in use"
        self._parked[token] = keys
        return token

    def unpark(self, token) -> None:
        """Drop a parked handle's references (the resumed admission has
        re-pinned through its own slot, or the suspension was discarded).
        Unknown handles are a no-op, mirroring `release`."""
        for key in self._parked.pop(token, []):
            e = self._index.get(key)
            if e is not None:
                e.refs -= 1
                assert e.refs >= 0, "refcount underflow"
        self._enforce_budgets()

    # ----------------------------------------------------------- internal --

    def _register_shape(self, page: dict) -> None:
        if not self._page_shape:
            self._page_shape = {n: tuple(a.shape) for n, a in page.items()}
            self._dtype = {n: a.dtype for n, a in page.items()}

    def _touch(self, e: _Entry) -> None:
        self._tick += 1
        e.stamp = self._tick

    def _compress(self, arr) -> tuple[bytes, float, float]:
        """Page array -> (bit-packed codebook indices, codebook range).
        The codebook is the transmission quantizer's uniform grid, fit to
        the page's own value range."""
        x = np.asarray(arr, np.float32)
        lo, hi = float(x.min()), float(x.max())
        if not hi > lo:
            hi = lo + 1.0
        qp = quantizer_init(1 << self.bits, lo, hi)
        idx = np.asarray(hard_indices(qp, jnp.asarray(x)))
        return pack_indices(idx, self.bits), lo, hi

    def _decompress(self, name: str, blob: tuple[bytes, float, float]):
        payload, lo, hi = blob
        qp = quantizer_init(1 << self.bits, lo, hi)
        shape = self._page_shape[name]
        idx = unpack_indices(payload, self.bits, int(np.prod(shape)))
        x = dequantize(qp, jnp.asarray(idx)).reshape(shape)
        return x.astype(self._dtype[name])

    def _cold_nbytes(self, e: _Entry) -> int:
        return sum(len(b[0]) for b in e.cold.values())

    def _demote(self, e: _Entry) -> None:
        """Hot -> cold (or gone, with no cold budget).  A page that
        already has a cold blob just drops its device copy — re-demotion
        never re-quantizes, so a page degrades at most once."""
        if self.cold_bytes > 0:
            if e.cold is None:
                e.cold = {n: self._compress(a) for n, a in e.hot.items()}
                self._cold_used += self._cold_nbytes(e)
            e.hot = None
            self.stats["demotions"] += 1
        else:
            e.hot = None
            del self._index[e.key]
            self.stats["hot_drops"] += 1

    def _enforce_budgets(self) -> None:
        """LRU-demote unpinned hot pages past hot_pages, then LRU-drop
        cold blobs past cold_bytes.  Pinned pages never move; a pinned
        working set larger than hot_pages overflows the budget rather
        than breaking the fetch contract."""
        n_hot = self.n_hot
        if n_hot > self.hot_pages:
            victims = sorted((e for e in self._index.values()
                              if e.hot is not None and e.refs == 0),
                             key=lambda e: e.stamp)
            for e in victims[:n_hot - self.hot_pages]:
                self._demote(e)
        if self._cold_used > self.cold_bytes:
            victims = sorted((e for e in self._index.values()
                              if e.cold is not None),
                             key=lambda e: e.stamp)
            for e in victims:
                if self._cold_used <= self.cold_bytes:
                    break
                self._cold_used -= self._cold_nbytes(e)
                e.cold = None
                self.stats["cold_drops"] += 1
                if e.hot is None:
                    del self._index[e.key]
