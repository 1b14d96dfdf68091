"""Decode-optimized paged-KV attention (ref: deepspeed/ops/transformer/
inference — the decode attention kernels behind init_inference's kernel
injection, which read a preallocated KV workspace; paging per vLLM-style
block tables is the modern equivalent contract).

TPU design: KV lives in fixed-size **pages**, one pool [L, KV, num_pages,
page_size, Dh] for all layers that the serving programs update in place;
each sequence owns a list of page ids (the page table).  Decode
attention is HBM-bandwidth-bound, so the pallas kernel streams exactly
the live pages of each sequence: the page table is a **scalar-prefetch**
operand and the K/V BlockSpec index maps dereference it, so the grid's
page axis walks `table[b, p]` — gathers happen in the DMA engine, never
materialising a contiguous copy of the sequence.  Online softmax (m, l,
acc in VMEM scratch) accumulates across the page sweep; pages at or past
the sequence length are masked (their DMA reads page 0 — cheap and safe).

The jnp reference path (`paged_attention_reference`) materialises the
gather and is the numerics oracle for tests/CPU.

The Mosaic kernels here, by the names a capture shows: `dstpu_paged_decode`
(live pages only, one decode step, whose new K/V row it writes to its
page), `dstpu_paged_chunk_v2` (a chunk over
history, in blocks), `dstpu_mla_decode` (latent rows), `dstpu_state_step`
(one token of a recurrent layer's rule on the per-slot state carried
beside the pool, a tile at a time, in place: :func:`state_step`) and
`dstpu_state_chunk` (a prompt chunk of it: :func:`state_chunk`).  Which a
program runs is a rule of the build (:func:`paged_reader` and its kin).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# what a recurrent layer's per-slot state is kept in (``PagedKVCache.
# state``): every token's update is rounded to it
STATE_DTYPE = jnp.float32


# ------------------------------------------------------------- page store
class PagedKVCache(NamedTuple):
    """Paged KV store for one layer stack.  Two kinds of payload:

    * per-head K and V: ``k``/``v`` [L, KV, num_pages, page_size, Dh]
      (or int8 codes with ``k_scale``/``v_scale``);
    * one latent row a token: ``k`` [L, 1, num_pages, page_size, C + Dr]
      holds ``[c | k_rope]``, which attention reads as keys and whose
      first C numbers it reads as values, so ``v`` is None (a family
      whose ``cache_row`` says ``values_in_keys``).

    The pool's leading dimension counts the layers that ATTEND over
    pages, which is not the model's depth where a family has recurrent
    layers (``DecoderFamily.recurrent``): those keep, a SLOT and not a
    page, ``conv`` [L_rec, B, rows, channels] and ``state`` [L_rec, B,
    heads, Dk, Dv] float32 beside the pool, B the engine's slots; a
    family of two per-slot kinds (``Recurrent.also``) keeps the second's
    rows in ``ring`` [L_ring, B, rows, channels].

    table: [B, max_pages] int32 page ids; seq_lens: [B] int32 valid
    token counts.  ``expert_rows`` ([Eh] int32, or None): rows routed to
    each held expert that the programs have added up since the last
    decode program handed the sum out (a family with ``expert_rows``);
    [Eh + 1] where the last counts the further passes of the held
    experts' pair buffer (an engine of a family that holds a share of
    the experts its ``router`` scores).

    ``real`` ([B] int32, or None: all) is how many of the T tokens a
    forward is handed are real, a row: a serving program sets it where
    the cache carries ``state`` (a prefill or chunk its last real
    position + 1; a decode step 1 for a live row and 0 for a slot that
    is idle or between two chunks of its prompt, which length 0 marks),
    and the forward hands the cache back without it.  ``slot`` ([1]
    int32): which slot's state the one row of a prefill's or chunk's
    private view stands for; None where the rows are the slots.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    table: jnp.ndarray
    seq_lens: jnp.ndarray
    page_size: int
    # int8-resident mode (kv_tier.quantized_resident): k/v hold int8
    # codes and these hold the per-token-row f32 scales
    # [L, KV, num_pages, page_size, 1]; None on the plain path.
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None
    expert_rows: Optional[jnp.ndarray] = None
    conv: Optional[jnp.ndarray] = None
    state: Optional[jnp.ndarray] = None
    real: Optional[jnp.ndarray] = None
    slot: Optional[jnp.ndarray] = None
    ring: Optional[jnp.ndarray] = None

    @classmethod
    def alloc(cls, n_layers: int, n_kv: int, num_pages: int, page_size: int,
              head_dim: int, batch: int, max_seq: int,
              dtype=jnp.bfloat16) -> "PagedKVCache":
        max_pages = -(-max_seq // page_size)
        if num_pages < batch * max_pages:
            raise ValueError(
                f"num_pages {num_pages} < batch*max_pages {batch * max_pages}")
        shape = (n_layers, n_kv, num_pages, page_size, head_dim)
        # static round-robin page assignment: sequence b, slot p → page id.
        # (A dynamic free-list allocator lives host-side in PageAllocator.)
        table = (np.arange(batch)[:, None] * max_pages
                 + np.arange(max_pages)[None]).astype(np.int32)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   table=jnp.asarray(table),
                   seq_lens=jnp.zeros((batch,), jnp.int32),
                   page_size=page_size)

    def write_token(self, layer: int, new_k: jnp.ndarray,
                    new_v: jnp.ndarray) -> "PagedKVCache":
        """Append one token's K/V ([B, KV, Dh]) at each sequence's frontier.

        Raises when a sequence is at capacity (concrete seq_lens); under a
        jit trace an overflowing sequence's write is *dropped* (validity
        predicate inside ``write_token_pages``) so live KV is never
        corrupted — overflow degrades to stale attention on the final
        token rather than silently overwriting the last slot.
        """
        pos = self.seq_lens                          # [B]
        capacity = self.table.shape[1] * self.page_size
        try:
            if int(jnp.max(pos)) >= capacity:
                raise ValueError(
                    f"KV cache overflow: seq_len {int(jnp.max(pos))} at "
                    f"capacity {capacity}")
        except (jax.errors.TracerArrayConversionError,
                jax.errors.ConcretizationTypeError):
            pass  # traced: bounded by the caller's decode-loop length
        k, v = write_token_pages(self.k, self.v, layer, new_k, new_v,
                                 self.table, pos)
        return self._replace(k=k, v=v)

    def bump(self) -> "PagedKVCache":
        return self._replace(seq_lens=self.seq_lens + 1)


class PageAllocator:
    """Host-side refcounted page allocator with an optional
    content-addressed warm pool (continuous-batching bookkeeping +
    automatic prefix caching).

    Every page handed out carries a reference count: ``allocate`` mints
    pages at refcount 1, ``share`` maps already-cached pages into
    another sequence with a refcount bump, and ``release`` drops one
    reference per owned page — a page is only reclaimable when its LAST
    owner releases it.  Full pages whose token span has been
    content-addressed via ``publish`` do not return to the free list on
    their last release: they enter a warm pool (capped at
    ``cache_pages``, eviction-ordered ``lru`` or ``fifo``) where their
    KV stays resident and matchable, and are only reclaimed when
    ``allocate`` finds the free list dry — allocation pressure, not
    request completion, is what erases cache.

    ``cache_pages=0`` (the default) disables publishing entirely and
    restores the classic free-list semantics: one owner per page,
    release returns pages immediately.

    Tiering (the ZeRO-Infinity idea applied to KV pages): with a
    ``spill`` pool installed (:class:`~deepspeed_tpu.inference.kv_tier.
    KVTierPool`) and a ``demote_hook``, a warm page reclaimed by
    ``_evict_one`` is offered to the hook first — on success the page's
    KV survives on a host/NVMe tier and its content key keeps matching
    through :meth:`lookup_tiered`, so eviction demotes instead of
    forgetting.  Tier hits re-enter HBM through PROMOTION: the engine
    allocates a fresh page, marks it with :meth:`begin_promotion`
    (unmatchable and unreclaimable until the payload lands), and
    :meth:`finish_promotion` publishes it once the upload completes.
    ``available`` excludes in-flight promotions so admission can never
    double-count a page as both warm and free.
    """

    def __init__(self, num_pages: int, cache_pages: int = 0,
                 eviction: str = "lru"):
        if eviction not in ("lru", "fifo"):
            raise ValueError(
                f"eviction must be 'lru' or 'fifo', got {eviction!r}")
        self.free = list(range(num_pages - 1, -1, -1))
        self.owned = {}           # seq_id -> [page, ...]
        self.refs = {}            # page -> live reference count
        self.index = {}           # content key -> page (published)
        self.key_of = {}          # page -> content key
        self.pool = {}            # page -> eviction priority (refs == 0)
        self.cache_pages = int(cache_pages)
        self.eviction = eviction
        self._published_at = {}   # page -> publish tick (fifo priority)
        self._tick = 0
        self.evicted = 0          # lifetime evicted-page count
        self.published = 0        # lifetime published-page count
        # ---- KV tiering (installed by the engine when kv_tier is on)
        self.spill = None         # KVTierPool: demoted-page index
        self.demote_hook = None   # (page, key) -> bool: capture to tier
        self.promoting = {}       # page -> key, promotion in flight
        self._parked = []         # promoting pages released pre-landing
        self.demoted = 0          # lifetime demoted-page count
        self.promoted = 0         # lifetime promoted-page count

    @property
    def available(self) -> int:
        """Pages an ``allocate`` could obtain right now: the free list
        plus the warm pool (reclaimed on demand).  Pages with an
        in-flight promotion are structurally excluded — they are owned
        (never in either list), ``_publish_full_pages`` skips them so
        they cannot enter the warm pool, and ``release`` PARKS rather
        than frees them — so an async upload can never land in a page
        this count let someone else re-allocate."""
        return len(self.free) + len(self.pool)

    def allocate(self, seq_id, n: int = 1):
        """Mint ``n`` fresh pages (refcount 1) for ``seq_id``, evicting
        warm-pool pages oldest-first when the free list runs dry."""
        if self.available < n:
            raise MemoryError(f"out of KV pages (need {n}, "
                              f"free {len(self.free)}, "
                              f"cached {len(self.pool)})")
        got = []
        for _ in range(n):
            p = self.free.pop() if self.free else self._evict_one()
            self.refs[p] = 1
            got.append(p)
        self.owned.setdefault(seq_id, []).extend(got)
        return got

    def _evict_one(self) -> int:
        p = min(self.pool, key=self.pool.get)
        del self.pool[p]
        key = self.key_of.pop(p)
        del self.index[key]
        self._published_at.pop(p, None)
        # demote instead of drop: the hook copies the page's KV to the
        # spill tier (device->host), and the key keeps matching there —
        # the physical page is reclaimed either way
        if self.demote_hook is not None and self.demote_hook(p, key):
            self.demoted += 1
        else:
            self.evicted += 1
        return p

    def oldest_warm(self, n: int):
        """The ``n`` oldest warm-pool pages with their keys — the
        watermark-demotion candidates (bookkeeping untouched; pair with
        :meth:`reclaim_warm` after the engine captured their KV)."""
        order = sorted(self.pool, key=self.pool.get)[:max(n, 0)]
        return [(p, self.key_of[p]) for p in order]

    def reclaim_warm(self, pages, demoted: bool) -> None:
        """Remove warm pages from the pool + index and free them,
        counting them demoted (their KV lives on the spill tier now) or
        evicted (dropped).  Pages that left the pool since
        :meth:`oldest_warm` (revived by a share) are skipped."""
        for p in pages:
            if p not in self.pool:
                continue
            del self.pool[p]
            del self.index[self.key_of.pop(p)]
            self._published_at.pop(p, None)
            self.free.append(p)
            if demoted:
                self.demoted += 1
            else:
                self.evicted += 1

    def lookup(self, keys):
        """Longest cached prefix: walk the chained keys in order and
        return the matched pages up to the first miss."""
        pages = []
        for k in keys:
            p = self.index.get(k)
            if p is None:
                break
            pages.append(p)
        return pages

    def lookup_tiered(self, keys):
        """Longest cached prefix across ALL tiers: walk the chained
        keys and return ``("hbm", page)`` / ``("tier", key)`` matches
        up to the first total miss.  HBM wins when a span is in both
        (a promoted page's spill copy is kept as a free re-demote)."""
        out = []
        for k in keys:
            p = self.index.get(k)
            if p is not None:
                out.append(("hbm", p))
                continue
            if self.spill is not None and self.spill.has(k):
                out.append(("tier", k))
                continue
            break
        return out

    # ------------------------------------------------------- promotion
    # (tier hit -> fresh HBM page; the engine streams the payload back
    # and calls finish; the page is quarantined from reclaim meanwhile)
    def begin_promotion(self, page: int, key: bytes) -> None:
        """Mark an allocated page as receiving a tier promotion: it
        must not be published (content hasn't landed) nor ever handed
        back out before :meth:`finish_promotion` or
        :meth:`cancel_promotion` resolves it."""
        if page not in self.refs:
            raise ValueError(f"begin_promotion of unowned page {page}")
        self.promoting[page] = key

    def finish_promotion(self, page: int, key: bytes) -> bool:
        """Payload landed: publish the page under its content key so
        concurrent same-prefix admissions share it.  A page whose owner
        vanished mid-flight (parked by ``release``) just frees.
        Returns True when the page was newly indexed."""
        self.promoting.pop(page, None)
        if page in self._parked:
            self._parked.remove(page)
            self.free.append(page)
            return False
        self.promoted += 1
        return self.publish(page, key)

    def cancel_promotion(self, page: int) -> None:
        """Abandon an in-flight promotion (preemption): the page stays
        owned by its sequence (released through the normal path) unless
        it was already parked, in which case it frees now."""
        self.promoting.pop(page, None)
        if page in self._parked:
            self._parked.remove(page)
            self.free.append(page)

    def share(self, seq_id, pages) -> None:
        """Map already-cached pages into ``seq_id``'s ownership with a
        refcount bump each; warm-pool pages revive (leave the pool) —
        the prefix-hit path.  Shared pages are READ-ONLY by contract:
        the engine only ever writes at a sequence's own frontier, which
        lies past every shared page."""
        for p in pages:
            if p in self.pool:
                del self.pool[p]
                self.refs[p] = 1
            else:
                self.refs[p] += 1
        if pages:
            self.owned.setdefault(seq_id, []).extend(pages)

    def publish(self, page: int, key: bytes) -> bool:
        """Content-address a live FULL page so future prompts can match
        it.  Dedup keeps the first publisher (an identical span already
        indexed under ``key`` wins); a page publishes at most once.
        Returns True when the page was newly indexed."""
        if self.cache_pages <= 0 or key in self.index \
                or page in self.key_of:
            return False
        if page not in self.refs:
            raise ValueError(f"publish of unowned page {page}")
        self.index[key] = page
        self.key_of[page] = key
        self._tick += 1
        self._published_at[page] = self._tick
        self.published += 1
        return True

    def writable(self, page: int) -> bool:
        """True when ``page`` may be written in place: exactly one live
        reference and never published.  A published page's CONTENT is
        pinned by its content key (a write would poison the index for
        every future match), and a shared page belongs to other
        sequences too.  Structurally the engine only ever writes at a
        sequence's own frontier, which lies past every shared/published
        page — the speculative verify sweep asserts this invariant on
        each page its K+1-position write window touches before any
        rejected-draft garbage can land (the COW-rollback guarantee)."""
        return self.refs.get(page, 0) == 1 and page not in self.key_of

    def release(self, seq_id) -> None:
        """Drop one reference per page owned by ``seq_id``.  Pages
        hitting refcount 0 return to the free list — unless published,
        in which case they enter the warm pool and keep their KV
        matchable until allocation pressure (or the pool cap) evicts
        them."""
        for p in reversed(self.owned.pop(seq_id, [])):
            self.refs[p] -= 1
            if self.refs[p]:
                continue
            del self.refs[p]
            if p in self.key_of:
                self._tick += 1
                self.pool[p] = (self._published_at[p]
                                if self.eviction == "fifo" else self._tick)
                while len(self.pool) > self.cache_pages:
                    self.free.append(self._evict_one())
            elif p in self.promoting:
                # released mid-promotion (preempt raced the upload):
                # park until the promotion resolves — freeing now could
                # hand the page to a new owner while the payload lands
                self._parked.append(p)
            else:
                self.free.append(p)


# ------------------------------------------------ in-place page writers
# The pool [L, KV, P, ps, Dh] is a CARRY of the models' layer loop, and a
# writer scatters only the new rows into it at a (possibly traced)
# ``layer``.  The token and chunk writers scatter windows of ONE Dh row
# (index arrays over kv head, page, row) and the whole-prompt writer
# windows of one page: both suit the pool's stored layout, so the
# compiled program updates it in place and holds no pool- or layer-sized
# copy (tests/test_aot_tpu_compile.py pins that for the described v5e).
# A [KV, Dh] window (one token across heads) does not: the TPU compiler
# re-lays the whole pool out around such a scatter.  Under TP the pool is
# sharded on KV and GSPMD partitions the row scatter by that index (it
# gathers the few new rows, never the pool).
def _row_targets(pool, table, pos):
    """Page id and row within the page of every position in ``pos``
    ([B] or [B, C]) of the rows ``table`` describes.  A position at or
    past a row's capacity gets the out-of-range page id ``num_pages``,
    which the scatter drops (free: no gather/blend on the hot path), so
    overflow never lands on a live slot (advisor finding r1)."""
    num_pages, page_size = pool.shape[2], pool.shape[3]
    max_pages = table.shape[1]
    pos2 = pos.reshape(pos.shape[0], -1)
    page_slot = jnp.minimum(pos2 // page_size, max_pages - 1)
    page_id = jnp.take_along_axis(table, page_slot, axis=1)
    page_id = jnp.where(pos2 < max_pages * page_size, page_id, num_pages)
    return page_id.reshape(-1), (pos2 % page_size).reshape(-1)


def _scatter_rows(pool, layer, page_id, in_page, rows):
    """``pool[layer, h, page_id[n], in_page[n]] = rows[n, h]`` for every
    kv head h.  pool: [L, KV, P, ps, D]; page_id/in_page: [N]; rows:
    [N, KV, D].  One vectorized scatter (no per-row unroll: decode B can
    be large under continuous batching)."""
    kv = jnp.arange(pool.shape[1], dtype=jnp.int32)[None, :]
    return pool.at[layer, kv, page_id[:, None], in_page[:, None]].set(
        rows.astype(pool.dtype), mode="drop")


def _scatter_pages(pool, layer, table, new, fill=0):
    """Whole pages of a fresh prompt: ``new`` [B, T, KV, D] from position
    0 into the first ceil(T / ps) pages of each table row, the tail of
    the last page padded with ``fill``."""
    B, T, KV, D = new.shape
    page_size = pool.shape[3]
    np_used = -(-T // page_size)
    pad = np_used * page_size - T
    if pad:
        new = jnp.concatenate(
            [new, jnp.full((B, pad, KV, D), fill, new.dtype)], axis=1)
    # [B, np, ps, KV, D] → [B*np, KV, ps, D]
    blocks = new.reshape(B, np_used, page_size, KV, D) \
        .transpose(0, 1, 3, 2, 4).reshape(B * np_used, KV, page_size, D)
    ids = table[:, :np_used].reshape(-1)                # [B*np]
    return pool.at[layer, :, ids].set(blocks.astype(pool.dtype))


def write_token_pages(pool_k, pool_v, layer, new_k, new_v, table,
                      seq_lens):
    """Append one token's K/V ([B, KV, Dh]) at each sequence frontier of
    ``layer``; a sequence at capacity writes nothing."""
    page_id, in_page = _row_targets(pool_k, table, seq_lens)
    return (_scatter_rows(pool_k, layer, page_id, in_page, new_k),
            _scatter_rows(pool_v, layer, page_id, in_page, new_v))


def write_prompt_pages(pool_k, pool_v, layer, new_k, new_v, table):
    """Bulk-write a fresh prompt's K/V ([B, T, KV, Dh]) into pages,
    starting at position 0 (prefill of an empty cache)."""
    return (_scatter_pages(pool_k, layer, table, new_k),
            _scatter_pages(pool_v, layer, table, new_v))


def write_chunk_pages(pool_k, pool_v, layer, new_k, new_v, table, start):
    """Write a mid-sequence chunk's K/V ([B, C, KV, Dh]) at each row's
    frontier ``start`` ([B] i32) — the chunked-prefill generalization of
    :func:`write_prompt_pages` (arbitrary, per-row, non-page-aligned
    offsets): the :func:`write_token_pages` scatter over B*C rows.
    Positions past a row's capacity are dropped."""
    B, C, KV, Dh = new_k.shape
    pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]   # [B, C]
    page_id, in_page = _row_targets(pool_k, table, pos)
    return (_scatter_rows(pool_k, layer, page_id, in_page,
                          new_k.reshape(B * C, KV, Dh)),
            _scatter_rows(pool_v, layer, page_id, in_page,
                          new_v.reshape(B * C, KV, Dh)))


# ------------------------------------------- int8-resident page helpers
# (kv_tier.quantized_resident: the resident pool holds the SAME symmetric
# per-token-row int8 codec kv_tier.quantize_page uses on demote, so a
# promotion publishes stored codes directly and the attention kernel
# dequantizes in VMEM.  These are the jnp twins of the numpy codec in
# deepspeed_tpu/inference/kv_tier.py — keep the rounding identical or the
# lossless demote→promote→demote round trip breaks.)
# dstpu: hot-path
def quantize_kv_rows(x):
    """Symmetric per-last-dim-row int8 quantization of K/V rows on
    device: ``x [..., Dh]`` → ``(codes int8 [..., Dh], scales f32
    [..., 1])``.  Matches ``kv_tier.quantize_page`` bit-for-bit
    (``scale = amax/127``, zero rows get scale 1.0, round-half-even)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax / 127.0)
    codes = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return codes, scale


# dstpu: hot-path
def dequantize_pages(codes, scales, dtype):
    """Dequantize int8 page codes with their per-row scales back to
    ``dtype`` — the XLA twin of the in-kernel VMEM dequant (and the
    oracle the quant-kernel identity tests reference against)."""
    return (codes.astype(jnp.float32) * scales).astype(dtype)


def write_token_pages_quant(pool_k, pool_ks, pool_v, pool_vs, layer,
                            new_k, new_v, table, seq_lens):
    """:func:`write_token_pages` for the int8-resident store: quantize
    the appended rows on device and scatter codes + scales with the
    same frontier/overflow-drop math.  Scale stores are
    ``[L, KV, P, ps, 1]`` f32."""
    page_id, in_page = _row_targets(pool_k, table, seq_lens)

    def upd(store, sstore, new):
        codes, scale = quantize_kv_rows(new)          # [B, KV, Dh/1]
        return (_scatter_rows(store, layer, page_id, in_page, codes),
                _scatter_rows(sstore, layer, page_id, in_page, scale))

    pk, pks = upd(pool_k, pool_ks, new_k)
    pv, pvs = upd(pool_v, pool_vs, new_v)
    return pk, pks, pv, pvs


def write_prompt_pages_quant(pool_k, pool_ks, pool_v, pool_vs, layer,
                             new_k, new_v, table):
    """:func:`write_prompt_pages` for the int8-resident store (prefill
    of an empty cache, quantizing per token row)."""
    def upd(store, sstore, new):
        codes, scale = quantize_kv_rows(new)     # [B,T,KV,Dh], [B,T,KV,1]
        # zero rows carry scale 1.0 by the codec's convention
        return (_scatter_pages(store, layer, table, codes),
                _scatter_pages(sstore, layer, table, scale, fill=1))

    pk, pks = upd(pool_k, pool_ks, new_k)
    pv, pvs = upd(pool_v, pool_vs, new_v)
    return pk, pks, pv, pvs


def write_chunk_pages_quant(pool_k, pool_ks, pool_v, pool_vs, layer,
                            new_k, new_v, table, start):
    """:func:`write_chunk_pages` for the int8-resident store (split-fuse
    continuation chunks at per-row frontiers)."""
    B, C, KV, Dh = new_k.shape
    pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    page_id, in_page = _row_targets(pool_k, table, pos)

    def upd(store, sstore, new):
        codes, scale = quantize_kv_rows(new)
        return (_scatter_rows(store, layer, page_id, in_page,
                              codes.reshape(B * C, KV, Dh)),
                _scatter_rows(sstore, layer, page_id, in_page,
                              scale.reshape(B * C, KV, 1)))

    pk, pks = upd(pool_k, pool_ks, new_k)
    pv, pvs = upd(pool_v, pool_vs, new_v)
    return pk, pks, pv, pvs


# -------------------------------------------------------- numerics oracle
# Every reader below takes the whole pool [L, KV, P, ps, Dh] and a
# (possibly traced) ``layer``, or one layer's pages [KV, P, ps, Dh] — a
# pool of one layer, read at layer 0.
def _as_pool(layer, *pages):
    if pages[0].ndim == 4:
        return (0, *(p if p is None else p[None] for p in pages))
    return (layer, *pages)


def _layer_operand(layer):
    """``layer`` as a scalar-prefetch operand of the Mosaic kernels."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _gather_rows(pages, layer, table, scales=None, dtype=None):
    """The rows ``table`` [B, mp] names in ``layer``, as [B, KV, mp*ps,
    Dh]: ONE gather with the layer index in it, so no layer-sized slice
    of the pool is materialised first.  ``scales`` (int8-resident
    pages): dequantize the gathered rows to ``dtype`` — elementwise, so
    the bits are those of dequantizing the whole layer first."""
    layer, pages, scales = _as_pool(layer, pages, scales)
    B, mp = table.shape
    # indices (layer, page) per table entry; the kv heads stay a window
    # dim (under TP the pool is sharded on it) and land second: the
    # result is [B, KV, mp, ps, Dh], a reshape away from what is wanted
    idx = jnp.stack([jnp.full_like(table, layer), table], axis=-1)
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(1, 3, 4), collapsed_slice_dims=(0, 2),
        start_index_map=(0, 2))

    def rows(pool):
        # A table names only pages of the pool, so no mode changes a
        # value.  "fill" is the measured choice: behind its mask the TPU
        # compiler fuses the callers' f32 converts into their products,
        # where after a clamped gather it writes the gathered K and V
        # out again in f32 (GPT-2 1.3B at 28 rows: 37 ms a decode step
        # against 67, PERF.md 6, PR 25; the AOT test's bound on
        # temporaries pins it).  Zeros, not NaN, so that a page id out
        # of range under a masked position could not reach the output.
        _, KV, _, ps, D = pool.shape
        return jax.lax.gather(pool, idx, dnums, (1, KV, 1, ps, D),
                              mode="fill", fill_value=0).reshape(
            B, KV, mp * ps, D)

    g = rows(pages)
    return g if scales is None else dequantize_pages(g, rows(scales), dtype)


# A chunk's float32 scores, every head over the whole table, that the
# gathered reader holds at once; past it the K/V heads go through one at
# a time.  The widest the benchmark's other chunk programs hold is 1.06
# GiB (16 heads x 1,024 x 17,408, docqa-sat), and they lower as they
# did; 48 heads over 18,432 rows would be 3.4 GiB, and are 0.42 a pass
# (over 8,192 rows 1.5 GiB, which did not fit beside a full chip's
# arguments: v5e, PR 44).
_CHUNK_SCORE_BYTES = 5 << 28


def paged_chunk_attention_reference(q, k_pages, v_pages, table, start,
                                    scale: Optional[float] = None, *,
                                    layer=None, k_scale=None, v_scale=None):
    """Chunked-prefill attention: q [B, C, H, Dh] at positions
    ``start + 0..C-1`` attends causally over the gathered pages (which
    must already contain the chunk's own K/V).  Returns [B, C, H, Dh].

    This is the split-fuse read path: history + chunk in one masked
    gather, so a long prompt can be absorbed ``C`` tokens per iteration
    between decode steps.  ``k_scale``/``v_scale``: the pages are int8
    codes, dequantized to q's dtype after the gather.  The scores are
    held for every head at once up to ``_CHUNK_SCORE_BYTES``, a rule of
    the shapes; past it a K/V head's query heads at a time."""
    B, C, H, Dh = q.shape
    kg = _gather_rows(k_pages, layer, table, k_scale, q.dtype)
    vg = _gather_rows(v_pages, layer, table, v_scale, q.dtype)
    KV, S = kg.shape[1], kg.shape[2]
    G = H // KV
    scale = scale if scale is not None else Dh ** -0.5
    qg = q.reshape(B, C, KV, G, Dh)
    if 4 * B * C * H * S > _CHUNK_SCORE_BYTES:
        kpos = jnp.arange(S)[None, None]
        qpos = (start[:, None] + jnp.arange(C)[None])[:, :, None]

        def head(qkv):
            qk, kk, vk = qkv          # [B, C, G, Dh], [B, S, Dh] twice
            s = jnp.einsum("bcgd,bsd->bcgs", qk.astype(jnp.float32),
                           kk.astype(jnp.float32)) * scale
            s = jnp.where((kpos <= qpos)[:, :, None], s, NEG_INF)
            return jnp.einsum("bcgs,bsd->bcgd", jax.nn.softmax(s, axis=-1),
                              vk.astype(jnp.float32)).astype(q.dtype)

        out = jax.lax.map(head, (jnp.moveaxis(qg, 2, 0),
                                 jnp.moveaxis(kg, 1, 0),
                                 jnp.moveaxis(vg, 1, 0)))
        return jnp.moveaxis(out, 0, 2).reshape(B, C, H, Dh)
    s = jnp.einsum("bckgd,bksd->bckgs", qg.astype(jnp.float32),
                   kg.astype(jnp.float32)) * scale
    kpos = jnp.arange(S)[None, None]                        # [1, 1, S]
    qpos = (start[:, None] + jnp.arange(C)[None])[:, :, None]  # [B, C, 1]
    s = jnp.where((kpos <= qpos)[:, :, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bckgs,bksd->bckgd", p, vg.astype(jnp.float32))
    return out.reshape(B, C, H, Dh).astype(q.dtype)


def paged_attention_reference(q, k_pages, v_pages, table, seq_lens,
                              scale: Optional[float] = None, *,
                              layer=None, k_scale=None, v_scale=None):
    """q: [B, H, Dh]; k/v_pages: the pool and ``layer``, or one layer's
    [KV, P, ps, Dh]; table: [B, max_pages]; seq_lens: [B].  Returns
    [B, H, Dh]."""
    B, H, Dh = q.shape
    kg = _gather_rows(k_pages, layer, table, k_scale, q.dtype)
    vg = _gather_rows(v_pages, layer, table, v_scale, q.dtype)
    KV, S = kg.shape[1], kg.shape[2]
    G = H // KV
    scale = scale if scale is not None else Dh ** -0.5
    qg = q.reshape(B, KV, G, Dh)
    s = jnp.einsum("bkgd,bksd->bkgs", qg.astype(jnp.float32),
                   kg.astype(jnp.float32)) * scale
    valid = jnp.arange(S)[None] < seq_lens[:, None]         # [B, S]
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, vg.astype(jnp.float32))
    # empty sequences (continuous batching admits them): zero, not mean-of-V
    out = jnp.where(seq_lens[:, None, None, None] > 0, out, 0.0)
    return out.reshape(B, H, Dh).astype(q.dtype)


# ------------------------------------------- live-pages-only decode kernel
# One K (or V) block of the decode kernel in VMEM, per buffer slot: big
# enough that a block's products amortise the loop's fixed cost, small
# enough that two slots of K and V plus their f32 copies stay far inside
# the 16 MiB a v5e kernel may use.
_DECODE_BLOCK_BYTES = 512 << 10


def decode_pages_per_block(n_kv: int, page_size: int, head_dim: int,
                           itemsize: int, max_pages: int) -> int:
    """Pages the decode kernel streams per inner iteration: what fits
    ``_DECODE_BLOCK_BYTES`` with every kv head of a page in one copy."""
    page_bytes = n_kv * page_size * head_dim * itemsize
    return max(1, min(max_pages, _DECODE_BLOCK_BYTES // page_bytes))


def _stream_live_blocks(table_ref, lens_ref, layer, streams, sem, turn,
                        body, init, *, ps):
    """Row ``pl.program_id(0)``'s ``body(c, slot, carry)`` over its blocks
    of ``ppb`` pages, block ``c`` in ``slot`` of every buffer when its
    ``body`` runs (design note: above :func:`_start_pages`).  ``streams``:
    (pool [L, KV, P, ps, W] in HBM, buffer [2, KV, ppb, ps, W] in VMEM)
    pairs; ``sem``: DMA semaphores [2, len(streams)]; ``turn``: SMEM
    scratch, the slot of this row's first block, which the row before it
    started.  No table entry past the row's live pages is read; a dead
    slot of a row's last block holds a live page's rows or the zeros the
    first row puts there: finite, under keys the ``body`` masks.  Every
    copy started is awaited before the grid ends (rows run in order)."""
    b, rows, ppb = pl.program_id(0), lens_ref.shape[0], streams[0][1].shape[2]
    pages = lambda r: jnp.minimum(table_ref.shape[1],
                                  (lens_ref[r] + ps - 1) // ps)
    live = pages(b)
    nblk = (live + ppb - 1) // ppb
    after = jnp.minimum(b + 1, rows - 1)
    live_after = jnp.where(b + 1 < rows, pages(after), 0)

    def fetch(row, c, slot, live):
        _start_pages(table_ref, row, c * ppb, jnp.minimum(
            ppb, live - c * ppb), layer, streams, sem, slot)

    @pl.when(b == 0)
    def _():
        turn[0] = 0
        for _, buf in streams:              # 0 * what VMEM held may be NaN
            buf[...] = jnp.zeros(buf.shape, buf.dtype)

    first = turn[0]
    own = jnp.logical_and(b == 0, nblk > 0)     # no row before the first

    @pl.when(jnp.logical_or(own, jnp.logical_and(nblk == 0, live_after > 0)))
    def _():                                # an empty row hands over too
        fetch(jnp.where(own, b, after), 0, first,
              jnp.where(own, live, live_after))

    def block(c, carry):
        slot = jax.lax.rem(first + c, 2)
        last = c + 1 == nblk

        @pl.when(jnp.logical_or(jnp.logical_not(last), live_after > 0))
        def _():
            fetch(jnp.where(last, after, b), jnp.where(last, 0, c + 1),
                  1 - slot, jnp.where(last, live_after, live))

        _await_pages(jnp.minimum(ppb, live - c * ppb), streams, sem, slot)
        return body(c, slot, carry)

    carry = jax.lax.fori_loop(0, nblk, block, init)
    turn[0] = jax.lax.rem(first + nblk, 2)
    return carry


# The step's new row reaches its page by the tile: a copy into a packed
# page addresses whole tiles of 8 rows (the chip's compiler refuses a
# slice of one or two, "must be aligned to tiling (8)", AOT, PR 54), so a
# row's tile is read, the row set in VMEM and the tile written back.  Both
# copies have a latency no product hides in a row with few pages, so they
# run ahead and behind the rows: a tile is read ``_APPEND_AHEAD`` rows
# before its row's turn, in one of ``_APPEND_TILES`` buffers, and its
# write-back awaited when the buffer comes round again.  GPT-2 1.3B's 24
# layers of 28 rows, the kernel in a loop of its own (v5e, PR 54): 0.95 ms
# with 3 rows live and 3.64 with all, where the row scatter and the reader
# took 3.16 and 5.60; the same at 2, 4 and 8 rows ahead, and 1.12 and 3.77
# by the page of 16 rows.
_APPEND_TILE_ROWS = 8
_APPEND_TILES, _APPEND_AHEAD = 4, 2


def _row_appender(table_ref, lens_ref, layer, streams, r_sem, w_sem, *, ps):
    """The grid's rows' new K/V rows into their pages, row
    ``pl.program_id(0)``'s in this step.  ``streams``: (pool in HBM, the
    same buffer as the call's result, tiles [held, KV, g, Dh] in VMEM)
    triples; ``lens_ref``: the lengths before the write, so row r's goes
    to position ``lens_ref[r]``, row ``pos % ps`` of page ``table[r, pos
    // ps]``, for every kv head: the contract of :func:`_row_targets`, a
    row at capacity (or whose entry names no page of the pool) writes
    nothing.  Returns (ahead, write): ``ahead()`` before the row's pages
    are swept starts the reads; ``write(news)`` after it sets ``news``
    ([KV, 1, Dh] f32 a stream) in the row's tiles and starts them back.
    No byte of the pool but the rows' own changes, and every copy started
    is awaited before the grid ends (rows run in order)."""
    b, rows, mp = pl.program_id(0), lens_ref.shape[0], table_ref.shape[1]
    held, g = streams[0][2].shape[0], streams[0][2].shape[2]

    def target(r):
        """(row r writes its row, the page, the tile of the page's rows)."""
        pos = lens_ref[r]
        pid = table_ref[r, jnp.minimum(pos // ps, mp - 1)]
        writes = (pos < mp * ps) & (pid >= 0) & (pid < streams[0][0].shape[2])
        return writes, pid, pl.ds(pl.multiple_of(pos % ps // g * g, g), g)

    def copies(r, out: bool):
        """Row r's tile a stream, into VMEM or ``out`` of it."""
        _, pid, at = target(r)
        w = jax.lax.rem(r, held)
        return [pltpu.make_async_copy(
            *((buf.at[w], res.at[layer, :, pid, at]) if out
              else (pool.at[layer, :, pid, at], buf.at[w])),
            (w_sem if out else r_sem).at[w, i])
            for i, (pool, res, buf) in enumerate(streams)]

    def when_written(r, do, in_grid=None):
        """``do()`` where row r (of the grid: ``in_grid``) writes its row."""
        def written():
            @pl.when(target(r)[0])
            def _():
                do()

        written() if in_grid is None else pl.when(in_grid)(written)

    start = lambda r, out: lambda: [c.start() for c in copies(r, out)]
    wait = lambda r, out: lambda: [c.wait() for c in copies(r, out)]

    def ahead():
        def row(r, _):                      # its buffer: row r - held's
            when_written(r - held, wait(r - held, True), r >= held)
            when_written(r, start(r, False))

        nxt = b + _APPEND_AHEAD             # the first row: those before too
        jax.lax.fori_loop(jnp.where(b == 0, 0, nxt),
                          jnp.minimum(nxt + 1, rows), row, None)

    def write(news):
        def patch():
            wait(b, False)()
            w, pos = jax.lax.rem(b, held), lens_ref[b]
            for (_, _, buf), new in zip(streams, news):
                row = jax.lax.broadcasted_iota(jnp.int32, buf.shape[1:], 1)
                buf[w] = jnp.where(row == pos % g, new,
                                   buf[w].astype(jnp.float32)
                                   ).astype(buf.dtype)
            start(b, True)()

        when_written(b, patch)

        @pl.when(b == rows - 1)             # the writes nobody awaited yet
        def _():
            first = max(0, rows - held)
            jax.lax.fori_loop(
                first, rows,
                lambda r, _: when_written(r, wait(r, True)), None)

    return ahead, write


def _decode_kernel(table_ref, lens_ref, layer_ref, q_ref, *refs, scale, ps,
                   append: bool):
    """One grid step a batch row.  The row's live pages stream ``ppb`` at
    a time through a double-buffered VMEM scratch (:func:`_stream_live_
    blocks`), each page's K (and V) for ALL kv heads in one strided copy
    out of the stored pool [L, KV, P, ps, Dh]; the products are batched
    over the kv heads.  Nothing past the row's ``seq_len`` is dereferenced
    and a row with ``seq_len == 0`` starts no copy and returns zeros.
    Scores, the online softmax and the accumulator are f32.

    ``append``: the step's new row a slot (``nk_ref``, ``nv_ref``, in the
    pool's dtype) is the softmax's first term, from VMEM, and goes to its
    page beside the sweep (:func:`_row_appender`): the sweep, over the
    ``seq_len`` keys from before the write, never expects it in HBM (a
    row's first block is fetched by the row before it).  A row at
    capacity attends to its pages only."""
    if append:
        (nk_ref, nv_ref, k_hbm, v_hbm, o_ref, ko_hbm, vo_hbm, kb, vb, sem,
         turn, kw, vw, r_sem, w_sem) = refs
    else:
        k_hbm, v_hbm, o_ref, kb, vb, sem, turn = refs
    n = lens_ref[pl.program_id(0)]
    q = q_ref[0].astype(jnp.float32)                # [KV, g8, Dh]
    kv, g8, dh = q.shape
    keys = kb.shape[2] * ps

    def block(buf, slot):
        """One slot's pages as [KV, ppb * ps, Dh] in f32: a page is a
        whole number of tiles, so the merge moves nothing."""
        return buf[slot].reshape(kv, keys, dh).astype(jnp.float32)

    def attend(c, slot, carry):
        m, l, acc = carry
        s = jax.lax.dot_general(
            q, block(kb, slot), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale     # [KV, g8, S]
        kpos = c * keys + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < n, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # block 0 always holds position 0 < n, so m_new is finite; the
        # select keeps a masked entry at exactly 0, over finite values
        pr = jnp.where(kpos < n, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(pr, axis=2, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            pr, block(vb, slot), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)             # [KV, g8, Dh]
        return m_new, l, acc

    init = (jnp.full((kv, g8, 1), NEG_INF, jnp.float32),
            jnp.zeros((kv, g8, 1), jnp.float32),
            jnp.zeros((kv, g8, dh), jnp.float32))
    if append:
        ahead, write = _row_appender(
            table_ref, lens_ref, layer_ref[0],
            ((k_hbm, ko_hbm, kw), (v_hbm, vo_hbm, vw)), r_sem, w_sem, ps=ps)
        ahead()
        news = [ref[0].astype(jnp.float32) for ref in (nk_ref, nv_ref)]
        room = n < table_ref.shape[1] * ps
        init = (jnp.where(room, jnp.sum(q * news[0], axis=2, keepdims=True)
                          * scale, NEG_INF),        # the row's own key: p = 1
                jnp.where(room, 1.0, init[1]),
                jnp.where(room, jnp.broadcast_to(news[1], init[2].shape),
                          0.0))
    m, l, acc = _stream_live_blocks(
        table_ref, lens_ref, layer_ref[0], ((k_hbm, kb), (v_hbm, vb)), sem,
        turn, attend, init, ps=ps)
    if append:
        write(news)
    l = jnp.where(l == 0.0, 1.0, l)                 # empty rows → zeros
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention_v2(q, k_pages, v_pages, table, seq_lens,
                              scale: Optional[float] = None,
                              pages_per_block: Optional[int] = None,
                              interpret: bool = False, layer=None,
                              new_k=None, new_v=None):
    """Paged decode attention that reads live pages only (same contract
    as :func:`paged_attention_reference`).

    q: [B, H, Dh] (one decode step), k/v_pages: the pool and ``layer``
    or one layer's [KV, P, ps, Dh], table: [B, mp] int32, seq_lens: [B]
    int32.  The pool stays in HBM (``pl.ANY``) in its stored layout; see
    :func:`_decode_kernel`.  Its cost follows the live tokens, not
    slots x ``max_seq``: table entries past ``seq_len`` (stale, or not a
    page of the pool) are never dereferenced.  ``pages_per_block`` is
    derived (:func:`decode_pages_per_block`); tests pass it to put a
    block edge where they want one.

    ``new_k``/``new_v`` [B, KV, Dh]: the step's new row a slot, and
    ``seq_lens`` the lengths BEFORE it.  The kernel writes the rows where
    :func:`write_token_pages` would and attends to each with its row's
    pages, as :func:`paged_attention_reference` over the written pool at
    ``seq_lens + 1`` (a row at capacity: its pages alone, nothing
    written).  Returns (attn, k_pages, v_pages), the pools in the
    buffers they came in."""
    B, H, Dh = q.shape
    one_layer = k_pages.ndim == 4
    layer, k_pages, v_pages = _as_pool(layer, k_pages, v_pages)
    _, KV, _, ps, _ = k_pages.shape
    G = H // KV
    mp = table.shape[1]
    scale = scale if scale is not None else Dh ** -0.5
    ppb = min(mp, pages_per_block or decode_pages_per_block(
        KV, ps, Dh, k_pages.dtype.itemsize, mp))
    g8 = -(-G // 8) * 8                             # sublane alignment
    qg = q.reshape(B, KV, G, Dh)
    if g8 != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g8 - G), (0, 0)))

    row = lambda b, *_: (b, 0, 0, 0)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    attn = jax.ShapeDtypeStruct((B, KV, g8, Dh), q.dtype)
    in_specs = [pl.BlockSpec((1, KV, g8, Dh), row), any_space, any_space]
    out_specs, out_shape = pl.BlockSpec((1, KV, g8, Dh), row), attn
    scratch = [pltpu.VMEM((2, KV, ppb, ps, Dh), k_pages.dtype),
               pltpu.VMEM((2, KV, ppb, ps, Dh), v_pages.dtype),
               pltpu.SemaphoreType.DMA((2, 2)),
               pltpu.SMEM((1,), jnp.int32)]
    append = new_k is not None
    news, aliases = (), {}
    if append:
        news = tuple(n.astype(p.dtype)[:, :, None]          # [B, KV, 1, Dh]
                     for n, p in ((new_k, k_pages), (new_v, v_pages)))
        in_specs[1:1] = [pl.BlockSpec((1, KV, 1, Dh), row)] * 2
        out_specs, out_shape = [out_specs, any_space, any_space], [
            attn, *(jax.ShapeDtypeStruct(p.shape, p.dtype)
                    for p in (k_pages, v_pages))]
        g = math.gcd(ps, _APPEND_TILE_ROWS)
        scratch += [pltpu.VMEM((_APPEND_TILES, KV, g, Dh), k_pages.dtype),
                    pltpu.VMEM((_APPEND_TILES, KV, g, Dh), v_pages.dtype),
                    pltpu.SemaphoreType.DMA((_APPEND_TILES, 2)),
                    pltpu.SemaphoreType.DMA((_APPEND_TILES, 2))]
        # operands 6 and 7 (behind table, seq_lens, layer, q and the rows):
        # the pools come back in their buffers
        aliases = {6: 1, 7: 2}
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, ps=ps, append=append),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,   # table, seq_lens, layer
            grid=(B,), in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape, input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(   # the rows in order
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dstpu_paged_decode",
    )(table, seq_lens, _layer_operand(layer), qg, *news, k_pages, v_pages)
    if not append:
        return out[:, :, :G].reshape(B, H, Dh)
    out, *pools = out
    return (out[:, :, :G].reshape(B, H, Dh),
            *(p[0] if one_layer else p for p in pools))


# ------------------------------------------ blocked chunk reader (v2)
# Why the grid is (row, block of queries) with the K/V heads a loop inside:
# a page of 16 rows is 4 KiB a K/V head, and starting a copy is ~0.07 us of
# scalar work the products do not hide.  One strided copy a page for all
# heads, shared by the heads' loop, is an eighth of a copy a page a head,
# and wider blocks of queries sweep the pages fewer times; the price is
# every head's running softmax in VMEM scratch (a tile a row a statistic:
# as much as the f32 sum).
def _chunk_v2_kernel(table_ref, start_ref, layer_ref, q_ref, k_hbm, v_hbm,
                     o_ref, qs, acc, m_scr, l_scr, kb, vb, sem, *, scale, ps,
                     group, chunk):
    """One grid step a (row, block of ``bq`` queries).  The block sweeps
    the row's pages ``ppb`` at a time up to ITS OWN frontier (``start +
    (i + 1) bq``): each page's K (and V) for all K/V heads in one strided
    copy out of the stored pool, as :func:`_decode_kernel`, through a
    double-buffered scratch.  Nothing behind the frontier is dereferenced
    (the last block's slots past it take the last live page again: masked
    keys, finite values, and every block awaits one byte count), no key
    block wholly above the diagonal is computed, and only the blocks that
    reach past the block's first query are masked.  A K/V head's
    ``group`` query heads are stacked as the rows of one product
    (``qs[h]``: ``group * bq`` rows), operands in the dtype the pool
    stores, f32 accumulation; the softmax runs across key blocks with
    its max, sum and rescale in f32 (``m_scr``, ``l_scr``, ``acc``).
    Every loop is rolled and traced once: the body's size is set-up time
    in each of a build's chunk programs."""
    b, i = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    kv, rows, dh = acc.shape
    ppb = kb.shape[2]
    bq, bk = rows // group, ppb * ps
    first = start_ref[b] + i * bq           # the block's first position
    pages_live = jnp.minimum(table_ref.shape[1], (   # a padded chunk may pass
        start_ref[b] + jnp.minimum((i + 1) * bq, chunk) + ps - 1) // ps)  # it
    nblk = (pages_live + ppb - 1) // ppb
    n_open = (first + 1) // bk              # blocks every row sees whole
    lanes = lambda h: pl.ds(pl.multiple_of(h * (group * dh), group * dh),
                            group * dh)

    def fetch(c, slot):
        def page(j, _):
            pid = table_ref[b, jnp.minimum(c * ppb + j, pages_live - 1)]
            pltpu.make_async_copy(k_hbm.at[layer, :, pid],
                                  kb.at[slot, :, j], sem.at[slot, 0]).start()
            pltpu.make_async_copy(v_hbm.at[layer, :, pid],
                                  vb.at[slot, :, j], sem.at[slot, 1]).start()

        jax.lax.fori_loop(0, ppb, page, None)

    def stage(h, _):                        # the query heads, head-major
        q = q_ref[0, :, lanes(h)]
        qs[h] = jnp.concatenate([q[:, g * dh:(g + 1) * dh]
                                 for g in range(group)], 0).astype(qs.dtype)
        m_scr[h] = jnp.full(m_scr.shape[1:], NEG_INF, jnp.float32)
        l_scr[h] = jnp.zeros(l_scr.shape[1:], jnp.float32)
        acc[h] = jnp.zeros(acc.shape[1:], jnp.float32)

    def head(masked, c, slot, h, _):
        k = kb[slot, h].reshape(bk, dh).astype(qs.dtype)
        v = vb[slot, h].reshape(bk, dh).astype(qs.dtype)
        s = jax.lax.dot_general(
            qs[h], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [rows, bk]
        if masked:
            n = functools.partial(jax.lax.broadcasted_iota, jnp.int32, s.shape)
            s = jnp.where(c * bk + n(1) <= first + jax.lax.rem(n(0), bq),
                          s, NEG_INF)               # key <= the row's position
        # block 0 holds position 0, which every query sees: m_new is a
        # score's, and a masked entry's exponential is 0
        m_new = jnp.maximum(m_scr[h], jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_scr[h] - m_new)
        pr = jnp.exp(s - m_new)
        l_scr[h] = l_scr[h] * alpha + jnp.sum(pr, axis=1, keepdims=True)
        m_scr[h] = m_new
        acc[h] = acc[h] * alpha + jax.lax.dot_general(
            pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def block(c, _):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nblk)
        def _():
            fetch(c + 1, 1 - slot)

        for n, buf in enumerate((kb, vb)):  # a semaphore counts bytes
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sem.at[slot, n]).wait()
        heads = lambda masked: lambda: jax.lax.fori_loop(
            0, kv, functools.partial(head, masked, c, slot), None)
        jax.lax.cond(c < n_open, heads(False), heads(True))

    def finish(h, _):
        l = jnp.where(l_scr[h] == 0.0, 1.0, l_scr[h])   # no key: zeros
        o = (acc[h] / l).astype(o_ref.dtype)
        o_ref[0, :, lanes(h)] = jnp.concatenate(
            [o[g * bq:(g + 1) * bq] for g in range(group)], 1)

    @pl.when(nblk > 0)
    def _():
        fetch(0, 0)

    jax.lax.fori_loop(0, kv, stage, None)
    jax.lax.fori_loop(0, nblk, block, None)
    jax.lax.fori_loop(0, kv, finish, None)


# A K/V head's block of keys in VMEM (a buffer slot holds every head's),
# and what a grid step of the chunk reader may hold there in all (a v5e
# has 128 MiB, of which a kernel gets 16 without asking).
_CHUNK_KEYS_BYTES, _CHUNK_STEP_BYTES = 256 << 10, 64 << 20


def _chunk_step_bytes(bq: int, keys: int, heads: int, n_kv: int,
                      head_dim: int, itemsize: int) -> int:
    """VMEM a grid step holds: a query row's operand, f32 sum and two
    statistics (a 128-lane tile a number) for every head, the q and o
    blocks twice and one K/V head's f32 scores three times over; K and V
    of a block of keys twice."""
    return (bq * (heads * (head_dim * (5 * itemsize + 4) + 2 * 512)
                  + 12 * heads // n_kv * keys)
            + 4 * n_kv * keys * head_dim * itemsize)


def chunk_blocks(heads: int, n_kv: int, head_dim: int, page_size: int,
                 itemsize: int, chunk: int, max_pages: int):
    """(queries, pages) a block of the chunk reader, from the shapes.
    Keys: ``_CHUNK_KEYS_BYTES`` of a K/V head's (1,024 at a head of 128:
    the running softmax's rescale is paid once a block of keys, and at
    512 it was a third of a step, v5e).  Queries: 256 where they divide
    the chunk and a step fits ``_CHUNK_STEP_BYTES``, else 128; off the
    128-row rule the chunk is one block of whole sublanes."""
    ppb = max(1, min(max_pages,
                     _CHUNK_KEYS_BYTES // (head_dim * itemsize * page_size)))
    if chunk % 128:
        return -(-chunk // 8) * 8, ppb
    wide = chunk % 256 == 0 and _CHUNK_STEP_BYTES >= _chunk_step_bytes(
        256, ppb * page_size, heads, n_kv, head_dim, itemsize)
    return (256 if wide else 128), ppb


def paged_chunk_attention_v2(q, k_pages, v_pages, table, start,
                             scale: Optional[float] = None,
                             pages_per_block: Optional[int] = None,
                             interpret: bool = False, layer=None,
                             block_q: Optional[int] = None):
    """Blocked chunked-prefill attention on the chip — same contract as
    :func:`paged_chunk_attention_reference`: the Mosaic kernel
    ``dstpu_paged_chunk_v2`` (:func:`_chunk_v2_kernel`).  q [B, C, H, Dh]
    goes in and the result comes out as the family's hooks hold them
    (``[B, C, H Dh]``: a reshape, no copy); the pool stays in HBM in its
    stored layout.  ``block_q`` and ``pages_per_block`` follow from the
    shapes (:func:`chunk_blocks`); tests pass them to put a block edge
    where they want one."""
    B, C, H, Dh = q.shape
    layer, k_pages, v_pages = _as_pool(layer, k_pages, v_pages)
    _, KV, _, ps, _ = k_pages.shape
    G, mp = H // KV, table.shape[1]
    scale = scale if scale is not None else Dh ** -0.5
    operand = jnp.promote_types(q.dtype, k_pages.dtype)
    bq, ppb = chunk_blocks(H, KV, Dh, ps, operand.itemsize, C, mp)
    bq, ppb = block_q or bq, min(mp, pages_per_block or ppb)
    Cp = -(-C // bq) * bq           # whole blocks: a few rows pad to 8
    qf = q.reshape(B, C, H * Dh)
    if Cp != C:
        qf = jnp.pad(qf, ((0, 0), (0, Cp - C), (0, 0)))
    rows = G * bq
    block = pl.BlockSpec((1, bq, H * Dh), lambda b, i, *_: (b, i, 0))
    out = pl.pallas_call(
        functools.partial(_chunk_v2_kernel, scale=scale, ps=ps, group=G,
                          chunk=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,   # table, start, layer
            grid=(B, Cp // bq),
            in_specs=[block, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((KV, rows, Dh), operand),
                pltpu.VMEM((KV, rows, Dh), jnp.float32),
                pltpu.VMEM((KV, rows, 1), jnp.float32),
                pltpu.VMEM((KV, rows, 1), jnp.float32),
                pltpu.VMEM((2, KV, ppb, ps, Dh), k_pages.dtype),
                pltpu.VMEM((2, KV, ppb, ps, Dh), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Cp, H * Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=(8 << 20) + (
            _chunk_step_bytes(bq, ppb * ps, H, KV, Dh, operand.itemsize))),
        interpret=interpret,
        name="dstpu_paged_chunk_v2",
    )(table, start, _layer_operand(layer), qf, k_pages, v_pages)
    return out[:, :C].reshape(B, C, H, Dh)


# ------------------------------------------------- latent (one-row) pages
# A latent family's pool is [L, 1, P, ps, Wp]: a token's row is W = C + Dr
# numbers, the normed compressed KV ``c`` and the rotated key part every
# head shares, stored in Wp = W rounded up to whole 128-lane tiles
# (``models.family.CacheRow.pool_width``; the tail is zeros).  The TPU lays a 576-wide
# row out in 640 lanes whatever its declared width; declared as 576 the
# compiler keeps a second, re-laid copy of the pool in a chunk program and
# Mosaic refuses the page copy ("Slice shape along dimension 4 must be
# aligned to tiling (128), but is 576"; AOT for a described v5e, PR 33).
# The writers are the per-head ones (one "kv head"); the readers attend in
# the absorbed form, q~ = [q_nope W_UK^T | q_rope] against the rows, with
# the rows' first C numbers as values.
def _pad_rows(rows, width: int):
    """``rows`` [..., W] -> [..., width], zeros behind."""
    pad = width - rows.shape[-1]
    return rows if not pad else jnp.pad(
        rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])


def latent_decode_reference(q_abs, pages, table, seq_lens, scale: float,
                            value_width: int, *, layer=None):
    """q_abs: [B, H, W]; pages: the latent pool and ``layer`` (or one
    layer's [1, P, ps, W]) -> [B, H, value_width].  The XLA formulation:
    gathers every row ``table`` names."""
    rows = _gather_rows(pages, layer, table)[:, 0, :, :q_abs.shape[-1]] \
        .astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q_abs.astype(jnp.float32), rows) * scale
    valid = jnp.arange(rows.shape[1])[None] < seq_lens[:, None]
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bsc->bhc", p, rows[..., :value_width])
    out = jnp.where(seq_lens[:, None, None] > 0, out, 0.0)
    return out.astype(q_abs.dtype)


# How a decode reader (K/V pages or latent rows) brings a row's live pages
# into VMEM (:func:`_stream_live_blocks`; these two stand below the chunk
# reader so that none of its lines moved: a Mosaic payload carries them,
# and with them every chunk program's compile-cache key).  A page of 16
# rows costs the kernel its scalar work, the same whatever the page holds
# (v5e, kernel alone, PR 49: 78 ns at 16 KiB and at 32 KiB a page with a
# guarded start and a guarded wait a page; 50 and 57 as it stands): the
# table read, the copies' starts and their waits, which the products do
# not hide.  So a block's live pages start with no guard, eight a loop
# trip (the scheduler overlaps their table reads and addresses); a block
# is awaited by its byte count, one wait a buffer when it is full (a DMA
# semaphore counts bytes); nothing is started for a dead slot of a row's
# last block; and the grid's rows are one stream: a row's last block
# computes over the next row's first block's copies (a sixth of the call
# at 28 rows of three blocks of 64 KiB pages; where the starts bind, as at
# 2 K/V heads, it costs 3%).  PERF.md 6, PR 49 has the table by step.
_DECODE_ISSUE_TRIP = 8


def _start_pages(table_ref, row, at, n, layer, streams, sem, slot):
    """Starts the copies of the ``n`` pages that entries ``at``, ``at +
    1``, ... of ``row``'s table name, into places 0, 1, ... of ``slot``:
    a page one strided copy a (pool, buffer) pair for all its KV heads.
    Reads no other entry of the table."""
    ppb = streams[0][1].shape[2]
    trip = max(u for u in (_DECODE_ISSUE_TRIP, 4, 2, 1) if ppb % u == 0)

    def page(j, _):
        pid = table_ref[row, at + j]
        for i, (pool, buf) in enumerate(streams):
            pltpu.make_async_copy(pool.at[layer, :, pid], buf.at[slot, :, j],
                                  sem.at[slot, i]).start()

    def some(t, _):
        for j in range(trip):
            page(t * trip + j, None)

    whole = n // trip
    jax.lax.fori_loop(0, whole, some, None)
    jax.lax.fori_loop(whole * trip, n, page, None)


def _await_pages(n, streams, sem, slot):
    """Awaits ``n`` pages' bytes on each buffer's semaphore of ``slot``,
    a wait a set bit of ``n``: a full block is one wait a buffer."""
    ppb = streams[0][1].shape[2]
    for bit in range(ppb.bit_length()):
        @pl.when((n >> bit) & 1 == 1)
        def _():
            for i, (_, buf) in enumerate(streams):
                part = buf.at[slot, :, pl.ds(0, 1 << bit)]
                pltpu.make_async_copy(part, part, sem.at[slot, i]).wait()


# tokens a block of the latent decode kernel holds in VMEM, a buffer slot
_MLA_BLOCK_TOKENS = 1024


def _mla_decode_kernel(table_ref, lens_ref, layer_ref, qc_ref, qr_ref,
                       pool_hbm, o_ref, buf, sem, turn, *, scale, ps,
                       width_c, width):
    """One grid step a batch row, as :func:`_decode_kernel`: the row's
    live pages stream ``ppb`` at a time through a double-buffered VMEM
    scratch (:func:`_stream_live_blocks`), each page ONE copy of [ps, W]
    that serves as keys and as values.  The row's H heads are the M
    dimension of both products (q~ @ rows^T, p @ c), so the MXU does the
    work; operands stay in the pool's dtype, scores, softmax and the
    accumulator are f32."""
    n = lens_ref[pl.program_id(0)]
    qc, qr = qc_ref[0], qr_ref[0]                   # [H, C], [H, Dr]
    heads = qc.shape[0]
    keys = buf.shape[2] * ps
    dims = (((1,), (1,)), ((), ()))

    def attend(c, slot, carry):
        m, l, acc = carry
        rows = buf[slot, 0].reshape(keys, buf.shape[4])
        lat, rope = rows[:, :width_c], rows[:, width_c:width]
        s = (jax.lax.dot_general(qc, lat, dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, rope, dims,
                                   preferred_element_type=jnp.float32)
             ) * scale                                      # [H, S]
        kpos = c * keys + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < n, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # the rows are values too: a masked entry is exactly 0 over a live
        # page's rows
        pr = jnp.where(kpos < n, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(pr, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            pr.astype(lat.dtype), lat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [H, C]
        return m_new, l, acc

    init = (jnp.full((heads, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, width_c), jnp.float32))
    m, l, acc = _stream_live_blocks(
        table_ref, lens_ref, layer_ref[0], ((pool_hbm, buf),), sem, turn,
        attend, init, ps=ps)
    l = jnp.where(l == 0.0, 1.0, l)                 # empty rows -> zeros
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def latent_decode_attention(q_abs, pages, table, seq_lens, scale: float,
                            value_width: int, *,
                            pages_per_block: Optional[int] = None,
                            interpret: bool = False, layer=None):
    """Latent decode attention that reads live pages only, each once
    (same contract as :func:`latent_decode_reference`): the Mosaic
    kernel ``dstpu_mla_decode``.  q_abs: [B, H, W] absorbed queries;
    pages: the latent pool [L, 1, P, ps, Wp] and ``layer`` (or one
    layer's pages); the pool stays in HBM in its stored layout."""
    B, H, W = q_abs.shape
    layer, pages = _as_pool(layer, pages)
    ps, Wp = pages.shape[3], pages.shape[4]
    mp = table.shape[1]
    ppb = min(mp, pages_per_block or max(1, _MLA_BLOCK_TOKENS // ps))
    h8 = -(-H // 8) * 8                             # sublane alignment
    if h8 != H:
        q_abs = jnp.pad(q_abs, ((0, 0), (0, h8 - H), (0, 0)))
    row = lambda b, *_: (b, 0, 0)
    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=scale, ps=ps,
                          width_c=value_width, width=W),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,   # table, seq_lens, layer
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, h8, value_width), row),
                pl.BlockSpec((1, h8, W - value_width), row),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h8, value_width), row),
            scratch_shapes=[
                pltpu.VMEM((2, 1, ppb, ps, Wp), pages.dtype),
                pltpu.SemaphoreType.DMA((2, 1)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, h8, value_width), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dstpu_mla_decode",
    )(table, seq_lens, _layer_operand(layer), q_abs[..., :value_width],
      q_abs[..., value_width:], pages)
    return out[:, :H]


def latent_reader(decode: Tuple[str, str]) -> Tuple[str, str]:
    """``ServingKernelPolicy.decode`` of a latent family's build: the
    same answer (:func:`paged_reader`), with the Mosaic reader under its
    own name."""
    reader, why = decode
    if reader != "xla":
        return "dstpu_mla_decode", (
            f"{why}; latent rows: absorbed queries against [c | k_rope], "
            "each live page read once as keys and values")
    return reader, f"{why}; latent rows gathered, absorbed form"


def latent_attention_step(q, row, w_uk, w_uv, scale, pool, layer, table,
                          start, *, continuation: bool, prefill: bool,
                          reader: str, flash_force_reference: bool):
    """:func:`paged_attention_step` of a latent family, on its one pool.

    q: [B, T, H, Dn + Dr] (rope part rotated); row: [B, T, 1, C + Dr] =
    ``[c | k_rope]``, what is cached; w_uk [C, H, Dn] and w_uv [C, H,
    Dv]: the two halves of W_kvb.  Decode (T == 1) absorbs W_UK into the
    query and attends over the rows of the live pages (``reader``
    "xla": the gather), then takes the result through W_UV.  T > 1
    attends in the per-head form, blocked: a whole prompt expands its
    own rows; a chunk writes its rows, gathers the rows its table names
    (history and itself) and expands those.  Returns
    (attn [B, T, H, Dv], pool)."""
    from deepspeed_tpu.ops.attention import latent_flash_attention

    B, T, H, _ = q.shape
    C, Dn = w_uk.shape[0], w_uk.shape[2]
    W = row.shape[-1]
    write, attend = jax.named_scope("kv_write"), jax.named_scope("kv_attend")
    row = _pad_rows(row, pool.shape[-1])
    if T == 1:
        with jax.named_scope("attn_qkv"), jax.named_scope("mla_q"):
            q_abs = jnp.concatenate(
                [jnp.einsum("bhd,chd->bhc", q[:, 0, :, :Dn], w_uk),
                 q[:, 0, :, Dn:]], -1)
        with write:
            page_id, in_page = _row_targets(pool, table, start)
            pool = _scatter_rows(pool, layer, page_id, in_page, row[:, 0])
        with attend:
            if reader != "xla":
                o = latent_decode_attention(
                    q_abs, pool, table, start + 1, scale, C, layer=layer)
            else:
                o = latent_decode_reference(q_abs, pool, table, start + 1,
                                            scale, C, layer=layer)
        with jax.named_scope("attn_out"):
            return jnp.einsum("bhc,chd->bhd", o, w_uv)[:, None], pool

    expand = lambda rows: (
        jnp.einsum("bsc,chd->bshd", rows[..., :C], w_uk), rows[..., C:W],
        jnp.einsum("bsc,chd->bshd", rows[..., :C], w_uv))
    if prefill:
        with jax.named_scope("attn_qkv"), jax.named_scope("mla_kv"):
            kn, kr, v = expand(row[:, :, 0])
        with jax.named_scope("flash"):
            attn = latent_flash_attention(
                q[..., :Dn], q[..., Dn:], kn, kr, v,
                jnp.zeros((B,), jnp.int32), scale,
                force_reference=flash_force_reference)
        with write:
            pool = _scatter_pages(pool, layer, table, row)
        return attn, pool
    if not continuation:
        raise ValueError("T > 1 over a cache is a continuation")
    with write:
        pos = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        page_id, in_page = _row_targets(pool, table, pos)
        pool = _scatter_rows(pool, layer, page_id, in_page,
                             row.reshape(B * T, 1, -1))
    with jax.named_scope("kv_attend"), jax.named_scope("mla_expand"):
        kn, kr, v = expand(_gather_rows(pool, layer, table)[:, 0])
    with attend:
        attn = latent_flash_attention(
            q[..., :Dn], q[..., Dn:], kn, kr, v, start, scale,
            force_reference=flash_force_reference)
    return attn, pool


# --------------------------------------------- shared per-layer dispatch
def paged_reader(*, decode: bool, tp: bool, interpret: bool, quant: bool,
                 tokens: int = 1, head_dim: int = 0) -> Tuple[str, str]:
    """Which reader a paged program's attention runs, and why: ("xla" or
    the Mosaic kernel's own name, reason), the one answer
    ``forward_paged``, ``paged_layered_fns`` and the engine's
    ``/statusz`` share.  A rule of the build: nothing configures it and
    nothing overrides it.

    It answers from the phase, the layout and the shapes: on one device
    over float pages a decode program (``T == 1``) reads live pages only
    and writes the step's row itself (``dstpu_paged_decode``,
    :func:`paged_decode_attention_v2`: no row scatter runs before it),
    and a continuation program of whole 128-row blocks of ``tokens``
    with heads of whole 128-lane tiles reads so too, each block of
    queries up to its own frontier (``dstpu_paged_chunk_v2``,
    :func:`paged_chunk_attention_v2`); the gather's cost follows the
    table.  It stays under tensor parallelism (the KV heads are
    sharded), over int8-resident pages (gathered and dequantized: the
    chip's compiler refuses a page copy of the ``[KV, P, ps, 1]`` scale
    planes, a 1-wide slice of a 128-lane tile), in ``interpret`` mode
    and off those shapes (a head of 64).  A reader need not be a writer:
    layers that read pages another layer wrote (``family.PoolReader``)
    run the decode reader without a row to append, over lengths that
    count what this program's writer wrote before them."""
    whole = lambda n: n > 0 and n % 128 == 0
    for off, why in ((tp, "tp: KV heads are sharded over the mesh"),
                     (quant, "int8-resident pages"),
                     (interpret, "interpret: no TPU backend"),
                     (not (decode or whole(tokens)),
                      "chunk program: its rows are not whole 128-row blocks"),
                     (not (decode or whole(head_dim)),
                      "chunk program: a head is not whole 128-lane tiles")):
        if off:
            return "xla", why
    if decode:
        return "dstpu_paged_decode", "decode on one device over float pages"
    return "dstpu_paged_chunk_v2", (
        "chunk in 128-row blocks on one device over float pages")


class ServingKernelPolicy(NamedTuple):
    """The readers an engine's build resolved: a report, not a request.
    Each row is a rule's answer from the family, the mesh, the pages and
    the shapes, baked into the programs and printed by ``/statusz``."""

    # (reader, reason) of the decode program's attention (:func:`paged_reader`)
    decode: Tuple[str, str] = ("xla", "")
    # (reader, reason) of a chunk program's attention over K/V pages
    chunk: Tuple[str, str] = ("xla", "no chunk program")
    # (reader, reason) of a window layer's chunk (ops.attention.window_reader)
    window: Tuple[str, str] = ("xla", "no window layer")
    # pallas | xla: a decode step of the per-slot state (:func:`state_stepper`)
    state_step: str = "xla"
    # (what, fell back to, reason) where a mesh took a kernel of the family's
    fallbacks: Tuple[Tuple[str, str, str], ...] = ()
    # (pallas | xla, reason): a prompt chunk's state (:func:`state_chunker`)
    state_chunk: Tuple[str, str] = ("xla", "")
    # (product, reason) of a chunk program's held experts
    # (:func:`~deepspeed_tpu.parallel.moe.held_product`)
    experts: Tuple[str, str] = ("none", "no expert layer")

    def as_dict(self) -> dict:
        pair = lambda k: dict(zip(("reader", "reason"), getattr(self, k)))
        # where a decode step's new K/V row is written: by the reader that
        # takes it (:func:`paged_decode_attention_v2`), else by the row
        # scatter before the reader, for the reader's own reason
        write = ("kernel" if self.decode[0] == "dstpu_paged_decode"
                 else "scatter")
        return {
            "decode": {**pair("decode"), "write": write},
            "chunk": pair("chunk"),
            "window": pair("window"), "state_step": self.state_step,
            "state_chunk": pair("state_chunk"),
            "experts": dict(zip(("product", "reason"), self.experts)),
            "fallbacks": [{"field": f, "demoted_to": d, "reason": r}
                          for f, d, r in self.fallbacks],
        }


def held_experts_product(params, fam, cfg, rows: int, whole: bool):
    """``ServingKernelPolicy.experts`` of a build: (product, reason) of
    the held experts in a chunk program of ``rows`` rows, by the rule the
    program itself follows (:func:`~deepspeed_tpu.parallel.moe.
    held_product`) on the family's router and its stacks' shapes.
    ``whole``: the paged forward hands the family's ``whole_stacks``
    over unsliced (plain arrays held on one device)."""
    from deepspeed_tpu.parallel.moe import held_product

    if not fam.whole_stacks:
        return ServingKernelPolicy().experts
    if not rows:
        return "every_row", "no chunk program"
    name = fam.whole_stacks[0]
    w = next(v[name] for v in params.values()
             if isinstance(v, dict) and name in v)
    (E, k), Eh = fam.router(cfg), fam.expert_rows(cfg)[0]
    return held_product(rows, k, Eh, E, *w.shape[-2:], w.dtype.itemsize,
                        len(fam.whole_stacks), whole)[:2]


def resolve_serving_kernels(*, tp: bool = False, interpret: bool = False,
                            quantized_resident: bool = False,
                            recurrent: bool = False, chunk=(0, 0),
                            state_block=None):
    """The readers of an engine's programs, resolved ONCE, at its build
    (``serving_engine``), from what the build can observe: so what the
    programs compiled with is what ``/statusz`` reports; nothing is read
    from a config or the environment.  ``tp``: a model or expert axis
    shards the cache; ``interpret``: no TPU backend; ``quantized_resident``:
    the pages are int8 codes, gathered.  ``recurrent``: it has recurrent
    layers, whose state a decode program steps in place through
    ``dstpu_state_step`` on one device (:func:`state_stepper`);
    ``state_block``: the family's ``(Recurrent, cfg)``, whose block and
    state's shape :func:`state_chunker` asks for a prompt chunk's
    ``dstpu_state_chunk``; where ``tp`` takes either kernel, a
    ``fallbacks`` row says so.  ``chunk``: the (tokens, head width) of
    the chunk programs' reader (``window`` is the family's)."""
    fallbacks = []
    stepper, why = state_stepper(decode=recurrent, tp=tp)
    if recurrent and stepper != "pallas":
        fallbacks.append(("state_step=pallas", stepper, why))
    chunker = state_chunker(state_block, tp=tp, interpret=interpret)
    if tp and chunker[1].startswith("tp"):
        fallbacks.append(("state_chunk=pallas",) + chunker)
    reader = functools.partial(paged_reader, tp=tp, interpret=interpret,
                               quant=quantized_resident)
    return ServingKernelPolicy(
        decode=reader(decode=True), state_step=stepper, state_chunk=chunker,
        chunk=reader(decode=False, tokens=chunk[0], head_dim=chunk[1]),
        fallbacks=tuple(fallbacks))


def paged_attention_step(q, k, v, kp, vp, layer, table, start, *,
                         continuation: bool, prefill: bool, reader: str,
                         flash_force_reference: bool, kps=None, vps=None):
    """The per-layer paged-attention step every model family shares:
    page writes + the right attention for the phase, on the WHOLE pool.

    q: [B, T, H, Dh]; k/v: [B, T, KV, Dh]; kp/vp: the pool [L, KV, P,
    ps, Dh], which the caller's layer loop carries; ``layer``: the index
    (traced in that loop) of the layer to write and read.  The writers
    scatter the new rows into the pool and the readers take the layer by
    its index, so the program updates the pool in place and never holds
    a copy of it or of one layer (a per-layer store passes ``kp[None]``
    and layer 0: :func:`~deepspeed_tpu.inference.paged_forward.paged_layered_fns`).
    ``reader`` is :func:`paged_reader`'s answer for this phase and these
    shapes: "xla" (the gather) or the Mosaic kernel's name; a decode
    step's Mosaic reader is its writer too.  ``kps``/
    ``vps`` non-None: the pages are int8-resident: kp/vp hold int8
    codes, kps/vps the per-token-row f32 scales, writes quantize on
    device, and the reader (always "xla") gathers the codes and
    dequantizes them (:func:`dequantize_pages`).  Phases:
    chunked-prefill continuation (split-fuse), whole-prompt prefill
    (empty cache), or single-token decode.  ``k`` None: a layer that
    reads ``layer``'s pages and writes none (one token a row, ``start``
    the lengths to read: the decode reader with nothing to append).
    Returns (attn [B, T, H, Dh], kp, vp, kps, vps)."""
    from deepspeed_tpu.ops.attention import flash_attention

    quant = kps is not None
    if quant and reader != "xla":
        raise ValueError(f"int8-resident pages are gathered, not {reader}")
    # the one place for the three attention scopes (kv_write, kv_attend,
    # flash): forward_paged and its layered twin pass through here
    write, attend = jax.named_scope("kv_write"), jax.named_scope("kv_attend")
    if k is None:
        with attend:
            if reader != "xla":
                attn = paged_decode_attention_v2(q[:, 0], kp, vp, table,
                                                 start, layer=layer)
            else:
                attn = paged_attention_reference(
                    q[:, 0], kp, vp, table, start, layer=layer, k_scale=kps,
                    v_scale=vps)
        attn = attn[:, None]
    elif continuation and q.shape[1] > 1:
        with write:
            if quant:
                kp, kps, vp, vps = write_chunk_pages_quant(
                    kp, kps, vp, vps, layer, k, v, table, start)
            else:
                kp, vp = write_chunk_pages(kp, vp, layer, k, v, table,
                                           start)
        with attend:
            if reader != "xla":
                attn = paged_chunk_attention_v2(q, kp, vp, table, start,
                                                layer=layer)
            else:
                attn = paged_chunk_attention_reference(
                    q, kp, vp, table, start, layer=layer, k_scale=kps,
                    v_scale=vps)
    elif prefill:
        with jax.named_scope("flash"):
            attn = flash_attention(q, k, v, causal=True,
                                   force_reference=flash_force_reference)
        with write:
            if quant:
                kp, kps, vp, vps = write_prompt_pages_quant(
                    kp, kps, vp, vps, layer, k, v, table)
            else:
                kp, vp = write_prompt_pages(kp, vp, layer, k, v, table)
    elif reader != "xla":
        with attend:                    # the reader writes the row it takes
            attn, kp, vp = paged_decode_attention_v2(
                q[:, 0], kp, vp, table, start, layer=layer, new_k=k[:, 0],
                new_v=v[:, 0])
        attn = attn[:, None]
    else:
        with write:
            if quant:
                kp, kps, vp, vps = write_token_pages_quant(
                    kp, kps, vp, vps, layer, k[:, 0], v[:, 0], table,
                    start)
            else:
                kp, vp = write_token_pages(kp, vp, layer, k[:, 0],
                                           v[:, 0], table, start)
        with attend:
            attn = paged_attention_reference(
                q[:, 0], kp, vp, table, start + 1, layer=layer,
                k_scale=kps, v_scale=vps)[:, None]
    return attn, kp, vp, kps, vps


def paged_layer_loop(block, x, blocks, cache: PagedKVCache,
                     first: int = 0, count: Optional[int] = None):
    """Run a model's layers over the paged cache with the pool as a CARRY.

    ``block(x, lp, layer, kp, vp, kps, vps, rows) -> (x, kp, vp, kps,
    vps, rows)`` is one layer (``vp`` is None over latent pages,
    ``kps``/``vps`` unless the cache is int8-resident, ``rows`` unless
    the family counts its experts' rows); ``blocks`` the stacked layer
    params of the pool's layers ``first .. first + count`` (all of them
    by default; a family with a leading stack runs the loop twice).  The
    scan runs over (params, layer index) and carries the activations
    with the pool, so every layer updates the SAME buffers: as a scanned
    input and stacked output the pool would be two buffers, and each
    layer would slice its pages out of one and copy them whole into the
    other.  Returns (x, cache) with the new pool; ``seq_lens`` is the
    caller's."""
    def body(carry, layer):
        x, pools = carry
        x, *pools = block(x, *layer, *pools)
        return (x, tuple(pools)), None

    count = cache.k.shape[0] if count is None else count
    layers = jnp.arange(count, dtype=jnp.int32)
    (x, (k, v, ks, vs, rows)), _ = jax.lax.scan(
        body, (x, (cache.k, cache.v, cache.k_scale, cache.v_scale,
                   cache.expert_rows)),
        (blocks, layers + first if first else layers))
    return x, cache._replace(k=k, v=v, k_scale=ks, v_scale=vs,
                             expert_rows=rows)


def state_rows(carried, layer, slot):
    """Recurrent layer ``layer``'s rows of each of the ``carried``
    buffers [layers, slots, ...] (the convolution's and the state's, or
    the former alone where the state is stepped where it lies,
    :func:`state_step`) that a forward runs: every slot's (``slot``
    None), or the one slot's that a one-row view stands for."""
    if slot is None:
        return tuple(jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)
                     for a in carried)
    at = lambda a: (layer, slot[0]) + (0,) * (a.ndim - 2)
    return tuple(jax.lax.dynamic_slice(a, at(a), (1, 1) + a.shape[2:])[0]
                 for a in carried)


def write_state_rows(carried, layer, slot, new):
    """The rows' ``new`` values into the ``carried`` buffers, in place."""
    at = lambda a: (layer, 0 if slot is None else slot[0]) \
        + (0,) * (a.ndim - 2)
    return tuple(jax.lax.dynamic_update_slice(a, n[None].astype(a.dtype),
                                              at(a))
                 for a, n in zip(carried, new))


def state_stepper(*, decode: bool, tp: bool) -> Tuple[str, str]:
    """How a recurrent layer's state takes a token, and why: ("pallas" |
    "xla", reason), the one answer ``forward_paged`` and the engine's
    ``/statusz`` share.  A decode step over every slot (``decode``: one
    token a row, the rows the slots) on one device steps the carried
    state where it lies (:func:`state_step`, in interpret mode off the
    TPU).  A prompt chunk's one-slot view is :func:`state_chunker`'s to
    answer for; under a mesh (``tp``) the kernel, which is one device's,
    cannot be partitioned."""
    for off, why in ((not decode, "no decode step over every slot's state"),
                     (tp, "tp: the kernel is one device's")):
        if off:
            return "xla", why
    return "pallas", "decode over every slot on one device"


# --------------------------------------------- the per-slot state's step
# A tile of the carried state, and how many of them the kernel keeps in
# the fast memory: while one is stepped, the next ``_STATE_TILES_AHEAD``
# are on their way in and the last ones on their way out (8 MiB of the
# 16 MiB a v5e kernel may use without asking).
_STATE_TILE_BYTES = 2 << 20
_STATE_TILES, _STATE_TILES_AHEAD = 4, 2


def _state_tile(slots: int, heads: int, head_bytes: int,
                tile_bytes: int) -> Tuple[int, int]:
    """(slots, heads) of a tile of at most ``tile_bytes`` that divides
    the layer: whole slots where a slot's heads fit, else some of one
    slot's heads."""
    most = lambda n, fit: max(d for d in range(1, n + 1)
                              if n % d == 0 and d <= max(1, fit))
    fit = tile_bytes // head_bytes
    if fit >= heads:
        return most(slots, fit // heads), heads
    return 1, most(heads, fit)


def _state_step_kernel(layer_ref, *refs, rule, kinds, o_kind, tile, ahead,
                       in_place=False):
    """Layer ``layer_ref[0]`` of the state, which stays where it is
    (``s_hbm`` and ``out_hbm`` are one buffer), a tile [slots, heads, R,
    C] at a time through ``buf``: tile t is stepped in its buffer while
    tiles up to t + ``ahead`` are read and the ones before it written
    back.  ``rule`` runs on one head's [R, C] at a time, its vectors
    beside it as [1, C] (``row``), [R, 1] (``col``), a scalar (``one``,
    in the scalar memory) or the head's [R, C] of a tile the slots share
    (``tile``).  A vector that runs along R arrives as a row
    of its array, R on the lanes, and is turned on the spot: the
    diagonal of its [R, R] broadcast, summed over the lanes (one number
    and zeros: exact); a result along R goes back the same way, summed
    over the sublanes.  A vector of another shape (``rows``: [n, w], what
    the rule expands itself) is handed over as it is, and so is an ``o``
    of n rows.  ``in_place``: the rule takes the head's state as the
    buffer's [R, C] view and reads and writes it a piece at a time itself
    (a head of megabytes is no value)."""
    n = kinds.count("one")
    s_hbm, *tiles, o_ref, out_hbm, buf, r_sem, w_sem = refs[n:]
    ones, tiles = iter(refs[:n]), iter(tiles)
    v_refs = [next(ones if k == "one" else tiles) for k in kinds]
    _, B, H, R, C = s_hbm.shape
    (bb, hb), layer, held = tile, layer_ref[0], buf.shape[0]
    n_tiles = (B // bb) * (H // hb)
    # (a rule in place turns what it needs turned itself: its R is not
    # its vectors' width)
    eye = None if in_place else (
        jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (R, R), 1))

    def first(t):
        """Tile t's first slot and head."""
        return t // (H // hb) * bb, t % (H // hb) * hb

    def copy(t, out: bool):
        b0, h0 = first(t)
        hbm = (out_hbm if out else s_hbm).at[layer, pl.ds(b0, bb),
                                             pl.ds(h0, hb)]
        at, sem = buf.at[t % held], (w_sem if out else r_sem).at[t % held]
        return pltpu.make_async_copy(*((at, hbm) if out else (hbm, at)), sem)

    def vector(ref, kind, b, h):
        if kind == "one":
            return ref[b * H + h]
        if kind == "tile":
            return ref[h]
        if isinstance(kind, int):       # rows: a head's ``kind`` of them
            at = h * kind
            return ref[b, pl.ds(pl.multiple_of(at, 8) if kind % 8 == 0
                                else at, kind), :]
        v = ref[pl.ds(b, 1), :] if ref.ndim == 2 else ref[b, pl.ds(h, 1), :]
        return (jnp.sum(jnp.where(eye, v, 0.0), axis=1, keepdims=True)
                if kind == "col" else v)

    def step(t, _):
        copy(t, False).wait()

        @pl.when(t + ahead < n_tiles)
        def _():
            @pl.when(t + ahead >= held)     # the buffer's last tile is out
            def _():
                copy(t + ahead - held, True).wait()

            copy(t + ahead, False).start()

        (b0, h0), cur = first(t), buf.at[t % held]

        def head(i, _):
            b, h = i // hb, i % hb
            vectors = (vector(ref, kind, b0 + b, h0 + h)
                       for ref, kind in zip(v_refs, kinds))
            if in_place:
                o, S = rule(cur.at[b, h], *vectors), None
            else:
                # float32 whatever the state is kept in; rounded on its
                # way out
                o, S = rule(cur[b, h].astype(jnp.float32), *vectors)
            if o_kind == "col":
                o = jnp.sum(jnp.where(eye, o, 0.0), axis=0, keepdims=True)
            n = o.shape[0]
            o_ref[b0 + b, pl.ds(h0 + h, 1) if n == 1 else pl.ds(
                pl.multiple_of((h0 + h) * n, 8) if n % 8 == 0
                else (h0 + h) * n, n), :] = o
            if S is not None:
                cur[b, h] = S.astype(cur.dtype)

        jax.lax.fori_loop(0, bb * hb, head, None)
        copy(t, True).start()

    for t in range(min(ahead, n_tiles)):
        copy(t, False).start()
    jax.lax.fori_loop(0, n_tiles, step, None)
    for t in range(max(0, n_tiles - held), n_tiles):
        copy(t, True).wait()


def state_step(rule, state, layer, vectors, *, interpret: bool = False,
               tile_bytes: Optional[int] = None, in_place=None):
    """One token of a recurrence on layer ``layer`` of the carried state
    ``state`` [layers, slots, H, R, C] (``STATE_DTYPE``), in place: the
    Mosaic kernel ``dstpu_state_step`` reads a tile of it, applies
    ``rule`` and writes the tile back, so a step moves the layer's state
    out of the memory once and into it once, and no layer of it is ever a
    value of the program's.

    ``rule(S, *vectors) -> (o, S)`` is the family's own statement of the
    step, written over the last two dimensions: ``S`` [..., R, C] and
    each vector [..., 1, C], [..., R, 1] or [..., 1, 1] (what it
    multiplies or adds to S by broadcasting; the kernel hands it the
    last as a scalar), ``o`` [..., 1, C] or [..., R, 1].  ``vectors``:
    every slot's, [slots, H or 1, R or 1, C or 1] float32 (1 heads:
    shared by the heads; one number a head is always [slots, H, 1, 1]),
    or a layer's tile [1, H, R, C] that every slot shares (a decay a
    (row, column) pair: handed over once, not a copy a slot);
    they and ``o`` are whole in the kernel's memory.  Returns (o [slots,
    H, 1, C] or [slots, H, R, 1], the buffer).  The tile is read from
    the shapes (:func:`_state_tile`); ``tile_bytes`` is a measurement's
    and a test's.

    A vector of any other shape [slots, H, n, w] (n rows a state head of
    what the rule expands in the fast memory itself: a key of w numbers
    that stands for a row of C, the n query heads that read one state) is
    handed to the rule as its [n, w], and ``o`` may be such rows too: [slots,
    H, n, w]; whole (8, 128) tiles a head where n is a multiple of 8.
    ``in_place(S_ref, *vectors) -> o``: the same rule on a head's state
    where it lies in the fast memory, a reference [R, C] it reads and
    writes a piece at a time: what the kernel runs where a head's state
    is megabytes (one head a tile, whatever ``_STATE_TILE_BYTES``);
    ``rule`` is then not read, and ``o`` is rows as the first of the
    vectors that is rows: the rows that read the state."""
    L, B, H, R, C = state.shape
    kind_of = lambda shape: {(1, C): "row", (R, 1): "col", (1, 1): "one",
                             (R, C): "tile"}.get(tuple(shape), shape[0])
    kinds = tuple(kind_of(v.shape[2:]) for v in vectors)
    o_shape = next(
        v.shape[2:] for v, k in zip(vectors, kinds) if isinstance(k, int)
    ) if in_place else jax.eval_shape(
        rule, jax.ShapeDtypeStruct((R, C), jnp.float32),
        *(jax.ShapeDtypeStruct(() if k == "one" else v.shape[2:], v.dtype)
          for v, k in zip(vectors, kinds)))[0].shape
    o_kind = kind_of(o_shape)
    tile = _state_tile(B, H, R * C * state.dtype.itemsize,
                       tile_bytes or _STATE_TILE_BYTES)
    # a vector's unit dimension is dropped in the memory, where a [.., R,
    # 1] array is stored a 128-lane tile a number (one shared by the heads
    # is [slots, width]: as [slots, 1, width] its layout, a tile a row,
    # went back through the convolution to the carried buffer of its
    # rows, which the program then re-laid on its way in and out, v5e)
    ones = [v.reshape(-1) for v, k in zip(vectors, kinds) if k == "one"]
    rows = [v.reshape((H, R, C) if k == "tile" else
                      (B, -1, v.shape[-1]) if isinstance(k, int) else
                      (B,) + ((H,) if v.shape[1] > 1 else ()) + (-1,))
            for v, k in zip(vectors, kinds) if k != "one"]
    o = jax.ShapeDtypeStruct(
        (B, H * o_kind, o_shape[1]) if isinstance(o_kind, int)
        else (B, H, R if o_kind == "col" else C), jnp.float32)
    held = min(_STATE_TILES, (B // tile[0]) * (H // tile[1]))
    buf = jax.ShapeDtypeStruct((held,) + tile + (R, C), state.dtype)
    in_vmem = sum(a.size * a.dtype.itemsize for a in rows + [o, buf])
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    o, state = pl.pallas_call(
        functools.partial(_state_step_kernel, rule=in_place or rule,
                          kinds=kinds, o_kind=o_kind, tile=tile,
                          ahead=max(1, min(_STATE_TILES_AHEAD, held - 1)),
                          **({"in_place": True} if in_place else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(ones),   # layer, the scalars
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + [whole] * len(rows),
            out_specs=[whole, pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM(buf.shape, buf.dtype),
                            pltpu.SemaphoreType.DMA((held,)),
                            pltpu.SemaphoreType.DMA((held,))],
        ),
        out_shape=[o, jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state comes back in its own buffer
        input_output_aliases={1 + len(ones): 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 << 20, in_vmem + (4 << 20))),
        interpret=interpret,
        name="dstpu_state_step",
    )(_layer_operand(layer), *ones, state, *rows)
    return o.reshape((B, H) + o_shape), state


def paged_period_loop(period, x, stacks, cache: PagedKVCache, periods: int):
    """:func:`paged_layer_loop` for a family whose layers come in
    periods of two kinds: ``period(x, lps, p, kp, vp, rows, conv, state,
    ring) -> (x, kp, vp, rows, conv, state, ring)`` runs period ``p``'s
    layers (``x`` whatever the caller carries with the activations),
    ``stacks`` the stacked params the loop slices a period out of
    (``[periods, ...]`` leaves; a kind whose layers run in a loop of
    their own inside ``period`` takes its layers out of its own stack).
    The pool and the per-slot state ride in the carry, so every layer
    updates the same buffers."""
    def body(carry, xs):
        x, held = carry
        x, *held = period(x, *xs, *held)
        return (x, tuple(held)), None

    (x, (k, v, rows, conv, state, ring)), _ = jax.lax.scan(
        body, (x, (cache.k, cache.v, cache.expert_rows, cache.conv,
                   cache.state, cache.ring)),
        (stacks, jnp.arange(periods, dtype=jnp.int32)))
    return x, cache._replace(k=k, v=v, expert_rows=rows, conv=conv,
                             state=state, ring=ring)



# ------------------------------------------- the per-slot state's chunk
# What a grid step of the chunk kernel may hold of its operands' tiles
# (the pipeline keeps two of each), and how many heads it carries side by
# side: their chains of products are independent, and a product costs the
# matrix unit its latency, so the more heads stand in one block's body the
# less the unit waits (blocks of 64, ms a layer of the recurrent cell's
# shape: 0.68 at 2 heads, 0.53 at 4, 0.47 at 8, 0.48 at 16; v5e, PR 50).
_CHUNK_SPAN_BYTES = 10 << 20
_CHUNK_HEADS = 8
# ... of states that fit in this together (a head of megabytes goes alone)
_CHUNK_STATE_BYTES = 8 << 20


def state_chunker(stated, *, tp: bool, interpret: bool) -> Tuple[str, str]:
    """How a prompt chunk carries a recurrent layer's state through its
    tokens, and why: ("pallas" | "xla", reason), the one answer
    ``forward_paged`` and the engine's ``/statusz`` share; a rule of the
    build, as :func:`state_stepper` is for a decode step.  ``stated``:
    the family's ``(Recurrent, cfg)`` or None.  Where the family states
    one block of its chunked rule on one head (``Recurrent.block``), on
    one device on the TPU, a head's state of whole (8, 128) tiles stays
    in VMEM from block to block (:func:`state_chunk`); elsewhere ``mix``
    runs the family's chunked rule in XLA on the slot's rows."""
    shape = stated and stated[0].block and stated[0].state_row(stated[1]).state
    for off, why in ((not shape, "the family states no block of its rule"),
                     (tp, "tp: the kernel is one device's"),
                     (interpret, "interpret: no TPU backend"),
                     (bool(shape) and (shape[-1] % 128 or shape[-2] % 8),
                      "a head's state is not whole 128-lane tiles")):
        if off:
            return "xla", why
    return "pallas", "a chunk's blocks on one device, the state in VMEM"


def _state_chunk_kernel(s_ref, *refs, rule, takes, block: int, heads: int,
                        in_place: bool = False):
    """``heads`` heads' states [heads, R, C] through the ``span`` tokens
    of this grid step, a block at a time, all the heads at once: ``acc``
    holds them in f32 from a head group's first step (read from
    ``s_ref``) to its last (written to ``out_ref``).  The rule gets
    operand m's heads of the step's tile stacked (``takes[m]``: a head's
    width, how many of them), the heads' ``col`` [heads, block, n] and
    ``lane`` [heads, n, block], and gives their ``o`` [heads, block, C].
    ``in_place``: the rule takes ``acc`` itself in S's place, moves it a
    piece at a time and gives ``o`` alone."""
    *m_refs, col_ref, lane_ref, o_ref, out_ref, acc = refs
    step, span = pl.program_id(2), o_ref.shape[0]
    width = o_ref.shape[-1] // heads
    nc, nl = col_ref.shape[-1] // heads, lane_ref.shape[-2] // heads

    @pl.when(step == 0)
    def _():
        acc[...] = s_ref[...].astype(jnp.float32)

    def one(j, S):
        at = pl.multiple_of(j * block, block)
        rows = pl.ds(at, block)
        out = rule(
            S, *(jnp.stack([ref[rows, h * w:(h + 1) * w] for h in range(n)])
                 for ref, (w, n) in zip(m_refs, takes)),
            jnp.stack([col_ref[rows, h * nc:(h + 1) * nc]
                       for h in range(heads)]),
            jnp.stack([lane_ref[j, h * nl:(h + 1) * nl, :]
                       for h in range(heads)]))
        o, S = (out, None) if in_place else out
        for h in range(heads):
            o_ref[rows, h * width:(h + 1) * width] = o[h]
        return S

    if in_place:
        jax.lax.fori_loop(0, span // block, lambda j, _: one(j, acc), None)
    else:
        S = acc[...]
        acc[...] = one(0, S) if span == block else jax.lax.fori_loop(
            0, span // block, one, S)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def _chunk_heads(H: int, reps, head_bytes: int = 0) -> int:
    """How many of the state's heads a grid step carries: the most
    within ``_CHUNK_HEADS``, and within ``_CHUNK_STATE_BYTES`` of state,
    that divide H and hold, or divide, what one head of each operand
    serves."""
    most = min(H, _CHUNK_HEADS, max(1, _CHUNK_STATE_BYTES
                                    // max(1, head_bytes)))
    return max(h for h in range(1, most + 1)
               if H % h == 0 and not any(h % r and r % h for r in reps))


def state_chunk(rule, S, mats, cols, lanes, *, block: int,
                interpret: bool = False, heads: Optional[int] = None,
                span: Optional[int] = None, in_place: bool = False):
    """A prompt chunk of a recurrence on the rows' state ``S`` [B, H, R,
    C] (``STATE_DTYPE``; f32 inside), in place: the Mosaic kernel
    ``dstpu_state_chunk`` holds a few heads' states in VMEM while the
    chunk's blocks of ``block`` tokens pass, so the state leaves the
    memory once and comes back once, and nothing a block makes on its way
    (its ``[block, block]`` matrices) is ever in the memory at all.

    ``rule(S [h, R, C], *tiles, col, lane) -> (o [h, block, C], S)`` is
    the family's own statement of one block on the h heads of a grid
    step, side by side, in the arithmetic a kernel body may use; it
    chooses its products' precision.  ``mats``: its operands [B, T, heads,
    w] f32 as the program has them, row-major (a tile [block, w] a head
    is picked where it lies and the step's stacked: nothing is
    transposed, and an operand of fewer heads than H is not repeated: the
    rule gets the ``h // (H // heads)`` of them that serve the step's).
    ``cols``, ``lanes`` [B, T, H, n] f32: what the rule needs a token and
    head, down a block (``col`` [h, block, n]) and across it (``lane``
    [h, n, block]): kilobytes, turned out here so that the kernel turns
    nothing.  T is whole blocks (a caller pads with tokens that move
    nothing).  Returns (o [B, T, H, C] f32, S).  The heads a grid step
    carries and the tokens it spans are read from the shapes; ``heads``
    and ``span`` are a measurement's and a test's.

    ``in_place``: ``rule(S_ref [h, R, C], *tiles, col, lane) -> o``, the
    step's states a reference the rule reads and writes a piece at a
    time (a head of megabytes is no value of a kernel's; such a head goes
    a grid step alone), and ``o`` is as wide a state head as the widest
    head of ``mats`` ([B, T, H, width]): an operand whose head holds the
    queries of several heads that read one state comes back as wide."""
    B, H, R, C = S.shape
    T, f32 = mats[0].shape[1], jnp.float32
    if T % block:
        raise ValueError(f"{T} tokens are not whole blocks of {block}")
    reps = tuple(H // m.shape[2] for m in mats)
    width = max(m.shape[3] for m in mats) if in_place else C
    hb = heads or _chunk_heads(H, reps, R * C * 4)
    if any(hb % rep and rep % hb for rep in reps) or H % hb:
        raise ValueError(f"{hb} heads a step over operands serving {reps}")
    # an operand's head's width and how many of its heads a step holds
    takes = tuple((m.shape[3], max(1, hb // rep)) for m, rep in zip(mats, reps))
    held = tuple(w * n for w, n in takes)                    # a step's lanes
    nc, nl = cols.shape[-1], lanes.shape[-1]
    tiles = lambda n, tile: -(-n // tile) * tile
    # f32, two buffers each: the operands' and o's lanes, a token's cols in
    # whole 128-lane tiles, a block's lanes in whole (8, 128) tiles
    per_token = 4 * 2 * (sum(held) + hb * width + tiles(hb * nc, 128)
                         + tiles(hb * nl, 8) * tiles(block, 128) // block)
    if span is None:
        span = block
        while T % (2 * span) == 0 and 2 * span * per_token <= _CHUNK_SPAN_BYTES:
            span *= 2
    N, G = T // block, H // hb
    flat = [m.reshape(B, T, -1).astype(f32) for m in mats]
    # down a block: [B, G, T, hb n]; across it: [B, G, N, hb n, block]
    cols = cols.astype(f32).reshape(B, T, G, hb * nc).transpose(0, 2, 1, 3)
    lanes = lanes.astype(f32).reshape(B, N, block, G, hb * nl).transpose(
        0, 3, 1, 4, 2)
    state = pl.BlockSpec((None, hb, R, C), lambda b, g, n: (b, g, 0, 0))
    o, S = pl.pallas_call(
        functools.partial(_state_chunk_kernel, rule=rule, takes=takes,
                          block=block, heads=hb, **(
                              {"in_place": True} if in_place else {})),
        grid=(B, G, T // span),
        in_specs=[state] + [
            pl.BlockSpec((None, span, w), functools.partial(
                lambda b, g, n, per: (b, n, g * hb // per), per=max(hb, rep)))
            for w, rep in zip(held, reps)] + [
            pl.BlockSpec((None, None, span, hb * nc),
                         lambda b, g, n: (b, g, n, 0)),
            pl.BlockSpec((None, None, span // block, hb * nl, block),
                         lambda b, g, n: (b, g, n, 0, 0))],
        out_specs=[pl.BlockSpec((None, span, hb * width),
                                lambda b, g, n: (b, n, g)), state],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * width), f32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        scratch_shapes=[pltpu.VMEM((hb, R, C), f32)],
        input_output_aliases={0: 1},        # the rows come back in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # (the step's states: in and out twice each, and ``acc``)
            vmem_limit_bytes=max(32 << 20, span * per_token + (24 << 20)
                                 + max(0, 5 * hb * R * C * 4 - (16 << 20)))),
        interpret=interpret,
        name="dstpu_state_chunk",
    )(S, *flat, cols, lanes)
    return o.reshape(B, T, H, width), S
