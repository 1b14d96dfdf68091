"""Tiered KV cache: host/NVMe spill pool for demoted prefix-cache pages
(ref: ZeRO-Infinity tiering, arXiv:2104.07857, and ZeRO-Offload host
staging, arXiv:2101.06840 — the weight-streaming playbook PR 1 built
`TierLayerReader` on, re-targeted at KV pages).

The paged prefix cache (PR 3) keeps published refcount-0 pages warm in
HBM until allocation pressure reclaims them; before this module,
reclaim meant DROP — the next prompt matching that span pays a full
re-prefill.  :class:`KVTierPool` gives the allocator somewhere cheaper
to put cold pages instead:

    HBM warm pool ──demote──▶ host pool ──spill──▶ NVMe ──▶ drop
         ▲                                │
         └──────────── promote ◀──────────┘

- **Demote** (eviction pressure or the ``demote_watermark`` sweep):
  the page's KV — one ``[L, KV, ps, Dh]`` array pair across the layer
  stack — is copied device→host and indexed under its content key.
  ``quantize_cold`` stores int8 codes + per-token-row f32 scales
  (~2x the pages per byte); off by default, keeping the spill path
  bit-exact.
- **Spill**: when the host pool overflows ``host_pool_bytes``, the
  OLDEST host entries cascade to per-page files under ``nvme_dir``
  through the aio pool (:mod:`deepspeed_tpu.io.aio`); with no
  ``nvme_dir`` (or past ``nvme_pool_bytes``) the oldest entries drop.
- **Promote**: an admission matching a demoted span allocates fresh
  HBM pages and streams the payload back through
  :class:`~deepspeed_tpu.param_stream.TierPageReader` — the pool
  implements the ``_Tier`` read interface (``get_submit`` /
  ``reads_pending`` / ``fence_reads`` / ``next_read_slot``), serving
  host entries as zero-copy arrays and NVMe entries as alternating-slot
  aio reads, so one promotion's group ``g+1`` reads overlap group
  ``g``'s dequant + H2D upload.

Quantization error contract (``quantize_cold``): symmetric per-row int8
over the head dim — scale = rowmax(|x|)/127, code = round(x/scale) — so
the dequantized page differs from the original by at most
``rowmax(|x|) * KV_TIER_QUANT_RTOL`` elementwise (one half quantization
step, plus the bf16 cast the cache dtype already imposes).  Tests gate
on exactly this bound.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu import faults as _faults
from deepspeed_tpu.faults import ChecksumError, retry_with_backoff
from deepspeed_tpu.inference.prefix_cache import TierEntry, key_hex
from deepspeed_tpu.utils.logging import logger

# per-element bound of the int8 cold-page codec, RELATIVE to the row's
# max |value| (the scale denominator): half a quantization step
KV_TIER_QUANT_RTOL = 0.5 / 127.0


def _crc(arr: np.ndarray) -> int:
    """crc32 of an array's raw bytes.  Extension dtypes (bfloat16,
    numpy type char 'E') refuse the buffer protocol, so checksum a
    uint8 VIEW — same bytes, no copy."""
    return zlib.crc32(np.ascontiguousarray(arr).view(np.uint8))


# ------------------------------------------------------------ int8 codec
def quantize_page(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric int8 over the last (head) dim: x [..., Dh] float →
    (codes int8 [..., Dh], scales f32 [..., 1]).  All-zero rows take
    scale 1.0 so the codec is exact on them."""
    x32 = np.asarray(x, np.float32)
    amax = np.abs(x32).max(axis=-1, keepdims=True)
    scale = amax / 127.0
    scale[scale == 0.0] = 1.0
    codes = np.clip(np.rint(x32 / scale), -127, 127).astype(np.int8)
    return codes, scale.astype(np.float32)


def dequantize_page(codes: np.ndarray, scale: np.ndarray,
                    dtype) -> np.ndarray:
    """Inverse of :func:`quantize_page`, cast back to the page dtype."""
    return (codes.astype(np.float32) * scale).astype(dtype)


def encode_entry(key: bytes, k: np.ndarray, v: np.ndarray, *,
                 quantize: bool, page_dtype, tick: int = 0) -> TierEntry:
    """Serialize one page's (k, v) into a host-resident
    :class:`~deepspeed_tpu.inference.prefix_cache.TierEntry`: the
    spill tier's demote path and the cross-replica KV fabric's export
    path share exactly this encoding (same buffer naming, same
    per-buffer crc32 recorded now and verified when a promotion — or a
    migrated admission on another replica — decodes the payload
    back)."""
    hexk = key_hex(key)
    if quantize:
        kq, ks = quantize_page(k)
        vq, vs = quantize_page(v)
        data = (kq, ks, vq, vs)
    else:
        data = (np.ascontiguousarray(k), np.ascontiguousarray(v))
    bufs = tuple((f"kv_{hexk}_{i}", tuple(b.shape), str(b.dtype))
                 for i, b in enumerate(data))
    sums = tuple(_crc(b) for b in data)
    return TierEntry(
        key=key, location="host", quantized=quantize,
        dtype=str(np.dtype(page_dtype)), buffers=bufs,
        nbytes=int(sum(b.nbytes for b in data)), data=data,
        tick=tick, checksums=sums)


def encode_prequantized_entry(key: bytes, kq: np.ndarray, ks: np.ndarray,
                              vq: np.ndarray, vs: np.ndarray, *,
                              page_dtype, tick: int = 0) -> TierEntry:
    """Serialize a page whose payload is ALREADY the int8 codec's
    (codes, scales) — the quantized-resident serving path demotes the
    device's code/scale planes verbatim, so no dequantize/requantize
    round-trip (and no second rounding) ever touches the data.  Buffer
    naming and checksums match :func:`encode_entry`'s quantized layout
    exactly: a prequantized demote and a host-side quantize of the
    same values produce interchangeable entries."""
    hexk = key_hex(key)
    data = tuple(np.ascontiguousarray(b) for b in (kq, ks, vq, vs))
    bufs = tuple((f"kv_{hexk}_{i}", tuple(b.shape), str(b.dtype))
                 for i, b in enumerate(data))
    sums = tuple(_crc(b) for b in data)
    return TierEntry(
        key=key, location="host", quantized=True,
        dtype=str(np.dtype(page_dtype)), buffers=bufs,
        nbytes=int(sum(b.nbytes for b in data)), data=data,
        tick=tick, checksums=sums)


# ------------------------------------------------- NVMe read/write legs
class _KVNvmeChannel:
    """Alternating-slot aio READ channel over per-page spill files,
    plus a blocking write leg for the spill cascade.

    Unlike :class:`~deepspeed_tpu.infinity._NvmeTier` (per-leaf files
    opened once and held for the engine's lifetime), spill files come
    and go with cache churn — fds open per batch and close at the
    fence, so a long-lived server never accumulates one fd per page it
    ever demoted."""

    def __init__(self, path: str, n_threads: int = 4, retries: int = 2,
                 backoff_s: float = 0.05, on_retry=None):
        from deepspeed_tpu.io.aio import AioHandle

        os.makedirs(path, exist_ok=True)
        self.dir = path
        self.rpools = [AioHandle(n_threads), AioHandle(n_threads)]
        self.rslot = 0
        self._rfds: List[List[int]] = [[], []]
        self._wpool = AioHandle(n_threads)
        # bounded spill-write retry (transient aio errors must not turn
        # a demotion into a dropped page on the first hiccup)
        self.retries = int(retries)
        self.backoff_s = float(backoff_s)
        self._on_retry = on_retry

    def _path(self, name: str) -> str:
        return os.path.join(self.dir, name + ".bin")

    # ---------------------------------------------------------- reads
    def submit_read(self, name: str, buf: np.ndarray) -> None:
        pool = self.rpools[self.rslot]
        fd = pool.open(self._path(name))
        pool.pread(fd, buf, 0)
        self._rfds[self.rslot].append(fd)

    def reads_pending(self) -> int:
        return self.rpools[self.rslot].pending()

    def fence_reads(self) -> None:
        pool = self.rpools[self.rslot]
        errs = pool.wait()
        for fd in self._rfds[self.rslot]:
            pool.close(fd)
        self._rfds[self.rslot] = []
        if errs:
            raise IOError(f"{errs} KV-tier NVMe reads failed")

    def next_read_slot(self) -> None:
        self.rslot ^= 1

    def fence_all_reads(self) -> None:
        """Drain BOTH slots (promotion cancel/abandon: the aio reads
        target host buffers the caller is about to drop).  Read errors
        are deliberately IGNORED here — every caller is abandoning the
        transfer, and an error raised mid-cancel would leave the
        channel/pin/quarantine state latched forever (the hang this
        drain exists to prevent)."""
        for s in (0, 1):
            pool = self.rpools[s]
            pool.wait()
            for fd in self._rfds[s]:
                pool.close(fd)
            self._rfds[s] = []
        self.rslot = 0

    # --------------------------------------------------------- writes
    def write(self, name: str, buf: np.ndarray) -> None:
        """Blocking spill write (demote is already the slow path),
        retried with backoff on transient errors; the LAST failure
        propagates and the caller degrades (the entry drops instead
        of spilling — correctness preserved, capacity lost)."""
        def attempt():
            fd = self._wpool.open(self._path(name), write=True)
            try:
                self._wpool.pwrite(fd, buf, 0)
                errs = self._wpool.wait()
            finally:
                self._wpool.close(fd)
            if errs:
                raise IOError(f"KV-tier NVMe write of {name} failed")

        retry_with_backoff(attempt, attempts=self.retries,
                           backoff_s=self.backoff_s,
                           on_retry=self._on_retry)

    def unlink(self, name: str) -> None:
        try:
            os.remove(self._path(name))
        except OSError:
            pass


class KVTierPool:
    """Host + NVMe spill pool for demoted KV pages, content-addressed
    by the same chained page keys as the HBM prefix cache.

    One pool per engine; the engine installs it as
    ``PageAllocator.spill`` so the allocator's chain walk
    (``lookup_tiered``) treats demoted spans as cache hits, and as
    ``demote_hook`` so eviction captures the page instead of dropping
    it.  The pool doubles as the ``_Tier`` read backend of
    :class:`~deepspeed_tpu.param_stream.TierPageReader` — ONE promotion
    streams through it at a time (the engine serializes admissions with
    tier hits), so the alternating aio read slots stay coherent.

    Entries pinned via :meth:`pin` (an in-flight promotion's keys) are
    exempt from the spill/drop cascade: a concurrent demotion must not
    delete a file the promotion's aio reads are about to land from.
    """

    def __init__(self, cfg, page_shape: Sequence[int], page_dtype,
                 registry=None):
        self.cfg = cfg
        self.page_shape = tuple(int(s) for s in page_shape)  # (L,KV,ps,Dh)
        self.page_dtype = np.dtype(page_dtype)
        self.entries: Dict[bytes, TierEntry] = {}
        self._tick = 0
        self.host_bytes = 0
        self.nvme_bytes = 0
        self._pinned: Dict[bytes, int] = {}   # key -> pin count
        self._host_n = 0
        self._nvme_n = 0
        # age order per location (oldest first; touch() refreshes):
        # the cascade pops victims in O(pinned-skips), not O(entries) —
        # a 64 GiB host pool holds ~65k cold pages and a linear scan
        # per displaced entry would go quadratic under churn
        self._order: Dict[str, "collections.OrderedDict"] = {
            "host": collections.OrderedDict(),
            "nvme": collections.OrderedDict()}
        # degraded state: a circuit breaker (the engine calls
        # :meth:`disable` after repeated promote failures) turns the
        # pool inert — lookups miss, demotes become plain evictions —
        # without touching entries an in-flight promotion still reads
        self.disabled: Optional[str] = None
        # write-path degradation accounting
        self.spill_failures = 0
        self.write_retries = 0

        def _note_write_retry(_a, _e):
            self.write_retries += 1
            self._c_write_retries.inc()

        self._nvme: Optional[_KVNvmeChannel] = None
        if cfg.nvme_dir:
            self._nvme = _KVNvmeChannel(
                cfg.nvme_dir, n_threads=cfg.aio_threads,
                retries=getattr(cfg, "io_retries", 2),
                backoff_s=getattr(cfg, "io_retry_backoff_s", 0.05),
                on_retry=_note_write_retry)
        # cooperative aio priority (set by the ZI engine when KV
        # promotion shares the disk with layer-weight streams)
        self._prio_group = None
        self._prio = 0
        # lifetime accounting
        self.spilled_pages = 0
        self.dropped_pages = 0
        if registry is None or not registry.enabled:
            from deepspeed_tpu.telemetry import NULL_METRIC

            self._c_spill_bytes = self._c_dropped = NULL_METRIC
            self._g_host = self._g_host_b = NULL_METRIC
            self._g_nvme = self._g_nvme_b = NULL_METRIC
            self._c_write_retries = self._c_spill_fail = NULL_METRIC
        else:
            self._c_spill_bytes = registry.counter(
                "kv_tier_spilled_bytes",
                "bytes cascaded host pool -> NVMe")
            self._c_dropped = registry.counter(
                "kv_tier_dropped_pages",
                "demoted pages dropped off the end of the tier "
                "cascade (no capacity left anywhere)")
            self._g_host = registry.gauge(
                "kv_tier_host_pages", "demoted pages host-resident")
            self._g_host_b = registry.gauge(
                "kv_tier_host_bytes", "host-pool bytes in use")
            self._g_nvme = registry.gauge(
                "kv_tier_nvme_pages", "demoted pages NVMe-resident")
            self._g_nvme_b = registry.gauge(
                "kv_tier_nvme_bytes", "NVMe spill bytes in use")
            self._c_write_retries = registry.counter(
                "kv_tier_write_retries",
                "spill writes retried after a transient aio error")
            self._c_spill_fail = registry.counter(
                "kv_tier_spill_failures",
                "spill writes that exhausted their retries (the entry "
                "dropped instead of spilling — capacity degradation, "
                "never incorrectness)")

    # ------------------------------------------------------- accounting
    @property
    def uses_aio(self) -> bool:
        return self._nvme is not None

    def _counts(self) -> Tuple[int, int]:
        # maintained incrementally like the byte totals: gauges refresh
        # on every demote/spill/discard, and an O(entries) scan there
        # would make batch sweeps quadratic in pool size
        return self._host_n, self._nvme_n

    def _refresh_gauges(self) -> None:
        h, n = self._counts()
        self._g_host.set(h)
        self._g_host_b.set(self.host_bytes)
        self._g_nvme.set(n)
        self._g_nvme_b.set(self.nvme_bytes)

    def occupancy(self) -> Dict[str, int]:
        h, n = self._counts()
        return {"host_pages": h, "host_bytes": int(self.host_bytes),
                "nvme_pages": n, "nvme_bytes": int(self.nvme_bytes),
                "spilled_pages": int(self.spilled_pages),
                "dropped_pages": int(self.dropped_pages),
                "spill_failures": int(self.spill_failures),
                "write_retries": int(self.write_retries),
                "disabled": self.disabled}

    # --------------------------------------------------- degraded state
    def disable(self, reason: str) -> None:
        """Circuit-break the tier: lookups miss (``has`` → False) and
        demotes become plain evictions, while entries stay intact for
        any promotion already streaming them.  Idempotent; surfaced by
        ``/healthz`` as a degraded reason."""
        if self.disabled is None:
            self.disabled = str(reason)
            logger.warning("kv_tier: tier DISABLED (%s) — demotes "
                           "become evictions, tier hits become misses",
                           reason)

    # --------------------------------------------------------- priority
    def set_priority(self, group, priority: int = 0) -> None:
        """Join an :class:`~deepspeed_tpu.io.aio.AioPriorityGroup`:
        promotion submission defers while a higher-priority member
        (e.g. the ZI layer-weight stream) has reads in flight."""
        self._prio_group = group
        self._prio = int(priority)
        if group is not None and self._nvme is not None:
            group.register(self._nvme.reads_pending, self._prio)

    def may_submit(self) -> bool:
        """False while a higher-priority aio user is mid-flight — the
        engine then defers the promotion presubmit (bounded: its
        deferral cap guarantees eventual submission)."""
        return self._prio_group is None or \
            not self._prio_group.busy_above(self._prio)

    # ------------------------------------------------------------ index
    def has(self, key: bytes) -> bool:
        return self.disabled is None and key in self.entries

    def location(self, key: bytes) -> Optional[str]:
        e = self.entries.get(key)
        return e.location if e is not None else None

    def touch(self, key: bytes) -> Optional[str]:
        """Refresh an entry's cascade age (a re-demote of a span whose
        payload is still spilled is free — no copy, no write)."""
        e = self.entries.get(key)
        if e is None:
            return None
        self._tick += 1
        e.tick = self._tick
        self._order[e.location].move_to_end(key)
        return e.location

    def pin(self, keys) -> None:
        """Refcounted: two concurrent promotions sharing a key must
        BOTH finish before the cascade may touch it — the first
        completion must not strip the other's protection."""
        for k in keys:
            self._pinned[k] = self._pinned.get(k, 0) + 1

    def unpin(self, keys) -> None:
        for k in keys:
            n = self._pinned.get(k, 0) - 1
            if n <= 0:
                self._pinned.pop(k, None)
            else:
                self._pinned[k] = n

    # ----------------------------------------------------------- demote
    def _encode(self, key: bytes, k: np.ndarray,
                v: np.ndarray) -> TierEntry:
        self._tick += 1
        # per-buffer crc32 recorded NOW (inside encode_entry), verified
        # when a promotion decodes the payload back — bit rot, a torn
        # spill write, or injected corruption all surface as
        # ChecksumError there, and the consumer re-prefills instead of
        # serving garbage KV
        return encode_entry(key, k, v,
                            quantize=self.cfg.quantize_cold,
                            page_dtype=self.page_dtype,
                            tick=self._tick)

    def demote(self, key: bytes, k: np.ndarray,
               v: np.ndarray) -> Optional[str]:
        """Capture one page's KV (``k``/``v``: [L, KV, ps, Dh] in the
        cache dtype) under ``key``.  Lands in the host pool, cascading
        older entries down (host → NVMe → drop) to make room; returns
        the landing tier, or None when nothing could hold it (the page
        is then a plain eviction).  A key already resident just
        refreshes its age — re-demoting a promoted page is free."""
        if self.disabled is not None:
            return None             # circuit-broken: plain eviction
        if key in self.entries:
            return self.touch(key)
        entry = self._encode(key, k, v)
        if _faults.active_plan() is not None:
            # kv_corrupt injection: flip a payload byte AFTER the
            # checksum was recorded — the promote-side verify must
            # catch exactly this
            _delay, err = _faults.poll("kv_corrupt", key_hex(key))
            if err is not None:
                _faults.corrupt_array(entry.data[0])
        return self._land(entry)

    def demote_prequantized(self, key: bytes, kq: np.ndarray,
                            ks: np.ndarray, vq: np.ndarray,
                            vs: np.ndarray) -> Optional[str]:
        """Capture one ALREADY-QUANTIZED page (``kq``/``vq``: int8
        codes [L, KV, ps, Dh]; ``ks``/``vs``: f32 scales [L, KV, ps,
        1]) — the quantized-resident engine's demote path, where the
        device planes ARE the codec form so the host-side quantize in
        :meth:`demote` would be a lossy no-op.  Same landing/cascade
        semantics; requires ``quantize_cold`` (the config validates
        the pairing, this guards direct callers)."""
        if self.disabled is not None:
            return None             # circuit-broken: plain eviction
        if key in self.entries:
            return self.touch(key)
        if not self.cfg.quantize_cold:
            raise ValueError(
                "demote_prequantized requires kv_tier.quantize_cold — "
                "a dense-entry pool cannot hold int8 codec payloads")
        self._tick += 1
        entry = encode_prequantized_entry(
            key, kq, ks, vq, vs, page_dtype=self.page_dtype,
            tick=self._tick)
        if _faults.active_plan() is not None:
            _delay, err = _faults.poll("kv_corrupt", key_hex(key))
            if err is not None:
                _faults.corrupt_array(entry.data[0])
        return self._land(entry)

    def admit_entry(self, entry: TierEntry) -> Optional[str]:
        """Admit an ALREADY-SERIALIZED entry (a fabric migration: the
        payload was encoded — and checksummed — on another replica;
        quantized cold pages ride as-is).  Record AND payload are
        copied — this pool's lifetime must never alias a shared
        transit buffer (a later in-fabric corruption or eviction
        cannot reach pages already admitted here).  Returns the
        landing tier like :meth:`demote`; the original checksums carry
        over, so a payload corrupted in transit fails this pool's
        promotion-time verify and the admitting engine re-prefills."""
        if self.disabled is not None:
            return None
        if entry.key in self.entries:
            return self.touch(entry.key)
        self._tick += 1
        clone = dataclasses.replace(
            entry, location="host", tick=self._tick,
            data=tuple(np.array(b, copy=True) for b in entry.data))
        return self._land(clone)

    def entry_payload(self, key: bytes) -> TierEntry:
        """A host-form view of one entry for export: host entries
        return as-is; an NVMe entry's buffers are read back
        synchronously (export is off the decode critical path).  The
        ORIGINAL checksums ride along — the importer's decode verifies
        them, so corruption anywhere between the demote that recorded
        them and the remote promotion is caught there."""
        e = self.entries[key]
        if e.location == "host":
            return e
        bufs = tuple(
            _faults.read_file_sync(self._nvme._path(name), shape,
                                   dtype, key=name)
            for name, shape, dtype in e.buffers)
        return dataclasses.replace(e, location="host", data=bufs)

    def _land(self, entry: TierEntry) -> Optional[str]:
        """Place a freshly encoded (or fabric-admitted) entry: host
        pool first, cascading older entries down (host → NVMe → drop)
        to make room; an entry bigger than the whole host pool goes
        straight to NVMe."""
        key = entry.key
        if entry.nbytes > self.cfg.host_pool_bytes:
            # bigger than the whole host pool: straight to NVMe (the
            # entry was never host-accounted — accounted=False keeps
            # host_bytes from going negative)
            if self._spill_entry(entry, accounted=False):
                self.entries[key] = entry
                self._refresh_gauges()
                return entry.location
            self.dropped_pages += 1
            self._c_dropped.inc()
            return None
        while self.host_bytes + entry.nbytes > self.cfg.host_pool_bytes:
            if not self._cascade_one():
                self.dropped_pages += 1
                self._c_dropped.inc()
                return None
        self.entries[key] = entry
        self.host_bytes += entry.nbytes
        self._host_n += 1
        self._order["host"][key] = None
        self._refresh_gauges()
        return "host"

    def _oldest(self, location: str) -> Optional[TierEntry]:
        for key in self._order[location]:
            if key not in self._pinned:
                return self.entries[key]
        return None

    def _cascade_one(self) -> bool:
        """Push the oldest unpinned host entry down one tier (NVMe when
        configured, else drop).  Returns False when the host pool holds
        only pinned entries — the caller's demote then drops."""
        victim = self._oldest("host")
        if victim is None:
            return False
        if self._spill_entry(victim):
            return True
        self._discard(victim, count_drop=True)
        return True

    def _spill_entry(self, e: TierEntry, accounted: bool = True) -> bool:
        """Write ``e``'s payload to NVMe files and retag it.
        ``accounted=False`` for an entry that never entered the host
        pool (demote's direct-to-NVMe path) — only pool residents may
        decrement ``host_bytes``."""
        if self._nvme is None:
            return False
        cap = self.cfg.nvme_pool_bytes
        while cap is not None and self.nvme_bytes + e.nbytes > cap:
            old = self._oldest("nvme")
            if old is None:
                return False
            self._discard(old, count_drop=True)
        try:
            for (name, _s, _d), buf in zip(e.buffers, e.data):
                self._nvme.write(name, buf)
        except (IOError, OSError):
            # retries exhausted: unlink any partial files (a later
            # same-key spill must not find a torn payload) and degrade
            # — the entry drops instead of spilling
            for name in e.names:
                self._nvme.unlink(name)
            self.spill_failures += 1
            self._c_spill_fail.inc()
            logger.warning("kv_tier: spill write of %s failed after "
                           "retries — dropping the entry",
                           key_hex(e.key)[:12])
            return False
        if accounted and e.location == "host":
            self.host_bytes -= e.nbytes
            self._host_n -= 1
        self._order["host"].pop(e.key, None)
        e.location = "nvme"
        e.data = None
        self.nvme_bytes += e.nbytes
        self._nvme_n += 1
        self._order["nvme"][e.key] = None
        self.spilled_pages += 1
        self._c_spill_bytes.inc(e.nbytes)
        self._refresh_gauges()
        return True

    def _discard(self, e: TierEntry, count_drop: bool = False) -> None:
        self.entries.pop(e.key, None)
        self._order[e.location].pop(e.key, None)
        if e.location == "host":
            self.host_bytes -= e.nbytes
            self._host_n -= 1
        else:
            self.nvme_bytes -= e.nbytes
            self._nvme_n -= 1
            if self._nvme is not None:
                for name in e.names:
                    self._nvme.unlink(name)
        if count_drop:
            self.dropped_pages += 1
            self._c_dropped.inc()
        self._refresh_gauges()

    def discard(self, key: bytes) -> None:
        e = self.entries.get(key)
        if e is not None:
            self._discard(e)

    def host_view(self) -> "_HostOnlyView":
        """A channel-free read view for promotions whose keys are ALL
        host-resident (pinned, so they cannot spill mid-flight): its
        fence/slot operations are no-ops, so any number of such
        promotions run concurrently without touching — or blocking
        on — the single NVMe aio channel another promotion may own."""
        return _HostOnlyView(self)

    # ------------------------------------- _Tier read interface (promote)
    # (consumed by param_stream.TierPageReader; the NVMe channel is
    # single-consumer — the engine serializes promotions that need it,
    # host-resident promotions ride host_view() instead.  The DEVICE
    # half of a promotion — the scatter of these payloads into HBM
    # pages — shows in a device trace; the host read side stays
    # visible through the kv_tier promote-stall histogram)
    def entry_meta(self, key: bytes):
        """(names, shapes, dtypes) of ``key``'s spilled buffers — the
        read plan a TierPageReader submits."""
        e = self.entries[key]
        return (list(e.names), [b[1] for b in e.buffers],
                [b[2] for b in e.buffers])

    def get_submit(self, name: str, shape, dtype, out=None):
        hexk, i = name[len("kv_"):].rsplit("_", 1)
        e = self.entries[bytes.fromhex(hexk)]
        if e.location == "host":
            # zero-copy: the stored array IS the fenced buffer (the
            # cascade may spill it to NVMe mid-promotion, but spilling
            # keeps the array alive in the file — and the returned
            # reference stays valid regardless)
            return e.data[int(i)]
        buf = np.empty(shape, np.dtype(dtype)) if out is None else out
        self._nvme.submit_read(name, buf)
        return buf

    def reads_pending(self) -> int:
        return self._nvme.reads_pending() if self._nvme is not None else 0

    def fence_reads(self) -> None:
        if self._nvme is not None:
            self._nvme.fence_reads()

    def next_read_slot(self) -> None:
        if self._nvme is not None:
            self._nvme.next_read_slot()

    def fence_all_reads(self) -> None:
        if self._nvme is not None:
            self._nvme.fence_all_reads()

    def read_sync(self, name: str, shape, dtype) -> np.ndarray:
        """Synchronous fallback read of one spilled buffer — the
        degradation rung below the aio channel (``TierLayerReader``
        falls here when a fence exhausted its retries): host entries
        return their stored array, NVMe entries read their file through
        the plain OS path, bypassing the aio pool entirely."""
        hexk, i = name[len("kv_"):].rsplit("_", 1)
        e = self.entries[bytes.fromhex(hexk)]
        if e.location == "host":
            _faults.inject("sync_read", key=name)
            return e.data[int(i)]
        return _faults.read_file_sync(self._nvme._path(name), shape,
                                      dtype, key=name)

    # ----------------------------------------------------------- decode
    def _host_buffer(self, name: str) -> np.ndarray:
        """Resolve ``name`` strictly from host storage (the
        channel-free view's read path — an NVMe entry here means a pin
        failed to hold the entry host-resident, which must fail loudly
        rather than fence a channel this promotion does not own)."""
        hexk, i = name[len("kv_"):].rsplit("_", 1)
        e = self.entries[bytes.fromhex(hexk)]
        if e.location != "host":
            raise RuntimeError(
                f"channel-free promotion read of {name} found the "
                f"entry on {e.location!r} — pinned entries must stay "
                "host-resident")
        return e.data[int(i)]

    def _verify(self, key: bytes, e: TierEntry, bufs) -> None:
        """Check every fenced buffer against the checksum recorded at
        demote time — corrupt payloads must raise
        :class:`~deepspeed_tpu.faults.ChecksumError` BEFORE anything
        scatters into live HBM pages."""
        if e.checksums is None:
            return
        for (name, _s, _d), buf, want in zip(e.buffers, bufs,
                                             e.checksums):
            got = _crc(buf)
            if got != want:
                raise ChecksumError(
                    f"KV-tier page {key_hex(key)[:12]} buffer "
                    f"{name}: payload checksum mismatch "
                    f"({got:#x} != {want:#x}) — spilled copy is "
                    "corrupt")

    def decode(self, key: bytes, bufs) -> Tuple[np.ndarray, np.ndarray]:
        """Fenced buffers → the page's (k, v) in the cache dtype
        (dequantizing cold pages).  Checksum-verified FIRST."""
        e = self.entries[key]
        self._verify(key, e, bufs)
        if e.quantized:
            kq, ks, vq, vs = bufs
            return (dequantize_page(kq, ks, self.page_dtype),
                    dequantize_page(vq, vs, self.page_dtype))
        k, v = bufs
        return (np.asarray(k, self.page_dtype),
                np.asarray(v, self.page_dtype))

    def decode_quantized(self, key: bytes, bufs):
        """Fenced buffers → the page's RAW int8 codec form ``(kq, ks,
        vq, vs)``, checksum-verified first — the quantized-resident
        publish path scatters these straight into the device's
        code/scale planes, skipping the dense dequantize entirely (the
        whole point of ``kv_tier.quantized_resident``).  Raises on a
        dense (unquantized) entry: there are no codes to publish."""
        e = self.entries[key]
        if not e.quantized:
            raise ValueError(
                f"KV-tier page {key_hex(key)[:12]} is a dense entry — "
                "quantized-resident promotion needs "
                "kv_tier.quantize_cold payloads")
        self._verify(key, e, bufs)
        kq, ks, vq, vs = bufs
        return (np.asarray(kq, np.int8), np.asarray(ks, np.float32),
                np.asarray(vq, np.int8), np.asarray(vs, np.float32))


class _HostOnlyView:
    """Channel-free ``_Tier`` read facade over a :class:`KVTierPool`:
    host-array reads with no-op fencing, so a host-resident promotion
    never blocks on (or corrupts the slot state of) the NVMe channel a
    concurrent promotion owns."""

    def __init__(self, pool: KVTierPool):
        self._pool = pool

    def entry_meta(self, key: bytes):
        return self._pool.entry_meta(key)

    def get_submit(self, name: str, shape, dtype, out=None):
        return self._pool._host_buffer(name)

    def read_sync(self, name: str, shape, dtype):
        return self._pool.read_sync(name, shape, dtype)

    def reads_pending(self) -> int:
        return 0

    def fence_reads(self) -> None:
        pass

    def next_read_slot(self) -> None:
        pass
