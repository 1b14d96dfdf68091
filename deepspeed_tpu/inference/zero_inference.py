"""ZeRO-Inference for TPU serving: serve models LARGER than HBM by
streaming layer weights host→HBM under the decode sweep.

Reference: DeepSpeed ZeRO-Inference (arXiv:2206.01861, built on
ZeRO-Infinity's parameter offload, arXiv:2104.07857 +
deepspeed/runtime/swap_tensor/partitioned_param_swapper.py): model
weights live on a host-RAM or NVMe tier; per-layer weights are fetched
into device memory just ahead of their layer's compute and released
after, so GPU/TPU residency is O(layers-in-flight), not O(model), and
throughput is bound by link bandwidth × batch, not by HBM capacity.

TPU design.  The serving stack already factors per request phase into
static-shape programs (:class:`~deepspeed_tpu.inference.serving.
ServingEngine`); this module re-factors the MODEL the same way the
training :class:`~deepspeed_tpu.param_stream.ParamStreamEngine` does —
per-LAYER jits instead of one whole-model jit:

    stem:   (stem, tokens, start) -> (x, ctx)            [resident]
    block:  (lp, x, ctx, kp, vp, table, start)
            -> (x, kp, vp)                               [one layer]
    head:   (head, x) -> logits                          [resident]

The HOST drives the layer sweep.  Streamed layers ride the shared
:class:`~deepspeed_tpu.param_stream.TierLayerReader` pipeline: while
layer ``l``'s block program computes, layer ``l+1``'s tier read (NVMe
aio on alternating slots, or host buffers) and its async H2D upload are
already in flight — the same double-buffered phase overlap the training
engine uses, re-targeted at decode.  The KV cache is stored as
PER-LAYER page arrays (a tuple, not a stacked [L, ...] block) so each
block program donates and updates exactly one layer's pages in place —
no cross-layer cache copies on the hot path.

An HBM-budget planner (:func:`plan_residency`) charges stem + head +
the KV cache + the ``(prefetch_depth + 1)``-layer streaming working set
against ``hbm_budget_bytes`` and pins as many leading layers resident
as still fit; the rest stream.  ``hbm_budget_bytes: null`` streams
every layer (the serve-anything default).  Composes with:

- the paged-KV decode kernels: block programs call the same
  :func:`~deepspeed_tpu.inference.kernels.paged_attention_step` the
  whole-model forward uses — token-identical output;
- int8 weight-only quantization: the tier holds int8 codes + group
  scales and each block program traces its own dequant;
- tensor/expert parallelism: streamed uploads land pre-sharded via the
  model's own PartitionSpecs (per-layer, layer axis dropped), the KV
  head axis shards over ``model``;
- the continuous-batching scheduler: admission, paging, split-fuse and
  chunked decode run unchanged — only the three compiled entry points
  are swapped for host-driven streamed executors;
- automatic prefix caching (``prefix_cache=``): matching, sharing, and
  warm-pool eviction live in the base scheduler's refcounted allocator
  and page-table bookkeeping, so streamed block programs read shared
  pages through the same per-layer page arrays — a cache-hit admission
  runs the "chunk" phase over the uncached suffix only;
- speculative decoding (``speculative=``): the verify pass is the
  SAME host-driven "chunk" executor, so one full layer-weight stream
  scores K+1 positions per slot and the streamed bytes per generated
  token drop by the mean acceptance length — the single biggest lever
  on a decode loop whose throughput is pinned to stream bandwidth
  (``zi_bytes_uploaded`` / generated tokens is the contract metric;
  SPEC_BENCH.json carries the A/B).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.config import KVTierConfig, ZeroInferenceConfig
from deepspeed_tpu.infinity import _NvmeTier, _RamTier
from deepspeed_tpu.inference.kernels import PagedKVCache
from deepspeed_tpu.inference.paged_forward import paged_layered_fns
from deepspeed_tpu.inference.serving import (_WIRE_MIN_ELEMS, ServingEngine,
                                             _dispatch_keys, _last_row,
                                             _sample_rows, boundary_program)
from deepspeed_tpu.models.family import decoder_families
from deepspeed_tpu.param_stream import TierLayerReader
from deepspeed_tpu.utils.logging import logger


def _unused_program(*_a, **_k):  # pragma: no cover - must never run
    raise AssertionError(
        "ZeroInferenceServingEngine replaces the whole-model programs "
        "with host-driven streamed executors")


def plan_residency(*, n_layers: int, layer_bytes: int,
                   stem_head_bytes: int, cache_bytes: int,
                   budget: Optional[int],
                   prefetch_depth: int) -> Dict[str, Any]:
    """HBM-budget planner: how many leading layers stay resident.

    Fixed charges come first — stem + head weights, the paged KV cache,
    and (whenever anything streams) the ``(prefetch_depth + 1)``-layer
    double-buffer working set.  Whatever budget remains pins layers
    resident.  ``budget=None`` streams everything; a budget that cannot
    even hold the fixed charges is a config error, not a silent OOM.
    """
    total_resident = stem_head_bytes + cache_bytes + n_layers * layer_bytes
    working = (prefetch_depth + 1) * layer_bytes
    if budget is None:
        n_res = 0
    elif budget >= total_resident:
        n_res = n_layers
    else:
        floor = stem_head_bytes + cache_bytes + working
        if floor > budget:
            raise ValueError(
                f"zero_inference.hbm_budget_bytes={budget} cannot hold "
                f"the streaming floor: stem+head {stem_head_bytes} B + "
                f"KV cache {cache_bytes} B + {prefetch_depth + 1}-layer "
                f"working set {working} B = {floor} B")
        n_res = min(n_layers - 1, (budget - floor) // max(layer_bytes, 1))
    ws = stem_head_bytes + cache_bytes + n_res * layer_bytes + (
        0 if n_res == n_layers else working)
    return {
        "n_layers": n_layers,
        "n_resident": int(n_res),
        "n_streamed": int(n_layers - n_res),
        "layer_bytes": int(layer_bytes),
        "stem_head_bytes": int(stem_head_bytes),
        "cache_bytes": int(cache_bytes),
        "weight_image_bytes": int(stem_head_bytes
                                  + n_layers * layer_bytes),
        "hbm_budget_bytes": budget,
        "prefetch_depth": int(prefetch_depth),
        "hbm_working_set_bytes": int(ws),
    }


class ZeroInferenceServingEngine(ServingEngine):
    """Weight-streamed continuous-batching serving engine.

    Drop-in for :class:`ServingEngine` — same ``submit``/``step``/
    ``run`` surface, same scheduler — with the three compiled entry
    points replaced by host drivers that sweep per-layer programs and
    stream non-resident layer weights from ``self.tier``.  ``plan``
    carries the residency decision;
    :meth:`hbm_weight_working_set_bytes` is the streaming contract
    (compare: the full weight image for the resident engine).
    """

    def __init__(self, *, stem, blocks, head, fns, zi: ZeroInferenceConfig,
                 n_layers: int, n_kv: int, head_dim: int, mesh=None,
                 stem_specs=None, head_specs=None, layer_specs=None,
                 **kw):
        self._zi = zi
        kvt = KVTierConfig.coerce(kw.get("kv_tier"))
        if kvt.enabled and kvt.quantized_resident:
            # the streamed engine's cache is a per-layer TUPLE of dense
            # pages (block programs donate one layer in place); it has
            # no int8 code/scale planes to publish into — fail loudly,
            # never silently serve dense pages under a quantized-
            # resident config
            raise NotImplementedError(
                "kv_tier.quantized_resident is not wired for the "
                "weight-streamed (zero_inference) engine — serve "
                "resident, or drop quantized_resident")
        self._stem_fn, self._block_fn, self._head_fn = fns
        self._layer_specs = layer_specs
        self._stem_specs = stem_specs
        self._head_specs = head_specs
        self._L = n_layers

        # ---- per-layer leaf records from the stacked blocks tree.
        # Leaves stay host-side VIEWS of the caller's arrays where
        # possible: inference never mutates weights, so the tier can
        # alias them (unlike the training engine's mutating tier).
        leaves, self._btree = jax.tree_util.tree_flatten(blocks)
        leaves = [np.asarray(a) for a in leaves]
        for a in leaves:
            if a.shape[0] != n_layers:
                raise ValueError(
                    f"stacked block leaf {a.shape} does not carry the "
                    f"layer axis (n_layers={n_layers}) in dim 0")
        self._bshapes = [a.shape[1:] for a in leaves]
        self._bdtypes = [a.dtype for a in leaves]
        layer_bytes = sum(a.nbytes // n_layers for a in leaves)

        # ---- residency plan.  Cache geometry mirrors ServingEngine's
        # signature defaults (kw is forwarded verbatim to super()).
        num_pages = kw.get("num_pages", 128)
        page_size = kw.get("page_size", 16)
        cache_dtype = kw.get("cache_dtype", jnp.bfloat16)
        cache_bytes = (2 * n_layers * n_kv * num_pages * page_size
                       * head_dim * jnp.dtype(cache_dtype).itemsize)
        # dedupe shared leaves by identity: tied-embedding models alias
        # ONE table between stem and head — charging it twice would
        # overstate the fixed charge by the largest resident tensor
        seen_ids = set()
        stem_head_bytes = 0
        for x in jax.tree.leaves((stem, head)):
            if id(x) not in seen_ids:
                seen_ids.add(id(x))
                stem_head_bytes += x.nbytes
        self.plan = plan_residency(
            n_layers=n_layers, layer_bytes=layer_bytes,
            stem_head_bytes=stem_head_bytes, cache_bytes=cache_bytes,
            budget=zi.hbm_budget_bytes, prefetch_depth=zi.prefetch_depth)
        n_res = self.plan["n_resident"]
        self._streamed_ids = list(range(n_res, n_layers))

        # ---- tier ingest for the streamed suffix
        if zi.tier == "nvme" and self._streamed_ids:
            self.tier = _NvmeTier(
                os.path.join(zi.nvme_path, "zero_inference"))
        else:
            self.tier = _RamTier()
        for l in self._streamed_ids:
            for i, a in enumerate(leaves):
                self.tier.put(f"zi_p_{l}_{i}", np.ascontiguousarray(a[l]))
        if isinstance(self.tier, _NvmeTier):
            self.tier.fence_all()

        # the scheduler never touches params in streamed mode — stem and
        # head live on device here, blocks on the tier
        super().__init__(None, _unused_program, _unused_program,
                         n_layers=n_layers, n_kv=n_kv, head_dim=head_dim,
                         mesh=mesh, chunk_prefill_fn=_unused_program,
                         **kw)

        # streaming telemetry on the engine's registry (created by the
        # base ctor): upload/sweep counters, bytes moved, the exposed
        # (non-hidden) prefetch wait distribution, and an achieved-
        # bandwidth gauge — the observability ZeRO-Inference needs to
        # answer "is the NVMe->host->HBM latency actually hidden?"
        self._layer_bytes = int(layer_bytes)
        r = self.registry
        self._c_h2d = r.counter(
            "zi_layer_h2d_uploads", "per-layer host->HBM weight uploads")
        self._c_sweeps = r.counter(
            "zi_layer_sweeps", "full layer-stack sweeps driven")
        self._c_bytes = r.counter(
            "zi_bytes_uploaded", "weight bytes shipped host->HBM")
        self._h_wait = r.histogram(
            "zi_prefetch_wait_seconds",
            "time the sweep blocked on a tier fence (exposed IO cost; "
            "0-heavy distribution means prefetch fully hides the link)")
        self._g_bw = r.gauge(
            "zi_h2d_bandwidth_bytes_per_s",
            "streamed bytes / sweep wall time (lower bound: the sweep "
            "window includes the compute the stream hides behind)")
        # int8 layer broadcast (comm.quantized_serving, ISSUE 18): every
        # upload — the resident pins below AND the steady-state tier
        # stream — packs float leaves host-side so the H2D link carries
        # int8 codes + f32 scales (the training gradient wire's codec,
        # comm/collectives.py).  The serving_rtol gate runs once per
        # layer, on its first upload.
        self._wire_on = self._comm.quantized_serving
        self._wire_checked: set = set()
        if self._wire_on:
            # the serving_rtol gate runs at BUILD over every layer's
            # leaves: a config the codec cannot honor must fail the
            # constructor, not surface later as swallowed per-request
            # admission failures from the reader thread (request
            # isolation treats a mid-stream exception as one bad
            # request, which a config error is not)
            for a in leaves:
                for l in range(n_layers):
                    self._wire_check(a[l], l)
            self._wire_checked.update(range(n_layers))
        self._c_comm_int8 = r.counter(
            "comm_bytes_on_wire_int8",
            "bytes actually shipped on the quantized wire (int8 codes "
            "+ f32 scales)")
        self._c_comm_f32 = r.counter(
            "comm_bytes_on_wire_f32",
            "bytes a flat f32 wire would have shipped for the same "
            "payload")
        # incident wiring (PR 15): a streamed engine's trajectory
        # pathology of interest is the tier fence — watch the
        # prefetch-wait p95 history series so a developing stall trend
        # trips an anomaly bundle before the burn alert fires.  Only
        # when the detector set is the DEFAULT one: an operator's
        # explicit `detect` list (incl. the hard-triggers-only `()`)
        # must not be re-armed behind their back
        if self.incident_mgr.enabled and self._detect_defaulted:
            self.incident_mgr.watch_series(
                "zi_prefetch_wait_seconds:p95")
        self._resident = {
            l: self._upload_layer([a[l] for a in leaves], l)
            for l in range(n_res)}
        # capture only the COUNT: a lambda closing over `leaves` would
        # pin the full host weight image for the engine's lifetime —
        # defeating the NVMe tier, whose whole point is that the host
        # drops the image once the per-layer files are fenced
        n_leaves = len(leaves)
        self._reader = TierLayerReader(
            self.tier,
            names_fn=lambda l: [f"zi_p_{l}_{i}"
                                for i in range(n_leaves)],
            shapes=self._bshapes, dtypes=self._bdtypes,
            to_device=self._upload_layer, depth=zi.prefetch_depth,
            registry=self.registry, prefix="zi_stream",
            # layer fetch-issue/arrive/stall events land in the same
            # flight recorder as the request lifecycle (base ctor built
            # the tracer): a slow request's trace shows WHICH layer's
            # tier fence it sat behind
            tracer=self.tracer,
            # graceful stream degradation: transient read failures
            # retry (resubmit + backoff), then fall over to synchronous
            # tier-file reads; only an unrecoverable failure raises the
            # structured fatal — after a flight-recorder postmortem
            retries=zi.io_retries,
            retry_backoff_s=zi.io_retry_backoff_s)
        # KV-tier promotion and the layer-weight stream share the same
        # storage device when both tiers are NVMe: register the weight
        # read pools ABOVE the KV pool in a cooperative priority group,
        # so a KV promote defers (bounded by the engine's deferral cap)
        # while layer fetches are in flight — the decode sweep's
        # double-buffered weight reads are a whole-batch stall if
        # starved, a deferred promotion only delays one admission
        if self._kv_pool is not None and isinstance(self.tier, _NvmeTier):
            from deepspeed_tpu.io.aio import AioPriorityGroup

            grp = AioPriorityGroup()
            for h in self.tier.rpools:
                grp.register(h.pending, 1)
            self._kv_pool.set_priority(grp, 0)
        self._stem_dev = self._place(stem, stem_specs)
        if "embed" in head and head["embed"] is stem["embed"]:
            # tied embeddings: hand head the ALREADY-PLACED table so the
            # device holds one copy (device_put of a placed array with
            # the same sharding is a no-op, not a second upload)
            head = dict(head, embed=self._stem_dev["embed"])
        self._head_dev = self._place(head, head_specs)
        logger.info(
            "zero-inference: %d/%d layers resident (%.1f MB/layer), "
            "tier=%s depth=%d, HBM weight working set %.1f MB of a "
            "%.1f MB image",
            n_res, n_layers, layer_bytes / 1e6, zi.tier,
            self._reader.depth,
            self.plan["hbm_working_set_bytes"] / 1e6,
            self.plan["weight_image_bytes"] / 1e6)

    # ------------------------------------------------------- placement
    def _place(self, tree, specs):
        if specs is not None:
            from deepspeed_tpu.inference.quantized import shard_quantized

            return shard_quantized(tree, specs, self._mesh)
        return jax.device_put(tree)

    def _upload_layer(self, bufs: List[np.ndarray], _l: int):
        """Fenced host buffers → device tree for ONE layer (the async
        H2D the reader keeps in flight behind the sweep); TP/EP uploads
        land pre-sharded under the model's own per-layer specs.  Under
        ``comm.quantized_serving`` float leaves cross the link as int8
        codes + scales and dequantize device-side."""
        if self._wire_on:
            bufs = [self._wire_put(a, _l) for a in bufs]
            self._wire_checked.add(_l)
        tree = jax.tree_util.tree_unflatten(self._btree, list(bufs))
        self._c_h2d.inc()
        self._c_bytes.inc(self._layer_bytes)
        return self._place(tree, self._layer_specs)

    def _wire_check(self, buf, l: int) -> None:
        """serving_rtol gate for one leaf of layer ``l`` — exact
        host-side round-trip error of the wire codec, raising on a
        config the codec cannot honor.  Build runs it over every layer;
        :meth:`_wire_put` re-runs it only for layers the build never
        saw (``_wire_checked`` is the ledger)."""
        from deepspeed_tpu.comm.collectives import quantize_for_wire_np

        a = np.asarray(buf)
        if a.dtype.kind != "f" or a.size < _WIRE_MIN_ELEMS:
            return
        q, s, _ = quantize_for_wire_np(a)
        af32 = a.astype(np.float32)
        deq = (q.astype(np.float32).reshape(s.size, -1)
               * s[:, None]).reshape(a.shape)
        rel = float(np.abs(deq - af32).max()) \
            / (float(np.abs(af32).max()) or 1.0)
        if rel > self._comm.serving_rtol:
            raise ValueError(
                f"comm.quantized_serving: layer {l} leaf {a.shape} "
                f"round-trips at rel err {rel:.3e} > serving_rtol "
                f"{self._comm.serving_rtol:g} — raise the tolerance "
                "or stream this model unquantized")

    def _wire_put(self, buf, l: int):
        """One leaf onto the int8 wire: host-side pack → H2D of codes +
        scales → device-side dequant to the leaf's dtype.  Non-float and
        tiny leaves ship exact (same threshold as the TP placement
        path).  The stream re-ships the same bytes every sweep, so the
        build-time gate covers the engine's lifetime without taxing the
        hot path."""
        from deepspeed_tpu.comm.collectives import (dequantize_from_wire,
                                                    quantize_for_wire_np)

        a = np.asarray(buf)
        if a.dtype.kind != "f" or a.size < _WIRE_MIN_ELEMS:
            return buf
        if l not in self._wire_checked:
            self._wire_check(a, l)
        q, s, dt = quantize_for_wire_np(a)
        self._c_comm_int8.inc(q.nbytes + s.nbytes)
        self._c_comm_f32.inc(a.size * 4)
        return dequantize_from_wire(jnp.asarray(q), jnp.asarray(s),
                                    jnp.dtype(dt))

    # ---------------------------------------------------- program hooks
    def _alloc_cache(self, n_layers, n_kv, num_pages, page_size,
                     head_dim, cache_dtype) -> PagedKVCache:
        # PER-LAYER page arrays: each block program donates and returns
        # one layer's [KV, P, ps, Dh] pages — a stacked cache would turn
        # every layer's update into a whole-cache copy under streaming
        from jax.sharding import PartitionSpec as P

        kv_sh = None
        if self._mesh is not None and self._mesh.size("model") > 1:
            kv_sh = self._mesh.sharding(P("model", None, None, None))

        def kv():
            z = jnp.zeros((n_kv, num_pages, page_size, head_dim),
                          cache_dtype)
            return jax.device_put(z, kv_sh) if kv_sh is not None else z

        return PagedKVCache(
            k=tuple(kv() for _ in range(n_layers)),
            v=tuple(kv() for _ in range(n_layers)),
            table=self._put(jnp.full(
                (self.max_batch, self.max_pages_per_seq),
                self.trash_page, jnp.int32)),
            seq_lens=self._put(jnp.zeros((self.max_batch,), jnp.int32)),
            page_size=page_size)

    def _build_programs(self, prefill_fn, decode_fn,
                        chunk_prefill_fn) -> None:
        self._stem_jit = jax.jit(self._stem_fn)
        self._head_jit = jax.jit(self._head_fn)
        self._bjits: Dict[Any, Any] = {}
        # the base engine's program contract over host-driven sweeps:
        # a prefill returns its last row, a decode chunk derives its
        # keys from the dispatch ordinal — each the same small function
        # the whole-model programs inline, jitted on its own behind the
        # streamed head
        K, B = self.decode_chunk, self.max_batch

        def dstpu_sample(logits, key, ordinal, j, temps):
            keys = _dispatch_keys(key, ordinal, K, B)
            return _sample_rows(logits[:, -1], keys[j], temps)

        self._row_jit = jax.jit(_last_row)
        self._sample_jit = jax.jit(dstpu_sample)
        self._boundary = jax.jit(boundary_program(_sample_rows))
        self._prefill = self._streamed_prefill
        self._chunk_prefill = self._streamed_chunk_prefill
        self._verify_chunk = self._streamed_verify_chunk
        self._decode_chunk_fn = self._streamed_decode_chunk

    def _devprof_warmup(self) -> None:
        """No build-time precompile either: a streamed-executor
        "dispatch" is a full host-driven layer sweep through the NVMe
        reader pipeline — running one at build would read every layer
        off disk before the first request.  The per-block jits compile
        lazily on the first sweep instead; the steady-state boundary
        (first token) already sits after that sweep."""
        return

    def _block_jit(self, phase: str):
        """Per-phase block program.  Only the pages donate (they update
        in place); the layer weights do NOT — no block output matches a
        weight leaf's shape, so weight donation could never be honored
        (it only warns), and a streamed layer's buffer frees the moment
        the sweep drops its last reference anyway."""
        if phase not in self._bjits:
            f = functools.partial(self._block_fn,
                                  continuation=phase == "chunk",
                                  prefill=phase == "prefill")
            self._bjits[phase] = jax.jit(f, donate_argnums=(3, 4))
        return self._bjits[phase]

    # ------------------------------------------------------ layer sweep
    # dstpu: hot-path
    def _layer_sweep(self):
        """Yield ``(l, layer_params)`` over all layers in order;
        streamed layers come off the double-buffered reader pipeline
        with the next layer's read + upload already in flight."""
        self._c_sweeps.inc()
        gen = (self._reader.sweep(self._streamed_ids,
                                  on_wait=self._note_wait)
               if self._streamed_ids else iter(()))
        # PRIME the pipeline before the resident prefix computes:
        # generators are lazy, and without this the first streamed
        # layer's tier read + upload would only start at layer
        # n_resident — one fully exposed fetch per sweep
        pending = next(gen, None)
        for l in range(self._L):
            if l in self._resident:
                yield l, self._resident[l]
            else:
                cur, pending = pending, next(gen, None)
                yield cur

    def _note_wait(self, dt: float) -> None:
        self._h_wait.observe(dt)

    # (the `stats` shim override was removed with the base shim on its
    # announced PR 9 schedule — read `engine.registry.snapshot()`)

    # ------------------------------------------------ streamed executors
    # dstpu: hot-path
    def _run_blocks(self, phase, x, ctx, k_list, v_list, table, start):
        bj = self._block_jit(phase)
        t0 = time.perf_counter() if self._tel_on else 0.0
        for l, lp in self._layer_sweep():
            x, k_list[l], v_list[l] = bj(
                lp, x, ctx, k_list[l], v_list[l], table, start)
        if self._tel_on and self._streamed_ids:
            dt = time.perf_counter() - t0
            if dt > 0:
                self._g_bw.set(
                    len(self._streamed_ids) * self._layer_bytes / dt)
        return x

    # dstpu: hot-path
    def _forward_view(self, phase, toks, view):
        k_list, v_list = list(view.k), list(view.v)
        start = view.seq_lens
        x, ctx = self._stem_jit(self._stem_dev, toks, start)
        x = self._run_blocks(phase, x, ctx, k_list, v_list, view.table,
                             start)
        logits = self._head_jit(self._head_dev, x)
        return logits, view._replace(k=tuple(k_list), v=tuple(v_list))

    def _streamed_prefill(self, _params, toks, view, last):
        # a bucket-1 single-token "prefill" takes the decode path, like
        # forward_paged's prelude (prefill = T > 1) — same kernels, same
        # tokens as the resident engine
        phase = "prefill" if toks.shape[1] > 1 else "decode"
        logits, view = self._forward_view(phase, toks, view)
        return self._row_jit(logits, last), view

    def _streamed_chunk_prefill(self, _params, toks, view, last):
        logits, view = self._forward_view("chunk", toks, view)
        return self._row_jit(logits, last), view

    def _streamed_verify_chunk(self, _params, toks, cache):
        # the speculative VERIFY executor: the scheduler hands it
        # [B, K+1] draft windows over the full cache, so one
        # layer-stack sweep (= one full weight stream for the streamed
        # suffix) scores every position of every active slot
        return self._forward_view("chunk", toks, cache)

    # dstpu: hot-path
    def _streamed_decode_chunk(self, _params, toks, cache, key, ordinal,
                               temps):
        """K decode steps, host-driven: each step sweeps the layer
        stack (streamed weights double-buffered ahead), samples on
        device, and feeds the token to the next step — tokens never
        visit the host inside the chunk, so the one-sync-per-K-tokens
        contract of the compiled path is preserved."""
        K = self.decode_chunk
        k_list, v_list = list(cache.k), list(cache.v)
        lens = cache.seq_lens
        tok = toks
        cols = []
        for j in range(K):
            start = lens + j if j else lens
            x, ctx = self._stem_jit(self._stem_dev, tok, start)
            x = self._run_blocks("decode", x, ctx, k_list, v_list,
                                 cache.table, start)
            logits = self._head_jit(self._head_dev, x)
            nxt = self._sample_jit(logits, key, ordinal, j, temps)
            cols.append(nxt)
            tok = nxt[:, None]
        cache = cache._replace(k=tuple(k_list), v=tuple(v_list),
                               seq_lens=lens + K)
        return jnp.stack(cols, axis=1), cache

    # ----------------------------------------------- KV tier page moves
    # (the base engine's demote/promote data paths assume the stacked
    # [L, KV, P, ps, Dh] cache; this engine's cache is a per-layer
    # TUPLE so block programs can donate one layer's pages — the tier
    # payload layout [L, KV, n, ps, Dh] stays identical, only the
    # gather/scatter changes)
    def _fetch_pages_host(self, pages):
        idx, n = self._fetch_idx(pages)
        ks = jax.device_get(tuple(k[:, idx] for k in self.cache.k))
        vs = jax.device_get(tuple(v[:, idx] for v in self.cache.v))
        return (np.stack([np.asarray(k) for k in ks])[:, :, :n],
                np.stack([np.asarray(v) for v in vs])[:, :, :n])

    def _upload_promoted(self, pages, k_host, v_host) -> None:
        idx, k_host, v_host = self._promote_idx(pages, k_host, v_host)
        k_list, v_list = list(self.cache.k), list(self.cache.v)
        for l in range(len(k_list)):
            k_list[l] = k_list[l].at[:, idx].set(
                jnp.asarray(k_host[l]), mode="drop")
            v_list[l] = v_list[l].at[:, idx].set(
                jnp.asarray(v_host[l]), mode="drop")
        self.cache = self.cache._replace(k=tuple(k_list),
                                         v=tuple(v_list))

    # -------------------------------------------- streamed→resident flip
    # (the elastic fleet's warm cold-start: a new replica spawns in
    # streamed mode — serving immediately while its weight image lives
    # on the host/NVMe tier — and the autoscaler promotes layers into
    # HBM residency between scheduler steps until the engine is fully
    # resident: the ZeRO-Inference paging made the replica cheap to
    # add, the flip makes it as fast as a resident one)
    @property
    def fully_resident(self) -> bool:
        """True once every layer's weights are HBM-resident (no tier
        reads left on the decode path)."""
        return not self._streamed_ids

    @property
    def resident_flip_blocked(self) -> bool:
        """True when ``hbm_budget_bytes`` cannot hold another resident
        layer: streaming IS this engine's steady state (the normal
        ZeRO-Inference operating point for a >HBM model) — a cold-start
        promoter should stop here, not wait for a flip that can never
        land."""
        return bool(self._streamed_ids) and not self._promote_budget_ok()

    def _promote_budget_ok(self) -> bool:
        budget = self._zi.hbm_budget_bytes
        if budget is None:
            return True
        n_res = len(self._resident)
        still_streaming = len(self._streamed_ids) > 1
        working = ((self._reader.depth + 1) * self._layer_bytes
                   if still_streaming else 0)
        after = (self.plan["stem_head_bytes"] + self.plan["cache_bytes"]
                 + (n_res + 1) * self._layer_bytes + working)
        return after <= budget

    def promote_resident_layers(self, n: int = 1) -> int:
        """Pull up to ``n`` streamed layers' weights into HBM residency
        (synchronous tier read + upload; call BETWEEN scheduler steps —
        the host drives the sweep, so nothing is mid-flight then).
        Stops early when ``hbm_budget_bytes`` cannot hold another
        resident layer.  Returns the number promoted; the engine is
        fully resident once :attr:`fully_resident` reports True."""
        done = 0
        while self._streamed_ids and done < n:
            if not self._promote_budget_ok():
                break
            l = self._streamed_ids[0]
            bufs = [self.tier.read_sync(f"zi_p_{l}_{i}", s, d)
                    for i, (s, d) in enumerate(
                        zip(self._bshapes, self._bdtypes))]
            self._resident[l] = self._upload_layer(bufs, l)
            self._streamed_ids.pop(0)
            done += 1
        return done

    # --------------------------------------------------- weight swap
    def swap_params(self, new_params, version=None) -> None:
        raise NotImplementedError(
            "the streamed engine serves a decomposed weight image "
            "(resident stem/head + tiered blocks) — use swap_weights("
            "stem, blocks, head, version=) with trees prepared like "
            "the constructor's (same quantization/sharding)")

    def swap_weights(self, stem, blocks, head, version=None) -> None:
        """Rolling-update weight swap for the streamed engine: refresh
        the tier entries of every streamed layer, re-upload the
        resident layers, re-place stem/head, and invalidate the warm
        prefix pages (old-version KV must never serve new-version
        requests).  Same drained-engine contract as
        :meth:`~deepspeed_tpu.inference.serving.ServingEngine.
        swap_params`."""
        from deepspeed_tpu.inference.serving import EngineClosed

        if self._closed:
            raise EngineClosed(
                "swap_weights on a shut-down engine"
                + (f" (replica {self.replica_id})"
                   if self.replica_id else ""))
        if self.has_work:
            raise RuntimeError(
                "swap_weights needs a drained engine (queue and slots "
                "empty) — drain the replica first so no in-flight "
                "request mixes weight versions")
        leaves, btree = jax.tree_util.tree_flatten(blocks)
        leaves = [np.asarray(a) for a in leaves]
        if btree != self._btree or any(
                a.shape[1:] != s or a.dtype != d
                for a, s, d in zip(leaves, self._bshapes,
                                   self._bdtypes)):
            raise ValueError(
                "swap_weights: new block tree does not match the "
                "served one (structure/shape/dtype) — rebuild the "
                "engine for an architecture change")
        for what, new, ref in (("stem", stem, self._stem_dev),
                               ("head", head, self._head_dev)):
            nl, nt = jax.tree_util.tree_flatten(new)
            rl, rt = jax.tree_util.tree_flatten(ref)
            if nt != rt or any(
                    getattr(a, "shape", None) != getattr(b, "shape",
                                                         None)
                    or getattr(a, "dtype", None) != getattr(b, "dtype",
                                                            None)
                    for a, b in zip(nl, rl)):
                raise ValueError(
                    f"swap_weights: new {what} tree does not match "
                    "the served one (structure/shape/dtype) — rebuild "
                    "the engine for an architecture change")
        for l in self._streamed_ids:
            for i, a in enumerate(leaves):
                self.tier.put(f"zi_p_{l}_{i}",
                              np.ascontiguousarray(a[l]))
        if isinstance(self.tier, _NvmeTier):
            self.tier.fence_all()
        for l in list(self._resident):
            self._resident[l] = self._upload_layer(
                [a[l] for a in leaves], l)
        self._stem_dev = self._place(stem, self._stem_specs)
        if "embed" in head and head["embed"] is stem["embed"]:
            head = dict(head, embed=self._stem_dev["embed"])
        self._head_dev = self._place(head, self._head_specs)
        self._invalidate_warm_pages()
        if version is not None:
            self.weights_version = version
        if self._trace_on:
            self.tracer.event("weights_swap", attrs={
                "version": str(self.weights_version)})

    # ------------------------------------------------------- inspection
    def statusz(self) -> Dict[str, Any]:
        """Base snapshot + the weight-streaming view: the residency
        plan, bytes shipped, and the stall totals that attribute a
        blown TTFT budget to the tier fence it sat behind (the
        ZeRO-Infinity / ZeRO-Offload stall-attribution question)."""
        s = ServingEngine.statusz(self)
        s["zero_inference"] = {
            "tier": self._zi.tier,
            "plan": dict(self.plan),
            # live residency (promote_resident_layers moves layers out
            # of the streamed set after the plan was stamped): the
            # elastic cold-start flip is visible here
            "n_streamed_now": len(self._streamed_ids),
            "n_resident_now": len(self._resident),
            "fully_resident": self.fully_resident,
            "layer_h2d_uploads": int(self._c_h2d.value),
            "layer_sweeps": int(self._c_sweeps.value),
            "bytes_uploaded": int(self._c_bytes.value),
            "stream_stalls": int(self._h_wait.count),
            "stream_stall_s": round(float(self._h_wait.sum), 6),
            "h2d_bandwidth_bytes_per_s": float(self._g_bw.value),
            # degradation accounting: retried fences and synchronous
            # fallback reads (nonzero = the aio channel misbehaved and
            # the stream limped on; a fatal would have postmortem'd)
            "stream_retries": int(self._reader.io_retries),
            "stream_sync_fallbacks": int(self._reader.sync_fallbacks),
        }
        return s

    def hbm_weight_working_set_bytes(self) -> int:
        """Peak weight bytes resident in HBM under the plan: stem +
        head + pinned layers + the streaming double buffer — the
        ZeRO-Inference contract (the full image never lands)."""
        return self.plan["hbm_working_set_bytes"]


# ---------------------------------------------------------------- builder
def zero_inference_serving_engine(params, cfg, zi, *, family, kernels,
                                  weight_dtype: str = "bfloat16",
                                  quant_group_size: int = 128,
                                  mesh=None, **kw
                                  ) -> ZeroInferenceServingEngine:
    """Build the weight-streamed serving engine (ref: deepspeed-
    inference's init_inference with ZeRO-Inference offload enabled);
    :func:`~deepspeed_tpu.inference.serving.serving_engine` routes a live
    ``zero_inference`` block here with ``family``, the config's
    :class:`~deepspeed_tpu.models.family.DecoderFamily`, and ``kernels``,
    the readers it resolved, which the engine reports in /statusz (the
    per-layer block programs ask the same rule).  ``zi.dtype`` overrides
    ``weight_dtype``; int8 quantizes on
    the family's one per-leaf grid (``quant_skip_paths``), so streamed
    int8 serving is token-identical to resident int8 serving."""
    if family.streamed_split is None:
        raise NotImplementedError(
            f"zero_inference streaming needs a family's streamed split, "
            f"which {family.name} does not state — supported: "
            + ", ".join(f.name for f in decoder_families()
                        if f.streamed_split is not None))
    sharded = family.sharded(mesh)
    fns = paged_layered_fns(cfg, tp=sharded)

    stem_keys, head_keys = family.streamed_split(cfg)
    stem = {k: params[k] for k in stem_keys}
    head = {k: params[k] for k in head_keys}
    blocks = params["blocks"]

    wd = zi.dtype or weight_dtype
    if wd != "bfloat16":
        if wd != "int8":
            raise NotImplementedError(
                f"weight-only quantized inference supports 'int8' only, "
                f"got {wd!r}")
        from deepspeed_tpu.inference.quantized import quantize_params

        q = lambda t: quantize_params(t, group_size=quant_group_size,
                                      skip_paths=family.quant_skip_paths)
        stem, blocks = q(stem), q(blocks)
        # a leaf in both (tied embeddings): quantize the shared table ONCE
        # and alias the object — the engine dedupes shared leaves by
        # identity, both for the planner's byte accounting and the device
        # placement
        head = {**q({k: v for k, v in head.items() if k not in stem}),
                **{k: stem[k] for k in head if k in stem}}

    stem_specs = head_specs = layer_specs = None
    if sharded:
        from jax.sharding import PartitionSpec as P

        specs = family.param_specs(cfg)

        def drop_layer_dim(spec):
            if spec is None:
                return None
            if len(spec) and spec[0] is not None:
                raise ValueError(
                    f"stacked block spec {spec} shards the layer axis — "
                    "the streaming engine owns that axis (host schedule)")
            return P(*tuple(spec)[1:])

        layer_specs = jax.tree.map(
            drop_layer_dim, specs["blocks"],
            is_leaf=lambda s: s is None or isinstance(s, P))
        stem_specs = {k: specs[k] for k in stem_keys}
        head_specs = {k: specs[k] for k in head_keys}

    return ZeroInferenceServingEngine(
        stem=stem, blocks=blocks, head=head, fns=fns, zi=zi,
        n_layers=cfg.n_layers, n_kv=cfg.n_kv_heads,
        head_dim=cfg.head_dim, mesh=mesh, stem_specs=stem_specs,
        head_specs=head_specs, layer_specs=layer_specs, kernels=kernels,
        **kw)
