"""Continuous-batching serving loop (ref: deepspeed/inference/engine.py's
generate path and the DeepSpeed-FastGen / inference-v2 direction —
dynamic admission, paged KV, iteration-level scheduling).

TPU design.  The compiled programs are STATIC-shape and know nothing
about requests:

  prefill(params, [1, Tbucket] tokens, cache-view)   one admission
  decode (params, [B, 1] tokens, cache)              one token for ALL slots

The host-side :class:`ServingEngine` owns everything dynamic — a FIFO of
requests, a slot table (batch row ↔ request), the
:class:`~deepspeed_tpu.inference.kernels.PageAllocator` free list, and
per-slot sequence lengths.  Iteration-level scheduling as in FastGen:
each ``step()`` admits as many queued requests as slots+pages allow
(one bucketed prefill each), then runs ONE batched decode for every
active slot.  Completed sequences free their pages immediately; when the
pool runs dry, the youngest sequence is preempted vLLM-style (pages
released, request requeued for recompute-from-scratch).

Static-shape tricks worth noting:
- prompt lengths are padded to ``prefill_bucket`` multiples → bounded
  compile count; the padded tail's K/V lands beyond the row's seq_len
  and is never attended to (then overwritten as decode advances).
- inactive slots' table rows point at a reserved TRASH page: the decode
  step structurally writes a token for every row, and aiming dead rows
  at a sacrificial page keeps them from corrupting live sequences.
- every serving jit donates the cache, and inside the program the pool
  is a carry of the layer loop that the page writers scatter rows
  into (inference/kernels.py), so pages update in place in HBM: no
  program holds a pool- or layer-sized copy.

Automatic prefix caching (``prefix_cache=`` / the config block): the
page allocator is a refcounted, content-addressed pool — full pages are
keyed by a chained hash of their token span, incoming prompts map to
their longest cached page-aligned prefix, matched pages are shared
read-only into the new sequence's table (prefill starts at the first
uncached token, cutting TTFT), and released pages stay warm in an
eviction-ordered pool reclaimed only under allocation pressure.  Token-
identical with caching on or off; composes with split-fuse, chunked
decode, int8 weights, TP meshes, and the ZeRO-Inference streamed engine
(which shares this scheduler).

Host-sync discipline (the part that makes this a TPU serving loop and
not a CPU one): the decode inner loop performs exactly ONE device→host
transfer per step — the batched sampled tokens.  Sampling runs on-device
for all rows at once (per-row temperature, greedy = argmax), the page
table and seq_lens upload only when the slot composition changed
(dirty flags), and between composition changes the device-side
structural ``seq_lens + 1`` of the decode step is simply trusted.
Prefill-boundary tokens follow the same discipline: a prefill program
returns the logits ROW of its last real position (sliced inside the
program), one small compiled program — the resolved sampler, the same
function the decode program calls — turns the row into a ``[1]`` token
array on the device, and that array STAYS on the device until the
decode that consumes it has been dispatched: ``dstpu_join`` writes it
at its row's newest position in the decode's token operand (the
output of the step in flight, or the tokens the host sent up), so an
admission or a prompt's last chunk goes out behind the decode in
flight and lands nothing first.  The token is read with the decode it
joined, in that decode's one fetch, and appended before that decode's
token.  Where the rule says no (``_joins``: speculation, a fault plan,
a tiered cache, a request that wants one token, a pool that could only
give the pages by preempting) every prompt that finished prefilling
within the step is fetched in ONE ``device_get`` before the decode is
built, as it always was.  Sampling keys are derived on the device from
integers the host already has (the admission ordinal, the dispatch
ordinal) folded into a base key uploaded once at build.  So between
the start of ``step()`` and the token fetch the host dispatches
compiled programs and uploads NumPy arrays, and runs no eager ``jnp``
/ ``jax.random`` operation.

Speculative decoding (``speculative=`` / the config block): each decode
iteration drafts up to K cheap tokens per slot (prompt-lookup n-gram by
default, or a resident small-model drafter), scores all K+1 positions
in ONE batched continuation forward — the same multi-position program
split-fuse chunks run — keeps the longest accepted prefix plus a
bonus/corrected token, and rewinds each slot's KV frontier past the
rejected tail (the device's structural ``seq_lens + K+1`` is replaced
by the host's per-slot accepted length on the next dirty upload).
Greedy outputs are token-identical to speculation off; temperature>0
uses point-mass rejection sampling so the distribution is unchanged.
Composes with chunked decode, split-fuse, int8, TP meshes, the prefix
cache, and the ZeRO-Inference engine — where one verify sweep amortizes
one full layer-weight stream over the whole accepted span.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu import faults as faults_mod
from deepspeed_tpu.config import (KERNELS_BLOCK_GONE, CommConfig,
                                  DevprofConfig, FaultsConfig,
                                  HistoryConfig, IncidentsConfig,
                                  KVTierConfig, PrefixCacheConfig,
                                  SLOConfig, SpeculativeConfig,
                                  TelemetryConfig, TracingConfig,
                                  ZeroInferenceConfig)
from deepspeed_tpu.devprof import (BUILD_LEDGER, NULL_DEVPROF, STEP_LEDGER,
                                   BuildCounters, DevProf, ProgramSpan,
                                   StepRow)
from deepspeed_tpu.faults import ChecksumError, FaultPlan, InjectedFault
from deepspeed_tpu.history import NULL_HISTORY, MetricHistory
from deepspeed_tpu.incidents import NULL_INCIDENTS, IncidentManager
from deepspeed_tpu.inference.kernels import (STATE_DTYPE, PagedKVCache,
                                             PageAllocator,
                                             ServingKernelPolicy,
                                             held_experts_product,
                                             latent_reader,
                                             resolve_serving_kernels)
from deepspeed_tpu.inference.paged_forward import forward_paged
from deepspeed_tpu.inference.prefix_cache import (extend_page_keys,
                                                  key_hex,
                                                  matchable_pages,
                                                  page_keys)
from deepspeed_tpu.inference.speculative import (build_drafter,
                                                 verify_accept)
from deepspeed_tpu.models.family import decoder_families, decoder_family
from deepspeed_tpu.request_trace import (BoundTracer, RequestTracer,
                                          event_to_dict)
from deepspeed_tpu.slo import NULL_SLO_TRACKER, SLOTracker
from deepspeed_tpu.telemetry import (LATENCY_BUCKETS_S, MetricsRegistry,
                                     TelemetryExporter)
from deepspeed_tpu.telemetry import mark as telemetry_mark
from deepspeed_tpu.utils.logging import logger


@jax.jit
def _sample_rows(logits: jnp.ndarray, keys: jnp.ndarray,
                 temps: jnp.ndarray) -> jnp.ndarray:
    """Batched per-row sampling: [B, V] logits + [B] keys + [B] temps →
    [B] tokens.  temperature 0 rows take the argmax; others sample
    categorically at their temperature.  One jit, one result array — the
    serving loop fetches it with a single device→host transfer."""
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits.astype(jnp.float32) / \
            jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(temps == 0.0, greedy,
                         sampled.astype(jnp.int32))


def _dispatch_keys(key: jnp.ndarray, ordinal: jnp.ndarray,
                   *shape: int) -> jnp.ndarray:
    """``[*shape, 2]`` sampling keys of one decode or verify dispatch,
    derived inside its program from the engine's ``key`` and the
    dispatch's ordinal."""
    with jax.named_scope("sample"):
        return jax.random.split(jax.random.fold_in(key, ordinal),
                                int(np.prod(shape))).reshape(*shape, -1)


def _last_row(logits: jnp.ndarray, last: jnp.ndarray) -> jnp.ndarray:
    """What a prefill's first token is sampled from and no more:
    ``[1, T, V]`` logits -> the ``[1, V]`` row at ``last`` (int32 [1]),
    the last REAL position of the call.  The padded tail's logits are
    never read, and no ``[1, T, V]`` leaves a program.  A family that
    cuts its rows before its last layers (``Recurrent.tail``) hands over
    that row alone, ``[1, 1, V]``."""
    if logits.shape[1] == 1:
        return logits[0, 0]
    return jax.lax.dynamic_index_in_dim(logits[0], last[0], axis=0)


def boundary_program(sample):
    """The boundary token of one admission: its row, the admissions'
    base key, its ordinal, its temperature [1] -> token [1].  ONE
    program whatever the prompt's length: inlined into every prefill
    program the sampler cost each of them 0.2 s of lowering on every
    engine build (PERF.md 6, PR 31), and a build warms one a bucket."""
    def dstpu_boundary(row, key, ordinal, temp):
        with jax.named_scope("sample"):
            return sample(row, jax.random.fold_in(key, ordinal)[None],
                          temp)

    return dstpu_boundary


def join_program():
    """A boundary token joins the decode that consumes it, on the
    device: ``prev``, a decode program's token operand in the form its
    output has (``[B, K]``, or flat behind the experts' rows), with the
    ``[1]`` token ``tok`` written at flat position ``at``, its row's
    newest (``b * K + K - 1``).  ONE program whatever the row, and the
    decode program's text, cache entry and compile-cache key are what
    they were.  (A function an engine, as the others: a jit's cache is
    its function's.)"""
    def dstpu_join(prev, tok, at):
        with jax.named_scope("sample"):
            return jax.lax.dynamic_update_slice(
                prev.reshape(-1), tok, (at,)).reshape(prev.shape)

    return dstpu_join


def serving_programs(prefill_fn, decode_fn, chunk_prefill_fn, sample,
                     decode_chunk: int, max_batch: int,
                     expert_rows: bool = False, state: bool = False):
    """The serving programs over a model's forwards ``(params, tokens,
    cache) -> (logits, cache)``, not yet jitted: ``(dstpu_prefill,
    dstpu_chunk, dstpu_boundary, dstpu_sweep, dstpu_decode)``.  The
    names are stable: a capture's "XLA Modules" line says which program
    ran (``jit_dstpu_prefill`` …).

    ``sample`` is the engine's sampler (``_sample_rows``).
    ``expert_rows``: the cache carries a family's count
    of rows routed to its held experts (and of its pair buffer's
    further passes, where it has one), and the decode program returns it
    flat behind its tokens (``[B * K + Eh]`` int32, or one more).
    ``state``: the cache carries a per-slot recurrent state, which only
    real tokens may move, so each program tells the forward which of its
    rows are real (``cache.real``): a prefill or chunk the tokens up to
    ``last``, a decode step the rows whose length is not 0 (an idle
    slot's, and a slot's between two chunks of its prompt, is)."""
    def real_upto(cache, last):
        return cache._replace(real=last + 1) if state else cache

    # A prefill returns its last row (_last_row): the model's contract
    # stays, the slice is the engine's.
    def dstpu_prefill(params, tokens, cache, last):
        logits, cache = prefill_fn(params, tokens, real_upto(cache, last))
        return _last_row(logits, last), cache

    def dstpu_chunk(params, tokens, cache, last):
        logits, cache = chunk_prefill_fn(params, tokens,
                                         real_upto(cache, last))
        return _last_row(logits, last), cache

    # the speculative verify sweep: logits at every position
    def dstpu_sweep(params, tokens, cache):
        return chunk_prefill_fn(params, tokens, cache)

    # K decode steps in ONE on-device scan: each step's sampled token
    # feeds the next, so the host syncs once per K tokens (what a
    # sync costs on the chip is not measured yet — ROADMAP S2).
    # Tokens a request emits after its own
    # EOS within a chunk are discarded by the host (waste < K).
    # K=1 runs the same path as a length-1 scan.
    def dstpu_decode(params, tok, cache, key, ordinal, temps):
        keys = _dispatch_keys(key, ordinal, decode_chunk, max_batch)

        def one(carry, key_k):
            t, c = carry
            if state:
                c = c._replace(real=(c.seq_lens > 0).astype(jnp.int32))
            logits, c = decode_fn(params, t, c)
            with jax.named_scope("sample"):
                nxt = sample(logits[:, -1], key_k, temps)
            return (nxt[:, None], c), nxt

        (_, cache), toks = jax.lax.scan(one, (tok, cache), keys)
        toks = jnp.swapaxes(toks, 0, 1)                 # [B, K]
        if expert_rows:
            # the rows routed to each held expert since the last decode
            # program (prefills' and chunks' too) ride in the one fetch
            # a step makes, behind the tokens; the sum starts again
            rows = cache.expert_rows
            return (jnp.concatenate([toks.reshape(-1), rows]),
                    cache._replace(expert_rows=jnp.zeros_like(rows)))
        return toks, cache

    return dstpu_prefill, dstpu_chunk, boundary_program(sample), \
        dstpu_sweep, dstpu_decode


def decode_from_output(decode, decode_chunk: int, max_batch: int):
    """``dstpu_decode`` over the LAST decode program's output in the
    form it left the device (``[B, K]``, or flat behind the experts'
    rows, ``[B * K + Eh]``): each row's newest token is cut out inside
    the program, so a step can be dispatched before the host has read
    the step before it.  With ``decode_chunk`` 1 and no expert rows the
    output IS the operand and no engine wraps its program in this; a
    caller that has the tokens themselves (``[B, 1]``) still hands
    them over as they are."""
    def dstpu_decode(params, prev, cache, key, ordinal, temps):
        if prev.shape != (max_batch, 1):
            prev = prev.reshape(-1)[:max_batch * decode_chunk].reshape(
                max_batch, decode_chunk)[:, -1:]
        return decode(params, prev, cache, key, ordinal, temps)

    return dstpu_decode


# why a decode step's tokens went up from the host (/statusz "decode"):
# a free slot stood beside a waiting queue; a boundary token waited; a
# row ended by count with the step before; a prompt's last chunk came;
# anything else (the first step, a page the pool could only give by
# preempting, a row that ended on its own, a promotion in flight)
DECODE_BEHIND = ("admission", "boundary", "finish", "prefill", "other")
# those of them a boundary token that joins its decode on the device
# takes away (``ServingEngine._may_join``)
_JOINED = ("admission", "boundary", "prefill")


def _req_key(req_id: Any) -> str:
    """Canonical string form of a request id — the /requestz?id= query
    arrives as text, so matching happens in string space."""
    return str(req_id)


class EngineClosed(RuntimeError):
    """``submit`` after ``shutdown()``: the engine is torn down and can
    never serve this request.  Typed (rather than whatever downstream
    error the dead telemetry/scheduler state would eventually raise) so
    a fleet router's DEAD-replica path is deterministic — catch, mark
    the replica dead, re-route."""


@dataclasses.dataclass
class RequestShed:
    """Typed admission rejection: the engine declined to serve this
    request (queue-depth or deadline load shedding).  Lands in
    ``engine.finished`` IN PLACE of a token list — a router retries it
    on another replica; nothing about this request ran."""

    req_id: Any
    reason: str                        # "queue_depth" | "deadline"
    tier: Optional[str] = None


@dataclasses.dataclass
class RequestFailed:
    """Typed per-request failure: an exception in this request's slot
    (or its admission) failed THIS request — its pages, COW refs and
    tier pins were released, and the engine kept serving its
    neighbors.  Lands in ``engine.finished`` in place of a token list
    (before this existed, the exception took down the whole engine)."""

    req_id: Any
    reason: str          # "slot_exception" | "admit_exception" |
    #                      "replica_failed" (router: the whole replica
    #                      died mid-generation)
    error: str = ""
    tier: Optional[str] = None
    # tokens this request had generated when it failed: a router may
    # safely re-submit only when this is 0 — a request that already
    # emitted tokens must fail typed, never double-generate
    generated: int = 0


# a finished entry: the served tokens, or a typed shed/failure result
RequestResult = Union[List[int], RequestShed, RequestFailed]

# a shed inside this window marks /healthz degraded (shedding active)
_SHED_ACTIVE_WINDOW_S = 30.0


@dataclasses.dataclass
class Request:
    req_id: Any
    tokens: List[int]                  # prompt
    max_new_tokens: int = 32
    temperature: float = 0.0           # 0 → greedy
    # TTFT clock: submit-time perf_counter, cleared once the first token
    # is observed (preempted requeues carry the cleared state so a
    # recompute never double-counts).  None also means "telemetry off".
    t_submit: Optional[float] = None
    # cached chained page-key list (prefix caching): grown lazily, never
    # recomputed — tokens are immutable per incarnation, and a preempted
    # requeue hands its extended chain to the recompute request
    page_keys: Optional[List[bytes]] = None
    # flight-recorder state: the per-request sampling decision (made
    # once at submit) and the first-token edge (a preempted requeue
    # carries both so a recompute never re-emits first_token)
    traced: bool = False
    first_token_seen: bool = False
    # introspection/SLO state: wall-clock arrival (never cleared —
    # unlike t_submit — so /statusz ages and the SLO deadline survive
    # the first token AND a preemption requeue) and the SLO tier
    t_arrival: float = 0.0
    tier: Optional[str] = None


@dataclasses.dataclass
class _Promotion:
    """One admission's in-flight tier→HBM page promotion: the demoted
    keys being streamed back, their freshly allocated target pages, and
    the double-buffered reader driving the transfer.  ``primed`` holds
    group 0's presubmitted tier-read buffers (issued at admission so
    NVMe reads overlap whatever the engine does before the slot's first
    suffix-prefill chunk needs the pages); it stays None while the aio
    priority group asks KV promotion to yield to layer-weight streams.
    ``deferred`` counts the steps this slot's prefill stood aside so
    the promotion could hide under other slots' compute."""

    keys: List[bytes]
    page_map: Dict[bytes, int]         # key -> target HBM page
    reader: Any                        # param_stream.TierPageReader
    primed: Optional[list] = None
    t_start: float = 0.0
    deferred: int = 0
    channel: bool = False              # owns the NVMe read channel


# promotion deferral cap: how many scheduler iterations one slot's
# prefill may stand aside waiting for its tier reads (or for aio
# priority) before it blocks on the fence — bounds starvation when the
# promoting slot is the only work
_KV_PROMO_DEFER_CAP = 16


class _Flying(NamedTuple):
    """A decode step the device has and the host has not read."""
    out: Any                            # its output, on the device
    rows: List[Tuple[int, "_Slot"]]     # the rows it was dispatched for
    ordinal: int                        # what its draws were folded from
    temps: Any                          # the temperatures it ran under


@dataclasses.dataclass
class _Slot:
    req: Request
    seq_len: int                       # tokens resident in the KV cache
    generated: List[int]
    # PageAllocator owner key, and the admission ordinal the boundary
    # draw's key is folded from
    seq_id: int = -1
    prefill_done: int = -1             # chunked prefill progress; -1 = done
    last_tok_t: float = 0.0            # inter-token latency clock
    promo: Optional[_Promotion] = None  # in-flight tier-page promotion
    # the first token, on the device: it joined a decode there and is
    # read, and appended before that decode's token, when it lands
    boundary: Any = None

    @property
    def prefilling(self) -> bool:
        return 0 <= self.prefill_done < len(self.req.tokens)


class ServingEngine:
    """Host scheduler driving jitted prefill/decode over a paged cache.

    model_fns: ``(prefill_fn, decode_fn)`` with the
    :func:`~deepspeed_tpu.inference.paged_forward.forward_paged`
    contract ``(params, tokens, cache) -> (logits, cache)``; built for a
    decoder family's config by :func:`serving_engine`.
    """

    # the chunk programs hand over their row's last token alone (a family
    # that states ``Recurrent.tail``; set by :func:`serving_engine`)
    tail_cut = False
    # the one jitted decode program and the form its output has (None: an
    # engine whose decode the host drives layer by layer, which takes
    # the tokens ``[B, 1]`` and never has a step in flight)
    _decode_jit = _out_shape = _join = None
    # the decode step the device has and the host has not read
    _flying: Optional[_Flying] = None

    def __init__(self, params, prefill_fn, decode_fn, *,
                 n_layers: int, n_kv: int, head_dim: int,
                 max_batch: int = 4, page_size: int = 16,
                 num_pages: int = 128, max_seq: int = 256,
                 prefill_bucket: int = 32, eos_token_id: Optional[int] = None,
                 cache_dtype=jnp.bfloat16, seed: int = 0,
                 decode_chunk: int = 1, prefill_chunk: int = 0,
                 chunk_prefill_fn=None, mesh=None, telemetry=None,
                 prefix_cache=None, admit_lookahead: int = 4,
                 tracing=None, speculative=None, drafter=None,
                 slo=None, kv_tier=None, faults=None,
                 shed_queue_depth: int = 0,
                 shed_expired_deadline: bool = False,
                 replica_id: Optional[str] = None,
                 history=None, incidents=None, kernels=None,
                 devprof=None, comm=None, values_in_keys: bool = False,
                 expert_rows=(0, False), routed_per_row: int = 0,
                 state_row=None):
        # ---- telemetry: one registry for every hot-path metric.
        # `telemetry` accepts None/bool/dict/TelemetryConfig — or an
        # existing MetricsRegistry to share one across engines.  First,
        # because the build is measured: the counters take every program
        # this constructor makes ready (deepspeed_tpu.devprof)
        if isinstance(telemetry, MetricsRegistry):
            self.registry = telemetry
            tcfg = None                    # caller owns the sinks
        else:
            tcfg = TelemetryConfig.coerce(telemetry)
            self.registry = MetricsRegistry(enabled=tcfg.enabled)
        self._build = BuildCounters(self.registry)
        # the build's phases on the profiler's clock, entered in place
        # (no frame above a dispatch that lowers: see serving_engine)
        self._sp_build_alloc = self.registry.span(
            "build_alloc", "the page pool, tables and state: _alloc_cache")
        self._sp_build_programs = self.registry.span(
            "build_programs", "the jits and their sentinel wraps")
        self._sp_build_warmup = self.registry.span(
            "build_warmup", "the warm-up: every program dispatched once")
        self._sp_build_program = ProgramSpan(self.registry.span(
            "build_program", "one warm-up dispatch: a program made ready"))
        # what a token's cache row is: per-head K and V pools, or one
        # pool whose rows are keys and values both (a latent family's
        # ``cache_row``); and how many held experts' routed rows the
        # programs count (0: none) and whether they count, behind them,
        # the further passes of the held experts' pair buffer (a family
        # that holds a share of the experts its router scores), each row
        # routed ``routed_per_row`` times in all (top-k x expert layers)
        self._values_in_keys = bool(values_in_keys)
        self._n_expert_rows, self._pair_passes = expert_rows
        self._routed_per_row = int(routed_per_row)
        # what a slot keeps beside its pages, a recurrent layer (a
        # family's ``Recurrent.state_row``; None: nothing)
        self._state_row = state_row
        # a family with no pool layer (every layer keeps its state a
        # slot): the cache holds no pool, no request needs a page, and a
        # request is admitted when a slot is free.  0 pages of 0
        self._pooled = n_layers > 0
        if not self._pooled:
            num_pages = 1           # the reserved one alone: none usable
        # Sharded serving (ref: deepspeed/module_inject/replace_module.py
        # TP injection + deepspeed/moe/sharded_moe.py expert-parallel
        # inference): with a mesh, params arrive pre-sharded from the
        # builder, the KV cache's head axis shards over ``model`` (TP;
        # under expert-only parallelism it stays replicated), and every
        # host-built jit input is placed replicated on the mesh (a
        # device-0-committed array mixed with sharded arrays is an
        # error, not a resharding).
        active = mesh is not None and any(
            mesh.size(ax) > 1 for ax in ("model", "expert"))
        self._mesh = mesh
        if active:
            from jax.sharding import PartitionSpec as P

            self._repl = mesh.replicated()
            if mesh.size("model") > 1:
                if n_kv % mesh.size("model"):
                    raise ValueError(
                        f"n_kv_heads {n_kv} not divisible by model-axis "
                        f"size {mesh.size('model')}")
                self._kv_sharding = mesh.sharding(
                    P(None, "model", None, None, None))
            else:
                self._kv_sharding = self._repl
        else:
            self._repl = self._kv_sharding = None
        self.params = params
        self.decode_chunk = int(decode_chunk)
        if self.decode_chunk < 1:
            raise ValueError(
                f"decode_chunk must be >= 1, got {decode_chunk}")
        # FastGen split-fuse scheduling: prompts are absorbed
        # prefill_chunk tokens per iteration BETWEEN decode steps, so one
        # long admission never stalls every in-flight decode.  0 = whole
        # prompt in one bucketed prefill at admission (the classic path).
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk and chunk_prefill_fn is None:
            raise ValueError(
                "prefill_chunk > 0 needs chunk_prefill_fn — a "
                "(params, tokens, cache) step that attends over history + "
                "chunk (forward_paged(..., continuation=True))")
        self.eos = eos_token_id
        self.page_size = page_size
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.prefill_bucket = prefill_bucket
        self.max_pages_per_seq = -(-max_seq // page_size)

        # last page is the sacrificial target for inactive-slot writes
        self.trash_page = num_pages - 1
        # ---- automatic prefix caching: the allocator becomes a
        # refcounted content-addressed pool (full pages keyed by a
        # chained hash of their token span); matched prompts share
        # pages read-only and skip their prefill compute.  The pool cap
        # is the planner's accounting for pinned shared pages: warm
        # (refcount-0) cached pages may hold at most this many of the
        # usable pages — everything above it frees eagerly.
        pc = PrefixCacheConfig.coerce(prefix_cache)
        self.prefix_cache = pc
        self._pc_on = pc.enabled
        usable = num_pages - 1
        self.allocator = PageAllocator(
            usable, cache_pages=pc.pool_cap(usable),
            eviction=pc.eviction)
        if self._pc_on and chunk_prefill_fn is None:
            raise ValueError(
                "prefix_cache needs chunk_prefill_fn — cache-hit "
                "admissions prefill only the uncached suffix via the "
                "continuation forward (forward_paged(..., "
                "continuation=True))")
        # bounded admission lookahead (head-of-line blocking fix): when
        # the queue head cannot fit its pages, up to this many younger
        # requests are considered instead of stalling the whole queue
        self.admit_lookahead = int(admit_lookahead)
        if self.admit_lookahead < 0:
            raise ValueError(
                f"admit_lookahead must be >= 0, got {admit_lookahead}")
        # ---- speculative decoding: draft K cheap tokens per slot,
        # score all K+1 positions in ONE continuation forward, keep the
        # accepted prefix + a bonus token, rewind the KV frontier past
        # the rejects.  The verify pass IS the continuation-chunk
        # program, so it needs the same forward split-fuse does.  When
        # enabled, the speculative sweep replaces the chunked decode
        # scan (decode_chunk is accepted and unused — the sweep already
        # syncs once per up-to-(K+1) tokens).
        sc = SpeculativeConfig.coerce(speculative)
        self.speculative = sc
        self._spec_on = sc.enabled
        self.drafter = None
        if self._spec_on:
            if chunk_prefill_fn is None:
                raise ValueError(
                    "speculative decoding needs chunk_prefill_fn — the "
                    "verify pass scores K+1 positions per slot via the "
                    "continuation forward (forward_paged(..., "
                    "continuation=True)), which must return logits at "
                    "EVERY position")
            self.drafter = drafter if drafter is not None \
                else build_drafter(sc)

        def put_repl(x):
            x = jnp.asarray(x)
            return (jax.device_put(x, self._repl)
                    if self._repl is not None else x)

        self._put = put_repl
        # kv_tier coerced BEFORE the cache alloc below: the
        # quantized_resident mode changes the DEVICE cache's layout
        # (int8 code planes + f32 per-token-row scale planes), not just
        # the tier pool's host encoding
        kvt = KVTierConfig.coerce(kv_tier)
        self.kv_tier = kvt
        self._kvt_on = kvt.enabled
        self._quant_resident = kvt.enabled and kvt.quantized_resident
        # the readers the builder resolved (``serving_engine``: what its
        # forwards baked): a report for /statusz, nothing here asks it
        self._kernels = kernels or ServingKernelPolicy()
        # ---- quantized weight placement (the training int8 wire
        # reused for serving, ISSUE 18): when comm.quantized_serving is
        # on, the BUILDER quantizes replica weights host-side so the
        # H2D upload carries int8 codes + scales, records placement
        # stats via _record_comm_placement, and this engine publishes
        # them (/statusz "comm" block + comm_* metric family).  The
        # engine itself only holds the coerced config — builders and
        # the ZeRO-Inference layer stream read it from here.
        self._comm = CommConfig.coerce(comm)
        self.comm_placement: Optional[Dict[str, Any]] = None
        with self._sp_build_alloc:
            self.cache = self._alloc_cache(n_layers, n_kv, num_pages,
                                           page_size, head_dim, cache_dtype)
        with self._sp_build_programs:
            self._build_programs(prefill_fn, decode_fn, chunk_prefill_fn)
        self._table_host = np.full((max_batch, self.max_pages_per_seq),
                                   self.trash_page, np.int32)
        # dirty flags: device table/seq_lens re-upload only when the slot
        # composition changed since the last decode
        self._table_dirty = True
        self._lens_dirty = True
        self.slots: List[Optional[_Slot]] = [None] * max_batch
        # prefill-boundary queue: (slot, the [1] token sampled on the
        # device from its prefill's last row), collected per admission
        # / final prefill chunk and fetched in ONE device_get per step
        self._pending_boundary: List[Tuple[int, Any]] = []
        self.queue: "collections.deque[Request]" = collections.deque()
        self._seq_counter = 0
        # sampling keys are derived on the device: the base key goes up
        # once, here, and every program that samples folds into it an
        # integer the host already has, from one counter space — odd
        # for an admission (2 * seq_id + 1: its boundary draw), even
        # for a decode or verify dispatch (2 * its ordinal)
        self._key = self._put(jax.random.PRNGKey(seed))
        self._n_dispatch = 0
        # decode dispatches whose tokens came up from the host, by what
        # made the step before them land first (DECODE_BEHIND), and the
        # reason the next such dispatch will be counted under
        self._behind = dict.fromkeys(DECODE_BEHIND, 0)
        self._behind_why = "other"
        self.finished: Dict[Any, List[int]] = {}
        self._newly_finished: List[Any] = []

        # _tel_on guards every perf_counter read in the decode loop: the
        # disabled path must cost nothing beyond this bool (no clock, no
        # lock, no TraceAnnotation)
        self._tel_on = self.registry.enabled
        r = self.registry
        self._c_admitted = r.counter(
            "serving_admitted_requests", "requests admitted to a slot")
        self._c_preempted = r.counter(
            "serving_preempted_requests",
            "vLLM-style recompute preemptions under page pressure")
        self._c_decode_steps = r.counter(
            "serving_decode_steps", "batched decode steps (tokens/slot)")
        self._c_decode_syncs = r.counter(
            "serving_decode_syncs", "device->host token syncs")
        self._c_decode_ahead = r.counter(
            "serving_decode_ahead",
            "decode programs dispatched before the tokens of the one "
            "before them were read: their token operand was its output")
        self._c_prefill_chunks = r.counter(
            "serving_prefill_chunks", "split-fuse prompt chunks absorbed")
        self._c_chunk_rows = r.counter(
            "serving_chunk_rows_total",
            "real prompt rows that chunk programs took through the layers")
        self._c_tail_rows = r.counter(
            "serving_tail_rows_total",
            "rows of chunk programs that paid the last layers and the "
            "head: every real row, or one a program where the family cuts "
            "its rows before them (Recurrent.tail)")
        # rows the programs routed to each held expert (the registry has
        # no labels: the expert's index is the name's suffix), and every
        # (row, expert) pair they routed anywhere, padding rows included
        self._c_expert_rows = [
            r.counter(f"serving_expert_rows_{e}",
                      f"rows routed to held expert {e}, all expert layers")
            for e in range(self._n_expert_rows)]
        self._c_routed_rows = r.counter(
            "serving_routed_rows",
            "(row, expert) pairs routed: rows x top-k x expert layers")
        self._c_pair_passes = r.counter(
            "serving_expert_pair_extra_passes",
            "passes beyond the first over the held experts' pair buffer: "
            "an expert layer of a program was routed more held pairs "
            "than the buffer's bound (none is dropped)")
        self._rows_pending = 0
        self._c_state_fresh = r.counter(
            "serving_state_fresh_starts",
            "first chunks that started a slot's recurrent state from zero")
        self._c_state_masked = r.counter(
            "serving_state_rows_masked",
            "rows of decode steps whose recurrent state was held still "
            "(idle slots, and slots between two chunks of a prompt)")
        self._g_state_bytes = r.gauge(
            "serving_state_cache_bytes",
            "bytes of the per-slot recurrent state beside the page pool")
        self._g_state_live = r.gauge(
            "serving_state_live_slots",
            "slots whose recurrent state belongs to a request")
        self._g_queue = r.gauge(
            "serving_queue_depth", "requests waiting for a slot")
        self._g_occupancy = r.gauge(
            "serving_batch_occupancy",
            "fraction of decode slots active this step")
        self._g_kv_util = r.gauge(
            "serving_kv_page_utilization",
            "fraction of the usable KV page pool referenced by live "
            "sequences (warm cached pages count as reclaimable, not "
            "allocated)")
        self._c_admit_skips = r.counter(
            "serving_admit_skips",
            "queue entries skipped over by admission lookahead (head-"
            "of-line blocking avoided; each admission at queue index i "
            "adds i)")
        # prefix-cache metric family (all zero when the feature is off)
        self._c_pc_hits = r.counter(
            "prefix_cache_hits",
            "admissions that matched >= 1 cached page")
        self._c_pc_misses = r.counter(
            "prefix_cache_misses", "admissions with no cached prefix")
        self._c_pc_cached_tokens = r.counter(
            "prefix_cache_cached_tokens",
            "prompt tokens served from cached pages (prefill compute "
            "skipped entirely)")
        self._c_pc_prompt_tokens = r.counter(
            "prefix_cache_prompt_tokens",
            "prompt tokens admitted (hit + miss denominators)")
        self._c_pc_published = r.counter(
            "prefix_cache_published_pages",
            "full pages content-addressed into the index")
        self._c_pc_evicted = r.counter(
            "prefix_cache_evicted_pages",
            "cached pages reclaimed (allocation pressure or pool cap)")
        self._g_pc_pool = r.gauge(
            "prefix_cache_pool_pages",
            "refcount-0 cached pages held warm in the pool")
        self._g_pc_frac = r.gauge(
            "prefix_cache_cached_token_fraction",
            "cumulative cached / admitted prompt tokens")
        self._evicted_seen = 0
        self._c_boundary_syncs = r.counter(
            "serving_boundary_syncs",
            "prefill-boundary token fetches (one per step with >= 1 "
            "prefill completion, however many admissions share it)")
        self._c_boundary_tokens = r.counter(
            "serving_boundary_tokens",
            "boundary tokens sampled on the device from a prefill "
            "program's row (÷ serving_boundary_syncs: admissions a "
            "fetch; "
            "÷ serving_admitted_requests: 1.0 less what failed or was "
            "preempted before its flush)")
        self._c_boundary_joined = r.counter(
            "serving_boundary_joined",
            "boundary tokens that reached the decode that consumes them "
            "on the device (dstpu_join): read with that decode's tokens, "
            "no fetch of their own (+ serving_boundary_tokens: the "
            "admissions that produced a token)")
        # speculative-decoding metric family (all zero when off)
        self._c_spec_drafted = r.counter(
            "spec_drafted_tokens",
            "draft tokens proposed across verify sweeps")
        self._c_spec_accepted = r.counter(
            "spec_accepted_tokens", "draft tokens accepted by verify")
        self._c_spec_rejected = r.counter(
            "spec_rejected_tokens",
            "draft tokens rejected (KV frontier rolled back past them)")
        self._c_spec_sweeps = r.counter(
            "spec_verify_sweeps", "batched draft-and-verify sweeps")
        self._c_spec_slots = r.counter(
            "spec_verify_slots",
            "slot-sweeps verified (the denominator of the mean "
            "acceptance length)")
        self._c_spec_emitted = r.counter(
            "spec_emitted_tokens",
            "tokens emitted by verify sweeps (accepted + bonus, before "
            "EOS/budget truncation) — divide by spec_verify_slots for "
            "the mean acceptance length")
        self._h_spec_len = r.histogram(
            "spec_accept_length",
            "tokens emitted per slot per verify sweep (accepted prefix "
            "+ bonus; 1 = nothing accepted, a plain decode step)",
            buckets=(1, 2, 3, 4, 6, 8, 12, 16))
        self._g_spec_occ = r.gauge(
            "spec_verify_occupancy",
            "fraction of decode slots active in the last verify sweep")
        self._h_ttft = r.histogram(
            "serving_ttft_seconds",
            "submit -> first generated token", LATENCY_BUCKETS_S)
        self._h_itl = r.histogram(
            "serving_inter_token_seconds",
            "gap between consecutive tokens of one request as a client "
            "sees them (chunked decode delivers bursts of K: K-1 "
            "near-zero gaps + one sync-interval gap per chunk)",
            LATENCY_BUCKETS_S)
        # span pieces hoisted out of step(): one histogram resolve and
        # one label format at build time, zero registry locks per step
        self._sp_step = r.span(
            "serving_step",
            "scheduler iteration wall time (admit -> decode sync)")
        # its children, one per PHASE of the iteration (never per slot
        # or per token): they tile serving_step, so a capture says
        # which host work fills a gap on the device's line.  Disabled,
        # each is the shared no-op span.
        self._sp_admit = r.span(
            "serving_admit", "shed + watermark sweep + admissions "
            "(whole-prompt prefill dispatches included)")
        self._sp_prefill = r.span(
            "serving_prefill", "one prefill chunk per pending slot")
        self._sp_boundary = r.span(
            "serving_boundary", "batched boundary-token sample + fetch "
            "(a device sync)")
        self._sp_grow = r.span(
            "serving_grow_pages", "page growth / preemption before "
            "decode, and the step's gauges")
        self._sp_upload = r.span(
            "serving_upload", "dirty page-table / seq_lens upload")
        self._sp_inputs = r.span(
            "serving_inputs", "decode inputs: tokens, temperatures, "
            "key splits, device puts (speculative: drafting too)")
        self._sp_dispatch = r.span(
            "serving_dispatch", "the decode chunk / verify sweep "
            "dispatch")
        self._sp_token_sync = r.span(
            "serving_token_sync", "the host waiting for the device: "
            "the one token fetch per chunk")
        self._sp_append = r.span(
            "serving_append", "appending the fetched tokens to every "
            "active slot (finishes, publishes, releases)")
        self._sp_tick = r.span(
            "serving_tick", "exporter / SLO / history / incident pass "
            "after the iteration")
        # the step ledger's open row (deepspeed_tpu.devprof): what a
        # step counts and where the device had nothing queued, written
        # once as the step ends; with telemetry off there is none
        self._row = self._step_row() if self._tel_on else None
        self._h_queue_wait = r.histogram(
            "serving_queue_wait_seconds",
            "arrival -> admitted to a slot: the time work waited",
            LATENCY_BUCKETS_S)
        self._mark_admitted = f"{r.namespace}/request_admitted"
        self._mark_first_token = f"{r.namespace}/request_first_token"
        # ---- time-series history + incidents (PR 15): both blocks
        # ride the exporter's tick-hook pass, so enabling either needs
        # an exporter even without Prometheus/HTTP sinks (a sink-less
        # exporter is just the shared timed pass — one monotonic read
        # per step).  Coerced here; constructed below once the tracer
        # and SLO tracker they observe exist.
        hcfg = HistoryConfig.coerce(history)
        icfg = IncidentsConfig.coerce(incidents)
        if hcfg.enabled and not self._tel_on:
            raise ValueError(
                "history needs the telemetry block — the rings sample "
                "the metrics registry; enable telemetry (or drop the "
                "history block)")
        if icfg.enabled and not (
                tracing.enabled
                if isinstance(tracing, (RequestTracer, BoundTracer))
                else TracingConfig.coerce(tracing).enabled):
            # validated BEFORE the exporter below: raising after it
            # would leak the bound HTTP port + server thread with no
            # handle left for the caller to shut down
            raise ValueError(
                "incidents needs the tracing block — the trigger "
                "events (slo_burn_alert, kv_promote_failed, replica "
                "failover, rollbacks) live in the flight recorder; "
                "enable tracing (or drop the incidents block)")
        dcfg = DevprofConfig.coerce(devprof)
        if dcfg.enabled and not self._tel_on:
            # validated BEFORE the exporter below, like incidents: the
            # sentinel's counters live in the registry
            raise ValueError(
                "devprof needs the telemetry block — the compile "
                "sentinel's surfaces are registry metrics; enable "
                "telemetry (or drop the devprof block)")
        self.history_cfg = hcfg
        self.incidents_cfg = icfg
        self.devprof_cfg = dcfg
        # telemetry sinks for serving loops: the exporter ticks from
        # step() (a monotonic compare until interval_s elapses)
        self._tel_exporter = None
        if tcfg is not None and self._tel_on and (
                tcfg.prometheus_path or tcfg.http_port is not None
                or hcfg.enabled or icfg.enabled):
            self._tel_exporter = TelemetryExporter(
                self.registry, prometheus_path=tcfg.prometheus_path,
                interval_s=tcfg.interval_s, http_port=tcfg.http_port)

        # ---- per-request tracing: every lifecycle edge lands in the
        # flight recorder (queued → admitted → prefill-chunk →
        # first-token → decode-batch → preempt/requeue → finish).
        # `tracing` accepts None/bool/dict/TracingConfig — or an
        # existing RequestTracer to share one recorder across engines.
        # _trace_on guards every emit site; the disabled tracer is the
        # shared no-op singleton (no clock, no lock, no ring).
        if isinstance(tracing, (RequestTracer, BoundTracer)):
            self.tracer = tracing
        else:
            self.tracer = RequestTracer.from_config(
                TracingConfig.coerce(tracing))
        # fleet replica identity: every trace event this engine emits
        # carries the replica id (the fleet's flight recorder is shared
        # across replicas — untagged events would be unattributable)
        self.replica_id = None if replica_id is None else str(replica_id)
        if self.replica_id is not None:
            self.tracer = self.tracer.bind(replica=self.replica_id)
        self._trace_on = self.tracer.enabled

        # ---- device-truth observability (see deepspeed_tpu.devprof):
        # sentinel wrappers around the compiled sweep programs count
        # and attribute every XLA compile (warmup vs steady-state),
        # each with the build ledger's seconds for it.  On-demand
        # /profilez captures land under the tracer's dump_dir.
        self.devprof = (
            DevProf(dcfg, registry=self.registry, tracer=self.tracer,
                    dump_dir=getattr(self.tracer, "dump_dir",
                                     "/tmp/dstpu_flight"))
            if dcfg.enabled else NULL_DEVPROF)
        self._devprof_on = self.devprof.enabled
        if self._devprof_on:
            with self._sp_build_programs:
                self._prefill = self.devprof.wrap("prefill", self._prefill)
                self._chunk_prefill = self.devprof.wrap(
                    "chunk_prefill", self._chunk_prefill)
                self._decode_chunk_fn = self.devprof.wrap(
                    "decode_chunk", self._decode_chunk_fn)
                self._verify_chunk = self.devprof.wrap(
                    "spec_verify", self._verify_chunk)
                self._boundary = self.devprof.wrap("boundary",
                                                   self._boundary)
                self._join = self.devprof.wrap("join", self._join)
            with self._sp_build_warmup:
                self._devprof_warmup()

        # rolling-update identity: which weight image this engine is
        # serving (swap_params bumps it; the fleet's per-version SLO
        # rollup groups replicas by it)
        self.weights_version: Any = 0

        # ---- tiered KV cache (ZeRO-Infinity tiering for the prefix
        # pool): published refcount-0 pages reclaimed under pressure
        # demote to a host pool (spilling onward to NVMe) instead of
        # dropping from the content index; tier hits re-admit through a
        # double-buffered promotion overlapped with the uncached
        # suffix's prefill chunks.  The allocator owns the index
        # states; the KVTierPool owns the payloads; this engine owns
        # the device<->host data movement.
        kvt = self.kv_tier        # coerced above, before the cache alloc
        self._kv_pool = None
        # cross-replica KV fabric (attach_fabric): export/admit ride
        # the spill pool, so the handle stays None unless kv_tier is on
        self._fabric = None
        # slot whose in-flight promotion owns the NVMe read channel
        # (host-resident promotions run concurrently and never claim it)
        self._promo_channel: Optional[int] = None
        self._kvt_wm_pages: Optional[int] = None
        if self._kvt_on:
            if not self._pc_on:
                raise ValueError(
                    "kv_tier needs the prefix_cache block — only "
                    "published refcount-0 prefix-cache pages demote; "
                    "without content addressing there is nothing to "
                    "spill or match")
            from deepspeed_tpu.inference.kv_tier import KVTierPool

            self._kv_pool = KVTierPool(
                kvt, page_shape=(n_layers, n_kv, page_size, head_dim),
                page_dtype=cache_dtype, registry=self.registry)
            self.allocator.spill = self._kv_pool
            self.allocator.demote_hook = self._demote_for_evict
            if kvt.demote_watermark < 1.0 and self.allocator.cache_pages:
                self._kvt_wm_pages = int(
                    kvt.demote_watermark * self.allocator.cache_pages)
            # compile the promote scatter + every pow2 demote-gather
            # bucket NOW (against the sacrificial trash page), off the
            # serving critical path — the first real demote/promote
            # must cost a DMA, not an XLA compile inside a request's
            # TTFT
            if self._quant_resident:
                zc = np.zeros((n_layers, n_kv, 1, page_size, head_dim),
                              np.int8)
                zs = np.ones((n_layers, n_kv, 1, page_size, 1),
                             np.float32)
                self._upload_promoted_q([self.trash_page], zc, zs,
                                        zc, zs)
            else:
                z = np.zeros((n_layers, n_kv, 1, page_size, head_dim),
                             np.dtype(cache_dtype))
                self._upload_promoted([self.trash_page], z, z)
            n = 1
            while True:
                if self._quant_resident:
                    self._fetch_pages_host_q([self.trash_page] * n)
                else:
                    self._fetch_pages_host([self.trash_page] * n)
                if n >= self.max_pages_per_seq:
                    break
                n *= 2
            # biggest prewarmed gather bucket: batched demotions chunk
            # their fetches to it so no sweep size compiles in-run
            self._kvt_fetch_cap = n
        self._c_kvt_demoted = r.counter(
            "kv_tier_demoted_pages",
            "warm pages captured to the host/NVMe tier instead of "
            "being dropped (re-demotes of still-spilled spans count: "
            "they kept a key matchable)")
        self._c_kvt_promoted = r.counter(
            "kv_tier_promoted_pages",
            "demoted pages streamed back into fresh HBM pages on a "
            "tier hit")
        self._c_kvt_deferrals = r.counter(
            "kv_tier_promote_deferrals",
            "scheduler iterations a promoting slot's prefill stood "
            "aside (promotion hiding under other slots' compute)")
        self._c_kvt_admit_waits = r.counter(
            "kv_tier_admit_waits",
            "admission ATTEMPTS held back because the tier hit needed "
            "the busy NVMe promotion channel (the admit loop may retry "
            "a waiting request several times per scheduler iteration, "
            "so this measures wait pressure, not distinct requests; "
            "waiting keeps the demoted span a DMA instead of "
            "re-prefilling it)")
        self._c_kvt_qres_promotes = r.counter(
            "kv_tier_quant_resident_promotes",
            "tier promotions published as int8-resident pages — the "
            "cold entry's codes+scales landed in HBM verbatim, the "
            "dequantize->scatter the dense path pays was skipped")
        self._g_kvt_inflight = r.gauge(
            "kv_tier_promoting_pages",
            "pages with a tier promotion in flight right now")
        # what a mesh took from the build's readers: /statusz
        # "kernels" names the readers themselves, and what a step
        # dispatched is in the step ledger's rows (/statusz "steps")
        self._c_kernel_fb = r.counter(
            "serving_kernel_fallbacks",
            "kernels of the family's that the build's mesh took (the "
            "reason is in /statusz kernels.fallbacks)")
        if self._kernels.fallbacks:
            self._c_kernel_fb.inc(len(self._kernels.fallbacks))
        self._h_kvt_promote = r.histogram(
            "kv_tier_promote_seconds",
            "admission-submit -> pages-landed latency of one "
            "promotion (all of its pages)")

        # ---- SLO & goodput accounting (the control-plane contract the
        # multi-replica router will route on): requests carry a tier,
        # are classified attained/violated at finish, and the tracker
        # keeps rolling attainment, multiwindow burn rates, and goodput
        # (attained-request tokens/s) live in the registry.  Burn-rate
        # trips fire structured slo_burn_alert events into the flight
        # recorder.  perf_counter clock: every timestamp the tracker
        # sees (shared `now` reads from the token path) is on it.
        self.slo_cfg = SLOConfig.coerce(slo)
        self.slo_tracker = (
            SLOTracker(self.slo_cfg, self.registry, tracer=self.tracer,
                       clock=time.perf_counter)
            if self.slo_cfg.enabled else NULL_SLO_TRACKER)
        self._slo_on = self.slo_tracker.enabled

        # ---- robustness: fault injection, load shedding, per-request
        # failure isolation, and the degraded-state accounting that
        # /healthz and /statusz surface.  A `faults` block builds a
        # deterministic FaultPlan and installs it process-wide for the
        # aio/tier hook points (the engine owns the install for its
        # lifetime; `shutdown` clears it).  Shedding: queue-depth sheds
        # reject at submit, deadline sheds drop queue entries whose SLO
        # deadline already expired — both produce typed RequestShed
        # results instead of letting doomed work consume the batch.
        self.shed_queue_depth = int(shed_queue_depth)
        if self.shed_queue_depth < 0:
            raise ValueError(
                f"shed_queue_depth must be >= 0 (0 = off), got "
                f"{shed_queue_depth}")
        self._shed_deadline = bool(shed_expired_deadline)
        if self._shed_deadline and not self._slo_on:
            raise ValueError(
                "shed_expired_deadline needs the slo block — deadlines "
                "are per-tier SLO objectives; without it there is "
                "nothing to shed against")
        if isinstance(faults, FaultPlan):
            fcfg = FaultsConfig(enabled=True)
            self._fault_plan: Optional[FaultPlan] = faults
        else:
            fcfg = FaultsConfig.coerce(faults)
            self._fault_plan = (FaultPlan.from_config(fcfg)
                                if fcfg.enabled else None)
        self.faults_cfg = fcfg
        self._owns_fault_plan = False
        if self._fault_plan is not None and \
                faults_mod.active_plan() is not self._fault_plan:
            faults_mod.install_fault_plan(self._fault_plan)
            self._owns_fault_plan = True
        self._c_shed = r.counter(
            "serving_shed_requests",
            "requests rejected at admission by load shedding "
            "(queue-depth or expired-deadline; typed RequestShed "
            "results, counted per SLO tier by the tracker)")
        self._c_failed = r.counter(
            "serving_failed_requests",
            "requests failed by a slot/admission exception and "
            "released in isolation (typed RequestFailed results; the "
            "engine kept serving)")
        self._c_kvt_checksum = r.counter(
            "kv_tier_checksum_failures",
            "promotions that hit a spilled-page checksum mismatch "
            "(entry dropped, span re-prefilled)")
        self._c_kvt_fb_events = r.counter(
            "kv_tier_fallback_events",
            "promotions abandoned after an unrecoverable tier "
            "read/checksum failure — the span fell back to re-prefill "
            "(correctness preserved, the DMA saving lost)")
        self._c_kvt_fb_pages = r.counter(
            "kv_tier_fallback_pages",
            "pages whose content was re-prefilled instead of promoted")
        # host-side ints mirror the counters so /statusz and the leak
        # checks work with telemetry disabled
        self._n_submitted = 0       # arrivals (queued + shed)
        self._n_shed = 0
        self._n_failed = 0
        self._shed_by_reason: Dict[str, int] = {"queue_depth": 0,
                                                "deadline": 0}
        self._last_shed_t: Optional[float] = None
        self._n_kvt_fallbacks = 0
        self._n_kvt_checksum = 0
        self._kvt_fault_streak = 0

        # ---- time-series history + incident capture (the black-box
        # flight recorder): rings over this registry sampled on the
        # exporter tick, an IncidentManager subscribed to the ring's
        # structured events plus EWMA detectors over key series.  Both
        # evaluate on the shared tick-hook pass — never the decode hot
        # path.  With no exporter (telemetry=MetricsRegistry, the
        # fleet-replica pattern) step() drives them inline.
        # (incidents-without-tracing already rejected above, before
        # the exporter existed to leak)
        self.history = (MetricHistory(hcfg, self.registry)
                        if hcfg.enabled else NULL_HISTORY)
        # subclasses adding their own default watch series (ZI's
        # prefetch-wait p95) must respect an operator's EXPLICIT
        # detect list — only a None (defaults in play) invites them
        self._detect_defaulted = icfg.enabled and icfg.detect is None
        if icfg.enabled:
            # None = engine defaults; an EXPLICIT empty detect list
            # disables the anomaly detectors (hard triggers only)
            detect = icfg.detect if icfg.detect is not None else (
                ("serving_ttft_seconds:p95",)
                + tuple(f"slo_{t}_goodput_tokens_per_s"
                        for t in self.slo_tracker.tiers))
            icfg = dataclasses.replace(icfg, detect=tuple(detect))
            self.incidents_cfg = icfg
            self.incident_mgr = IncidentManager(
                icfg, registry=self.registry, tracer=self.tracer,
                history=self.history if self.history.enabled else None,
                statusz_fn=self.statusz,
                source=self.replica_id or "engine")
        else:
            self.incident_mgr = NULL_INCIDENTS
        if self._devprof_on and self.incident_mgr.enabled:
            # a steady-state recompile is a contract violation: the
            # probe trips a bundle, and every bundle (whatever its
            # class) carries the compile ledger + capture references
            self.incident_mgr.add_probe(self.devprof.incident_probe)
            self.incident_mgr.add_attachment("devprof",
                                             self.devprof.bundle_info)
        # shared timed pass: SLO window refresh + history sampling +
        # incident evaluation ride ONE exporter tick-hook walk (the
        # register_tick_hook contract) instead of three per-step paths
        self._slo_tick_hooked = False
        self._tick_inline = (self._tel_exporter is None and
                             (self.history.enabled
                              or self.incident_mgr.enabled))
        if self._tel_exporter is not None:
            ex = self._tel_exporter
            if self._slo_on:
                ex.register_tick_hook(
                    lambda now: self.slo_tracker.maybe_refresh(),
                    interval_s=1.0, name="slo_refresh")
                self._slo_tick_hooked = True
            if self.history.enabled:
                ex.register_tick_hook(
                    self.history.maybe_sample,
                    interval_s=hcfg.sample_interval_s,
                    name="history_sample")
            if self.incident_mgr.enabled:
                # after history: detectors judge THIS tick's sample
                ex.register_tick_hook(
                    self.incident_mgr.maybe_evaluate,
                    interval_s=icfg.eval_interval_s,
                    name="incident_evaluate")

        # ---- introspection: /statusz (live engine snapshot),
        # /healthz (liveness/readiness, watchdog-fed), /requestz?id=
        # (one request's ring events), /historyz (metric-history rings
        # + incident ticker) ride the telemetry HTTP server
        self._t_start = time.perf_counter()
        self._last_step_t: Optional[float] = None
        self._watchdog = None
        self._closed = False
        if self._tel_exporter is not None:
            self._tel_exporter.register_provider("statusz", self.statusz)
            self._tel_exporter.register_provider("healthz", self.healthz)
            self._tel_exporter.register_provider("requestz",
                                                 self.requestz)
            if self.history.enabled or self.incident_mgr.enabled:
                self._tel_exporter.register_provider("historyz",
                                                     self.historyz)
            if self._devprof_on:
                self._tel_exporter.register_provider("profilez",
                                                     self.profilez)
            if self._trace_on:
                # /tracez?since= — incremental flight-recorder drain
                # for the remote scrape plane (obs_wire)
                from deepspeed_tpu.obs_wire import tracez_provider
                self._tel_exporter.register_provider(
                    "tracez", tracez_provider(
                        self.tracer.recorder, replica=self.replica_id))
        # build_seconds: this line's clock less the first line's
        self._build.built()

    # (the `stats` deprecation shim from PR 2/PR 6 was removed on its
    # announced schedule — read `engine.registry.snapshot()` instead)

    # -------------------------------------------------- subclass hooks
    # (the ZeRO-Inference engine swaps both: per-layer cache tuples so
    # streamed block programs update one layer's pages in place, and
    # host-driven streamed executors in place of the whole-model jits)
    def _alloc_cache(self, n_layers, n_kv, num_pages, page_size,
                     head_dim, cache_dtype) -> PagedKVCache:
        def put_kv(x):
            return (jax.device_put(x, self._kv_sharding)
                    if self._kv_sharding is not None else x)

        table = self._put(jnp.full(
            (self.max_batch, self.max_pages_per_seq),
            self.trash_page, jnp.int32))
        seq_lens = self._put(jnp.zeros((self.max_batch,), jnp.int32))
        expert_rows = (self._put(np.zeros(
            (self._n_expert_rows + self._pair_passes,), np.int32))
            if self._n_expert_rows else None)
        conv = state = ring = None
        if self._state_row is not None:
            # indexed by slot, not by page; made on the device, as the
            # pool is: 6.8 GiB of host zeros took 29 s to upload (PR 42)
            sr = self._state_row
            conv = None if sr.conv is None else self._put(jnp.zeros(
                (sr.layers, self.max_batch) + sr.conv, cache_dtype))
            state = None if sr.state is None else self._put(jnp.zeros(
                (sr.layers, self.max_batch) + sr.state, STATE_DTYPE))
            if sr.ring is not None:     # the family's second per-slot kind
                ring = self._put(jnp.zeros(
                    (sr.ring.layers, self.max_batch) + sr.ring.conv,
                    cache_dtype))
        if self._quant_resident:
            # int8-resident pages: codes replace the dense planes
            # (~2x the pages per HBM byte at bf16, 4x at f32) and a
            # per-token-row f32 scale plane rides along.  Scales init
            # to ONE — the codec's convention for all-zero rows, so an
            # untouched page round-trips exactly.
            shape = (n_layers, n_kv, num_pages, page_size, head_dim)
            sshape = (n_layers, n_kv, num_pages, page_size, 1)
            return PagedKVCache(
                k=put_kv(jnp.zeros(shape, jnp.int8)),
                v=put_kv(jnp.zeros(shape, jnp.int8)),
                table=table, seq_lens=seq_lens, page_size=page_size,
                k_scale=put_kv(jnp.ones(sshape, jnp.float32)),
                v_scale=put_kv(jnp.ones(sshape, jnp.float32)),
                expert_rows=expert_rows)
        return PagedKVCache(
            k=put_kv(jnp.zeros(
                (n_layers, n_kv, num_pages, page_size, head_dim),
                cache_dtype)) if self._pooled else None,
            v=None if self._values_in_keys or not self._pooled
            else put_kv(jnp.zeros(
                (n_layers, n_kv, num_pages, page_size, head_dim),
                cache_dtype)),
            table=table, seq_lens=seq_lens,
            page_size=page_size,
            expert_rows=expert_rows, conv=conv, state=state, ring=ring)

    def _state_bytes(self) -> int:
        """Bytes of the per-slot state beside the pool."""
        return int(sum(a.nbytes for a in (self.cache.conv, self.cache.state,
                                          self.cache.ring)
                       if a is not None))

    def _pool_bytes(self) -> int:
        """Bytes of the page pool (one array, or a layer each where the
        weights stream)."""
        c = self.cache
        return int(sum(a.nbytes for a in jax.tree.leaves(
            (c.k, c.v, c.k_scale, c.v_scale))))

    def _row_view(self, table_row, seq_len: int, b: int) -> PagedKVCache:
        """The private one-row view a prefill or chunk program works
        on: the engine's buffers under the row's own table (from the
        HOST copy: a device slice can alias the live table buffer, which
        the program's donation would delete under the decode path) and
        length, and, where slots keep a recurrent state, the slot
        (``b``) whose state the row is."""
        return PagedKVCache(
            k=self.cache.k, v=self.cache.v,
            expert_rows=self.cache.expert_rows,
            conv=self.cache.conv, state=self.cache.state,
            ring=self.cache.ring,
            slot=(None if self._state_row is None
                  else self._put(np.full((1,), b, np.int32))),
            table=self._put(table_row),
            seq_lens=self._put(np.full((1,), seq_len, np.int32)),
            page_size=self.page_size)

    def _adopt(self, view: PagedKVCache) -> PagedKVCache:
        """The engine's cache with the buffers a prefill or chunk
        program returned in ``view`` (its table and lengths were the
        call's own)."""
        return self.cache._replace(k=view.k, v=view.v,
                                   expert_rows=view.expert_rows,
                                   conv=view.conv, state=view.state,
                                   ring=view.ring)

    def _build_programs(self, prefill_fn, decode_fn,
                        chunk_prefill_fn) -> None:
        """Install ``self._prefill`` / ``self._chunk_prefill`` /
        ``self._decode_chunk_fn`` — any callables honoring the jitted
        contracts; the base engine compiles whole-model programs."""
        (dstpu_prefill, dstpu_chunk, dstpu_boundary, dstpu_sweep,
         dstpu_decode) = serving_programs(
            prefill_fn, decode_fn, chunk_prefill_fn, _sample_rows,
            self.decode_chunk, self.max_batch,
            expert_rows=bool(self._n_expert_rows),
            state=self._state_row is not None)
        self._prefill = jax.jit(dstpu_prefill, donate_argnums=(2,))
        self._chunk_prefill = (jax.jit(dstpu_chunk, donate_argnums=(2,))
                               if chunk_prefill_fn is not None else None)
        self._boundary = jax.jit(dstpu_boundary)
        self._join = jax.jit(join_program(), out_shardings=self._repl)
        # the speculative verify sweep scores K+1 positions and needs
        # them all: the one caller that reads the continuation
        # forward's logits, so the one engine that builds this program
        self._verify_chunk = (jax.jit(dstpu_sweep, donate_argnums=(2,))
                              if self._spec_on else None)
        # ONE decode program, whose token operand has the form of its
        # own output: the tokens' [B, 1] itself where that is the form,
        # else cut out of it inside the program (decode_from_output)
        self._out_shape = (self.max_batch, self.decode_chunk)
        if self._n_expert_rows:
            self._out_shape = (self.max_batch * self.decode_chunk
                               + self.cache.expert_rows.shape[0],)
        if self._out_shape != (self.max_batch, 1):
            dstpu_decode = decode_from_output(
                dstpu_decode, self.decode_chunk, self.max_batch)
        self._decode_chunk_fn = self._decode_jit = jax.jit(
            dstpu_decode, donate_argnums=(2,))

    def _devprof_warmup(self) -> None:
        """Devprof build-time precompile: dispatch every sweep program
        once per steady shape so the jit caches are fully populated
        before the first request.  The zero-steady-recompile contract
        ("a compile after the first token is a shape-drift bug") is
        only honest if the shape set is CLOSED at build — without
        this, the decode chunk's first compile and chunk-prefill's
        lazily-reached power-of-two table buckets would land after the
        first token and read as violations.  Every warmup write goes
        to the trash page (all table rows are trash at build) so
        serving state is untouched; the dispatches run through the
        sentinel wrappers and are counted — and attributed — as
        warmup compiles.  Side benefit: the first real request pays
        zero compilation (production TPU serving does exactly this —
        precompile the bucket set at startup)."""
        # operands go up as the steady state's do, from NumPy: an eager
        # jnp.zeros is a tiny program a shape, compiled on every build
        # (too small for the persistent cache to keep)
        zi = np.zeros
        n0 = BUILD_LEDGER.mark()
        # one dstpu/build_program span a dispatch (site, shape word):
        # the ledger's entry for that program gets the word and run_s.
        # This frame is under every lowering below, and its size moves
        # set-up by seconds (PERF.md 6, PR 37): no new local here
        row = self.max_pages_per_seq * self.page_size
        last = self._put(zi((1,), np.int32))
        logits_row = None
        if self.prefill_bucket:
            # cold full prefill pads the prompt to prefill_bucket
            # MULTIPLES clamped at the table row — enumerate them all
            bkt = self.prefill_bucket
            ends = sorted({min(i * bkt, row)
                           for i in range(1, -(-row // bkt) + 1)})
            for end in ends:
                view = self._row_view(self._table_host[0:1], 0, 0)
                with self._sp_build_program("prefill", end=end):
                    logits_row, view = self._prefill(
                        self.params, self._put(zi((1, end), np.int32)),
                        view, last)
                self.cache = self._adopt(view)
        if self._chunk_prefill is not None:
            # the continuation forward's page-table width is bucketed
            # to powers of two clamped at the full row — enumerate the
            # same closed set _advance_prefill draws from: its table
            # spans the chunk itself at least, so no width under the
            # power of two that holds C tokens is ever dispatched
            C = self.prefill_chunk or self.prefill_bucket
            widths, w = [], 1
            while w < -(-C // self.page_size):
                w *= 2
            while w < self.max_pages_per_seq and self._pooled:
                widths.append(w)
                w *= 2
            widths.append(self.max_pages_per_seq)
            for w in widths:
                view = self._row_view(self._table_host[0:1, :w], 0, 0)
                with self._sp_build_program("chunk_prefill", w=w):
                    logits_row, view = self._chunk_prefill(
                        self.params, self._put(zi((1, C), np.int32)),
                        view, last)
                self.cache = self._adopt(view)
        # whole-cache dispatches (spec verify, decode) see the
        # page_size leaf as the weak-i32 scalar a previous jit RETURN
        # left in the cache, not the python int the constructor put
        # there — normalize first, or the warmup would compile the
        # int-leaf twin of each program and the first real dispatch
        # would still compile (and read as a steady "recompile")
        self.cache = self.cache._replace(
            page_size=jnp.asarray(self.page_size))
        if self._spec_on:
            # the verify sweep's whole-cache continuation shape
            Kd = self.speculative.draft_tokens
            with self._sp_build_program("spec_verify", k=Kd + 1):
                _, self.cache = self._verify_chunk(
                    self.params,
                    self._put(zi((self.max_batch, Kd + 1), np.int32)),
                    self.cache)
        ordinal = self._put(zi((), np.int32))
        if logits_row is not None:
            # the boundary sampler, over a row a prefill above returned
            with self._sp_build_program("boundary"):
                self._boundary(logits_row, self._key, ordinal,
                               self._put(zi((1,), np.float32)))
            # and what writes its token into a decode's operand
            with self._sp_build_program("join"):
                self._join(self._put(zi(self._out_shape, np.int32)),
                           last, ordinal)
        with self._sp_build_program("decode_chunk", b=self.max_batch):
            _, self.cache = self._decode_chunk_fn(
                self.params,
                self._put(zi(self._out_shape, np.int32)),
                self.cache, self._key, ordinal,
                self._put(zi((self.max_batch,), np.float32)))
        logger.info("devprof warmup: %s", BUILD_LEDGER.since(n0))

    # ------------------------------------------------------------- requests
    def submit(self, req_id, tokens, max_new_tokens: int = 32,
               temperature: float = 0.0,
               tier: Optional[str] = None,
               arrival: Optional[float] = None) -> Optional[RequestShed]:
        """Queue a request.  ``tier`` names an SLO tier from the
        ``slo`` config block (None → the block's default tier); naming
        a tier with the block disabled raises rather than silently
        dropping the latency objective.  ``arrival`` carries an
        earlier ``perf_counter`` arrival time through a router's
        failover re-submit, so SLO deadlines and TTFT judge the user's
        real clock, not the re-route.

        Returns None when queued.  With ``shed_queue_depth`` set and
        the queue at capacity, the request is NOT queued: a typed
        :class:`RequestShed` is recorded in ``finished`` and returned
        (load shedding is a first-class outcome a router retries
        elsewhere, never an exception).  Raises :class:`EngineClosed`
        after :meth:`shutdown` — a dead engine must reject
        deterministically, not fail downstream."""
        if self._closed:
            raise EngineClosed(
                f"request {req_id!r} submitted after shutdown"
                + (f" (replica {self.replica_id})"
                   if self.replica_id else ""))
        tokens = list(map(int, tokens))
        if not tokens:
            raise ValueError(f"request {req_id}: empty prompt")
        if len(tokens) + max_new_tokens > self.max_seq:
            raise ValueError(
                f"request {req_id}: prompt {len(tokens)} + "
                f"{max_new_tokens} new > max_seq {self.max_seq}")
        lifetime_pages = self._pages_needed(len(tokens) + max_new_tokens)
        usable = self.trash_page  # pool size minus the reserved page
        if lifetime_pages > usable:
            raise ValueError(
                f"request {req_id}: needs {lifetime_pages} pages at full "
                f"length but the pool has {usable} — it could never "
                "complete even alone")
        self._n_submitted += 1
        if self.shed_queue_depth and \
                len(self.queue) >= self.shed_queue_depth:
            return self._shed(req_id, tier, "queue_depth")
        traced = self._trace_on and self.tracer.sampled(req_id)
        now = time.perf_counter() if arrival is None else float(arrival)
        if self._slo_on or tier is not None:
            # BEFORE the queue append: an unknown tier must reject the
            # request, not classify it later under a KeyError
            self.slo_tracker.on_submit(req_id, tier, now=now)
        self.queue.append(Request(
            req_id, tokens, max_new_tokens, temperature,
            t_submit=now if self._tel_on else None,
            traced=traced, t_arrival=now, tier=tier))
        self._g_queue.set(len(self.queue))
        if traced:
            self.tracer.event("queued", req_id, attrs={
                "prompt_tokens": len(tokens),
                "max_new_tokens": max_new_tokens,
                "queue_depth": len(self.queue)})

    @property
    def has_work(self) -> bool:
        # a step in flight is work: its tokens (all void, where no row
        # of it lives) are read by the next ``step()``
        return bool(self.queue) or self._flying is not None \
            or any(s is not None for s in self.slots)

    # ------------------------------------- robustness: shed / fail / leaks
    def _shed(self, req_id, tier: Optional[str],
              reason: str) -> RequestShed:
        """Record a typed admission rejection: per-tier SLO shed
        accounting, telemetry, trace event, and the degraded-state
        clock /healthz reads.  Nothing about the request ran — there
        is nothing to release."""
        # validates the tier name exactly like on_submit would (an
        # unknown tier is a caller bug even when the answer is "no")
        self.slo_tracker.on_shed(req_id, tier)
        res = RequestShed(req_id, reason, tier)
        self.finished[req_id] = res
        self._c_shed.inc()
        self._n_shed += 1
        self._shed_by_reason[reason] = \
            self._shed_by_reason.get(reason, 0) + 1
        self._last_shed_t = time.perf_counter()
        if self._trace_on:
            self.tracer.event("request_shed", req_id, attrs={
                "reason": reason, "tier": tier,
                "queue_depth": len(self.queue)})
        self._g_queue.set(len(self.queue))
        return res

    def _shed_expired(self) -> None:
        """Deadline shedding at admission: drop queued requests whose
        SLO deadline has already expired — serving them would burn a
        slot on work no client is waiting for.  Runs once per step
        before admission."""
        now = time.perf_counter()
        kept: List[Request] = []
        shed = False
        for r in self.queue:
            obj = self.slo_cfg.tiers.get(
                r.tier or self.slo_cfg.default_tier)
            dl = obj.deadline_s if obj is not None else None
            if dl is not None and now - r.t_arrival > dl:
                self._shed(r.req_id, r.tier, "deadline")
                self._newly_finished.append(r.req_id)
                shed = True
            else:
                kept.append(r)
        if shed:
            self.queue = collections.deque(kept)

    def _record_failure(self, req: Request, reason: str,
                        exc: BaseException, b: int = -1,
                        generated: int = 0) -> None:
        """ONE failure ledger for both the slot and admission paths:
        the chaos soak reconciles typed results, telemetry counters,
        per-tier SLO lifetimes and trace events against each other, so
        the bookkeeping must never fork."""
        self._c_failed.inc()
        self._n_failed += 1
        self.slo_tracker.on_fail(req.req_id)
        self.finished[req.req_id] = RequestFailed(
            req.req_id, reason, repr(exc), req.tier,
            generated=generated)
        self._newly_finished.append(req.req_id)
        if self._trace_on:
            # always emitted (not sampling-gated): a failure is exactly
            # what the flight recorder exists to explain
            self.tracer.event("request_failed", req.req_id, b, attrs={
                "error": repr(exc)[:200], "reason": reason,
                "generated": generated})

    def _fail_slot(self, b: int, exc: BaseException) -> None:
        """Per-request failure isolation: an exception in slot ``b``'s
        host-side work fails THAT request — its promotion is fenced
        and cancelled, its pages/COW refs released, its pending
        boundary sample dropped — and the engine keeps serving the
        other slots.  The request finishes as a typed
        :class:`RequestFailed` (before this, the exception killed the
        whole engine)."""
        s = self.slots[b]
        req = s.req
        logger.warning(
            "serving: request %r failed in slot %d (%s) — releasing "
            "and continuing", req.req_id, b, exc)
        if s.promo is not None:
            try:
                self._cancel_promotion(s)
            except Exception:
                logger.exception(
                    "serving: promotion cancel during slot failure")
        self.allocator.release(s.seq_id)
        self._table_host[b, :] = self.trash_page
        self._table_dirty = self._lens_dirty = True
        self.slots[b] = None
        self._drop_boundary(b)
        self._record_failure(req, "slot_exception", exc, b=b,
                             generated=len(s.generated))

    def check_leaks(self) -> List[str]:
        """Page-accounting invariants; returns violations (empty =
        clean).  Reused by the chaos soak and the fault tests after
        every scenario: each page must sit in exactly one of
        {free list, warm pool, live-owned, parked}, refcounts must
        match ownership multiplicity, and an idle engine must own
        nothing.  A decode step in flight owns no page of its own: it
        writes its rows' pages, which are the slots' until they end (a
        row that ended under it wrote a released page before any
        program that is handed the page runs), so the books are these
        with or without one."""
        al = self.allocator
        probs: List[str] = []
        usable = self.trash_page
        owned_flat = [p for pages in al.owned.values() for p in pages]
        live = set(owned_flat)
        cnt = collections.Counter(al.free)
        cnt.update(al.pool.keys())     # keys() — a dict would be read
        cnt.update(live)               # as a counts mapping
        cnt.update(al._parked)
        missing = [p for p in range(usable) if cnt[p] != 1]
        if missing:
            probs.append(
                f"pages not in exactly one of free/warm/live/parked: "
                f"{missing[:16]}")
        for p, n in al.refs.items():
            owners = sum(1 for pages in al.owned.values()
                         if p in pages)
            if n != owners:
                probs.append(
                    f"page {p}: refcount {n} != {owners} owners")
        for p in al.promoting:
            if p not in al.refs and p not in al._parked:
                probs.append(
                    f"page {p}: promoting but neither owned nor parked")
        idle = not any(s is not None for s in self.slots) \
            and not self.queue
        if idle:
            if al.owned:
                probs.append(f"idle engine owns pages: {dict(al.owned)}")
            if al.promoting:
                probs.append(
                    f"idle engine has promotions in flight: "
                    f"{dict(al.promoting)}")
            if al._parked:
                probs.append(f"idle engine has parked pages: "
                             f"{al._parked}")
            if self._kv_pool is not None and self._kv_pool._pinned:
                probs.append(
                    f"idle engine holds tier pins: "
                    f"{list(self._kv_pool._pinned)}")
        return probs

    # ------------------------------------------- fleet handoff hooks
    # (consumed by deepspeed_tpu.fleet.FleetRouter: drain re-routes a
    # replica's queued work, failover salvages a dead replica's whole
    # request set; both are pure host bookkeeping — no device work, so
    # they stay callable on an engine whose compute path is wedged)
    def take_queued(self) -> List[Request]:
        """Pop and return every queued (not-yet-admitted) request —
        the drain/failover queue handoff.  Each request's SLO record
        is forgotten here (the destination replica re-announces it;
        carry ``t_arrival`` through ``submit(arrival=)`` so the user's
        clock survives the hop)."""
        taken, self.queue = list(self.queue), collections.deque()
        for r in taken:
            self.slo_tracker.forget(r.req_id)
        self._g_queue.set(0)
        if taken and self._trace_on:
            self.tracer.event("queue_handoff",
                              attrs={"requests": len(taken)})
        return taken

    def abandon_inflight(self) -> List[Tuple[Request, int]]:
        """Release every active slot WITHOUT finishing its request:
        promotions fenced and cancelled, pages/COW refs freed, pending
        boundary samples dropped, SLO records forgotten.  Returns
        ``[(request, tokens_generated)]`` so a router can decide per
        request: zero tokens → safe to re-submit elsewhere; any tokens
        → must fail typed (re-running would double-generate).  The
        failover half of the fleet handoff; leaves ``check_leaks``
        clean on this engine."""
        out: List[Tuple[Request, int]] = []
        # a step in flight is dropped unread (no device work here): its
        # tokens were these rows', and are nobody's now
        self._flying = None
        for b, s in enumerate(self.slots):
            if s is None:
                continue
            if s.promo is not None:
                try:
                    self._cancel_promotion(s)
                except Exception:
                    logger.exception(
                        "serving: promotion cancel during abandon")
            self.allocator.release(s.seq_id)
            self._table_host[b, :] = self.trash_page
            self.slots[b] = None
            self.slo_tracker.forget(s.req.req_id)
            if self._trace_on:
                self.tracer.event("abandoned", s.req.req_id, b, attrs={
                    "generated": len(s.generated)})
            out.append((s.req, len(s.generated)))
        if out:
            self._table_dirty = self._lens_dirty = True
            self._pending_boundary = []
        return out

    def warm_keys(self) -> frozenset:
        """The replica's published-key digest: every content key
        matchable at admission — the HBM prefix-cache index plus (when
        the tier is live) the spilled host/NVMe entries.  The fleet
        router diffs these digests to answer "which replica has this
        prompt warm" without touching any page payloads."""
        return frozenset(self.warm_digest())

    def warm_digest(self) -> Dict[bytes, str]:
        """:meth:`warm_keys` with tier locations: content key →
        ``"hbm"`` / ``"host"`` / ``"nvme"``.  The fleet router's
        cost-aware affinity prefers an HBM-warm replica over an
        NVMe-warm one when warm-prefix lengths tie — a promotion from
        NVMe is a DMA plus an aio read, not a dict lookup.  A span
        resident in both HBM and the spill (a promoted page whose
        spill copy was kept as a free re-demote) reports HBM."""
        d = {k: "hbm" for k in self.allocator.index}
        pool = self._kv_pool
        if pool is not None and pool.disabled is None:
            for k, e in pool.entries.items():
                d.setdefault(k, e.location)
        return d

    # ------------------------------------------------ KV fabric verbs
    # (consumed by deepspeed_tpu.fleet.FleetRouter's migration and
    # prefill→decode handoff paths; both are host bookkeeping + one
    # batched device→host gather on the export side)
    def attach_fabric(self, fabric) -> None:
        """Join a :class:`~deepspeed_tpu.kv_fabric.KVFabric`: this
        replica may then export page chains into it and admit chains
        other replicas computed.  Requires the ``kv_tier`` block —
        admitted entries land in the local spill pool so the existing
        tier-hit admission path (``begin_promotion`` + TierPageReader,
        checksum-verified, re-prefill fallback) serves them."""
        if fabric is not None and not self._kvt_on:
            raise ValueError(
                "attach_fabric needs the kv_tier block — the local "
                "spill pool is the admission side of the transport "
                "(migrated chains land there and re-admit through the "
                "tier promotion path)")
        self._fabric = fabric

    def export_pages(self, keys: List[bytes], fabric=None) -> int:
        """Export the longest contiguous prefix of ``keys`` this
        replica holds (HBM published pages batch-fetch device→host and
        encode; spilled tier entries ride as-is, int8 cold pages
        included) into the fabric.  Returns the number of leading keys
        now covered by the fabric; an export failure mid-chain stops
        there — the published prefix is still chain-valid, and the
        uncovered tail re-prefills on the importer."""
        from deepspeed_tpu.inference.kv_tier import encode_entry

        fab = fabric if fabric is not None else self._fabric
        if fab is None or not self._kvt_on:
            raise ValueError(
                "export_pages needs an attached fabric and the "
                "kv_tier block")
        plan: List[Tuple[bytes, str, Optional[int]]] = []
        for k in keys:
            if fab.has(k):
                plan.append((k, "fab", None))
            elif k in self.allocator.index:
                plan.append((k, "hbm", self.allocator.index[k]))
            elif self._kv_pool.has(k):
                plan.append((k, "tier", None))
            else:
                break
        hbm = [(k, p) for k, kind, p in plan if kind == "hbm"]
        payload: Dict[bytes, tuple] = {}
        # one batched gather per prewarmed-bucket chunk, not one
        # device read per page — same discipline as the demote sweep
        cap = self._kvt_fetch_cap
        for i in range(0, len(hbm), cap):
            chunk = hbm[i:i + cap]
            kh, vh = self._fetch_pages_host([p for _, p in chunk])
            for j, (kk, _p) in enumerate(chunk):
                payload[kk] = (kh[:, :, j], vh[:, :, j])
        n = 0
        nbytes = 0
        for k, kind, _p in plan:
            try:
                if kind == "hbm":
                    e = encode_entry(
                        k, *payload[k],
                        quantize=self.kv_tier.quantize_cold,
                        page_dtype=self._kv_pool.page_dtype)
                    fab.publish(k, e)
                    nbytes += e.nbytes
                elif kind == "tier":
                    e = self._kv_pool.entry_payload(k)
                    fab.publish(k, e)
                    nbytes += e.nbytes
            except (IOError, OSError) as exc:
                # injected export failure or an unreadable spill file:
                # the chain stops here, the rest re-prefills remotely
                logger.warning(
                    "serving: fabric export stopped at page %d/%d "
                    "(%s)", n, len(plan), exc)
                break
            n += 1
        if n and self._trace_on:
            self.tracer.event("kv_export", attrs={
                "pages": n, "bytes": nbytes})
        return n

    def admit_fabric(self, keys: List[bytes],
                     deadline: Optional[float] = None) -> int:
        """Fetch the longest contiguous prefix of ``keys`` out of the
        fabric into the LOCAL spill pool, so the next admission's
        chained walk treats the span as tier hits and promotes it
        through the existing checksum-verified path.  ``deadline``
        (perf_counter): stop fetching once past it — a migration that
        blows its budget admits the partial prefix it has (still
        chain-valid) and the rest re-prefills.  Returns the leading
        keys now locally matchable."""
        fab = self._fabric
        if fab is None or not self._kvt_on:
            raise ValueError(
                "admit_fabric needs an attached fabric and the "
                "kv_tier block")
        n = 0
        for k in keys:
            if k in self.allocator.index or self._kv_pool.has(k):
                n += 1              # already warm here — free
                continue
            if deadline is not None and \
                    time.perf_counter() > deadline:
                break
            if not fab.has(k):
                break
            try:
                entry = fab.fetch(k)
            except (KeyError, IOError, OSError):
                break               # evicted or injected fetch failure
            if self._kv_pool.admit_entry(entry) is None:
                break               # pool can't hold it (or disabled)
            n += 1
        if n and self._trace_on:
            self.tracer.event("fabric_admit", attrs={"pages": n})
        return n

    def swap_params(self, new_params, version=None) -> None:
        """Rolling-update weight swap: replace the served weight image
        in place (the jitted programs take params as a plain argument,
        so no recompile as long as shapes/dtypes match — and they MUST
        match, because a shape change would silently retrace inside
        the next request's TTFT).  ``new_params`` must be prepared
        exactly like the originals (same quantization, same TP
        sharding — use :func:`serving_engine`'s preparation).

        Only a DRAINED engine may swap: the fleet's rollout drains the
        replica first, so no in-flight request ever mixes layers from
        two versions.  The engine's generated prefix-cache pages are
        version-poisoned by a swap (old-version KV under new weights),
        so the ENTIRE warm pool and spill tier are invalidated here.
        """
        if self._closed:
            raise EngineClosed(
                "swap_params on a shut-down engine"
                + (f" (replica {self.replica_id})"
                   if self.replica_id else ""))
        if self.has_work:
            raise RuntimeError(
                "swap_params needs a drained engine (queue and slots "
                "empty) — drain the replica first so no in-flight "
                "request mixes weight versions")
        old_leaves = jax.tree_util.tree_flatten(self.params)
        new_leaves = jax.tree_util.tree_flatten(new_params)
        if old_leaves[1] != new_leaves[1] or any(
                getattr(a, "shape", None) != getattr(b, "shape", None)
                or getattr(a, "dtype", None) != getattr(b, "dtype", None)
                for a, b in zip(old_leaves[0], new_leaves[0])):
            raise ValueError(
                "swap_params: new weight tree does not match the "
                "served one (structure/shape/dtype) — a mismatched "
                "swap would retrace or mis-serve; rebuild the engine "
                "for an architecture change")
        self.params = new_params
        self._invalidate_warm_pages()
        if version is not None:
            self.weights_version = version
        if self._trace_on:
            self.tracer.event("weights_swap", attrs={
                "version": _req_key(self.weights_version)})

    def _invalidate_warm_pages(self) -> None:
        """Drop every published prefix-cache page (HBM warm pool and
        spill tier): KV computed under the old weights must never be
        shared into a new-version request's page table."""
        if not self._pc_on:
            return
        al = self.allocator
        # a drained engine's published pages are all warm (refcount 0);
        # reclaim_warm drops them from the pool + content index without
        # the demote hook — a version swap must not spill poisoned
        # pages to the tier — and the tier's existing entries discard
        if al.pool:
            al.reclaim_warm(list(al.pool), demoted=False)
        if self._kv_pool is not None:
            for key in list(self._kv_pool.entries):
                self._kv_pool.discard(key)

    # ----------------------------------------------------------- scheduling
    # dstpu: hot-path
    def _upload_dirty(self) -> None:
        """One batched host→device upload of whatever changed (the whole
        table is [max_batch, pages_per_seq] int32 — tiny; uploading it
        wholesale beats per-row ``.at[b].set`` device updates).

        Rows still mid-chunked-prefill upload as TRASH with len 0: the
        batched decode writes a token structurally for every row, and a
        half-prefilled row must not take that write into its real pages
        (its chunk forwards use a private host-built view instead)."""
        pending = [b for b, s in enumerate(self.slots)
                   if s is not None and s.prefilling]
        if self._table_dirty:
            up = self._table_host.copy()
            for b in pending:
                up[b, :] = self.trash_page
            self.cache = self.cache._replace(table=self._put(up))
            self._table_dirty = False
        if self._lens_dirty:
            lens = np.zeros((self.max_batch,), np.int32)
            for b, s in enumerate(self.slots):
                if s is not None and not s.prefilling:
                    lens[b] = s.seq_len
            self.cache = self.cache._replace(seq_lens=self._put(lens))
            self._lens_dirty = False

    def _free_slot(self) -> Optional[int]:
        for b, s in enumerate(self.slots):
            if s is None:
                return b
        return None

    def _pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.page_size) if self._pooled else 0

    def _admit_one(self) -> bool:
        """Admit one queued request into a free slot; returns True if
        admitted.  Head-of-line blocking fix: when the HEAD request's
        pages do not fit, up to ``admit_lookahead`` younger requests
        are considered instead of stalling the whole queue (skipped
        entries counted in ``serving_admit_skips``).  The head is
        always tried first, so a large request is never starved — it
        admits the moment its pages exist."""
        if not self.queue:
            return False
        b = self._free_slot()
        if b is None:
            return False       # no slot: nothing in the window fits
        window = min(len(self.queue), 1 + self.admit_lookahead)
        for i in range(window):
            req = self.queue[i]
            try:
                admitted = self._try_admit(b, req, queue_skips=i)
            except faults_mod.FatalStreamError:
                # an unrecoverable WEIGHT stream is engine-fatal, not
                # per-request: every future admission needs the same
                # bytes.  _try_admit cleaned up (the request stays
                # queued for a restarted engine); the structured fatal
                # — postmortem already dumped — reaches the supervisor
                raise
            except Exception as e:
                # _try_admit cleaned up after itself (pages released,
                # promotions cancelled, pins dropped) — fail THIS
                # request and keep the engine serving
                logger.warning(
                    "serving: request %r failed during admission (%s) "
                    "— releasing and continuing", req.req_id, e)
                del self.queue[i]
                self._record_failure(req, "admit_exception", e)
                return True      # progress: the queue shrank
            if admitted:
                del self.queue[i]
                if i:
                    self._c_admit_skips.inc(i)
                return True
        return False

    def _try_admit(self, b: int, req: Request,
                   queue_skips: int = 0) -> bool:
        """Admit ``req`` into slot ``b`` if its pages fit; no side
        effects on failure.  Cache-aware: the prompt's longest cached
        page-aligned prefix is shared into the page table (refcount
        bumps, read-only) and prefill starts at the first uncached
        token — cached-prefix tokens skip compute entirely."""
        T = len(req.tokens)
        ps = self.page_size
        # ---- longest cached page-aligned prefix (chained-hash walk
        # across EVERY tier: HBM index hits share read-only as before;
        # demoted spans on the host/NVMe tier are hits too, re-admitted
        # through promotion).  At least one prompt token always
        # prefills (the engine samples the first generated token from
        # the last prompt position's logits), so a fully covered prompt
        # gives up its final page.
        matched: List[Tuple[str, Any]] = []
        if self._pc_on:
            if req.page_keys is None:
                req.page_keys = page_keys(req.tokens, ps)
            keys = req.page_keys[:matchable_pages(T, ps)]
            if self._kvt_on:
                matched = self.allocator.lookup_tiered(keys)
                if self._promo_channel is not None and any(
                        kind == "tier" and
                        self._kv_pool.location(k) == "nvme"
                        for kind, k in matched):
                    # the NVMe read channel is single-consumer (one
                    # promotion's alternating aio slots at a time).
                    # Host-resident tier hits promote concurrently —
                    # their reads are dict lookups — but an admission
                    # needing NVMe bytes while another promotion owns
                    # the channel WAITS: admitting with only the HBM
                    # prefix would re-prefill a span that is sitting
                    # demoted, turning a DMA back into compute.  The
                    # lookahead window keeps other traffic admitting.
                    self._c_kvt_admit_waits.inc()
                    return False
            else:
                matched = [("hbm", p)
                           for p in self.allocator.lookup(keys)]
        cm = len(matched)
        cached = cm * ps
        hbm_pages = [p for kind, p in matched if kind == "hbm"]
        tier_keys = [k for kind, k in matched if kind == "tier"]
        bkt = self.prefill_chunk or self.prefill_bucket
        # bucket-pad the UNCACHED suffix for a bounded compile count,
        # clamped to the table width (a prompt near max_seq must not
        # pad past the row)
        end = min(cached + -(-(T - cached) // bkt) * bkt,
                  self.max_pages_per_seq * ps)
        # tier-matched spans skip prefill COMPUTE but still need fresh
        # physical pages for the promoted payload to land in
        need = self._pages_needed(max(end, T + 1)) - cm + len(tier_keys)
        # matched warm-pool pages revive rather than consume free pages,
        # but they stop being evictable once shared — the fresh-page
        # demand must be met WITHOUT counting them as reclaimable
        pooled = sum(1 for p in hbm_pages if p in self.allocator.pool)
        if self.allocator.available - pooled < need:
            return False
        seq_id = self._seq_counter
        self._seq_counter += 1
        promo = None
        page_map: Dict[bytes, int] = {}
        try:
            # share BEFORE allocate: allocation pressure must never
            # evict a page this very admission is about to map.  (It
            # MAY demote a warm page into the tier pool mid-allocate —
            # the pool pins this admission's tier keys below, so the
            # cascade can't drop the very entries about to be
            # promoted.)
            if tier_keys:
                self._kv_pool.pin(tier_keys)
            if hbm_pages:
                self.allocator.share(seq_id, hbm_pages)
            # batch-demote the shortfall up front: one device read for
            # the whole admission instead of one per page in _evict_one
            self._ensure_free(need)
            pages = self.allocator.allocate(seq_id, need)
            fresh = iter(pages)
            row: List[int] = []
            for kind, val in matched:
                if kind == "hbm":
                    row.append(val)
                else:
                    pg = next(fresh)
                    page_map[val] = pg
                    row.append(pg)
            suffix = list(fresh)
            self._table_host[b, :] = self.trash_page
            self._table_host[b, :cm] = row
            self._table_host[b, cm:cm + len(suffix)] = suffix
            self._table_dirty = self._lens_dirty = True
            if self._pc_on:
                (self._c_pc_hits if cm else self._c_pc_misses).inc()
                self._c_pc_cached_tokens.inc(cached)
                self._c_pc_prompt_tokens.inc(T)
            if req.traced:
                # BEFORE the prefill compute below: the trace's
                # admitted→first_token span is the prefill cost
                self.tracer.event("admitted", req.req_id, b, attrs={
                    "cached_tokens": cached,
                    "tier_pages": len(tier_keys),
                    "queue_skips": queue_skips})

            if tier_keys:
                promo = self._begin_promotion(b, tier_keys, page_map)
            if self.prefill_chunk or cached:
                # split-fuse and/or cache-hit admission: the uncached
                # suffix is absorbed in continuation chunks starting at
                # the first uncached token; the slot is not
                # decode-ready until prefill_done reaches T.  (A hit
                # under prefill_chunk=0 absorbs prefill_bucket tokens
                # per iteration.)
                self.slots[b] = _Slot(req=req, seq_len=cached,
                                      generated=[], seq_id=seq_id,
                                      prefill_done=cached, promo=promo)
                self._note_admitted(req)
                return True

            toks = np.full((1, end), 0, np.int32)
            toks[0, :T] = req.tokens
            view = self._row_view(self._table_host[b:b + 1], 0, b)
            self._c_state_fresh.inc(self._state_row is not None)
            if self._tel_on:
                self._row.dispatch("prefill", end, T)
            row, view = self._prefill(
                self.params, self._put(toks), view,
                self._put(np.full((1,), T - 1, np.int32)))
            if self._tel_on:
                self._row.edge()
            self._rows_pending += end
            self.cache = self._adopt(view)

            slot = _Slot(req=req, seq_len=T, generated=[], seq_id=seq_id)
            self.slots[b] = slot
            self._note_admitted(req)
            # the prompt's full pages are immutable from here on
            # (decode writes only at the frontier) — make them
            # matchable now so concurrent same-prefix requests hit
            self._publish_full_pages(b, slot, upto=T)
            # first generated token comes from the REAL last prompt
            # position's row; it is sampled on the device and fetched
            # in the step's one boundary flush
            self._queue_boundary(b, row, slot)
            return True
        except BaseException:
            # an exception between page allocation and slot publish
            # must not leak: fence + cancel any in-flight tier
            # promotion, drop the pins, release every page this seq
            # acquired (shared AND fresh), and clear the table row —
            # then let the caller decide the request's fate
            if self._promo_channel == b:
                # this admission owned the NVMe channel: drain ANY
                # reads it submitted — a presubmit that raised partway
                # (promo never assigned, primed never set) still left
                # in-flight aio ops targeting buffers about to be
                # dropped, and stale fds on the shared channel slot
                try:
                    self._kv_pool.fence_all_reads()
                except Exception:
                    logger.exception(
                        "serving: fence during admission cleanup")
            if page_map:
                # covers a promotion begun partway too (cancel of a
                # never-begun page is a no-op)
                for pg in page_map.values():
                    self.allocator.cancel_promotion(pg)
                if self._promo_channel == b:
                    self._promo_channel = None
                self._g_kvt_inflight.set(len(self.allocator.promoting))
            if tier_keys:
                self._kv_pool.unpin(tier_keys)
            self.allocator.release(seq_id)
            self._table_host[b, :] = self.trash_page
            self._table_dirty = self._lens_dirty = True
            self.slots[b] = None
            self._drop_boundary(b)
            raise

    def _note_admitted(self, req: Request) -> None:
        """Admission succeeded: count it and, with telemetry on, record
        how long the work waited (once a request: a preempted requeue
        that already produced a token is not work newly waiting) and
        mark the edge in a capture under the request's id."""
        self._c_admitted.inc()
        if not self._tel_on:
            return
        self._row.admitted += 1
        if req.t_submit is not None:
            self._h_queue_wait.observe(
                time.perf_counter() - req.t_arrival)
            telemetry_mark(self._mark_admitted,
                           request_id=str(req.req_id))

    def _valid_tokens(self, s: "_Slot") -> int:
        """Positions of slot ``s`` that hold REAL written KV: mid-
        prefill that is the absorbed prefix; once decoding, the prompt
        plus every generated token fed back through decode (the final
        generated token never is, and structural post-EOS chunk writes
        land past this bound — never inside a publishable page)."""
        if s.prefilling:
            return s.prefill_done
        return len(s.req.tokens) + max(len(s.generated) - 1, 0)

    def _publish_full_pages(self, b: int, s: "_Slot",
                            upto: int) -> None:
        """Content-address every full page of slot ``b`` holding tokens
        ``0..upto-1`` (chained keys; idempotent — shared prefix pages
        dedup onto their existing index entries)."""
        if not self._pc_on:
            return
        ps = self.page_size
        full = min(upto, self.max_pages_per_seq * ps) // ps
        if full <= 0:
            return
        if s.req.page_keys is None:
            s.req.page_keys = []
        if len(s.req.page_keys) < full:
            # incremental: only the pages grown since the last event
            # (admission hashed the prompt; finish hashes generated)
            extend_page_keys(s.req.page_keys,
                             s.req.tokens + s.generated, full, ps)
        for slot_idx in range(full):
            page = int(self._table_host[b, slot_idx])
            if page == self.trash_page:
                break
            if page in self.allocator.promoting:
                # in-flight promotion: the payload hasn't landed, so
                # indexing this page now would serve garbage to every
                # future match — finish_promotion publishes it
                continue
            if self.allocator.publish(page, s.req.page_keys[slot_idx]):
                self._c_pc_published.inc()

    # ------------------------------------------------ KV tier: promote
    # dstpu: page-guard-ok: every quarantine lands in page_map first,
    # and the caller (_try_admit)'s BaseException handler cancels each
    # page_map entry, drops the tier pins and releases the seq
    def _begin_promotion(self, b: int, tier_keys: List[bytes],
                         page_map: Dict[bytes, int]) -> _Promotion:
        """Start streaming a tier-matched span back into the fresh HBM
        pages just allocated for it.  The reader's group-0 reads are
        presubmitted HERE (admission time) when the aio priority group
        allows, so NVMe latency overlaps every step the engine runs
        before this slot's first suffix-prefill chunk; the upload
        itself happens in :meth:`_complete_promotion`, batched per
        group, double-buffered against the next group's reads."""
        from deepspeed_tpu.param_stream import TierPageReader

        for key, pg in page_map.items():
            self.allocator.begin_promotion(pg, key)
        # pinned entries can neither drop nor spill, so a promotion
        # whose keys are all host-resident stays channel-free: it
        # reads through the pool's no-op-fencing host view and any
        # number may be in flight.  Only an NVMe-backed promotion
        # claims the single aio channel (and only it may fence or
        # slot-toggle that channel).
        channel = any(self._kv_pool.location(k) == "nvme"
                      for k in tier_keys)
        reader = TierPageReader(
            self._kv_pool if channel else self._kv_pool.host_view(),
            tier_keys, to_device=None,
            group_pages=self.kv_tier.promote_group_pages,
            registry=self.registry, tracer=self.tracer,
            retries=self.kv_tier.io_retries,
            retry_backoff_s=self.kv_tier.io_retry_backoff_s)
        # bound late: the callback needs the reader's own group table
        reader.to_device = lambda bufs, g: self._promote_group(
            page_map, bufs, reader.group_keys(g))
        promo = _Promotion(keys=list(tier_keys), page_map=page_map,
                           reader=reader, channel=channel,
                           t_start=time.perf_counter())
        if channel:
            self._promo_channel = b
        # host-resident presubmit is pure dict lookups — never defer
        # it on aio priority; only NVMe reads yield to weight streams
        if not channel or self._kv_pool.may_submit():
            promo.primed = reader.presubmit(0)
        self._g_kvt_inflight.set(len(self.allocator.promoting))
        return promo

    def _promotion_ready(self, b: int, s: "_Slot") -> bool:
        """Gate for the promoting slot's prefill: defer (bounded) while
        the tier reads are still in flight — the promotion then hides
        under other slots' compute — or while aio priority asks KV to
        yield to layer-weight streams; once ready (or at the deferral
        cap), drain the promotion and let prefill proceed."""
        p = s.promo
        if p.primed is None:
            if self._kv_pool.may_submit() or \
                    p.deferred >= _KV_PROMO_DEFER_CAP:
                p.primed = p.reader.presubmit(0)
            else:
                p.deferred += 1
                self._c_kvt_deferrals.inc()
                return False
        # only the channel owner's reads are on the aio queue — a
        # host-resident promotion's buffers fenced for free at
        # presubmit, so it never defers on another slot's reads
        if p.channel and self._kv_pool.reads_pending() and \
                p.deferred < _KV_PROMO_DEFER_CAP:
            p.deferred += 1
            self._c_kvt_deferrals.inc()
            return False
        self._complete_promotion(b, s)
        return True

    def _complete_promotion(self, b: int, s: "_Slot") -> None:
        """Drain the slot's promotion: every group fences, dequantizes
        and scatters into its target pages (group g+1's tier reads in
        flight while group g uploads), then the pages publish under
        their content keys — matchable for concurrent admissions.

        Graceful degradation: the reader already retried transient aio
        errors and tried the synchronous fallback; whatever still
        escapes (a checksum mismatch, an unrecoverable read) abandons
        the promotion and falls back to re-prefilling the unlanded
        span — correctness preserved, the DMA saving lost."""
        p = s.promo
        try:
            for _ in p.reader.sweep(range(p.reader.n_groups),
                                    primed=p.primed):
                pass
        except Exception as e:
            self._promotion_fallback(b, s, e)
            return
        self._kvt_fault_streak = 0
        dt = time.perf_counter() - p.t_start
        self._h_kvt_promote.observe(dt)
        self._kv_pool.unpin(p.keys)
        if s.req.traced:
            self.tracer.event("kv_promote", s.req.req_id, b, attrs={
                "pages": len(p.keys), "wait_s": round(dt, 6),
                "deferred_steps": p.deferred})
        s.promo = None
        if p.channel and self._promo_channel == b:
            self._promo_channel = None
        self._g_kvt_inflight.set(len(self.allocator.promoting))

    def _promotion_fallback(self, b: int, s: "_Slot",
                            exc: BaseException) -> None:
        """Abandon a failed promotion and re-prefill the span it was
        supposed to stream (ISSUE acceptance: promote failure or
        checksum mismatch must cost compute, never correctness).

        Groups land in page order, so landed pages (already published)
        form a contiguous prefix; everything from the first unlanded
        page onward rolls back: its allocator quarantine is cancelled
        (the pages stay owned — prefill writes them now), its suspect
        tier entries drop from the pool, and the slot's absorbed
        prefix retreats to the first unlanded page boundary.  Repeated
        failures trip the tier circuit breaker
        (``kv_tier.disable_after``)."""
        p = s.promo
        try:
            self._kv_pool.fence_all_reads()
        except Exception:
            pass                    # the channel may be the failure
        unlanded = [(key, pg) for key, pg in p.page_map.items()
                    if pg in self.allocator.promoting]
        self._kv_pool.unpin(p.keys)
        for key, pg in unlanded:
            self.allocator.cancel_promotion(pg)
            # the payload is suspect (failed read or corrupt) — a
            # future admission must re-prefill, not re-promote it.
            # UNLESS a concurrent promotion still pins the key: its
            # reads are in flight against this entry, so it must keep
            # resolving (it will hit the same checksum and run its own
            # fallback, which then drops the entry)
            if key not in self._kv_pool._pinned:
                self._kv_pool.discard(key)
        if unlanded:
            # roll the absorbed prefix back to the first unlanded
            # page: everything before it (HBM-shared + landed
            # promotions) is intact history the continuation chunks
            # attend over
            row = [int(x) for x in self._table_host[b]]
            first_bad = min(row.index(pg) for _k, pg in unlanded)
            fb_tokens = first_bad * self.page_size
            s.prefill_done = min(s.prefill_done, fb_tokens)
            s.seq_len = min(s.seq_len, fb_tokens)
        else:
            fb_tokens = s.prefill_done
        if isinstance(exc, ChecksumError):
            self._c_kvt_checksum.inc()
            self._n_kvt_checksum += 1
        self._c_kvt_fb_events.inc()
        self._c_kvt_fb_pages.inc(len(unlanded))
        self._n_kvt_fallbacks += 1
        logger.warning(
            "serving: KV-tier promotion failed for request %r "
            "(%s) — re-prefilling %d pages from token %d",
            s.req.req_id, exc, len(unlanded), fb_tokens)
        if self._trace_on:
            self.tracer.event("kv_promote_failed", s.req.req_id, b,
                              attrs={"error": repr(exc)[:200],
                                     "pages": len(unlanded),
                                     "resume_token": fb_tokens})
        s.promo = None
        if p.channel and self._promo_channel == b:
            self._promo_channel = None
        self._g_kvt_inflight.set(len(self.allocator.promoting))
        # circuit breaker: repeated promote failures disable the tier
        # (demotes become evictions, hits become misses) — /healthz
        # reports degraded, the router routes around
        self._kvt_fault_streak += 1
        da = self.kv_tier.disable_after
        if da and self._kvt_fault_streak >= da and \
                self._kv_pool.disabled is None:
            self._kv_pool.disable(
                f"{self._kvt_fault_streak} consecutive promotion "
                "failures")

    def _promote_group(self, page_map: Dict[bytes, int], bufs,
                       g_keys) -> List[int]:
        """TierPageReader ``to_device``: one fenced GROUP of spilled
        pages → decode (dequantize cold pages) → one batched scatter
        into the target HBM pages → publish."""
        i = 0
        if self._quant_resident:
            # int8-resident publish: the entry's codes + scales go to
            # the device VERBATIM — no dequantize on the host, no
            # dense scatter, and (because decode_quantized still
            # verifies the stored checksums first) the same corruption
            # guarantees as the dense path
            pages, kqs, kss, vqs, vss = [], [], [], [], []
            for key in g_keys:
                names, _shapes, _dtypes = self._kv_pool.entry_meta(key)
                take = bufs[i:i + len(names)]
                i += len(names)
                kq, ks_, vq, vs_ = self._kv_pool.decode_quantized(
                    key, take)
                kqs.append(kq)
                kss.append(ks_)
                vqs.append(vq)
                vss.append(vs_)
                pages.append(page_map[key])
            self._upload_promoted_q(
                pages, np.stack(kqs, axis=2), np.stack(kss, axis=2),
                np.stack(vqs, axis=2), np.stack(vss, axis=2))
            self._c_kvt_qres_promotes.inc(len(g_keys))
        else:
            pages, ks, vs = [], [], []
            for key in g_keys:
                names, _shapes, _dtypes = self._kv_pool.entry_meta(key)
                take = bufs[i:i + len(names)]
                i += len(names)
                k, v = self._kv_pool.decode(key, take)
                ks.append(k)
                vs.append(v)
                pages.append(page_map[key])
            self._upload_promoted(pages, np.stack(ks, axis=2),
                                  np.stack(vs, axis=2))
        for key, pg in zip(g_keys, pages):
            if self.allocator.finish_promotion(pg, key):
                self._c_pc_published.inc()
        self._c_kvt_promoted.inc(len(g_keys))
        return pages

    def _cancel_promotion(self, s: "_Slot") -> None:
        """Abandon a slot's in-flight promotion (preemption): fence any
        outstanding tier reads (they target host buffers about to be
        dropped), release the allocator quarantine, and let the pages
        free through the normal release path.  The spill entries stay
        — the recompute requeue will hit and promote them again."""
        p = s.promo
        if p is None:
            return
        if p.channel:
            # regardless of `primed`: a presubmit that raised partway
            # may have submitted reads without ever assigning it —
            # drain whatever is on the channel (free when nothing is)
            try:
                self._kv_pool.fence_all_reads()
            except Exception:
                # a failing drain must never abort the cancel — the
                # quarantine/pin/channel cleanup below is what keeps
                # the engine admitting
                logger.exception("serving: promotion-cancel fence")
        for pg in p.page_map.values():
            self.allocator.cancel_promotion(pg)
        self._kv_pool.unpin(p.keys)
        s.promo = None
        if p.channel and self._promo_channel is not None:
            self._promo_channel = None
        self._g_kvt_inflight.set(len(self.allocator.promoting))

    # ------------------------------------------------- KV tier: demote
    def _fetch_idx(self, pages: List[int]):
        """Bucket a page-id list to a power-of-two length (repeating
        the last id) so the eager gather/scatter ops below compile a
        BOUNDED set of shapes — a churning cache must not pay one XLA
        compile per distinct batch size."""
        n = len(pages)
        cap = 1
        while cap < n:
            cap *= 2
        return np.asarray(list(pages) + [pages[-1]] * (cap - n),
                          np.int32), n

    def _fetch_pages_host(self, pages: List[int]):
        """Device→host copy of whole pages across the layer stack:
        ``[L, KV, n, ps, Dh]`` (k, v).  The ZI engine overrides for its
        per-layer cache tuples."""
        idx, n = self._fetch_idx(pages)
        k, v = jax.device_get((self.cache.k[:, :, idx],
                               self.cache.v[:, :, idx]))
        return np.asarray(k)[:, :, :n], np.asarray(v)[:, :, :n]

    def _fetch_pages_host_q(self, pages: List[int]):
        """Quantized-resident twin of :meth:`_fetch_pages_host`: ONE
        device→host transfer of the int8 codes + f32 scales —
        ``(kq [L, KV, n, ps, Dh] i8, ks [L, KV, n, ps, 1] f32, vq,
        vs)`` — so a demotion captures the page VERBATIM (no dequant,
        no requantize, no extra rounding)."""
        idx, n = self._fetch_idx(pages)
        c = self.cache
        kq, ks, vq, vs = jax.device_get(
            (c.k[:, :, idx], c.k_scale[:, :, idx],
             c.v[:, :, idx], c.v_scale[:, :, idx]))
        return (np.asarray(kq)[:, :, :n], np.asarray(ks)[:, :, :n],
                np.asarray(vq)[:, :, :n], np.asarray(vs)[:, :, :n])

    def _promote_idx(self, pages: List[int], *arrays):
        """Pad a promotion scatter to the FIXED promote group size:
        pad lanes aim one past the page array and drop (the
        ``write_token_pages`` trick), so every group — full, tail, or
        short chain — runs the same compiled update."""
        G = max(self.kv_tier.promote_group_pages, len(pages))
        pad = G - len(pages)
        idx = np.asarray(list(pages) + [self.trash_page + 1] * pad,
                         np.int32)
        if pad:
            arrays = tuple(
                np.concatenate(
                    [a, np.zeros(a.shape[:2] + (pad,) + a.shape[3:],
                                 a.dtype)], axis=2)
                for a in arrays)
        return (jnp.asarray(idx),) + tuple(arrays)

    def _upload_promoted(self, pages: List[int], k_host, v_host) -> None:
        """Scatter promoted payloads (``[L, KV, n, ps, Dh]``) into
        their target pages.  One dispatch per array; jax's async
        dispatch overlaps the H2D DMA with whatever device work is in
        flight, and the first forward reading these pages orders after
        the update through the value dependency."""
        idx, k_host, v_host = self._promote_idx(pages, k_host, v_host)
        self.cache = self.cache._replace(
            k=self.cache.k.at[:, :, idx].set(
                self._put(jnp.asarray(k_host)), mode="drop"),
            v=self.cache.v.at[:, :, idx].set(
                self._put(jnp.asarray(v_host)), mode="drop"))

    def _upload_promoted_q(self, pages: List[int], kq, ks,
                           vq, vs) -> None:
        """Quantized-resident promote scatter: the cold entry's int8
        codes + scales land in the device planes DIRECTLY — the dense
        path's dequantize (host) + wide scatter never runs, which is
        the point of ``kv_tier.quantized_resident`` (the page is also
        4x smaller on the H2D wire than its f32 decode)."""
        idx, kq, ks, vq, vs = self._promote_idx(pages, kq, ks, vq, vs)
        c = self.cache
        self.cache = c._replace(
            k=c.k.at[:, :, idx].set(
                self._put(jnp.asarray(kq)), mode="drop"),
            k_scale=c.k_scale.at[:, :, idx].set(
                self._put(jnp.asarray(ks)), mode="drop"),
            v=c.v.at[:, :, idx].set(
                self._put(jnp.asarray(vq)), mode="drop"),
            v_scale=c.v_scale.at[:, :, idx].set(
                self._put(jnp.asarray(vs)), mode="drop"))

    def _demote_for_evict(self, page: int, key: bytes) -> bool:
        """``PageAllocator.demote_hook``: capture an evicted warm
        page's KV to the tier pool.  A span whose payload is already
        spilled (promoted earlier, evicted again) re-demotes for free —
        no device read, no copy."""
        pool = self._kv_pool
        if pool is None:
            return False
        if pool.has(key):
            pool.touch(key)
            self._c_kvt_demoted.inc()
            return True
        if self._quant_resident:
            kq, ks, vq, vs = self._fetch_pages_host_q([page])
            loc = pool.demote_prequantized(
                key, kq[:, :, 0], ks[:, :, 0], vq[:, :, 0], vs[:, :, 0])
        else:
            k, v = self._fetch_pages_host([page])
            loc = pool.demote(key, k[:, :, 0], v[:, :, 0])
        if loc is None:
            return False
        self._c_kvt_demoted.inc()
        if self._trace_on:
            self.tracer.event("kv_demote", attrs={
                "key": key_hex(key)[:12], "tier": loc})
        return True

    def _demote_warm_batch(self, cands) -> None:
        """Demote a batch of warm ``(page, key)`` candidates with ONE
        batched device→host read (pages whose spans are already
        spilled just refresh their age), then reclaim them to the free
        list.  Shared by the watermark sweep and the pre-allocation
        top-up — the per-page ``_evict_one`` hook stays only as the
        fallback for pressure neither anticipated."""
        al = self.allocator
        fresh = [(p, k) for p, k in cands if not self._kv_pool.has(k)]
        if fresh:
            # fetch in precompiled-bucket chunks: a big watermark sweep
            # over the whole warm pool must not trigger a fresh gather
            # compile inside the serving step.  The quantized-resident
            # fetch returns 4 component arrays (codes + scales); the
            # dense one 2 — zip/concat handles both.
            cap = self._kvt_fetch_cap
            parts = []
            for i in range(0, len(fresh), cap):
                pg = [p for p, _ in fresh[i:i + cap]]
                parts.append(self._fetch_pages_host_q(pg)
                             if self._quant_resident
                             else self._fetch_pages_host(pg))
            bufs = tuple(np.concatenate(comp, axis=2)
                         for comp in zip(*parts))
        at = {p: i for i, (p, _) in enumerate(fresh)}
        demoted, dropped = [], []
        for p, key in cands:
            if p in at:
                i = at[p]
                page = tuple(a[:, :, i] for a in bufs)
                loc = (self._kv_pool.demote_prequantized(key, *page)
                       if self._quant_resident
                       else self._kv_pool.demote(key, *page))
            else:
                loc = self._kv_pool.touch(key)
            (demoted if loc else dropped).append(p)
        al.reclaim_warm(demoted, demoted=True)
        al.reclaim_warm(dropped, demoted=False)
        if demoted:
            self._c_kvt_demoted.inc(len(demoted))
            if self._trace_on:
                self.tracer.event("kv_demote", attrs={
                    "pages": len(demoted)})

    def _ensure_free(self, n: int) -> None:
        """Top the free list up to ``n`` pages by batch-demoting the
        oldest warm pages BEFORE an allocation dips into the warm
        pool — one batched device read per shortfall instead of one
        synchronous per-page copy inside each ``_evict_one``."""
        if not self._kvt_on:
            return
        al = self.allocator
        short = n - len(al.free)
        if short <= 0:
            return
        cands = al.oldest_warm(short)
        if cands:
            self._demote_warm_batch(cands)

    def _demote_watermark_sweep(self) -> None:
        """Proactive demotion: when the warm pool fills past the
        ``demote_watermark`` fraction of its cap, the oldest warm pages
        demote in ONE batched device→host read — freeing HBM pages
        ahead of allocation pressure so admissions stop paying the
        per-eviction copy on their own critical path."""
        al = self.allocator
        excess = len(al.pool) - self._kvt_wm_pages
        if excess <= 0:
            return
        self._demote_warm_batch(al.oldest_warm(excess))

    # dstpu: hot-path
    def _advance_prefill(self, b: int, s: "_Slot") -> None:
        """Absorb the next chunk of slot ``b``'s prompt (one fixed-shape
        continuation forward: history + chunk).  On the final chunk,
        sample the first generated token from the last REAL prompt
        position and flip the slot decode-ready.

        Chunk size is ``prefill_chunk`` under split-fuse; a cache-hit
        admission with ``prefill_chunk=0`` absorbs its uncached suffix
        ``prefill_bucket`` tokens per iteration through the same path
        (history = the shared cached pages).  A slot with an in-flight
        tier promotion must not run its first chunk before the promoted
        pages land (the chunk attends over them); it defers — bounded —
        while the reads are still in flight, hiding the promotion under
        the other slots' compute in the same scheduler iteration."""
        if s.promo is not None and not self._promotion_ready(b, s):
            return
        C = self.prefill_chunk or self.prefill_bucket
        T = len(s.req.tokens)
        done = s.prefill_done
        take = min(C, T - done)
        toks = np.zeros((1, C), np.int32)
        toks[0, :take] = s.req.tokens[done:done + take]
        # slice the view table to the live history: the chunk attention
        # gathers every page the table names, so handing it the full
        # max_seq row would make each chunk cost O(max_seq) rather than
        # O(done + C).  Power-of-two bucketing bounds the compile count.
        np_live = -(-(done + C) // self.page_size)
        np_bkt = 1
        while np_bkt < np_live:
            np_bkt *= 2
        # (no pool: no page is read, and the one program takes the row)
        np_bkt = (min(np_bkt, self.max_pages_per_seq) if self._pooled
                  else self.max_pages_per_seq)
        view = self._row_view(self._table_host[b:b + 1, :np_bkt], done, b)
        # a chunk that starts at position 0 starts the slot's recurrent
        # state from zero, whatever the slot held
        self._c_state_fresh.inc(self._state_row is not None and done == 0)
        if self._tel_on:
            self._row.dispatch("chunk", C, take)
        row, view = self._chunk_prefill(
            self.params, self._put(toks), view,
            self._put(np.full((1,), take - 1, np.int32)))
        if self._tel_on:
            self._row.edge()
        self._rows_pending += C
        self.cache = self._adopt(view)
        s.prefill_done = done + take
        s.seq_len = s.prefill_done
        self._c_prefill_chunks.inc()
        self._c_chunk_rows.inc(take)
        self._c_tail_rows.inc(1 if self.tail_cut else take)
        if s.req.traced:
            self.tracer.event("prefill_chunk", s.req.req_id, b, attrs={
                "done": s.prefill_done, "of": T, "take": take})
        if s.prefill_done >= T:
            s.prefill_done = -1
            # decode-ready: the device table/lens row must flip from
            # trash to the real pages before the next decode
            self._table_dirty = self._lens_dirty = True
            # prompt pages are full and immutable now — make them
            # matchable before the first token can finish the request
            self._publish_full_pages(b, s, upto=T)
            self._queue_boundary(b, row, s)

    def _preempt_youngest(self) -> None:
        """vLLM-style recompute preemption: release the youngest slot's
        pages and requeue prompt+generated as a fresh request."""
        cand = [(len(s.generated), b) for b, s in enumerate(self.slots)
                if s is not None]
        if not cand:
            raise MemoryError("out of KV pages with no slot to preempt")
        _, b = min(cand)
        s = self.slots[b]
        logger.warning("serving: preempting request %r (%d generated)",
                       s.req.req_id, len(s.generated))
        # publish-then-release: the victim's full pages stay matchable
        # in the warm pool, so its recompute-from-scratch requeue (and
        # any same-prefix request) re-admits against its own cached
        # prefix — preemption releases REFERENCES, not page contents
        self._publish_full_pages(b, s, upto=self._valid_tokens(s))
        # promotion pages were skipped by the publish guard above; now
        # fence + abandon the in-flight transfer before release frees
        # them (the spill entries survive for the recompute to re-hit)
        if s.promo is not None:
            self._cancel_promotion(s)
        self.allocator.release(s.seq_id)
        self._table_host[b, :] = self.trash_page
        self._table_dirty = self._lens_dirty = True
        self.slots[b] = None
        self._drop_boundary(b)
        req = s.req
        if req.traced:
            self.tracer.event("preempt", req.req_id, b, attrs={
                "generated": len(s.generated)})
        # requeue prompt+generated for recompute; the finished output is
        # simply tokens+generated of the FINAL incarnation, which already
        # contains everything produced before preemption
        # NOT re-announced to the SLO tracker: its record (and with it
        # the original arrival time) survives under the same req_id, so
        # the recompute is judged against the user's real clock
        self.queue.appendleft(Request(
            req.req_id, req.tokens + s.generated,
            req.max_new_tokens - len(s.generated), req.temperature,
            t_submit=req.t_submit, page_keys=req.page_keys,
            traced=req.traced, first_token_seen=req.first_token_seen,
            t_arrival=req.t_arrival, tier=req.tier))
        self._c_preempted.inc()
        if self._tel_on:
            self._row.preempted += 1
        if req.traced:
            self.tracer.event("requeue", req.req_id)

    def _drop_boundary(self, b: int) -> None:
        """Slot ``b`` was vacated before the flush: its queued boundary
        token would be appended to a dead request (or index the vacated
        slot)."""
        self._pending_boundary = [p for p in self._pending_boundary
                                  if p[0] != b]

    def _queue_boundary(self, b: int, row, slot: _Slot) -> None:
        """Sample slot ``b``'s first token from the last-position logits
        ``row`` its prefill returned — on the device, under the key its
        admission ordinal (``seq_id``) folds to — and hold the ``[1]``
        token array for the step's one boundary fetch."""
        tok = self._boundary(
            row, self._key,
            self._put(np.full((), (2 * slot.seq_id + 1) & 0x7FFFFFFF,
                              np.int32)),
            self._put(np.full((1,), slot.req.temperature, np.float32)))
        self._pending_boundary.append((b, tok))

    # dstpu: hot-path
    def _flush_boundary(self) -> None:
        if not self._pending_boundary:
            return
        pend, self._pending_boundary = self._pending_boundary, []
        # dstpu: host-sync-ok: boundary token fetch, one transfer per
        # step for every prefill completion (each token was sampled on
        # the device when its prefill was dispatched; nothing is
        # dispatched here)
        toks = jax.device_get([tok for _, tok in pend])
        if self._tel_on:
            # every program dispatched so far has run: the device has
            # nothing queued until the next dispatch call
            self._row.drained = time.perf_counter()
            self._row.boundary += len(pend)
        self._c_boundary_syncs.inc()
        self._c_boundary_tokens.inc(len(pend))
        for (b, _), tok in zip(pend, toks):
            self._append_token(b, int(tok[0]))

    # dstpu: hot-path
    def _append_token(self, b: int, tok: int) -> None:
        if self._devprof_on and not self.devprof.steady:
            # first token of the FIRST request: everything before this
            # is warmup compilation; every compile after is steady-state
            # (and trips the incident probe + bench gate)
            self.devprof.mark_steady()
        s = self.slots[b]
        s.generated.append(tok)
        if self._tel_on or self._slo_on:
            # ONE clock read shared by the TTFT/ITL histograms and the
            # SLO tracker — the slo-on-top-of-telemetry cost is a dict
            # hit, not a second perf_counter
            now = time.perf_counter()
            if self._tel_on:
                if s.req.t_submit is not None:
                    self._h_ttft.observe(now - s.req.t_submit)
                    s.req.t_submit = None  # once per request lifetime
                    telemetry_mark(self._mark_first_token,
                                   request_id=str(s.req.req_id))
                elif s.last_tok_t:
                    self._h_itl.observe(now - s.last_tok_t)
                s.last_tok_t = now
            if self._slo_on:
                self.slo_tracker.on_token(s.req.req_id, now=now)
        if s.req.traced and not s.req.first_token_seen:
            # adjacent to the TTFT observation above so the trace's
            # queued→first_token delta agrees with the histogram
            s.req.first_token_seen = True
            self.tracer.event("first_token", s.req.req_id, b)
        done = (self.eos is not None and tok == self.eos) or \
            len(s.generated) >= s.req.max_new_tokens
        if done:
            self.finished[s.req.req_id] = list(s.req.tokens) + s.generated
            self._newly_finished.append(s.req.req_id)
            if self._slo_on:
                # classify against the tier objectives NOW: attainment,
                # burn rates and goodput update; a burn trip fires the
                # alert into the flight recorder
                self.slo_tracker.on_finish(s.req.req_id)
            if s.req.traced:
                self.tracer.event("finish", s.req.req_id, b, attrs={
                    "generated": len(s.generated),
                    "total_tokens": len(s.req.tokens) + len(s.generated)})
            # publish-then-release: the finished request's full pages
            # (prompt AND generated history — the multi-turn prefix of
            # a follow-up request) enter the warm pool matchable, and
            # are reclaimed only under allocation pressure
            self._publish_full_pages(b, s, upto=self._valid_tokens(s))
            self.allocator.release(s.seq_id)
            self._table_host[b, :] = self.trash_page
            self._table_dirty = self._lens_dirty = True
            self.slots[b] = None

    # dstpu: hot-path
    def _grow_pages(self, ahead: int = 1,
                    preempt: bool = True) -> bool:
        """Before decode writes: map every page the next ``ahead`` token
        positions will touch (chunked decode provisions its whole window
        up front); preempt when the pool is dry.  Positions past the
        request's lifetime are NOT provisioned — their garbage writes
        clamp into the sequence's own final page, which is released when
        it finishes.  ``preempt=False`` is how a step in flight asks (no
        row may leave under it, and ``seq_len`` counts it already): a dry
        pool ends the walk with False and what it mapped stays mapped."""
        ps = self.page_size
        for b, s in enumerate(self.slots if self._pooled else ()):
            if s is None or s.prefilling:
                # chunk writes land in the pages reserved at admission
                continue
            lifetime = len(s.req.tokens) + s.req.max_new_tokens
            # last KV write is at lifetime-2: the final generated token is
            # appended to the output but never fed back through decode
            last_pos = min(s.seq_len + ahead - 1, lifetime - 2,
                           self.max_pages_per_seq * ps - 1)
            for slot_idx in range(s.seq_len // ps, last_pos // ps + 1):
                if self._table_host[b, slot_idx] != self.trash_page:
                    continue
                # available counts the warm pool: allocate reclaims
                # cached pages before any preemption is considered
                while not self.allocator.available:
                    if not preempt:
                        return False
                    self._preempt_youngest()
                    if self.slots[b] is None:   # we preempted ourselves
                        break
                if self.slots[b] is None:
                    break
                self._ensure_free(1)
                # dstpu: page-guard-ok: allocate records the page in
                # owned[seq_id] atomically, so _fail_slot / preemption
                # / fleet abandon_inflight release it with the seq —
                # there is no owned-but-untracked window here
                pg = self.allocator.allocate(s.seq_id, 1)[0]
                self._table_host[b, slot_idx] = pg
                self._table_dirty = True
        return True

    # ------------------------------------------------------------------ step
    def _step_row(self) -> StepRow:
        """This engine's pen in the step ledger: a row's phases are
        these spans' own clock readings, in ``devprof.STEP_PHASES``'
        order.  (Not inline in the constructor: its frame's words are
        pinned, see ``tests/test_devprof.py``.)"""
        return StepRow(self.registry.namespace, self._sp_step,
                       self._sp_tick, (
                           self._sp_admit, self._sp_prefill,
                           self._sp_boundary, self._sp_grow,
                           self._sp_upload, self._sp_inputs,
                           self._sp_dispatch, self._sp_token_sync,
                           self._sp_append))

    def step(self) -> List[Any]:
        """One scheduling iteration: admit → batched decode.  Returns
        request ids that finished during this step."""
        self._newly_finished = []
        self._last_step_t = time.perf_counter()   # /healthz heartbeat
        if self._tel_on:
            # span: wall time into serving_step_seconds + a
            # TraceAnnotation so captured device timelines show the
            # scheduler iteration, under the ordinal its row in the
            # step ledger has
            with self._sp_step(n=self._row.begin(len(self.queue))):
                self._step_inner()
            with self._sp_tick:
                self._tick()
            self._row.end(self.decode_chunk)
        else:
            self._step_inner()
            if self._tick_inline:
                self.incident_mgr.maybe_evaluate()
            self._slo_refresh()
        return list(self._newly_finished)

    def _tick(self) -> None:
        """The timed control plane after an iteration (telemetry on)."""
        if self._tel_exporter is not None:
            # one monotonic read drives the WHOLE timed control
            # plane: sink exports plus the tick hooks (SLO window
            # refresh, history sampling, incident evaluation)
            self._tel_exporter.maybe_export()
        elif self._tick_inline:
            # no exporter (telemetry= was a bare registry — the
            # fleet-replica pattern): drive the same pass inline
            now = time.monotonic()
            self.history.maybe_sample(now)
            self.incident_mgr.maybe_evaluate(now)
        self._slo_refresh()

    def _slo_refresh(self) -> None:
        if self._slo_on and not self._slo_tick_hooked:
            # time-driven window refresh (rate-limited to ~1/s inside):
            # an idle engine's burn gauges must decay as violations age
            # out, not stay latched at their last finish-time values.
            # (With an exporter this runs as a tick hook instead.)
            self.slo_tracker.maybe_refresh()

    # dstpu: hot-path
    def _step_inner(self) -> None:
        # What the host could not know when it let a step fly (a row
        # that ended on its own, rows a router changed between two
        # calls) finds that step in flight: it lands first, and the
        # call goes on as it always has, over the state it has always
        # seen.  An arrival beside a free slot and a prompt's last chunk
        # land nothing where the boundary token can join the next decode
        # on the device (_may_join): their programs take the cache the
        # step in flight returns, so the device runs them behind it.
        landed = self._flying is not None and self._land_first(
            self._why_sync(self._flying.rows))
        with self._sp_admit:
            if self._shed_deadline and self.queue:
                # BEFORE admission: a request whose deadline already
                # expired must shed, not burn a slot on unwanted work
                self._shed_expired()
            if self._kvt_wm_pages is not None:
                # BEFORE admission: proactively demoting past the
                # watermark frees pages the admissions below can use
                # without paying a per-eviction device read each
                self._demote_watermark_sweep()
            while self._admit_one():
                pass
        # split-fuse: absorb ONE chunk per pending-prefill slot, then
        # run the batched decode for every ready slot in the same
        # iteration.  Failure isolation: an exception in one slot's
        # host-side work (including injected `slot` faults) fails THAT
        # request and releases its resources; the others keep serving.
        with self._sp_prefill:
            for b, s in list(enumerate(self.slots)):
                if s is not None and s.prefilling:
                    try:
                        if self._fault_plan is not None:
                            faults_mod.inject("slot", key=s.req.req_id)
                        self._advance_prefill(b, s)
                    except faults_mod.FatalStreamError:
                        raise    # dead WEIGHT stream: engine-fatal, not
                    except Exception as e:       # a per-request failure
                        self._fail_slot(b, e)
            if self._fault_plan is not None:
                # decode-ready slots get the same per-step injection
                # opportunity (a request that skipped chunked prefill
                # would otherwise be untargetable)
                for b, s in enumerate(self.slots):
                    if s is not None and not s.prefilling:
                        try:
                            faults_mod.inject("slot", key=s.req.req_id)
                        except InjectedFault as e:
                            self._fail_slot(b, e)
        K = self.decode_chunk
        # every prompt that finished prefilling this step has its
        # boundary token on the device.  Where the rule lets them and
        # the pool maps the step's pages without preempting, they join
        # this call's decode there (``joins``); else the parent's
        # sequence: a step still in flight lands, and ONE batched fetch
        # appends them before the decode phase reads generated[-1]
        with self._sp_boundary:
            joins = self._pending_boundary
            if joins and self._joins() and self._grow_pages(
                    ahead=K, preempt=False):
                self._pending_boundary = []
                for b, tok in joins:
                    self.slots[b].boundary = tok
            elif joins:
                if self._flying is not None:
                    landed = self._land_first("boundary", newest=False)
                self._flush_boundary()
                joins = []
        # the speculative sweep writes K_draft+1 positions per slot —
        # provision its whole window, like chunked decode does
        ahead = (self.speculative.draft_tokens + 1 if self._spec_on
                 else K)
        ready = lambda: [(b, s) for b, s in enumerate(self.slots)
                         if s is not None and not s.prefilling]
        active, flying = ready(), self._flying
        # the rows the step in flight left with: these, less the rows
        # that join the step behind it
        joining = dict(joins)
        stay = [r for r in active if r[0] not in joining] \
            if joins else active
        if flying is not None and (len(stay) != len(flying.rows) or any(
                s is not t for (_, s), (_, t) in zip(stay, flying.rows))):
            # not the rows it flew with (a router or a test admitted
            # between two calls): it lands, as at the top
            landed, flying = self._land_first("other", newest=False), None
        # ``why``: what keeps the step after the one this call lands on
        # the ground (None: its rows are known to be these rows)
        why = "other"
        with self._sp_grow:
            if active and flying is None:
                self._grow_pages(ahead=ahead)
                active = ready()
            if joins and flying is not None:
                # the step that joins IS the step behind the one in
                # flight (its pages were mapped above)
                why = None
            elif active and not landed and not self._spec_on:
                why = self._why_behind(active, K)
                if why is None and not self._grow_pages(
                        ahead=K if flying else 2 * K, preempt=False):
                    why = "other"
            if self._tel_on:
                self._set_step_gauges(len(active))
        if active and self._spec_on:
            self._spec_step(active)
        elif active:
            with self._sp_upload:
                self._upload_dirty()
            fresh = flying is None
            if not fresh:
                toks_d, temps_d = flying.out, flying.temps
            if fresh or joins:
                with self._sp_inputs:
                    # the tokens in the form the program's output has:
                    # a row's newest is the last of its K (a row that
                    # joins has its own written there on the device)
                    kf = K if self._out_shape else 1
                    toks = np.zeros(self._out_shape or (self.max_batch, 1),
                                    np.int32)
                    temps = np.zeros((self.max_batch,), np.float32)
                    newest = toks.reshape(-1)
                    for b, s in active:
                        newest[b * kf + kf - 1] = s.generated[-1] \
                            if s.generated else s.req.tokens[-1]
                        temps[b] = s.req.temperature
                    if fresh:
                        toks_d = self._put(toks)
                    temps_d = self._put(temps)
                    for b, tok in joins:
                        toks_d = self._join(toks_d, tok, self._put(
                            np.full((), b * kf + kf - 1, np.int32)))
                    if joins:
                        self._c_boundary_joined.inc(len(joins))
                        if self._tel_on:
                            self._row.boundary += len(joins)
            with self._sp_dispatch:
                if fresh:
                    self._behind[self._behind_why] += 1
                    flying = self._dispatch_decode(
                        "decode", active, toks_d, temps_d)
                    toks_d = flying.out
                nxt = None
                if landed and self._fault_plan is None:
                    # one call, one decode's tokens: this one's are the
                    # next call's, and the caller's turn is in its shadow
                    flying, nxt = None, flying
                elif why is None:
                    self._c_decode_ahead.inc()
                    nxt = self._dispatch_decode(
                        "decode_ahead", active, toks_d, temps_d)
                else:
                    self._behind_why = why
            self._flying = nxt
            if flying is not None:
                self._land(flying, newest=nxt is None and (
                    fresh or not (self._tel_on and self._row.dispatched)))

    # dstpu: hot-path
    def _dispatch_decode(self, site: str, rows, prev, temps_d):
        """Hand the device the ONE decode program over ``rows``, its
        token operand ``prev`` in the form the program's output has (up
        from the host, or the output of the step before it, still on the
        device); what the host counts advances here, at dispatch.
        Returns the step as ``_flying`` holds it."""
        K = self.decode_chunk
        if self._tel_on:
            self._row.dispatch(site, self.max_batch, len(rows))
        n = self._n_dispatch
        out, self.cache = self._decode_chunk_fn(
            self.params, prev, self.cache, self._key,
            self._next_dispatch(), temps_d)
        # trust the decode's structural seq_lens+K between composition
        # changes (inactive rows drift, rebuilt on the next dirty upload)
        for _, s in rows:
            s.seq_len += K
        self._c_decode_steps.inc(K)
        self._c_decode_syncs.inc()
        if self._state_row is not None:
            self._c_state_masked.inc(K * (self.max_batch - len(rows)))
        if self._tel_on:
            # the device has its program: what the host does from here
            # to the fetch is in its shadow
            self._row.edge()
        return _Flying(out, rows, n, temps_d)

    def _rows_change(self) -> Optional[str]:
        """Whether the next call's own work will change the decode rows,
        from what the host holds: the reason (``DECODE_BEHIND``), or
        None.  A prefilling slot with more than one chunk left changes
        none: its row is the trash page at length 0 whichever of the two
        programs runs first."""
        if self._fault_plan is not None or self._decode_jit is None:
            return "other"
        if self._pending_boundary:
            return "boundary"
        free = False
        for s in self.slots:
            if s is None:
                free = True
            elif s.prefilling:
                if s.promo is not None:
                    return "other"
                if len(s.req.tokens) - s.prefill_done <= (
                        self.prefill_chunk or self.prefill_bucket):
                    return "prefill"
        return "admission" if free and self.queue else None

    def _why_sync(self, rows) -> Optional[str]:
        """A call begins with a step in flight over ``rows``: why it has
        to land before anything else, or None."""
        if any(self.slots[b] is not s for b, s in rows):
            return "other"
        why = self._rows_change()
        if why in _JOINED and self._ends_by_count(rows, self.decode_chunk):
            # the slot it frees is landed for, as it always was
            return "finish"
        return None if self._may_join(why, rows) else why

    def _why_behind(self, rows, K: int) -> Optional[str]:
        """THE RULE, read from the engine's state and set by no one.
        With a decode step over ``rows`` about to be read: why the next
        step has to wait for its tokens, or None where the next call's
        decode rows are already known to be these rows, whatever the
        tokens are: no row ends by count with this step's tokens (one
        that ends on ``eos`` the host cannot know: its token in the next
        step is void), and the next call admits nothing, finishes no
        prompt and fetches no boundary token, or what it admits and
        finishes joins the step behind this one on the device."""
        if self._ends_by_count(rows, K):
            return "finish"
        why = self._rows_change()
        return None if self._may_join(why, rows) else why

    @staticmethod
    def _ends_by_count(rows, K: int) -> bool:
        """Some row of ``rows`` ends by count with the next ``K`` tokens
        it is given (a first token still on the device is one it has)."""
        return any(len(s.generated) + (s.boundary is not None) + K
                   >= s.req.max_new_tokens for _, s in rows)

    def _joins(self) -> bool:
        """THE RULE's other half, read from the engine's state alone:
        whether the boundary tokens that wait may stay on the device and
        join this call's decode there.  No: under speculation (the sweep
        reads them), a fault plan or a tiered cache (each keeps the
        synchronous sequence), an engine whose decode the host drives,
        and where the host knows ahead that a boundary token is its
        request's last."""
        return not (self._spec_on or self._kvt_on
                    or self._fault_plan is not None
                    or self._decode_jit is None) and all(
            self.slots[b].req.max_new_tokens > 1
            for b, _ in self._pending_boundary)

    def _may_join(self, why: Optional[str], rows) -> bool:
        """With a decode step over ``rows`` in flight or about to be:
        whether the work that ``why`` names (``_JOINED``: an admission,
        a prompt's last chunk, a boundary token that waits) goes out
        behind it and joins the step after it on the device (the
        callers have seen that no row ends by count with that step's
        tokens).  Only where no request that could finish its prompt
        wants one token alone, and where the pages are free NOW: the
        head of the queue's and a step's growth of every row, with no
        eviction and no preemption."""
        if why not in _JOINED or not self._joins():
            return False
        ends = [s.req for s in self.slots if s is not None and s.prefilling]
        need = (1 + self.decode_chunk // self.page_size) * (len(rows) + 1)
        if why == "admission":
            head = self.queue[0]
            ends.append(head)
            bkt = self.prefill_chunk or self.prefill_bucket
            T = len(head.tokens)
            need += self._pages_needed(max(-(-T // bkt) * bkt, T + 1))
        return all(req.max_new_tokens > 1 for req in ends) and (
            not self._pooled or len(self.allocator.free) >= need)

    def _land_first(self, why: Optional[str], newest: bool = True) -> bool:
        """Land the step in flight before going on, if there is a reason
        to (the next host-fed dispatch is counted under it)."""
        if why is None:
            return False
        flying, self._flying = self._flying, None
        self._behind_why = why
        self._land(flying, newest)
        return True

    # dstpu: hot-path
    def _land(self, flying, newest: bool) -> None:
        """Read a decode step's tokens and append them, each to the row
        it was dispatched for if that row still lives: a row that ended
        while the step flew (on ``eos``, failed, abandoned) left a void
        token there, which is dropped and counted nowhere.  ``newest``:
        nothing was dispatched behind it, so the device has nothing
        queued once the fetch returns."""
        out, rows, ordinal, _ = flying
        K = self.decode_chunk
        # the rows whose first token joined this step on the device
        first = [(b, s) for b, s in rows if s.boundary is not None]
        with self._sp_token_sync:
            # dstpu: host-sync-ok: the ONE device→host transfer per
            # decode chunk (K tokens per sync — the module contract);
            # the boundary tokens that joined the step come with it
            host_toks, *heads = jax.device_get(
                [out, *(s.boundary for _, s in first)])
            if self._n_expert_rows:
                host_toks = self._take_expert_rows(host_toks, K)
        if self._tel_on and newest:
            # the fetch returned as the span ended: nothing is queued
            # from its own clock reading on
            self._row.drained = self._sp_token_sync.t1
        with self._sp_append:
            for (b, s), tok in zip(first, heads):
                # before the step's own: the order the tokens have
                s.boundary = None
                if self.slots[b] is s:
                    self._append_token(b, int(tok[0]))
            live = [(b, s) for b, s in rows if self.slots[b] is s]
            if self._trace_on and any(s.req.traced for _, s in live):
                # one event per BATCH sync (not per token): the decode
                # timeline at chunk granularity, nothing hotter
                self.tracer.event("decode_batch", attrs={
                    "active": len(live), "chunk": K})
            for b, s in live:
                for j in range(K):
                    self._append_token(b, int(host_toks[b, j]))
                    if self.slots[b] is None:   # finished mid-chunk:
                        break                   # rest is discard
        if not live and self._flying is None:
            # a step no row lived to see drew nothing: the next one
            # draws what it would have drawn
            self._n_dispatch = ordinal

    def _take_expert_rows(self, flat: np.ndarray, K: int) -> np.ndarray:
        """Split what a decode program of a family that counts its
        experts' rows returned (``[B * K + Eh]``, and one more where
        the pair buffer's further passes are counted): the counters
        advance by the held experts' rows and by every pair the programs
        routed since the last decode (this one's ``B * K`` rows, padding
        and idle slots included, and the prefills' in between); the
        tokens come back ``[B, K]``."""
        n = self.max_batch * K
        for c, rows in zip(self._c_expert_rows, flat[n:]):
            c.inc(int(rows))
        if self._pair_passes:
            self._c_pair_passes.inc(int(flat[-1]))
        self._c_routed_rows.inc(
            (self._rows_pending + n) * self._routed_per_row)
        self._rows_pending = 0
        return flat[:n].reshape(self.max_batch, K)

    def _next_dispatch(self):
        """What this decode or verify dispatch's program folds into the
        base key for its draws, uploaded: twice the dispatch's ordinal
        (the odd numbers are the admissions')."""
        n = self._n_dispatch
        self._n_dispatch = (n + 2) & 0x7FFFFFFF
        return self._put(np.full((), n, np.int32))

    def _set_step_gauges(self, n_active: int) -> None:
        self._g_queue.set(len(self.queue))
        self._g_occupancy.set(n_active / self.max_batch)
        usable = self.trash_page       # pool minus the reserved page
        # live-referenced pages only: the warm prefix pool is
        # reclaimable on demand, so it does not count as utilized
        self._g_kv_util.set(
            (usable - self.allocator.available) / max(usable, 1))
        if self._state_row is not None:
            self._g_state_bytes.set(self._state_bytes())
            self._g_state_live.set(
                sum(1 for s in self.slots if s is not None))
        if self._pc_on:
            ev = self.allocator.evicted
            if ev > self._evicted_seen:
                self._c_pc_evicted.inc(ev - self._evicted_seen)
                self._evicted_seen = ev
            self._g_pc_pool.set(len(self.allocator.pool))
            pt = self._c_pc_prompt_tokens.value
            if pt:
                self._g_pc_frac.set(
                    self._c_pc_cached_tokens.value / pt)

    def _check_frontier_writable(self, active, ahead: int) -> None:
        """COW guard for the speculative write window: every page the
        verify's ``ahead`` frontier positions can touch must be
        privately owned (or the trash page).  Structurally always true
        — shared/published prefix-cache pages live strictly below the
        frontier — but a write into one would silently poison the
        content-addressed index for every future match, so the sweep
        asserts rather than trusts."""
        ps = self.page_size
        for b, s in active:
            last = min((s.seq_len + ahead - 1) // ps,
                       self.max_pages_per_seq - 1)
            for slot_idx in range(s.seq_len // ps, last + 1):
                pg = int(self._table_host[b, slot_idx])
                if pg != self.trash_page and \
                        not self.allocator.writable(pg):
                    raise RuntimeError(
                        f"speculative verify would write shared/"
                        f"published page {pg} (slot {b}, table slot "
                        f"{slot_idx}) — COW invariant violated")

    # dstpu: hot-path
    def _spec_step(self, active) -> None:
        """One draft-and-verify sweep over every decode-ready slot.

        Draft: the drafter proposes up to K tokens per slot from the
        request's own history (host-side; ∅ is fine — that row rides
        the sweep as a plain decode step).  Verify: ONE continuation
        forward scores all K+1 positions for the whole batch (under
        ZeRO-Inference this is one full layer-weight stream, amortized
        over every accepted token), then :func:`~deepspeed_tpu.
        inference.speculative.verify_accept` computes on device the
        accepted prefix length and the bonus/corrected token at every
        stop position — one host transfer per sweep, same discipline
        as chunked decode.  Rollback: each slot's ``seq_len`` advances
        by accepted+1 (not the structural K+1 the forward wrote), so
        rejected drafts' KV is abandoned above the frontier and
        overwritten by the next sweep; ``_publish_full_pages`` bounds
        on ``_valid_tokens`` keep rejected garbage out of the prefix
        cache."""
        with self._sp_inputs:
            K = self.speculative.draft_tokens
            Bm = self.max_batch
            toks = np.zeros((Bm, K + 1), np.int32)
            drafts = np.zeros((Bm, K), np.int32)
            dlens = np.zeros((Bm,), np.int32)
            temps = np.zeros((Bm,), np.float32)
            drafted = 0
            for b, s in active:
                hist = s.req.tokens + s.generated
                d = list(self.drafter.propose(hist, K))[:K]
                dlens[b] = len(d)
                drafts[b, :len(d)] = d
                toks[b, 0] = hist[-1]
                toks[b, 1:1 + len(d)] = d
                temps[b] = s.req.temperature
                drafted += len(d)
            self._c_spec_drafted.inc(drafted)
            traced_any = self._trace_on and any(
                s.req.traced for _, s in active)
            if traced_any:
                self.tracer.event("spec_draft", attrs={
                    "active": len(active), "drafted": drafted})
            if self._pc_on:
                self._check_frontier_writable(active, K + 1)
        with self._sp_upload:
            self._upload_dirty()
        with self._sp_dispatch:
            if self._tel_on:
                self._row.dispatch("sweep", Bm * (K + 1),
                                   len(active) + drafted)
            logits, self.cache = self._verify_chunk(
                self.params, self._put(toks), self.cache)
            n_acc_d, stop_d = verify_accept(
                logits, self._put(drafts), self._put(dlens),
                self._key, self._next_dispatch(),
                self._put(temps))
            if self._tel_on:
                self._row.edge()
        with self._sp_token_sync:
            if traced_any:
                self.tracer.event("spec_verify", attrs={
                    "active": len(active), "positions": K + 1})
            # dstpu: host-sync-ok: the ONE device→host transfer per
            # verify sweep (accepted lengths + stop tokens for the
            # whole batch)
            n_acc, stop = jax.device_get((n_acc_d, stop_d))
        if self._tel_on:
            self._row.drained = self._sp_token_sync.t1
        with self._sp_append:
            self._spec_accept(active, n_acc, stop, drafts, dlens,
                              traced_any)

    # dstpu: hot-path
    def _spec_accept(self, active, n_acc, stop, drafts, dlens,
                     traced_any) -> None:
        """The host half of a sweep's end: advance every slot by its
        accepted span and append the tokens."""
        K = self.speculative.draft_tokens
        Bm = self.max_batch
        self._c_decode_syncs.inc()
        self._behind["other"] += 1      # a sweep's tokens are read here
        self._c_decode_steps.inc(K + 1)
        self._c_spec_sweeps.inc()
        if self._tel_on:
            self._g_spec_occ.set(len(active) / Bm)
        rejected = 0
        for b, s in active:
            a = int(n_acc[b])
            rejected += int(dlens[b]) - a
            self._c_spec_accepted.inc(a)
            self._c_spec_slots.inc()
            self._c_spec_emitted.inc(a + 1)
            self._h_spec_len.observe(a + 1)
            # KV rollback: the forward wrote K+1 positions and bumped
            # the device seq_lens structurally; only accepted+1 of them
            # (the re-fed token + accepted drafts) hold real history
            s.seq_len += a + 1
            if s.req.traced:
                self.tracer.event("spec_accept", s.req.req_id, b,
                                  attrs={"drafted": int(dlens[b]),
                                         "accepted": a})
            for j in range(a):
                self._append_token(b, int(drafts[b, j]))
                if self.slots[b] is None:    # finished mid-span:
                    break                    # rest is discard
            if self.slots[b] is not None:
                self._append_token(b, int(stop[b, a]))
        self._c_spec_rejected.inc(rejected)
        if rejected and traced_any:
            self.tracer.event("spec_rollback", attrs={
                "rejected": rejected})
        # every row was rewound below the structural seq_lens the
        # verify left on device — force the re-upload before the next
        # forward reads them
        self._lens_dirty = True

    def run(self, max_steps: int = 10_000) -> Dict[Any, List[int]]:
        """Drive until every submitted request completes."""
        steps = 0
        while self.has_work:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("serving loop did not converge")
        return dict(self.finished)

    def drain_finished(self) -> Dict[Any, List[int]]:
        """Hand over and forget completed outputs (long-running servers
        call this instead of letting ``finished`` grow unboundedly)."""
        out, self.finished = self.finished, {}
        return out

    # --------------------------------------------------- introspection
    # (/statusz, /healthz and /requestz providers — registered on the
    # telemetry HTTP server when the config block carries http_port;
    # all three are also plain methods a fleet supervisor or test can
    # call in-process)
    def attach_watchdog(self, watchdog) -> None:
        """Feed ``/healthz`` from a :class:`~deepspeed_tpu.utils.
        watchdog.Watchdog`: readiness goes false the moment the
        watchdog fires, so a fleet probe drains traffic off a hung
        engine before the abort lands."""
        self._watchdog = watchdog
        if self.incident_mgr.enabled:
            # a watchdog fire is an incident class of its own: the
            # probe trips ONCE (latched — `fired` stays true for the
            # process's lifetime, and re-tripping every dedup window
            # would eat the max_bundles budget)
            tripped = []

            def _wd_probe():
                if watchdog.fired and not tripped:
                    tripped.append(True)
                    return "watchdog", {"phase": "watchdog_fired",
                                        **watchdog.health()}
                return None

            self.incident_mgr.add_probe(_wd_probe)
            # the probe alone only runs if the engine keeps stepping —
            # a genuinely hung scheduler thread (the case the watchdog
            # exists for) never reaches another tick, and an
            # abort_on_timeout fire kills the process right after
            # on_timeout.  Chaining the fire callback captures the
            # bundle from the WATCHDOG thread before any abort: safe
            # because the single writer has, by the fire's definition,
            # stopped stepping for timeout_s — worst case on a slow-
            # not-hung engine resuming mid-capture is one duplicate
            # bundle on a once-per-process path, vs losing the capture
            prev_timeout = watchdog.on_timeout

            def _on_timeout():
                try:
                    self.incident_mgr.evaluate()
                except Exception:
                    pass        # never mask the watchdog's own path
                if prev_timeout is not None:
                    prev_timeout()

            watchdog.on_timeout = _on_timeout

    def mesh_info(self) -> Dict[str, Any]:
        """The /statusz ``mesh`` block: is this replica an SPMD-sharded
        engine, and over what?  Axis names/sizes plus the device count
        it spans — a TP-sharded fleet is visibly sharded (``dstpu_top``
        renders the tp column from this)."""
        ms = self._mesh
        if ms is None:
            return {"sharded": False, "devices": 1, "axes": {},
                    "tp": 1, "ep": 1}
        axes = {a: int(s) for a, s in ms.sizes.items() if int(s) > 1}
        return {
            "sharded": any(s > 1 for s in axes.values()),
            "devices": int(ms.mesh.devices.size),
            "axes": axes,
            "tp": int(ms.size("model")),
            "ep": int(ms.size("expert")),
        }

    def statusz(self) -> Dict[str, Any]:
        """Live machine-readable engine snapshot: per-slot state,
        in-flight requests with phase and age, KV/prefix-cache pool
        occupancy and fragmentation, speculation acceptance, SLO
        attainment per tier, and the full metrics snapshot.  Assembled
        from host-side bookkeeping only — no device sync, safe to poll
        every second (``tools/dstpu_top.py`` does)."""
        now = time.perf_counter()
        slots: List[Dict[str, Any]] = []
        mapped_capacity = 0
        valid_tokens = 0
        for b, s in enumerate(self.slots):
            if s is None:
                slots.append({"slot": b, "state": "idle"})
                continue
            pages = int(np.sum(self._table_host[b] != self.trash_page))
            mapped_capacity += pages * self.page_size
            valid_tokens += self._valid_tokens(s)
            row: Dict[str, Any] = {
                "slot": b,
                "state": "prefill" if s.prefilling else "decode",
                "req": _req_key(s.req.req_id),
                "tier": s.req.tier,
                "prompt_tokens": len(s.req.tokens),
                "generated": len(s.generated),
                "max_new_tokens": s.req.max_new_tokens,
                "seq_len": s.seq_len,
                "pages": pages,
                "age_s": round(now - s.req.t_arrival, 3),
            }
            if s.prefilling:
                row["prefill_done"] = s.prefill_done
            slots.append(row)
        queue = [{"req": _req_key(r.req_id), "tier": r.tier,
                  "prompt_tokens": len(r.tokens),
                  "age_s": round(now - r.t_arrival, 3)}
                 for r in list(self.queue)[:32]]
        al = self.allocator
        usable = self.trash_page       # pool minus the reserved page
        live = usable - al.available
        spec_slots = int(self._c_spec_slots.value)
        cnt_hits = int(self._c_pc_hits.value)
        cnt_miss = int(self._c_pc_misses.value)
        pt = int(self._c_pc_prompt_tokens.value)
        from deepspeed_tpu.obs_wire import wire_stamp
        status: Dict[str, Any] = {
            "schema_version": 1,
            **wire_stamp(),
            "engine": type(self).__name__,
            "replica": self.replica_id,
            "weights_version": _req_key(self.weights_version),
            "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "uptime_s": round(now - self._t_start, 3),
            "last_step_age_s": (
                round(now - self._last_step_t, 3)
                if self._last_step_t is not None else None),
            "max_batch": self.max_batch,
            "active_slots": sum(1 for s in self.slots if s is not None),
            "slots": slots,
            "queue": {"depth": len(self.queue), "head": queue},
            "finished_pending_drain": len(self.finished),
            "kv": {
                # the layers that attend over pages (not the model's
                # depth where some keep a state a slot: "cache.state")
                "layers": (len(self.cache.k)
                           if isinstance(self.cache.k, (tuple, list))
                           else int(self.cache.k.shape[0])
                           if self._pooled else 0),
                "bytes_per_token": self._pool_bytes() // (
                    (self.trash_page + 1) * self.page_size),
                "page_size": self.page_size,
                "pages_usable": usable,
                "pages_free": len(al.free),
                "pages_warm": len(al.pool),
                "pages_live": live,
                "utilization": round(live / max(usable, 1), 4),
                # internal fragmentation of the mapped working set:
                # the fraction of page capacity mapped into live slots
                # that holds no real KV yet (bucket padding + decode
                # headroom) — high values mean page_size is oversized
                # for the traffic
                "fragmentation": round(
                    1.0 - valid_tokens / mapped_capacity, 4)
                if mapped_capacity else 0.0,
            },
            # the per-slot state beside the pages; layers that keep nothing
            "cache.state": {
                "layers": self._state_row.layers,
                # a family's second per-slot kind (Recurrent.also)
                "ring_layers": (self._state_row.ring.layers
                                if self._state_row.ring else 0),
                "ffn_alone": {"bytes": 0, "layers": getattr(
                    self, "ffn_alone_layers", 0)},
                "bytes": self._state_bytes(),
                "bytes_per_slot": self._state_bytes() // self.max_batch,
                "live_slots": sum(1 for s in self.slots if s is not None),
                "fresh_starts": int(self._c_state_fresh.value),
                "rows_masked": int(self._c_state_masked.value),
            } if self._state_row is not None else None,
            "prefix_cache": {
                "enabled": self._pc_on,
                "warm_pool_pages": len(al.pool),
                "published_lifetime": al.published,
                "evicted_lifetime": al.evicted,
                "admission_hits": cnt_hits,
                "admission_misses": cnt_miss,
                "token_hit_rate": round(
                    self._c_pc_cached_tokens.value / pt, 4) if pt
                else 0.0,
            },
            "kv_tier": {
                "enabled": self._kvt_on,
                **(self._kv_pool.occupancy() if self._kv_pool is not None
                   else {}),
                "quantize_cold": self.kv_tier.quantize_cold
                if self._kvt_on else False,
                "quantized_resident": self._quant_resident,
                "demoted_lifetime": al.demoted,
                "promoted_lifetime": al.promoted,
                "promoting_pages": len(al.promoting),
                "promote_stall_s": round(
                    float(self._h_kvt_promote.sum), 6)
                if self._kvt_on else 0.0,
            },
            "speculative": {
                "enabled": self._spec_on,
                "verify_sweeps": int(self._c_spec_sweeps.value),
                "mean_accept_len": round(
                    self._c_spec_emitted.value / spec_slots, 4)
                if spec_slots else None,
            },
            "mesh": self.mesh_info(),
            "kernels": self._kernels.as_dict(),
            "history": {
                "enabled": self.history.enabled,
                "series": len(self.history.series_names()),
            },
            "incidents": self.incident_mgr.snapshot(),
            "devprof": self.devprof.statusz_block(),
            # what making the process's programs ready cost (the
            # process-wide build ledger, its newest entries)
            "build": BUILD_LEDGER.snapshot(last=64),
            # what the steps of the process's engines did (the step
            # ledger's running totals and its newest row)
            "steps": STEP_LEDGER.snapshot(last=1),
            # the decode dispatches: those that went ahead of the host
            # (their tokens came from the step before, on the device)
            # and those that stayed behind it, by reason; the two sum
            # to ``dispatches`` (= serving_decode_syncs)
            "decode": {
                "dispatches": int(self._c_decode_syncs.value),
                "ahead": int(self._c_decode_ahead.value),
                "behind": dict(self._behind),
                # boundary tokens that joined their decode on the device
                "joined": int(self._c_boundary_joined.value),
                "in_flight": self._flying is not None,
            },
            # the BOUND port (meaningful when http_port=0 asked for an
            # ephemeral bind): how a parent process that spawned this
            # replica learns where to scrape it
            "telemetry": {
                "http_port": self._tel_exporter.port
                if self._tel_exporter is not None else None,
            },
        }
        if self.comm_placement is not None:
            # quantized TP weight placement (comm.quantized_serving):
            # wire-byte ledger + worst per-leaf round-trip error, stamped
            # once at build by _record_comm_placement
            status["comm"] = dict(self.comm_placement)
        metrics = self.registry.snapshot()
        status["slo"] = self.slo_tracker.snapshot(now=now)
        # reuse the snapshot just taken — _robustness_status only
        # filters its counters, and /statusz is polled on an interval
        status["robustness"] = self._robustness_status(
            now, counters=metrics.get("counters", {}))
        status["metrics"] = metrics
        return status

    def _degraded_state(self, now: float) -> Tuple[bool, List[str]]:
        """Degraded = still serving, but shedding load or running with
        a tier disabled by repeated faults.  /healthz stays 200 (a
        degraded engine is exactly the one a router should KEEP
        probing) with ``{"degraded": true, "reasons": [...]}``; only a
        watchdog fire or shutdown turns readiness off (503)."""
        reasons: List[str] = []
        if self._last_shed_t is not None and \
                now - self._last_shed_t < _SHED_ACTIVE_WINDOW_S:
            reasons.append("load_shedding_active")
        if self._kv_pool is not None and \
                self._kv_pool.disabled is not None:
            reasons.append(
                f"kv_tier_disabled: {self._kv_pool.disabled}")
        return bool(reasons), reasons

    def _robustness_status(self, now: float,
                           counters: Optional[Dict[str, float]] = None
                           ) -> Dict[str, Any]:
        """The /statusz ``robustness`` block: shed/failed accounting,
        per-tier fault/retry/fallback counters, degraded state, and —
        when a fault plan is installed — the injection ledger the
        chaos soak reconciles against.  ``counters``: a registry
        snapshot's counter dict, when the caller already took one
        (statusz does — no second registry walk per poll)."""
        degraded, reasons = self._degraded_state(now)
        cnt = counters if counters is not None else ({}
            if not self._tel_on
            else self.registry.snapshot().get("counters", {}))
        out: Dict[str, Any] = {
            "degraded": degraded,
            "reasons": reasons,
            "shed_requests": self._n_shed,
            "shed_rate": round(
                self._n_shed / self._n_submitted, 4)
            if self._n_submitted else 0.0,
            "shed_by_reason": {k: v for k, v in
                               self._shed_by_reason.items() if v},
            "failed_requests": self._n_failed,
            "shed_queue_depth": self.shed_queue_depth,
            "shed_expired_deadline": self._shed_deadline,
            "kv_tier": {
                "fallback_events": self._n_kvt_fallbacks,
                "checksum_failures": self._n_kvt_checksum,
                "disabled": (self._kv_pool.disabled
                             if self._kv_pool is not None else None),
                "spill_failures": (self._kv_pool.spill_failures
                                   if self._kv_pool is not None else 0),
            },
            "io_retries": {
                k: int(v) for k, v in cnt.items()
                if k.endswith(("_io_retries", "_sync_fallbacks",
                               "_write_retries")) and v},
        }
        if self._fault_plan is not None:
            out["faults"] = self._fault_plan.snapshot()
        return out

    def healthz(self) -> Dict[str, Any]:
        """Liveness/readiness for a fleet supervisor probe.  ``ready``
        goes false after :meth:`shutdown` or once an attached
        watchdog has fired (the HTTP endpoint turns that into a 503)."""
        from deepspeed_tpu.obs_wire import wire_stamp
        now = time.perf_counter()
        h: Dict[str, Any] = {
            **wire_stamp(),
            "alive": True,
            "ready": not self._closed,
            "replica": self.replica_id,
            "uptime_s": round(now - self._t_start, 3),
            "last_step_age_s": (
                round(now - self._last_step_t, 3)
                if self._last_step_t is not None else None),
            "queue_depth": len(self.queue),
            "active_slots": sum(1 for s in self.slots if s is not None),
            "watchdog": None,
        }
        wd = self._watchdog
        if wd is not None:
            h["watchdog"] = wd.health()
            if wd.fired:
                h["ready"] = False
        # degraded ≠ unready: shedding or a disabled tier keeps the
        # 200 (the engine IS serving) and reports why it is limping —
        # the router's shed/fail-over signal, not a kill signal
        degraded, reasons = self._degraded_state(now)
        h["degraded"] = degraded
        h["reasons"] = reasons
        return h

    def requestz(self, req_id) -> Dict[str, Any]:
        """Drill into ONE request: its flight-recorder events (from the
        ring — a wrapped ring may have lost the oldest) plus its
        current disposition.  ``req_id`` matches on the string form, so
        the HTTP query ``/requestz?id=3`` finds integer id 3."""
        rid = str(req_id)
        events = []
        if self.tracer.enabled:
            events = [e for e in self.tracer.recorder.events()
                      if e[1] is not None and _req_key(e[1]) == rid]
        # list() snapshots: this runs on the HTTP serving thread while
        # the engine thread mutates queue/finished — iterating the live
        # containers would raise "mutated during iteration"
        in_queue = any(_req_key(r.req_id) == rid
                       for r in list(self.queue))
        slot = next((b for b, s in enumerate(list(self.slots))
                     if s is not None
                     and _req_key(s.req.req_id) == rid), None)
        finished = any(_req_key(k) == rid for k in list(self.finished))
        out: Dict[str, Any] = {
            "req": rid,
            "found": bool(events) or in_queue or slot is not None
            or finished,
            "state": ("finished" if finished
                      else "active" if slot is not None
                      else "queued" if in_queue
                      else "unknown"),
            "slot": slot,
            "tracing_enabled": self.tracer.enabled,
            "events": [event_to_dict(e) for e in events],
        }
        if events:
            from deepspeed_tpu.request_trace import request_breakdown

            rows = request_breakdown(events)["requests"]
            if rows:
                out["breakdown"] = next(iter(rows.values()))
        return out

    def historyz(self) -> Dict[str, Any]:
        """The ``/historyz`` document: every metric-history ring
        (multi-resolution time series sampled on the exporter tick)
        plus recent incident-bundle metadata — the machine-readable
        feed behind ``dstpu_top``'s sparklines and incident ticker.
        Host-side bookkeeping only, safe to poll."""
        from deepspeed_tpu.obs_wire import wire_stamp
        return {
            **wire_stamp(),
            "history": self.history.snapshot(),
            "incidents": self.incident_mgr.snapshot(),
        }

    def profilez(self, capture_s=None) -> Dict[str, Any]:
        """The ``/profilez`` document: devprof's statusz block (compile
        ledger totals, per-phase device seconds, MFU/MBU), and — when
        ``capture_s`` is given — an on-demand :mod:`jax.profiler` trace
        capture of that many seconds written under the tracer's
        ``dump_dir`` (clamped to ``devprof.capture_max_s``)."""
        return self.devprof.profilez(capture_s)

    def shutdown(self) -> None:
        """Idempotent teardown: final sink flush, then stop the
        telemetry/introspection HTTP server and join its thread — so
        back-to-back engine constructions on one fixed port (the test
        suite's pattern) never hit ``EADDRINUSE`` or leak the serving
        thread."""
        if self._closed:
            return
        self._closed = True
        self._flying = None     # a step in flight is never read
        if self._owns_fault_plan:
            faults_mod.clear_fault_plan(self._fault_plan)
        ex = self._tel_exporter
        if ex is not None:
            try:
                ex.maybe_export(force=True)
            except Exception:
                pass
            ex.close()

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass


def _shard_params_for_serving(params, specs_tree, mesh):
    """Place a serving param tree (bf16 or int8-quantized) on ``mesh``
    under the model's own TP/EP specs — int8 codes take the weight's
    spec, per-row group scales ride alongside (ref: module_inject's
    int8 + mp_size injection composing with TP)."""
    from deepspeed_tpu import zero as _zero
    from deepspeed_tpu.inference.quantized import shard_quantized

    return shard_quantized(params, _zero.resolve_specs(None, specs_tree),
                           mesh)


# below this, the exact path keeps a leaf: scales would outweigh the
# payload saved, and tiny leaves are the accuracy-critical ones (norm
# gains, biases)
_WIRE_MIN_ELEMS = 1024


def _quantized_shard_params(params, specs_tree, mesh, comm_cfg):
    """int8-wire variant of :func:`_shard_params_for_serving` (ref:
    ZeRO++ qwZ's quantized weight gather reused at serving time,
    arXiv:2306.10209): each float weight leaf is quantized ON THE HOST
    so the H2D upload that places the TP replica carries int8 codes +
    f32 scales instead of the full-precision image, then dequantized on
    device back to the leaf's own dtype under the leaf's own
    PartitionSpec (scales ride replicated — they are tiny).  Every
    quantized leaf is gated by ``comm_cfg.serving_rtol`` on its exact
    host-side round-trip error — a leaf the codec cannot represent
    within tolerance fails the BUILD, never silently serves degraded
    weights.  QuantizedTensor leaves (weight_dtype="int8" already
    shipped codes), non-float leaves, and sub-``_WIRE_MIN_ELEMS``
    leaves take the exact path.  Returns ``(placed, stats)``; the
    caller stamps ``stats`` onto the engine via
    :func:`_record_comm_placement`."""
    from jax.tree_util import keystr, tree_map_with_path

    from deepspeed_tpu import zero as _zero
    from deepspeed_tpu.comm.collectives import (dequantize_from_wire,
                                                quantize_for_wire_np)
    from deepspeed_tpu.inference.quantized import _is_qt, shard_quantized

    specs = _zero.resolve_specs(None, specs_tree)
    stats = {"leaves_quantized": 0, "leaves_exact": 0,
             "bytes_on_wire_int8": 0, "bytes_on_wire_f32": 0,
             "max_rel_err": 0.0,
             "serving_rtol": float(comm_cfg.serving_rtol)}

    def put(path, leaf, spec):
        a = None if _is_qt(leaf) else np.asarray(leaf)
        if a is None or a.dtype.kind != "f" or a.size < _WIRE_MIN_ELEMS:
            stats["leaves_exact"] += 1
            return shard_quantized(leaf, spec, mesh)
        q, s, dt = quantize_for_wire_np(a)
        af32 = a.astype(np.float32)
        deq_host = (q.astype(np.float32).reshape(s.size, -1)
                    * s[:, None]).reshape(a.shape)
        ref = float(np.abs(af32).max()) or 1.0
        rel = float(np.abs(deq_host - af32).max()) / ref
        if rel > comm_cfg.serving_rtol:
            raise ValueError(
                f"comm.quantized_serving: leaf {keystr(path)} "
                f"{a.shape} round-trips at rel err {rel:.3e} > "
                f"serving_rtol {comm_cfg.serving_rtol:g} — raise the "
                "tolerance or serve this model unquantized")
        stats["leaves_quantized"] += 1
        stats["bytes_on_wire_int8"] += q.nbytes + s.nbytes
        stats["bytes_on_wire_f32"] += a.size * 4
        stats["max_rel_err"] = max(stats["max_rel_err"], rel)
        # the H2D below is the wire this whole path exists for: int8
        # codes under the weight's spec + replicated scales, dequantized
        # device-side into the leaf's serving dtype
        q_dev = jax.device_put(q, mesh.sharding(spec))
        s_dev = jax.device_put(s, mesh.replicated())
        return jax.device_put(
            dequantize_from_wire(q_dev, s_dev, jnp.dtype(dt)),
            mesh.sharding(spec))

    placed = tree_map_with_path(put, params, specs, is_leaf=_is_qt)
    i8 = stats["bytes_on_wire_int8"]
    stats["compression_ratio"] = round(
        stats["bytes_on_wire_f32"] / i8, 4) if i8 else 0.0
    stats["max_rel_err"] = round(stats["max_rel_err"], 8)
    return placed, stats


def _record_comm_placement(eng: ServingEngine, stats: Dict[str, Any]):
    """Stamp quantized-placement stats onto a built engine: the
    /statusz ``comm`` block plus the ``comm_*`` metric family — the
    SAME names the training engine reports for its gradient wire, so
    one dashboard joins both sides of the shared int8 codec."""
    eng.comm_placement = dict(stats)
    r = eng.registry
    if not r.enabled:
        return
    r.counter(
        "comm_bytes_on_wire_int8",
        "bytes actually shipped on the quantized wire (int8 codes + "
        "f32 scales)").inc(stats["bytes_on_wire_int8"])
    r.counter(
        "comm_bytes_on_wire_f32",
        "bytes a flat f32 wire would have shipped for the same "
        "payload").inc(stats["bytes_on_wire_f32"])
    r.gauge(
        "comm_compression_ratio",
        "f32 wire bytes / quantized wire bytes").set(
        stats["compression_ratio"])
    r.gauge(
        "comm_serving_max_rel_err",
        "worst per-leaf round-trip error of the quantized weight "
        "placement (gated by comm.serving_rtol at build)").set(
        stats["max_rel_err"])


def _live(config_cls):
    return lambda v: config_cls.coerce(v).enabled


# What only the paged-KV decode scheduler has: (keyword, is the value a
# live request for it, what it asks for).  The encoder engines are
# fixed-shape batch scorers with no pages, decode loop, sampler or request
# lifecycle; a live block fails loudly there, never silently serves
# without what the config pinned, and an inert one is dropped.
_DECODER_ONLY = (
    ("zero_inference", _live(ZeroInferenceConfig),
     "zero_inference streams a paged-KV decoder's layer weights"),
    ("comm", lambda v: CommConfig.coerce(v).quantized_serving,
     "comm.quantized_serving quantizes TP replica weight placement"),
    ("speculative", _live(SpeculativeConfig),
     "speculative decoding needs the paged-KV decode path"),
    ("slo", _live(SLOConfig),
     "the slo block needs the paged-KV decode path"),
    ("prefix_cache", _live(PrefixCacheConfig),
     "prefix_cache needs the paged-KV decode path"),
    ("kv_tier", _live(KVTierConfig),
     "kv_tier needs the paged-KV decode path"),
    ("faults", lambda v: isinstance(v, FaultPlan)
     or FaultsConfig.coerce(v).enabled,
     "the faults block needs the paged-KV decode path"),
    ("shed_queue_depth", bool,
     "load shedding lives in the paged-KV admission path"),
    ("shed_expired_deadline", bool,
     "load shedding lives in the paged-KV admission path"),
)
# accepted and unused on the encoder path, never an error: the request
# tracer, history, incidents and devprof ride the decode scheduler's
# lifecycle (queued/admitted/first-token/finish edges), and a drafter
# instance is inert without a live speculative block
_DECODER_ONLY_INERT = ("tracing", "history", "incidents", "devprof",
                       "drafter")


def _encoder_serving_engine(params, cfg, kw):
    from deepspeed_tpu.models.bert import BertConfig
    from deepspeed_tpu.models.cnn import CNNConfig

    decoders = ", ".join(f.name for f in decoder_families())
    for key, live, what in _DECODER_ONLY:
        value = kw.pop(key, None)
        if value is not None and live(value):
            raise NotImplementedError(
                f"{what}, which {type(cfg).__name__} does not serve — "
                f"supported: {decoders}")
    for key in _DECODER_ONLY_INERT:
        kw.pop(key, None)
    if isinstance(cfg, BertConfig):
        from deepspeed_tpu.inference.encoder_serving import (
            bert_serving_engine)

        return bert_serving_engine(params, cfg, **kw)
    if isinstance(cfg, CNNConfig):
        from deepspeed_tpu.inference.encoder_serving import (
            CNNServingEngine)

        for unsupported in ("mesh", "weight_dtype"):
            if kw.get(unsupported) not in (None, "bfloat16"):
                raise NotImplementedError(
                    f"CNN serving does not support {unsupported!r} — "
                    "it is a fixed-shape batched scorer")
            kw.pop(unsupported, None)
        return CNNServingEngine(params, cfg=cfg, **kw)
    raise TypeError(
        f"no serving path for config type {type(cfg).__name__}; "
        f"supported: {decoders}, BertConfig, CNNConfig")


def serving_engine(params, cfg, **kw):
    """The one serving builder: dispatch on the config type (ref:
    init_inference accepting any supported model).  A decoder family
    (:func:`~deepspeed_tpu.models.family.decoder_family`) gets the paged
    continuous-batching :class:`ServingEngine` over
    :func:`~deepspeed_tpu.inference.paged_forward.forward_paged`;
    encoder families get the lot-batching :class:`~deepspeed_tpu.
    inference.encoder_serving.EncoderServingEngine` (same submit/run
    surface, no decode loop).

    ``weight_dtype="int8"``: weight-only quantized serving (ref:
    init_inference(dtype=int8)) — int8 codes + group scales
    (``quant_group_size``) in HBM, half the bf16 weight residency,
    dequant traced into the forward; the family's ``quant_skip_paths``
    stay exact.

    ``mesh``: sharded serving (ref: replace_module.py TP injection;
    DeepSpeed-MoE inference's expert parallelism) — params shard by the
    family's own ``param_specs`` over its ``shard_axes``, the KV cache
    shards its head axis over ``model``, and both jits run under GSPMD
    with the psums inserted by XLA.

    ``zero_inference``: a :class:`~deepspeed_tpu.config.
    ZeroInferenceConfig` (or its dict form) routes to the weight-
    streamed ZeRO-Inference engine — layer weights live on a host/NVMe
    tier and stream through a double-buffered HBM working set, so the
    served model's weight image may exceed HBM.
    """
    if kw.get("kernels") is not None:
        raise ValueError(KERNELS_BLOCK_GONE)
    try:
        fam = decoder_family(cfg)
    except TypeError:
        return _encoder_serving_engine(params, cfg, kw)
    # the decoder build stays in THIS function: a helper's frame between
    # here and ServingEngine.__init__ cost 2-3 s of set-up on the chip's
    # host (CPython's frame-stack chunks: PERF.md 6, PRs 30 and 37)
    weight_dtype = kw.pop("weight_dtype", "bfloat16")
    quant_group_size = kw.pop("quant_group_size", 128)
    mesh = kw.pop("mesh", None)
    zero_inference = kw.pop("zero_inference", None)
    fam.check(cfg, mesh, kw.get("max_seq", 256))
    kvt = KVTierConfig.coerce(kw.get("kv_tier"))
    zi = ZeroInferenceConfig.coerce(zero_inference)
    speculating = SpeculativeConfig.coerce(kw.get("speculative")).enabled
    if fam.refuses:
        fam.refuse(
            zero_inference=zi.enabled, kv_tier=kvt.enabled,
            quantized_resident=kvt.quantized_resident,
            prefix_cache=PrefixCacheConfig.coerce(
                kw.get("prefix_cache")).enabled,
            speculative=speculating, tensor_parallel=fam.sharded(mesh))
    # sharded-ness is baked in at BUILD time: the compiled paths must not
    # re-read the mutable ambient mesh on a later retrace (a cleared one
    # would silently re-enable pallas kernels over the sharded cache)
    sharded = fam.sharded(mesh)
    # the readers resolve HERE, once, from what the build can observe: what
    # the closures bake and /statusz reports are one object
    kw["kernels"] = resolve_serving_kernels(
        tp=mesh is not None and any(
            mesh.size(ax) > 1 for ax in ("model", "expert")),
        interpret=jax.default_backend() != "tpu",
        quantized_resident=kvt.enabled and kvt.quantized_resident,
        recurrent=fam.recurrent is not None
        and fam.recurrent.state_row(cfg).state is not None, chunk=(
            kw.get("prefill_chunk") or kw.get("prefill_bucket", 32),
            fam.cache_row(cfg).head_width or fam.cache_row(cfg).key_width),
        state_block=fam.recurrent and (fam.recurrent, cfg))
    if fam.latent is not None:
        kw["kernels"] = kw["kernels"]._replace(
            decode=latent_reader(kw["kernels"].decode))
    if fam.recurrent is not None and fam.recurrent.chunk_reader is not None:
        kw["kernels"] = kw["kernels"]._replace(
            window=fam.recurrent.chunk_reader(
                cfg, kw.get("prefill_chunk") or 0,
                jax.default_backend() != "tpu"))

    kw["kernels"] = kw["kernels"]._replace(experts=held_experts_product(
        params, fam, cfg, kw.get("prefill_chunk") or 0,
        weight_dtype == "bfloat16" and not sharded and not zi.enabled))

    if zi.enabled:
        from deepspeed_tpu.inference.zero_inference import (
            zero_inference_serving_engine)

        return zero_inference_serving_engine(
            params, cfg, zi, family=fam, weight_dtype=weight_dtype,
            quant_group_size=quant_group_size, mesh=mesh, **kw)

    # int8 leaves are dequantised in each program: no stack to read in place
    resident = weight_dtype == "bfloat16"

    def step(params, tokens, cache):
        return forward_paged(params, tokens, cfg, cache, tp=sharded,
                             resident=resident)

    def chunk_step(params, tokens, cache):
        return forward_paged(params, tokens, cfg, cache, continuation=True,
                             tp=sharded, resident=resident)

    if weight_dtype != "bfloat16":
        from deepspeed_tpu.inference.quantized import quantize_for_inference

        # raises on anything but "int8" — never silently serve unquantized
        params, step, chunk_step = quantize_for_inference(
            params, step, chunk_step, weight_dtype=weight_dtype,
            group_size=quant_group_size, skip_paths=fam.quant_skip_paths)

    comm_stats = None
    if sharded:
        cc = CommConfig.coerce(kw.get("comm"))
        if cc.quantized_serving:
            # the training int8 wire reused for replica placement: H2D
            # ships codes + scales, gated by serving_rtol per leaf
            params, comm_stats = _quantized_shard_params(
                params, fam.param_specs(cfg), mesh, cc)
        else:
            params = _shard_params_for_serving(
                params, fam.param_specs(cfg), mesh)

    row = fam.cache_row(cfg)
    n_layers = cfg.n_layers
    if fam.recurrent is not None:
        # the pool has the layers that attend over pages; the others
        # keep a state a slot beside it
        kw["state_row"] = fam.recurrent.state_row(cfg)
        n_layers = fam.pool_layers(cfg)
    held, per_row = fam.expert_rows(cfg)
    # the counts ride in the decode program's fetch; a speculating
    # engine's steady program is the verify sweep, which has none
    if held and not speculating:
        kw.update(expert_rows=(held, fam.router(cfg)[0] > held),
                  routed_per_row=per_row)
    if row.values_in_keys:
        kw["values_in_keys"] = True
    eng = ServingEngine(
        params, step, step, n_layers=n_layers, n_kv=row.n_kv,
        head_dim=row.pool_width, chunk_prefill_fn=chunk_step, mesh=mesh,
        **kw)
    if comm_stats is not None:
        _record_comm_placement(eng, comm_stats)
    if fam.recurrent is not None:
        # layers that are an FFN alone: neither pages nor a state
        eng.ffn_alone_layers = fam.ffn_alone_layers(cfg)
        # chunk programs give their row's last token alone (Recurrent.tail)
        eng.tail_cut = bool(fam.recurrent.tail)
    return eng
