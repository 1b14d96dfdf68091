"""Autoregressive generation: KV cache, prefill/decode split, sampling.

Reference behavior: deepspeed/inference/engine.py generate path +
ops/transformer/inference kernels (decode attention over a KV cache,
static cache allocation, greedy/temperature sampling).

TPU design: the cache is a static-shape ``[L, B, max_seq, KV, Dh]`` pytree
(XLA needs static shapes — no dynamic growth); prefill and decode are two
separately-jitted programs.  Prefill processes the whole prompt at once
(MXU-friendly big matmuls); decode steps one token with
``lax.dynamic_update_slice`` cache writes and masked attention up to the
current length.  Sampling (greedy/temperature/top-k/top-p) runs on-device
inside the decode jit so generation never round-trips to host per token.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kernels import PagedKVCache
from deepspeed_tpu.inference.paged_forward import (forward_paged,
                                                   forward_with_cache)
from deepspeed_tpu.models.family import decoder_family


class KVCache(NamedTuple):
    """Static-shape KV cache; ``length`` = number of valid positions."""

    k: jnp.ndarray          # [L, B, maxT, KV, Dh]
    v: jnp.ndarray          # [L, B, maxT, KV, Dh]
    length: jnp.ndarray     # i32 scalar

    @classmethod
    def alloc(cls, n_layers: int, batch: int, max_seq: int, n_kv: int,
              head_dim: int, dtype=jnp.bfloat16) -> "KVCache":
        shape = (n_layers, batch, max_seq, n_kv, head_dim)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((), jnp.int32))


def sample_logits(logits, rng, temperature: float = 1.0,
                  top_k: int = 0, top_p: float = 1.0):
    """logits: [B, V] → token ids [B].  temperature==0 → greedy."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p; cutoff logit value
        keep = cum - probs < top_p
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


class Generator:
    """Model-agnostic generation loop over jitted prefill/decode.

    prefill_fn(params, tokens, cache) -> (logits [B,T,V], cache)
    decode_fn(params, token [B,1], cache) -> (logits [B,1,V], cache)
    alloc_cache(batch, max_seq) -> KVCache
    """

    def __init__(self, params, prefill_fn, decode_fn, alloc_cache,
                 eos_token_id: Optional[int] = None):
        self.params = params
        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn)
        self._alloc = alloc_cache
        self.eos = eos_token_id

    def generate(self, tokens, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 rng: Optional[jax.Array] = None, max_seq: Optional[int] = None):
        """tokens: [B, T] prompt → [B, T + max_new_tokens] (eos-padded)."""
        return generate_loop(
            self.params, self._prefill, self._decode, self._alloc, tokens,
            max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, rng=rng, max_seq=max_seq, eos=self.eos)


def generate_loop(params, prefill, decode, alloc_cache, tokens,
                  max_new_tokens: int = 32, temperature: float = 0.0,
                  top_k: int = 0, top_p: float = 1.0,
                  rng: Optional[jax.Array] = None,
                  max_seq: Optional[int] = None, eos: Optional[int] = None):
    """The host-side autoregressive loop shared by :class:`Generator` and
    the hybrid engine: prefill once, then decode one token at a time with
    on-device sampling.  ``prefill``/``decode`` must already be jitted.

    Always returns ``[B, T + max_new_tokens]`` — early all-eos exits pad
    with eos so callers (jitted train steps, slicing code) see one static
    shape regardless of where generation stopped.
    """
    tokens = jnp.asarray(tokens, jnp.int32)
    B, T = tokens.shape
    total = max_seq or (T + max_new_tokens)
    if T + max_new_tokens > total:
        # dynamic_update_slice CLAMPS out-of-bounds cache writes, so an
        # overrun would silently corrupt the rollout instead of failing
        raise ValueError(
            f"prompt ({T}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"KV cache budget (max_seq={total}) — raise max_seq or shorten "
            "the prompt")
    cache = alloc_cache(B, total)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    logits, cache = prefill(params, tokens, cache)
    out = [tokens]
    rng, step_rng = jax.random.split(rng)
    next_tok = sample_logits(logits[:, -1], step_rng, temperature,
                             top_k, top_p)[:, None]
    done = jnp.zeros((B,), bool)
    for produced in range(1, max_new_tokens + 1):
        out.append(next_tok)
        if eos is not None:
            done = done | (next_tok[:, 0] == eos)
            if produced < max_new_tokens and bool(done.all()):
                out.append(jnp.full((B, max_new_tokens - produced), eos,
                                    jnp.int32))
                break
        if produced == max_new_tokens:
            break
        logits, cache = decode(params, next_tok, cache)
        rng, step_rng = jax.random.split(rng)
        nxt = sample_logits(logits[:, -1], step_rng, temperature,
                            top_k, top_p)[:, None]
        if eos is not None:
            nxt = jnp.where(done[:, None], jnp.int32(eos), nxt)
        next_tok = nxt
    return jnp.concatenate(out, axis=1)


def greedy_draft_fn(step, alloc_cache, window: int, k: int):
    """One-dispatch greedy rollout for speculative drafting (see
    :class:`~deepspeed_tpu.inference.speculative.ModelDrafter`): jit of
    ``(params, tokens [B, window]) -> drafts [B, k]`` — prefill the
    (left-padded) history window once, then ``lax.scan`` ``k`` argmax
    decode steps feeding each token forward.  Everything stays on
    device until the caller fetches the k drafts, so a draft proposal
    costs one dispatch + one transfer regardless of ``k``.

    Drafts only gate PERFORMANCE (the verify pass re-scores them under
    the target model), so the fixed window and its padded positions
    trade draft quality for a single compiled shape — never
    correctness."""

    def rollout(params, tokens):
        cache = alloc_cache(tokens.shape[0], window + k)
        logits, cache = step(params, tokens, cache)
        first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

        def one(carry, _):
            tok, c = carry
            logits, c = step(params, tok[:, None], c)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (nxt, c), tok

        (_, _), toks = jax.lax.scan(one, (first, cache), None, length=k)
        return jnp.swapaxes(toks, 0, 1)                   # [B, k]

    return jax.jit(rollout)


def cached_step_alloc(cfg, cache_dtype=jnp.bfloat16):
    """The (step, alloc_cache) pair over the contiguous-cache forward —
    shared by :func:`generator`, the drafter and the hybrid engine so the
    cache wiring lives once.  ``alloc_cache`` refuses a ``max_seq`` the
    family cannot hold (a learned position table's rows: a traced gather
    CLAMPS out-of-range indices, so generating past the table would
    silently reuse the last position's embedding)."""
    fam = decoder_family(cfg)
    fam.refuse(contiguous_cache=True)

    def alloc(batch, max_seq):
        fam.check(cfg, None, max_seq)
        return KVCache.alloc(cfg.n_layers, batch, max_seq, cfg.n_kv_heads,
                             cfg.head_dim, dtype=cache_dtype)

    def step(params, tokens, cache):
        return forward_with_cache(params, tokens, cfg, cache)

    return step, alloc


def generator(params, cfg, eos_token_id: Optional[int] = None,
              cache_dtype=jnp.bfloat16) -> Generator:
    """Cached-attention generation for any decoder family's weights
    (MoE: capacity-free dense top-k expert combine, ref: DeepSpeed-MoE
    inference)."""
    step, alloc = cached_step_alloc(cfg, cache_dtype)
    return Generator(params, step, step, alloc, eos_token_id=eos_token_id)


def paged_generator(params, cfg, eos_token_id: Optional[int] = None,
                    page_size: int = 16, num_pages: Optional[int] = None,
                    cache_dtype=jnp.bfloat16) -> Generator:
    """Paged-KV generation over :func:`forward_paged` — the offline
    oracle for serving (ref contract: deepspeed/ops/transformer/
    inference decode kernels + their preallocated KV workspace)."""
    def alloc(batch, max_seq):
        mp = -(-max_seq // page_size)
        n = num_pages if num_pages is not None else batch * mp
        return PagedKVCache.alloc(cfg.n_layers, cfg.n_kv_heads, n, page_size,
                                  cfg.head_dim, batch, max_seq,
                                  dtype=cache_dtype)

    def step(params, tokens, cache):
        return forward_paged(params, tokens, cfg, cache)

    return Generator(params, step, step, alloc, eos_token_id=eos_token_id)
