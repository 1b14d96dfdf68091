"""Speculative decoding for the paged-KV serving path: draft-and-verify
multi-token generation that amortizes each model sweep — and, under
ZeRO-Inference, each full layer-weight stream — over several tokens.

Reference framing: speculative sampling (arXiv:2302.01318) + prompt-
lookup decoding, applied to the memory-wall analysis of ZeRO-Inference
(arXiv:2206.01861) and ZeRO-Infinity (arXiv:2104.07857): a weight-
offloaded decode re-streams the ENTIRE layer stack host/NVMe→HBM per
emitted token, so tokens/s is pinned to stream bandwidth.  Scoring K+1
positions in one sweep divides the streamed bytes (and, resident, the
HBM weight reads) per generated token by the mean acceptance length.

The pieces:

- :class:`Drafter` — the proposal interface.  Drafters propose
  DETERMINISTICALLY (greedy); that makes the temperature>0 acceptance
  below exact with the simple point-mass math, for any drafter.
- :class:`NgramDrafter` — zero-weight prompt-lookup: propose the
  continuation that followed the most recent occurrence of the
  sequence's own suffix n-gram (longest n first), self-extending over
  its own draft so loops fill the whole window.  Proposes ``[]`` when
  nothing matches — the verify sweep then degrades to a plain decode
  step for that slot, never an error.
- :class:`ModelDrafter` — a resident small draft model (same family
  forwards the generators use) rolled out greedily over a fixed tail
  window.  One extra device round-trip per slot per sweep — the ngram
  drafter is the zero-cost default; this one pays off when a real
  small model is available and acceptance quality matters more.
- :func:`verify_accept` — the device-side acceptance: given the verify
  pass's logits at all K+1 positions, compute per row the longest
  accepted draft prefix and the bonus/corrected token at every possible
  stop position, so the host needs ONE transfer per sweep.

Exactness.  Greedy rows accept draft ``d_j`` iff it equals the target
argmax at its position — the emitted sequence is bit-for-bit the
sequential greedy decode.  Temperature rows use rejection sampling
against the drafter's point-mass proposal: accept ``d_j`` with
probability ``p_j(d_j)``; on rejection sample from ``p_j`` with
``d_j``'s mass removed (the residual ``max(p - q, 0)`` of a point mass
``q``), which reproduces the target distribution exactly.  Rows whose
drafts ran out (or proposed nothing) sample their stop token from the
full ``p_j`` — a plain decode step riding the same sweep.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.config import SpeculativeConfig


# ------------------------------------------------------------- drafters
class Drafter:
    """Proposal interface for speculative decoding.

    ``propose(tokens, k)`` sees the request's full history (prompt +
    generated so far) and returns up to ``k`` draft continuation
    tokens (possibly ``[]`` — fewer drafts just means a shorter verify
    window for that slot).  Proposals must be DETERMINISTIC given the
    history: the engine's temperature-mode acceptance treats the
    proposal as a point mass, which is exact only for deterministic
    drafters.  Tokens must be valid vocab ids.
    """

    def propose(self, tokens: Sequence[int], k: int) -> List[int]:
        raise NotImplementedError


class NgramDrafter(Drafter):
    """Prompt-lookup / n-gram drafter: zero weights, zero device work.

    The draft for a sequence is the continuation that followed the most
    recent earlier occurrence of its own suffix n-gram, searching the
    longest n first (``max_ngram`` down to ``min_ngram``), and SELF-
    EXTENDING: when the matched continuation runs into the end of the
    history, matching restarts over history + draft-so-far until ``k``
    tokens are drafted or nothing matches — so a period-``p`` decode
    loop drafts the full ``k`` window, not just ``p`` tokens.
    Repetitive traffic — code, templated documents, multi-turn chat,
    and the loops greedy decoding itself falls into — makes this
    surprisingly strong for its price (the classic prompt-lookup
    observation).
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 lookback: int = 512):
        if not 1 <= int(min_ngram) <= int(max_ngram):
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"min_ngram={min_ngram} max_ngram={max_ngram}")
        if int(lookback) < 1:
            raise ValueError(f"lookback must be >= 1, got {lookback}")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)
        # bound the host-side scan: drafting runs per slot per sweep on
        # the scheduler's critical path, and a miss-heavy (random)
        # history would otherwise pay O(T) slice comparisons per ngram
        # size for every emitted token.  The live decode loop sits at
        # the frontier, so a bounded window loses almost nothing.
        self.lookback = int(lookback)

    def propose(self, tokens: Sequence[int], k: int) -> List[int]:
        out: List[int] = []
        ext = list(tokens)
        # SELF-EXTENSION: when a match's continuation runs into the end
        # of the history (the live frontier — exactly where a greedy
        # loop's most recent occurrence sits), re-match on history +
        # draft-so-far and keep drafting.  The verify window is a fixed
        # K+1 positions whether the draft is 1 token or K, so a longer
        # draft costs nothing — a period-p loop fills the whole window
        # instead of stalling at p-ish tokens per sweep.
        while len(out) < k:
            got = self._match_once(ext, k - len(out))
            if not got:
                break
            out.extend(got)
            ext.extend(got)
        return out

    def _match_once(self, tokens: List[int], k: int) -> List[int]:
        tokens = tokens[-self.lookback:]
        T = len(tokens)
        if k <= 0 or T < self.min_ngram + 1:
            return []
        for n in range(min(self.max_ngram, T - 1), self.min_ngram - 1,
                       -1):
            tail = tokens[-n:]
            # most recent EARLIER occurrence (j + n <= T - 1 so the
            # match is never the suffix itself and the continuation is
            # non-empty)
            for j in range(T - n - 1, -1, -1):
                if tokens[j:j + n] == tail:
                    return tokens[j + n:j + n + k]
        return []


class ModelDrafter(Drafter):
    """Resident small-model drafter: greedy ``k``-token rollout of a
    draft model over the tail of the history, reusing the
    contiguous-cache forward (the same step the generators run — see
    :func:`~deepspeed_tpu.inference.generation.greedy_draft_fn`).

    The history tail is LEFT-padded to a fixed ``window`` so the
    rollout compiles once; padding (and the shifted absolute positions
    it implies) can only degrade draft QUALITY, never correctness —
    rejected drafts cost a rolled-back KV write, nothing else.  Each
    ``propose`` is one jit dispatch + one device fetch per slot per
    sweep; prefer :class:`NgramDrafter` when that round-trip is the
    bottleneck.
    """

    def __init__(self, params, cfg, draft_tokens: int = 4,
                 window: int = 64):
        from deepspeed_tpu.inference.generation import (cached_step_alloc,
                                                        greedy_draft_fn)
        from deepspeed_tpu.models.family import decoder_family

        bound = decoder_family(cfg).max_positions(cfg)
        if bound is not None:
            # learned positions are hard-bounded by the table
            window = min(window, bound - draft_tokens)
        self.params = params
        self.k = int(draft_tokens)
        self.window = int(window)
        if self.k < 1 or self.window < 1:
            raise ValueError(
                f"draft_tokens and window must be >= 1, got "
                f"{draft_tokens}/{window}")
        step, alloc = cached_step_alloc(cfg)
        self._rollout = greedy_draft_fn(step, alloc, self.window, self.k)

    def propose(self, tokens: Sequence[int], k: int) -> List[int]:
        if k <= 0:
            return []
        tail = list(tokens)[-self.window:]
        toks = np.zeros((1, self.window), np.int32)
        toks[0, self.window - len(tail):] = tail
        drafts = np.asarray(self._rollout(self.params, jnp.asarray(toks)))
        return [int(t) for t in drafts[0, :min(k, self.k)]]


def build_drafter(cfg: SpeculativeConfig) -> Drafter:
    """Drafter from the config block.  ``model`` cannot be built here —
    a config block carries no params — so it must arrive as an explicit
    ``drafter=`` instance on the engine."""
    if cfg.drafter == "ngram":
        return NgramDrafter(max_ngram=cfg.max_ngram,
                            min_ngram=cfg.min_ngram)
    raise ValueError(
        f"speculative.drafter={cfg.drafter!r} needs an explicit drafter "
        "instance — build ModelDrafter(draft_params, draft_cfg, "
        "draft_tokens=K) and pass it as serving_engine(..., drafter=)")


# ------------------------------------------------------ device accept
# NOTE: module-level jit shared across engines, so no engine's compile
# sentinel wraps it: the build ledger has its entry by name.
def dstpu_verify(logits, drafts, draft_lens, key, ordinal, temps):
    """Batched acceptance for one verify sweep — ONE host transfer.

    logits: [B, K+1, V] target logits at the K+1 scored positions
    (position 0 = the re-fed last token, positions 1..K = the drafts);
    drafts: [B, K] i32 proposed tokens; draft_lens: [B] i32 how many
    are real per row; key: the engine's base PRNG key and ordinal: i32
    scalar, the sweep's dispatch ordinal — folded together and split
    here, on the device, into one key a (row, position); temps: [B] f32.

    Returns ``(n_acc [B] i32, stop_tok [B, K+1] i32)``: ``n_acc`` is
    the longest accepted draft prefix, and ``stop_tok[:, j]`` is the
    token to emit when acceptance stops at position ``j`` — the
    residual rejection-sample where a draft was rejected, the full
    target sample (argmax for greedy rows) where drafts ran out or at
    the all-accepted bonus position ``K``.  The host emits
    ``drafts[:n_acc] + [stop_tok[n_acc]]`` per row.

    The accept test and the stop-token draw use INDEPENDENT key
    streams (``fold_in`` 0/1): sharing one key would correlate the
    rejection event with the residual draw and bias the output
    distribution.
    """
    lg = logits.astype(jnp.float32)
    B, K1, V = lg.shape
    K = K1 - 1
    greedy = (temps == 0.0)[:, None]                         # [B, 1]
    argmax = jnp.argmax(lg, axis=-1).astype(jnp.int32)       # [B, K+1]
    scaled = lg / jnp.maximum(temps, 1e-6)[:, None, None]
    probs = jax.nn.softmax(scaled, axis=-1)                  # [B, K+1, V]

    flat = jax.random.split(jax.random.fold_in(key, ordinal), B * K1)
    ku = jax.vmap(lambda kk: jax.random.fold_in(kk, 0))(flat)
    ks = jax.vmap(lambda kk: jax.random.fold_in(kk, 1))(flat)
    u = jax.vmap(jax.random.uniform)(ku).reshape(B, K1)[:, :K]

    # accept draft j+1 against the target at position j: greedy rows
    # need exact argmax equality, temperature rows accept with
    # probability p_j(d) (point-mass proposal → always-accept weight 1)
    p_draft = jnp.take_along_axis(
        probs[:, :K], drafts[..., None], axis=-1)[..., 0]    # [B, K]
    in_draft = jnp.arange(K)[None] < draft_lens[:, None]     # [B, K]
    ok = jnp.where(greedy, drafts == argmax[:, :K], u < p_draft)
    ok = ok & in_draft
    n_acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)

    # stop tokens at every position: a rejected draft's replacement
    # samples the residual (p with the draft's mass removed — exact for
    # a point-mass proposal); exhausted-draft and bonus positions
    # sample the full target; greedy rows take the argmax everywhere
    resid = probs[:, :K] * (1.0 - jax.nn.one_hot(drafts, V,
                                                 dtype=jnp.float32))
    cat = jax.vmap(jax.random.categorical)
    resid_tok = cat(ks.reshape(B, K1, 2)[:, :K].reshape(B * K, 2),
                    jnp.log(resid + 1e-30).reshape(B * K, V)
                    ).reshape(B, K).astype(jnp.int32)
    full_tok = cat(ks, scaled.reshape(B * K1, V)
                   ).reshape(B, K1).astype(jnp.int32)
    sampled = jnp.concatenate(
        [jnp.where(in_draft, resid_tok, full_tok[:, :K]),
         full_tok[:, K:]], axis=1)                           # [B, K+1]
    stop = jnp.where(greedy, argmax, sampled)
    return n_acc.astype(jnp.int32), stop


# the jitted program keeps the function's name in a capture
# (jit_dstpu_verify on the "XLA Modules" line)
verify_accept = jax.jit(dstpu_verify)
