#!/usr/bin/env python3
"""The quickest proof that deepspeed_tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the full width of GPT-2 1.3B (dim 2048, 16 heads of 128, vocab 50257,
context 1024 — ``GPT2Config.gpt2_1_3b()``, BASELINE.json config #2):

- train: ``deepspeed_tpu.initialize`` (bf16, AdamW, ZeRO stage 2), depth
  cut to 8 layers so master + moments + compute copy fit one chip's HBM;
  one warm-up step, then 5 steps timed to ``block_until_ready`` and 5
  timed to a fetched value; the loss must be finite and fall.
- serve: all 24 layers in bf16 through ``serving_engine`` with the
  readers its build chooses; 8 seeded requests of 256-768 prompt tokens and
  64 new tokens, greedy; every served token must be a (near-)argmax of
  the un-paged ``gpt2.forward`` on the same weights, no page may leak
  and nothing may compile after the first token.

``--chips 4`` runs, instead of both, the train phase's model and global
batch under ZeRO stage 3 on four devices against stage 0 on one.

Every line of standard output is one JSON object.  The times in them are
smoke numbers, not benchmark results.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and is printed only after every phase passed on a TPU; any failure is an
exception and a non-zero exit.  ``--rehearse`` (only with
``JAX_PLATFORMS=cpu``) walks the same control flow at a toy size and
never prints that line.

One process runs everything: a chip belongs to one process at a time.
"""

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

SMOKE = "smoke numbers, not benchmark results"


@dataclasses.dataclass(frozen=True)
class Size:
    """One model and traffic size; widths are the config's own."""

    cfg: object                 # GPT2Config at full depth
    train_layers: int           # depth of the train phase
    micro_batch: int
    remat: str
    max_batch: int              # serving slots = number of requests
    page_size: int
    prefill_bucket: int
    prompt_lo: int
    prompt_hi: int
    new_tokens: int

    @classmethod
    def real(cls):
        from deepspeed_tpu.models import gpt2

        # micro-batch and remat come from memory_analysis() of the step
        # compiled for a described v5e (PERF.md, PR 21): state 5.68 GiB;
        # temporaries 9.16 GiB at B4 without remat (14.84 of 15.75 — too
        # tight), 4.75 GiB at B4 with save_dots (10.43 GiB).
        # prefill_bucket 256: every padded prompt is a multiple of 128
        # and >= 256, the shapes ops/attention.py sends to the kernel.
        return cls(cfg=gpt2.GPT2Config.gpt2_1_3b(), train_layers=8,
                   micro_batch=4, remat="save_dots", max_batch=8,
                   page_size=16, prefill_bucket=256, prompt_lo=256,
                   prompt_hi=768, new_tokens=64)

    @classmethod
    def toy(cls):
        from deepspeed_tpu.models import gpt2

        return cls(cfg=gpt2.GPT2Config.tiny(vocab_size=512, dim=128,
                                            n_layers=3, n_heads=2,
                                            max_seq_len=128),
                   train_layers=2, micro_batch=4, remat="save_dots",
                   max_batch=4, page_size=8, prefill_bucket=16,
                   prompt_lo=16, prompt_hi=48, new_tokens=8)


def emit(**obj):
    print(json.dumps(obj), flush=True)


def memory(devices):
    """Per-device HBM limit, bytes held now and the peak since the
    process started (None where the backend keeps no statistics, as on
    the CPU)."""
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append({"limit_bytes": st.get("bytes_limit"),
                    "in_use_bytes": st.get("bytes_in_use"),
                    "peak_bytes": st.get("peak_bytes_in_use")})
    return out


class CacheCounter:
    """Persistent-compilation-cache hits and writes, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.hits = self.writes = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def since(self, mark):
        return {"hits": self.hits - mark[0], "writes": self.writes - mark[1]}

    def mark(self):
        return (self.hits, self.writes)


# ---------------------------------------------------------------- kernels
def kernel_check(size, seed):
    """The flash kernel (TPU only) against the jnp reference on a small
    input, plain and packed, forward and gradients: the first thing to
    know when a later phase disagrees."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import _reference
    from deepspeed_tpu.ops.attention_pallas import flash_attention_tpu

    T, H, D = 512, size.cfg.n_heads, size.cfg.head_dim
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(x, (2, T, H, D), jnp.bfloat16)
               for x in (kq, kk, kv))
    # three documents per row, boundaries off the 128-token tiles
    seg = jnp.asarray(np.searchsorted([T // 3 + 5, 2 * T // 3 + 11],
                                      np.arange(T), side="right")[None]
                      .repeat(2, 0), jnp.int32)

    def both(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

        return jax.jit(lambda q, k, v: (
            fn(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))

    # bf16 keeps 8 significant bits; kernel and reference both round the
    # probabilities to bf16 before the PV product, in different block
    # orders: 3e-2 of the largest value is the repo's own bf16 bound
    # (tests/test_attention_pallas.py::test_bf16_forward), gradients
    # pass through one more bf16 product
    tol = {"out": 3e-2, "grad": 6e-2}
    worst = {}
    for name, s in (("plain", None), ("packed", seg)):
        got = both(lambda q, k, v: flash_attention_tpu(
            q, k, v, causal=True, segment_ids=s))(q, k, v)
        want = both(lambda q, k, v: _reference(
            q, k, v, causal=True, segment_ids=s))(q, k, v)
        for part, a, b in zip(("out", "grad", "grad", "grad"), got, want):
            a, b = (np.asarray(x, np.float32) for x in (a, b))
            if not np.isfinite(a).all():
                raise AssertionError(f"flash {name} {part}: not finite")
            err = float(np.abs(a - b).max() / np.abs(b).max())
            key = f"{name}_{part}"
            worst[key] = max(worst.get(key, 0.0), err)
            if err > tol[part]:
                raise AssertionError(
                    f"flash {name} {part}: {err:.4f} of max > {tol[part]}")
    emit(phase="kernel_check", shape=[2, T, H, D], dtype="bfloat16",
         rel_err_of_max=worst, tolerance=tol)


def state_chunk_check(seed, on_tpu):
    """A prompt chunk's delta rule in ``dstpu_state_chunk`` against
    ``gdn_chunk_rule`` at ``HIGHEST`` on the same operands, at the
    recurrent cell's shape (32 value heads over 16 key heads of 128, a
    chunk of 1,024, a random state, the last eighth of the rows masked;
    a toy shape in interpret mode off the chip): o and S within 1e-4 of
    the rule's by norm, and a chunk with no real row leaves S bit for
    bit."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.kernels import state_chunk
    from deepspeed_tpu.models import qwen3_next as qn
    from deepspeed_tpu.models.family import SlotState

    T, Hk, Hv, D, block = (1024, 16, 32, 128, 64) if on_tpu \
        else (32, 2, 4, 16, 8)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda t: t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    q = l2(jax.random.normal(ks[0], (1, T, Hk, D))) * D ** -0.5
    k = l2(jax.random.normal(ks[1], (1, T, Hk, D)))
    v = jax.random.normal(ks[2], (1, T, Hv, D))
    real = (jnp.arange(T) < T - T // 8)[None, :, None]
    g = jnp.where(real, -jnp.exp(jax.random.normal(ks[3], (1, T, Hv)) - 2.0),
                  0.0)
    beta = jnp.where(real, jax.nn.sigmoid(
        jax.random.normal(ks[4], (1, T, Hv))), 0.0)
    S = jax.random.normal(ks[5], (1, Hv, D, D))
    wide = lambda t: jnp.repeat(t, Hv // Hk, axis=2)
    rule = jax.jit(lambda g, beta: qn.gdn_chunk_rule(
        wide(q), wide(k), v, g, beta, S, block))
    chunk = functools.partial(state_chunk, interpret=not on_tpu)
    kernel = jax.jit(lambda g, beta: qn.gdn_chunk_kernel(
        q, k, v, g, beta, SlotState(S, chunk), block))
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    err = dict(zip(("o", "S"), map(rel, kernel(g, beta), rule(g, beta))))
    tol = 1e-4
    for name, e in err.items():
        if not e <= tol:
            raise AssertionError(f"state chunk {name}: {e:.3g} > {tol}")
    _, kept = kernel(jnp.zeros_like(g), jnp.zeros_like(beta))
    if not np.array_equal(np.asarray(kept), np.asarray(S)):
        raise AssertionError("a chunk with no real row moved the state")
    emit(phase="state_chunk_check", shape=[T, Hk, Hv, D], block=block,
         rel_err_by_norm=err, tolerance=tol, masked_chunk="bit for bit")


# decode shapes of the cells whose programs run ``dstpu_paged_decode``:
# (slots, query heads, K/V heads, table entries)
DECODE_ROW_SHAPES = {
    "gpt2 chat-0.8knee": (28, 16, 16, 64),
    "mixtral chat-sat": (64, 32, 8, 64),
    "laguna code-sat": (8, 48, 8, 128),
}


def decode_row_check(seed, on_tpu):
    """A decode step's new K/V row through ``dstpu_paged_decode`` (it
    attends to the row from VMEM and copies it to its page by the tile of
    8 packed rows, which interpret mode cannot judge) against the row
    scatter and the gather, bfloat16 pages of 16 x 128 at three cells'
    shapes (toys in interpret mode off the chip): rows that open a page,
    fill one, stand at capacity, idle slots on the trash page.  Both
    pools bit for bit (the trash page's row 0 is any idle slot's), the
    attention of the live rows within bfloat16's 3e-2."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import kernels as K

    shapes = DECODE_ROW_SHAPES if on_tpu else {"toy": (6, 6, 2, 4)}
    fused = functools.partial(K.paged_decode_attention_v2,
                              interpret=not on_tpu)
    worst, tol = {}, 3e-2
    for name, (B, H, KV, mp) in shapes.items():
        rng = np.random.default_rng(seed)
        P, cap = B * mp + 1, mp * 16
        keys = jax.random.split(jax.random.PRNGKey(seed), 5)
        bf = lambda key, *shape: jax.random.normal(key, shape, jnp.bfloat16)
        k, v = (bf(key, 3, KV, P, 16, 128) for key in keys[:2])
        q, nk, nv = bf(keys[2], B, H, 128), bf(keys[3], B, KV, 128), \
            bf(keys[4], B, KV, 128)
        lens = rng.integers(1, cap, B).astype(np.int32)
        lens[:6] = [0, cap, 16, cap - 1, 0, 15]
        table = rng.permutation(P - 1)[:B * mp].reshape(B, mp)
        table[lens == 0] = P - 1                    # idle: the trash page
        table, n = jnp.asarray(table, jnp.int32), jnp.asarray(lens)
        rk, rv = jax.jit(lambda k, v: K.write_token_pages(
            k, v, 1, nk, nv, table, n))(k, v)
        ref = jax.jit(lambda: K.paged_attention_reference(
            q, rk, rv, table, jnp.minimum(n + 1, cap), layer=1))()
        out, ok, ov = jax.jit(lambda k, v, layer: fused(
            q, k, v, table, n, layer=layer, new_k=nk, new_v=nv))(
                k, v, jnp.int32(1))
        live = lens > 0
        err = float(np.abs(np.asarray(out, np.float32)
                           - np.asarray(ref, np.float32))[live].max())
        worst[name] = err
        for got, want, was in ((ok, rk, k), (ov, rv, v)):    # on the device
            if not (jnp.array_equal(got[:, :, :P - 1], want[:, :, :P - 1])
                    and jnp.array_equal(got[:, :, P - 1, 1:],
                                        was[:, :, P - 1, 1:])):
                raise AssertionError(f"decode row {name}: the pool differs "
                                     "from the row scatter's")
        if not err <= tol:
            raise AssertionError(f"decode row {name}: {err:.3g} > {tol}")
    emit(phase="decode_row_check", shapes={k: list(v) for k, v in
                                           shapes.items()},
         max_abs_err=worst, tolerance=tol, pools="bit for bit")


# chunk shapes of the cells whose expert layers run the grouped branch:
# (rows, k, held, experts, d, f, gated)
HELD_FFN_SHAPES = {
    "qwen3-next docqa-sat": (1024, 10, 64, 512, 2048, 512, True),
    "ling docqa-sat": (1024, 8, 64, 512, 2560, 768, True),
    "laguna code-sat": (1024, 10, 16, 256, 3072, 1024, True),
    "nemotron code-sat": (1024, 6, 16, 128, 2688, 1920, False),
    "mixtral docs-sat": (1024, 2, 8, 8, 4096, 14336, True),
}


def held_ffn_check(seed, on_tpu):
    """A pass of the held experts in ``dstpu_held_ffn`` against XLA's
    ``ragged_dot`` and the slot-by-slot combine on the same operands
    (``held_experts_ffn`` with and without the kernel), a layer of a
    two-layer stack, at the five cells' chunk shapes under a router
    drawn from the seed (a toy shape in interpret mode off the chip):
    within bf16's rounding of the largest value, and the ms a call
    beside ``benchmark/roofline/moe.py``'s floor for the rows drawn."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.roofline import moe as gated_floor, moe_ungated
    from deepspeed_tpu.parallel import moe

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "peaks.json")) as fh:
        peaks = json.load(fh)["devices"]["TPU v5 lite"]
    shapes = HELD_FFN_SHAPES if on_tpu else {
        "toy": (48, 2, 4, 8, 128, 256, True),
        "toy, two matrices": (48, 2, 4, 8, 128, 256, False)}
    dtype, L, tol = (jnp.bfloat16 if on_tpu else jnp.float32), 2, 3e-2
    laps = 8 if on_tpu else 1
    relu2 = lambda a: jnp.square(jax.nn.relu(a))
    was = moe._on_chip, moe._every_row_pays
    for name, (N, k, Eh, E, d, f, gated) in shapes.items():
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        first = Eh if E > Eh else 0
        h = jax.random.normal(ks[0], (N, d), dtype)
        wts, experts = jax.lax.top_k(jax.nn.softmax(
            jax.random.normal(ks[1], (N, E))), k)
        g = lambda key, *s: (jax.random.normal(key, s) * s[-2] ** -0.5
                             ).astype(dtype)
        w1, w2 = g(ks[2], L, Eh, d, f), g(ks[4], L, Eh, f, d)
        w3 = g(ks[3], L, Eh, d, f) if gated else None

        def layers(h, w1, w3, w2):
            def one(l, acc):
                y, _ = moe.held_experts_ffn(
                    h, wts, experts.astype(jnp.int32), w1, w3, w2,
                    first=first, layer=l, n_experts=E,
                    act=None if gated else relu2)
                return acc + y.astype(jnp.float32)
            # each layer several times a dispatch: the host's part of a
            # call is then a small share of what is timed
            return jax.lax.fori_loop(0, L * laps, lambda i, acc: one(
                i % L, acc), jnp.zeros((N, d)))

        got = {}
        try:
            moe._every_row_pays = lambda *a: False
            for which in (True, False):
                moe._on_chip = lambda which=which: which
                fn = jax.jit(lambda *a: layers(*a))    # a trace a side
                y = jax.block_until_ready(fn(h, w1, w3, w2))
                t0 = time.perf_counter()
                for _ in range(5):
                    y = fn(h, w1, w3, w2)
                jax.block_until_ready(y)
                got[which] = (np.asarray(y, np.float32),
                              (time.perf_counter() - t0) / 5 / (L * laps)
                              * 1e3)
        finally:
            moe._on_chip, moe._every_row_pays = was
        (y, ms), (ref, ref_ms) = got[True], got[False]
        if not np.isfinite(y).all():
            raise AssertionError(f"held ffn {name}: not finite")
        err = float(np.abs(y - ref).max() / np.abs(ref).max())
        if not err <= tol:
            raise AssertionError(f"held ffn {name}: {err:.4f} of max > {tol}")
        local = np.asarray(experts) - first
        rows = np.bincount(local[(local >= 0) & (local < Eh)], minlength=Eh)
        floor_ms = 1e3 * (gated_floor if gated else moe_ungated).floor_seconds(
            d, f, N * k, [r / (N * k) for r in rows], peaks)
        emit(phase="held_ffn_check", cell=name, shape=[N, k, Eh, E, d, f],
             gated=gated, rows_held=int(rows.sum()),
             tiles=list(moe._held_ffn_tiles(N, k, E, d, f, w1.dtype.itemsize,
                                            2 + gated)),
             err_of_max=err, tolerance=tol,
             **({"ms_a_call": ms, "ragged_dot_ms_a_call": ref_ms,
                 "floor_ms": floor_ms} if on_tpu else {}))
        del w1, w2, w3


# ------------------------------------------------------------------ train
def build_trainer(size, seed, stage, chips):
    """(engine, batch, cfg): the train phase's model through
    ``deepspeed_tpu.initialize`` at one ZeRO stage, data-parallel over
    the first ``chips`` devices."""
    import jax
    import numpy as np

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.topology import default_mesh

    cfg = dataclasses.replace(size.cfg, n_layers=size.train_layers,
                              remat=size.remat)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=gpt2.loss_fn(cfg),
        # a thunk: the state is initialised inside a jit, directly in
        # its ZeRO layout, never whole on one device
        params=lambda: gpt2.init_params(jax.random.PRNGKey(seed), cfg),
        mesh=default_mesh(chips),
        config={
            "train_micro_batch_size_per_gpu": size.micro_batch // chips,
            "zero_optimization": {"stage": stage},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
        })
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (size.micro_batch, cfg.max_seq_len + 1),
        dtype=np.int32)
    return engine, {"tokens": tokens}, cfg


def run_steps(engine, batch, n, end):
    """n steps on one batch; the clock stops at ``block_until_ready`` of
    the last loss or at its fetched value.  Returns (seconds, losses)."""
    import jax

    t0 = time.perf_counter()
    losses = [engine.train_batch(batch) for _ in range(n)]
    if end == "block":
        jax.block_until_ready(losses[-1])
    else:
        float(losses[-1])
    dt = time.perf_counter() - t0
    return dt, [float(x) for x in losses]


def check_losses(losses, what):
    import math

    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{what}: loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall: {losses}")


def train_phase(size, seed, cache):
    import jax

    from deepspeed_tpu.models import gpt2

    mark, t0 = cache.mark(), time.perf_counter()
    engine, batch, cfg = build_trainer(size, seed, stage=2, chips=1)
    losses = [float(engine.train_batch(batch))]      # warm-up: compiles
    cold_s = time.perf_counter() - t0
    t_block, l1 = run_steps(engine, batch, 5, "block")
    t_fetch, l2 = run_steps(engine, batch, 5, "fetch")
    losses += l1 + l2
    check_losses(losses, "train")
    tokens = size.micro_batch * cfg.max_seq_len
    emit(phase="train", note=SMOKE, model="gpt2_1_3b",
         reduced={"n_layers": {
             "published": size.cfg.n_layers, "used": cfg.n_layers,
             "why": "f32 master + Adam moments + bf16 copy of all "
                    f"{size.cfg.n_layers} layers do not fit one chip"}},
         params=gpt2.param_count(cfg), zero_stage=2,
         micro_batch=size.micro_batch, seq=cfg.max_seq_len,
         remat=cfg.remat, cold_start_s=round(cold_s, 2),
         compile_cache=cache.since(mark), losses=losses,
         steps_5_to_block_until_ready_s=round(t_block, 4),
         steps_5_to_fetched_value_s=round(t_fetch, 4),
         block_vs_fetch=round(t_block / t_fetch, 3),
         step_s=round(t_fetch / 5, 4),
         tokens_per_s=round(5 * tokens / t_fetch, 1),
         memory=memory(jax.devices()[:1]))


# ------------------------------------------------------------------ serve
def serve_phase(size, seed, cache, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.kernels import PagedKVCache
    from deepspeed_tpu.inference.serving import serving_engine
    from deepspeed_tpu.models import gpt2

    cfg = size.cfg
    params = jax.jit(lambda k: gpt2.init_params(k, cfg, jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
               for n in rng.integers(size.prompt_lo, size.prompt_hi + 1,
                                     size.max_batch)]

    pages_per_seq = -(-cfg.max_seq_len // size.page_size)
    mark, t0 = cache.mark(), time.perf_counter()
    # devprof's build-time warm-up compiles every program the engine can
    # dispatch, so what compiles later is counted as a steady-state
    # compile
    eng = serving_engine(
        params, cfg, max_batch=size.max_batch, page_size=size.page_size,
        num_pages=size.max_batch * pages_per_seq + 1,
        max_seq=cfg.max_seq_len, prefill_bucket=size.prefill_bucket,
        telemetry=True, devprof=True)
    cold_s = time.perf_counter() - t0
    cache_use = cache.since(mark)

    # did prefill reach the flash kernel?  Read it off the program the
    # engine runs, lowered at the first bucket (the view _admit_one
    # hands it); lowering compiles nothing
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    view = PagedKVCache(
        k=shape(eng.cache.k), v=shape(eng.cache.v),
        table=jax.ShapeDtypeStruct((1, pages_per_seq), jnp.int32),
        seq_lens=jax.ShapeDtypeStruct((1,), jnp.int32),
        page_size=size.page_size)
    bucket0 = -(-size.prompt_lo // size.prefill_bucket) * size.prefill_bucket
    prefill_flash = "tpu_custom_call" in eng._prefill.lower(
        jax.tree.map(shape, params),
        jax.ShapeDtypeStruct((1, bucket0), jnp.int32), view,
        jax.ShapeDtypeStruct((1,), jnp.int32)).as_text()
    if on_tpu and not prefill_flash:
        raise AssertionError("prefill did not reach the flash kernel")

    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new_tokens=size.new_tokens)
    t0 = time.perf_counter()
    done = eng.run()
    serve_s = time.perf_counter() - t0
    for i, p in enumerate(prompts):
        out = done[i]
        if not isinstance(out, list) or out[:len(p)] != p \
                or len(out) != len(p) + size.new_tokens:
            raise AssertionError(f"request {i} did not finish: {out!r:.200}")
    leaks = eng.check_leaks()
    status = eng.statusz()
    counters = eng.registry.snapshot()["counters"]
    mem = memory(jax.devices()[:1])
    eng.shutdown()
    del eng
    gc.collect()
    if leaks:
        raise AssertionError(f"leaked pages: {leaks}")
    if status["devprof"]["compiles_steady"] != 0:
        raise AssertionError(f"steady-state compiles: {status['devprof']}")

    # the un-paged forward over prompt + served tokens: its logits at
    # position p predict token p + 1
    T = cfg.max_seq_len

    @jax.jit
    def gaps(params, toks):
        lg = gpt2.forward(params, toks[None], cfg)[0]           # [T, V]
        nxt = jnp.roll(toks, -1)
        top = lg.max(-1)
        return (top - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0], top,
                lg.argmax(-1) == nxt, jnp.isfinite(lg).all())

    # Why tokens, and why with a margin.  The engine hands out tokens,
    # not logits, and on random weights the top two of 50257 logits are
    # often closer than bf16 rounding, so bit-equal greedy streams
    # cannot be asked of two programs that round in different places
    # (flash blocks vs gathered pages, 24 layers deep).  Each served
    # token must instead be a near-argmax of the reference: within 2^-5
    # of the top logit's magnitude (bf16 keeps 8 bits: ~16 roundings of
    # 2^-9).  A wrong page, position or cache row yields an unrelated
    # token, whose logit sits several units lower, far outside this.
    worst, worst_top, exact, total = 0.0, None, 0, 0
    for i, p in enumerate(prompts):
        seq = np.zeros(T, np.int32)
        seq[:len(done[i])] = done[i]
        gap, top, same, finite = gaps(params, jnp.asarray(seq))
        if not bool(finite):
            raise AssertionError(f"request {i}: reference logits not finite")
        span = slice(len(p) - 1, len(done[i]) - 1)
        gap, top = np.asarray(gap)[span], np.asarray(top)[span]
        over = gap > 2.0 ** -5 * np.maximum(np.abs(top), 1.0)
        if over.any():
            raise AssertionError(
                f"request {i}: {int(over.sum())} served tokens are not "
                f"near-argmax of gpt2.forward (worst gap {gap.max():.3f} "
                f"at top logit {top[gap.argmax()]:.3f})")
        if gap.max() >= worst:
            worst, worst_top = float(gap.max()), float(top[gap.argmax()])
        exact += int(np.asarray(same)[span].sum())
        total += gap.size

    emit(phase="serve", note=SMOKE, model="gpt2_1_3b", reduced={},
         n_layers=cfg.n_layers, dtype="bfloat16",
         requests=len(prompts), prompt_tokens=[len(p) for p in prompts],
         new_tokens=size.new_tokens, cold_start_s=round(cold_s, 2),
         compile_cache=cache_use, serve_s=round(serve_s, 3),
         generated_tokens_per_s=round(total / serve_s, 1),
         kernels=status["kernels"], prefill_reached_flash=prefill_flash,
         dispatch={k: v for k, v in counters.items()
                   if k.startswith("serving_kernel_")},
         compiles_warmup=status["devprof"]["compiles_warmup"],
         compiles_steady=status["devprof"]["compiles_steady"],
         leaks=leaks, reference="gpt2.forward",
         tokens_exact_argmax=[exact, total],
         worst_gap_to_top_logit=round(worst, 4),
         top_logit_there=round(worst_top, 4), memory=mem)


# ------------------------------------------------------------- four chips
def four_chip_phase(size, seed, chips):
    """ZeRO stage 3 on every device against stage 0 on one, same seed
    and global batch, in this order: peak memory cannot be reset, so the
    sharded run reads its peaks before the one-device run adds to them."""
    import jax
    import numpy as np

    def leaf_bytes(tree, dev):
        return sum(s.data.nbytes for x in jax.tree.leaves(tree)
                   for s in x.addressable_shards if s.device == dev)

    devs = jax.devices()[:chips]
    t0 = time.perf_counter()
    engine, batch, cfg = build_trainer(size, seed, 3, chips)
    # what GSPMD put in the compiled step (engine.comms_digest reads the
    # HLO).  XLA:CPU spells a reduce-scatter as all-reduce + slice
    collectives = engine.comms_digest(batch)["per_kind"]
    reduce = "reduce-scatter" if devs[0].platform == "tpu" else "all-reduce"
    if not ("all-gather" in collectives and reduce in collectives):
        raise AssertionError(f"stage 3 step lacks collectives: {collectives}")
    _, sharded = run_steps(engine, batch, 6, "fetch")
    sharded_s = time.perf_counter() - t0
    total = sum(x.nbytes for x in jax.tree.leaves(
        (engine.state.params, engine.state.opt_state)))
    share = [leaf_bytes((engine.state.params, engine.state.opt_state), d)
             / total for d in devs]
    mem = memory(devs)
    del engine
    gc.collect()
    # "about a quarter": the few leaves no axis divides stay replicated
    if max(share) > 1.1 / chips:
        raise AssertionError(f"state is not spread over {chips}: {share}")
    peaks = [m["peak_bytes"] for m in mem]
    if all(p is not None for p in peaks) and max(peaks) > 1.1 * min(peaks):
        raise AssertionError(f"per-device peaks differ: {peaks}")

    t0 = time.perf_counter()
    engine, batch, _ = build_trainer(size, seed, 0, 1)
    _, single = run_steps(engine, batch, 6, "fetch")
    single_s = time.perf_counter() - t0
    del engine
    gc.collect()
    check_losses(sharded, "stage 3")
    check_losses(single, "stage 0")
    # same seed, same batch, same f32 master arithmetic; the two programs
    # differ in batch shape per chip (1 against 4) and in the order the
    # gradients are summed (a reduce-scatter of four per-chip sums), so
    # bf16 roundings fall differently.  Step for step the losses must
    # agree to 2^-7 — two bf16 ulps; a wrong shard or a lost
    # contribution moves the loss by far more within a step or two
    diff = np.abs(np.subtract(sharded, single))
    tol = 2.0 ** -7
    if (diff > tol * np.asarray(single)).any():
        raise AssertionError(
            f"stage 3 on {chips} != stage 0 on 1: {sharded} vs {single}")
    emit(phase="zero3_vs_zero0", note=SMOKE, model="gpt2_1_3b",
         n_layers=cfg.n_layers, chips=chips, global_batch=size.micro_batch,
         losses_stage3=sharded, losses_stage0=single,
         max_rel_loss_diff=float((diff / single).max()), tolerance=tol,
         collectives_in_step=collectives,
         state_share_per_device=[round(s, 4) for s in share],
         memory_stage3=mem, stage3_s=round(sharded_s, 2),
         stage0_s=round(single_s, 2))


# ------------------------------------------------------------------- main
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: ZeRO-3 over four chips against ZeRO-0 on "
                         "one, and no other phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on the CPU (needs JAX_PLATFORMS=cpu); "
                         "never prints the ok line")
    args = ap.parse_args()
    if args.rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("--rehearse runs only with JAX_PLATFORMS=cpu")

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from deepspeed_tpu.utils import backend

    if args.rehearse and args.chips > 1:
        from deepspeed_tpu.mesh import host_device_count

        host_device_count(args.chips)
    import jax
    import jaxlib

    device = backend.device_info() if args.rehearse \
        else backend.require_tpu()
    if device["count"] < args.chips:
        raise SystemExit(f"--chips {args.chips} on {device}")
    cache_dir = backend.enable_compile_cache()
    cache = CacheCounter()

    from importlib import metadata

    from deepspeed_tpu.io import aio, native
    from deepspeed_tpu.ops import cpu_adam

    helpers = {"aio": aio._ensure_lib() is not None,
               "host": native._ensure_lib() is not None,
               "cpu_adam": cpu_adam._ensure_lib() is not None}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    emit(phase="env", note=SMOKE, jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu,
         python=sys.version.split()[0], device=device,
         compile_cache_dir=cache_dir, native_helpers=helpers,
         memory=memory(jax.devices()[:args.chips]),
         rehearsal=args.rehearse)
    if not all(helpers.values()):
        raise AssertionError(f"native helpers did not build: {helpers}")

    size = Size.toy() if args.rehearse else Size.real()
    if args.chips > 1:
        four_chip_phase(size, args.seed, args.chips)
    else:
        on_tpu = device["platform"] == "tpu"
        if on_tpu:
            kernel_check(size, args.seed)
        state_chunk_check(args.seed, on_tpu)
        held_ffn_check(args.seed, on_tpu)
        decode_row_check(args.seed, on_tpu)
        train_phase(size, args.seed, cache)
        gc.collect()                  # the trainer's HBM, before the server
        serve_phase(size, args.seed, cache, on_tpu)
    if args.rehearse:
        emit(rehearsal="passed", device=device)
    else:
        emit(ok=True, device=device)


if __name__ == "__main__":
    main()
