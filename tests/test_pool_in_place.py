"""The carried K/V pool against per-layer pools, bit for bit.

``forward_paged`` carries the whole pool ``[L, KV, P, ps, Dh]`` through
its layer loop, scatters only the new rows into it and reads a layer by
its index.  The reference here runs the same layers one at a time, each
on its own ``[KV, P, ps, Dh]`` copy of its layer: ``paged_layered_fns``
where the family streams and the pool is plain, and otherwise the same
factoring spelled out from the family's own pieces (GPT-2 states no
streamed split, and the layered block carries no scale planes).  Logits of
every call and the whole pool at the end, trash page included, must be
identical.  What the compiled programs hold is
``tests/test_aot_tpu_compile.py``'s to say.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kernels import PagedKVCache, paged_attention_step
from deepspeed_tpu.inference.paged_forward import (forward_paged,
                                                   paged_layered_fns)
from deepspeed_tpu.models import gpt2, llama, mixtral

PS, MAX_PAGES, ROWS = 8, 4, 4              # a row holds 32 positions
TRASH = ROWS * MAX_PAGES                   # the last page of the pool
FAMILIES = {
    "gpt2": (gpt2, gpt2.GPT2Config.tiny),
    "llama": (llama, llama.LlamaConfig.tiny),
    "mixtral": (mixtral, mixtral.MixtralConfig.tiny),
}
STEP = dict(reader="xla", flash_force_reference=False)


def _layered(family, cfg, quant):
    """``(stem(params, tokens, start) -> (x, aux),
    block(lp, x, aux, pages, table, start, continuation, prefill)
    -> (x, pages), head(params, x) -> logits)`` over ONE layer's pages
    ``(kp, vp[, kps, vps])``."""
    if family is not gpt2 and not quant:
        stem_fn, block_fn, head_fn = paged_layered_fns(cfg)

        def block(lp, x, aux, pages, table, start, **phase):
            x, kp, vp = block_fn(lp, x, aux, *pages, table, start, **phase)
            return x, (kp, vp)

        return stem_fn, block, head_fn

    if family is gpt2:
        mod, lcfg = gpt2, cfg
        qkv = lambda lp, x, aux: gpt2._qkv(cfg, x, lp)
        out = lambda lp, x, attn: gpt2._out_mlp(cfg, x, attn, lp)

        def stem(p, tokens, start):
            pos = start[:, None] + jnp.arange(tokens.shape[1])[None]
            return p["wte"][tokens] + p["wpe"][pos], ()
    else:
        mod = llama
        lcfg = cfg.llama_view() if family is mixtral else cfg
        qkv = lambda lp, x, aux: llama._qkv(lcfg, x, lp, *aux)
        out = ((lambda lp, x, attn: mixtral._out_moe(cfg, x, attn, lp)[0])
               if family is mixtral
               else (lambda lp, x, attn: llama._out_ffn(lcfg, x, attn, lp)))

        def stem(p, tokens, start):
            pos = start[:, None] + jnp.arange(tokens.shape[1])[None]
            return p["embed"][tokens], llama.rope_tables(lcfg, pos)

    def block(lp, x, aux, pages, table, start, **phase):
        q, k, v = qkv(lp, x, aux)
        kp, vp, kps, vps = (*(p[None] for p in pages), None, None)[:4]
        attn, *pages = paged_attention_step(
            q, k, v, kp, vp, 0, table, start, kps=kps, vps=vps,
            **phase, **STEP)
        B, T = x.shape[:2]
        return (out(lp, x, attn.reshape(B, T, -1)),
                tuple(p[0] for p in pages if p is not None))

    return stem, block, lambda p, x: mod._head(p, x, lcfg)


def _pool(cfg, quant):
    shape = (cfg.n_layers, cfg.n_kv_heads, TRASH + 1, PS, cfg.head_dim)
    if not quant:
        return (jnp.zeros(shape, jnp.float32),) * 2 + (None, None)
    ones = jnp.ones(shape[:-1] + (1,), jnp.float32)
    return (jnp.zeros(shape, jnp.int8),) * 2 + (ones, ones)


@pytest.mark.parametrize("quant", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("name", FAMILIES)
def test_carried_pool_equals_per_layer_pools(name, quant):
    family, tiny = FAMILIES[name]
    cfg = tiny()
    params = family.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    toks = lambda *shape: jnp.asarray(
        rng.integers(1, cfg.vocab_size, shape), jnp.int32)
    stem, block, head = _layered(family, cfg, quant)
    L = cfg.n_layers

    carried = _pool(cfg, quant)                        # (k, v, ks, vs)
    layered = [tuple(p[l] for p in carried if p is not None)
               for l in range(L)]
    table = np.full((ROWS, MAX_PAGES), TRASH, np.int32)
    table[:3] = np.arange(3 * MAX_PAGES).reshape(3, MAX_PAGES)
    lens = np.zeros((ROWS,), np.int32)                 # row 3 stays empty

    fwd = jax.jit(
        lambda params, tokens, cache, continuation: forward_paged(
            params, tokens, cfg, cache, interpret=True, tp=False,
            continuation=continuation),
        static_argnums=3)

    @functools.partial(jax.jit, static_argnums=5)
    def ref(params, tokens, layered, tbl, start, continuation):
        phase = dict(continuation=continuation,
                     prefill=tokens.shape[1] > 1 and not continuation)
        x, aux = stem(params, tokens, start)
        layered = list(layered)
        for l in range(L):
            lp = jax.tree.map(lambda a: a[l], params["blocks"])
            x, layered[l] = block(lp, x, aux, layered[l], tbl, start,
                                  **phase)
        return head(params, x), layered

    def both(tokens, rows, continuation):
        """One program run on the rows ``rows`` (a view of the table, as
        the engine's one-row prefill takes one), both ways."""
        nonlocal carried, layered
        tbl, start = jnp.asarray(table[rows]), jnp.asarray(lens[rows])
        k, v, ks, vs = carried
        logits, cache = fwd(params, tokens, PagedKVCache(
            k=k, v=v, table=tbl, seq_lens=start, page_size=PS,
            k_scale=ks, v_scale=vs), continuation)
        carried = (cache.k, cache.v, cache.k_scale, cache.v_scale)
        want, layered = ref(params, tokens, layered, tbl, start,
                            continuation)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
        lens[rows] += tokens.shape[1]

    # Compiled inside a scan body, the MoE combine's float fusions differ
    # from the unrolled loop's by 1e-6, which is not this test's subject:
    # Mixtral runs both sides op by op, the others as compiled programs.
    with jax.disable_jit(family is mixtral):
        both(toks(1, 24), slice(0, 1), False)     # whole pages
        both(toks(1, 5), slice(1, 2), False)      # a page's tail padded
        both(toks(1, 9), slice(2, 3), False)
        both(toks(1, 7), slice(0, 1), True)       # 24..30, inside a page
        both(toks(1, 7), slice(1, 2), True)       # 5..11, across a boundary
        for _ in range(3):                    # row 0: 31, then at capacity
            both(toks(ROWS, 1), slice(0, ROWS), False)
            lens[3] = 0                       # the engine keeps it empty
            lens[0] = min(lens[0], PS * MAX_PAGES)
    assert lens[0] == PS * MAX_PAGES
    for i, pool in enumerate(p for p in carried if p is not None):
        np.testing.assert_array_equal(
            np.asarray(pool), np.stack([np.asarray(lay[i])
                                        for lay in layered]))
    k = np.asarray(carried[0])
    assert k[:, :, TRASH].any() and k[:, :, :3 * MAX_PAGES].any()
