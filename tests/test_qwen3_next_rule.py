"""The qwen3_next family's rule by itself (``test_qwen3_next.py`` holds the
family on the serving path, against the reference): the chunked delta
rule against the token-by-token recurrence, the chunk and the state-step
kernels in interpret mode against the rule, and a rank's share of the
experts.  Toy widths, seeded inputs, CPU."""

import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import qwen3_next as bench_family  # noqa: E402
from benchmark.reference import qwen3_next as reference  # noqa: E402
from deepspeed_tpu.inference import kernels as K  # noqa: E402
from deepspeed_tpu.models import qwen3_next as qn  # noqa: E402
from deepspeed_tpu.parallel import moe  # noqa: E402

from test_qwen3_next import params  # noqa: E402, F401  (a fixture)


# ------------------------------------------------ (iii) the rule itself
def _rule_inputs(T, H=3, Dk=8, Dv=6, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    q = l2(jax.random.normal(ks[0], (T, H, Dk))) * Dk ** -0.5
    k = l2(jax.random.normal(ks[1], (T, H, Dk)))
    v = jax.random.normal(ks[2], (T, H, Dv))
    g = -jnp.exp(jax.random.normal(ks[3], (T, H)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    S = jax.random.normal(ks[5], (H, Dk, Dv))
    return q, k, v, g, beta, S


@pytest.mark.parametrize("T,block", [(64, 16), (37, 8), (37, 5), (20, 64),
                                     (9, 1)])
def test_the_chunked_rule_is_the_recurrence(T, block):
    """Blocks that divide T, blocks that do not, a block longer than T
    and a block of one token: the outputs and the state the blocks
    leave are the token-by-token recurrence's (the reference's)."""
    q, k, v, g, beta, S = _rule_inputs(T)
    want_o, want_S, _ = reference.recurrence(q, k, v, g, beta, S, T)
    o, S1 = qn.gdn_chunk_rule(q[None], k[None], v[None], g[None],
                              beta[None], S[None], block)
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want_o),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(S1[0]), np.asarray(want_S),
                               atol=2e-5, rtol=2e-5)
    # and the step, a token at a time, is the same recurrence
    S2 = S[None]
    for t in range(T):
        o_t, S2 = qn.gdn_step(q[None, t], k[None, t], v[None, t],
                              g[None, t], beta[None, t], S2)
        np.testing.assert_allclose(np.asarray(o_t[0]),
                                   np.asarray(want_o[t]), atol=2e-5,
                                   rtol=2e-5)
    np.testing.assert_allclose(np.asarray(S2[0]), np.asarray(want_S),
                               atol=2e-5, rtol=2e-5)


# case -> (T, block, key heads, value heads, real rows, S from zero,
# heads a grid step, tokens a grid step)
CHUNK_CASES = {
    "whole_blocks": (64, 16, 4, 4, 64, False, None, None),
    "sixteen_real_rows": (64, 16, 4, 4, 16, False, None, None),
    "no_real_row": (64, 16, 4, 4, 0, False, None, None),
    "from_zero_state": (64, 16, 4, 4, 64, True, None, None),
    "two_value_heads_a_key_head": (64, 8, 2, 4, 64, False, None, None),
    "one_head_a_step": (64, 8, 2, 4, 40, False, 1, None),
    "two_heads_a_step_a_block_a_step": (64, 16, 2, 4, 64, False, 2, 16),
    "eight_heads_a_step": (32, 8, 4, 8, 30, False, None, None),
    "heads_of_128": (128, 64, 1, 2, 100, False, None, None),
}


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_the_chunk_kernel_is_the_recurrence_and_the_chunked_rule(case):
    """``dstpu_state_chunk`` (interpret mode) under the family's block
    rule, against the reference's token-by-token recurrence and against
    ``gdn_chunk_rule`` on the same operands: whole blocks; a last chunk
    with 16 real rows (beta = g = 0 behind them); a chunk with no real
    row, which leaves S bit for bit; S from zero and not; a key head
    serving two value heads through the index map, not repeated; one
    head, two and eight a grid step, the whole chunk and one block a step."""
    from deepspeed_tpu.models.family import SlotState

    T, block, Hk, Hv, real, from_zero, heads, span = CHUNK_CASES[case]
    wide = case == "heads_of_128"
    q, k, v, g, beta, S = _rule_inputs(T, H=Hv, Dk=128 if wide else 8,
                                       Dv=128 if wide else 6)
    q, k = q[:, :Hk], k[:, :Hk]
    live = (jnp.arange(T) < real)[:, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    S = jnp.zeros_like(S) if from_zero else S
    wide_q, wide_k = (jnp.repeat(t, Hv // Hk, axis=1) for t in (q, k))
    want_o, want_S, _ = reference.recurrence(wide_q, wide_k, v, g, beta,
                                             S, T)
    rule_o, rule_S = qn.gdn_chunk_rule(
        wide_q[None], wide_k[None], v[None], g[None], beta[None], S[None],
        block)
    chunk = functools.partial(K.state_chunk, interpret=True, heads=heads,
                              span=span)
    o, new = jax.jit(lambda q, k, v, g, beta, S: qn.gdn_chunk_kernel(
        q, k, v, g, beta, SlotState(S, chunk), block))(
            q[None], k[None], v[None], g[None], beta[None], S[None])
    assert o.shape == (1, T, Hv, v.shape[-1]) and new.shape == S[None].shape
    for got, want in ((o[0, :real], want_o[:real]), (new[0], want_S),
                      (o[:, :real], rule_o[:, :real]), (new, rule_S)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
    if not real:
        np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(S))


def test_the_chunk_kernel_refuses_what_it_cannot_tile():
    """Tokens that are not whole blocks (a caller pads), and heads a
    step that neither hold nor divide a key head's value heads."""
    q, k, v, g, beta, S = _rule_inputs(24, H=6)
    cols = jnp.stack([g, beta], -1)[None]
    run = lambda block, heads, q=q: K.state_chunk(
        qn.gdn_block_rule, S[None], (q[None], k[None], v[None]), cols,
        g[None, ..., None], block=block, interpret=True, heads=heads)
    with pytest.raises(ValueError, match="whole blocks"):
        run(16, None)
    with pytest.raises(ValueError, match="heads a step"):
        run(8, 4)
    with pytest.raises(ValueError, match="heads a step"):
        run(8, 2, q=q[:, :2])           # a key head serves three


# ---------------------------------------------------- (iv) the share
@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
@pytest.mark.parametrize("tile_heads", [None, 8],
                         ids=["whole_slots", "6_of_12_heads"])
def test_the_state_step_kernel_is_the_rule_in_place(layer, tile_heads):
    """``dstpu_state_step`` (interpret mode) against the family's jnp
    rule on the same operands: equal to f32 rounding, every other layer
    bit for bit as it was, a masked slot's state (beta = g = 0) bit for
    bit; whole slots a tile, and room for 8 heads, which do not divide the
    12: tiles of 6."""
    slots, H, Dk, Dv = 5, 12, 32, 128
    q, k, v, g, beta, _ = _rule_inputs(slots, H=H, Dk=Dk, Dv=Dv)
    g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
    vectors = (q[..., None], k[..., None], v[..., None, :],
               jnp.exp(g)[..., None, None], beta[..., None, None])
    state = jax.random.normal(jax.random.PRNGKey(1), (3, slots, H, Dk, Dv))
    o, new = jax.jit(lambda state, layer, *v: K.state_step(
        qn.gdn_rule, state, layer, v, interpret=True,
        tile_bytes=tile_heads and tile_heads * Dk * Dv * 4))(
            state, layer, *vectors)
    want_o, want_S = qn.gdn_rule(state[layer], *vectors)
    assert o.shape == want_o.shape == (slots, H, 1, Dv)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new[layer]), np.asarray(want_S),
                               atol=1e-6, rtol=1e-6)
    others = [l for l in range(state.shape[0]) if l != layer]
    np.testing.assert_array_equal(np.asarray(new)[others],
                                  np.asarray(state)[others])
    np.testing.assert_array_equal(np.asarray(new[layer, 1]),
                                  np.asarray(state[layer, 1]))


def test_eight_ranks_shares_add_up_to_the_uncut_layer(params):
    """The routed parts of all 8 ranks, with what every rank computes
    alike (the gated shared expert) counted once, are the uncut layer:
    the program's expert layer on each rank's two experts against the
    reference's on all sixteen."""
    whole = qn.Qwen3NextConfig.tiny(experts_held=(0, 16))
    full = qn.init_params(jax.random.PRNGKey(1), whole)
    lp = jax.tree.map(lambda a: a[0], full["gdn_blocks"])
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 24, whole.dim))
    kw = bench_family._ref_kw(whole)
    w, idx, _ = reference.route(h[0], lp["gate"], kw["top_k"],
                                kw["normalize"])
    shared = jax.nn.sigmoid(h[0] @ lp["shared_gate"]) * reference._swiglu(
        h[0], lp["sw1"], lp["sw3"], lp["sw2"])
    stack = {n: full["gdn_blocks"][n] for n in reference.EXPERT_WEIGHTS}
    want = reference.held_part(h[0], stack, 0, w, idx, 0) + shared
    routed, rows = 0.0, 0
    for rank in range(8):
        cfg = qn.Qwen3NextConfig.tiny(experts_held=(2 * rank, 2))
        mine = dict(lp, **{n: lp[n][2 * rank:2 * rank + 2]
                           for n in reference.EXPERT_WEIGHTS})
        y, n = qn.expert_layer(cfg, h, mine)
        routed = routed + (y[0] - shared)
        rows += int(n.sum())
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(want), atol=2e-5, rtol=2e-5)
    assert rows == 24 * whole.top_k             # every pair, once


def test_softmax_router_is_float32_and_renormalised():
    h = jax.random.normal(jax.random.PRNGKey(0), (32, 64), jnp.bfloat16)
    gate = jax.random.normal(jax.random.PRNGKey(1), (64, 16), jnp.bfloat16)
    w, idx = moe.softmax_topk_route(h, gate, 4)
    want_w, want_idx, _ = reference.route(h.astype(jnp.float32), gate, 4,
                                          True)
    assert w.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    raw, _ = moe.softmax_topk_route(h, gate, 4, normalize=False)
    assert float(raw.sum(-1).max()) < 1.0
