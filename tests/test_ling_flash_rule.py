"""The ling_flash family's rule by itself (``test_ling_flash.py`` holds the
family on the serving path, against the reference): the KDA block rule
and the one-token step against the token-by-token recurrence, the chunk
and the state-step kernels in interpret mode against the rule, and the
group-limited share of the experts.  Toy widths, seeded inputs, CPU."""

import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import ling_flash as bench_family  # noqa: E402
from benchmark.reference import ling_flash as reference  # noqa: E402
from deepspeed_tpu.inference import kernels as K  # noqa: E402
from deepspeed_tpu.models import ling_flash as lf  # noqa: E402
from deepspeed_tpu.models.family import SlotState  # noqa: E402
from deepspeed_tpu.parallel import moe  # noqa: E402


# ------------------------------------------------ (iii) the rule itself
def _rule_inputs(T, H=3, Dk=8, Dv=6, seed=0, gate="mixed"):
    """``gate``: "mixed" draws g a channel between the lower bound and
    0; "lowest" puts every channel of every token AT the lower bound;
    "blocks" alternates whole blocks of 16 tokens at the bound with
    blocks that hardly decay."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    q = l2(jax.random.normal(ks[0], (T, H, Dk))) * Dk ** -0.5
    k = l2(jax.random.normal(ks[1], (T, H, Dk)))
    v = jax.random.normal(ks[2], (T, H, Dv))
    g = -5.0 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (T, H, Dk)) - 3)
    if gate == "lowest":
        g = jnp.full_like(g, -5.0)
    elif gate == "blocks":
        g = jnp.where((jnp.arange(T) // 16 % 2 == 0)[:, None, None], -5.0,
                      -1e-3 * jnp.ones_like(g))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    S = jax.random.normal(ks[5], (H, Dk, Dv))
    return q, k, v, g, beta, S


@pytest.mark.parametrize("T,block,gate", [
    (64, 16, "mixed"), (37, 8, "mixed"), (64, 64, "mixed"),
    (20, 64, "mixed"), (9, 1, "mixed"), (64, 32, "lowest"),
    (128, 64, "lowest"), (128, 64, "blocks"), (48, 16, "blocks")])
def test_the_block_rule_and_the_step_are_the_recurrence(T, block, gate):
    """Blocks that divide T, blocks that do not, a block longer than T,
    a block of one token, strips of 16 inside blocks of 32 and 64; gates
    drawn a channel, gates at the lower bound for every token of every
    block (the strip's factors at their caps), and whole blocks at the
    bound beside blocks that hardly decay: the outputs and the state the
    blocks leave are the token-by-token recurrence's (the reference's),
    and so are the one-token rule's."""
    q, k, v, g, beta, S = _rule_inputs(T, gate=gate)
    want_o, want_S, _ = reference.recurrence(q, k, v, g, beta, S, T)
    o, S1 = jax.jit(lf.kda_chunk, static_argnums=6)(
        q[None], k[None], v[None], g[None], beta[None], S[None], block)
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want_o),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(S1[0]), np.asarray(want_S),
                               atol=2e-5, rtol=2e-5)
    step = jax.jit(lf.kda_step)
    S2 = S[None]
    for t in range(T):
        o_t, S2 = step(q[None, t], k[None, t], v[None, t], g[None, t],
                       beta[None, t], S2)
        np.testing.assert_allclose(np.asarray(o_t[0]),
                                   np.asarray(want_o[t]), atol=2e-5,
                                   rtol=2e-5)
    np.testing.assert_allclose(np.asarray(S2[0]), np.asarray(want_S),
                               atol=2e-5, rtol=2e-5)


def test_a_scalar_gate_is_another_model():
    """The gate averaged over a head's channels (Gated DeltaNet's scalar
    decay under this model's name) parts from the recurrence at once:
    what the tolerances above would catch."""
    q, k, v, g, beta, S = _rule_inputs(32)
    want_o, _, _ = reference.recurrence(q, k, v, g, beta, S, 32)
    flat = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    o, _ = lf.kda_chunk(q[None], k[None], v[None], flat[None], beta[None],
                        S[None], 16)
    assert float(jnp.abs(o[0] - want_o).max()) > 1e-2


# case -> (T, block, heads, real rows, S from zero, gate, heads a grid
# step, tokens a grid step)
CHUNK_CASES = {
    "whole_blocks": (64, 16, 4, 64, False, "mixed", None, None),
    "sixteen_real_rows": (64, 16, 4, 16, False, "mixed", None, None),
    "no_real_row": (64, 16, 4, 0, False, "mixed", None, None),
    "from_zero_state": (64, 16, 4, 64, True, "mixed", None, None),
    "one_head_a_step": (64, 8, 4, 40, False, "mixed", 1, None),
    "two_heads_a_step_a_block_a_step": (64, 16, 4, 64, False, "mixed", 2,
                                        16),
    "eight_heads_a_step": (32, 8, 8, 30, False, "mixed", None, None),
    "heads_of_128": (128, 64, 2, 100, False, "mixed", None, None),
    "heads_of_128_at_the_lower_bound": (128, 64, 2, 128, False, "lowest",
                                        None, None),
}


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_the_chunk_kernel_is_the_recurrence_under_the_block_rule(case):
    """``dstpu_state_chunk`` (interpret mode) under the family's block
    rule in a kernel body's arithmetic (three bf16 passes), handed the
    rule's own operands (q, k, v and ``c``, 128 numbers a token and head,
    as a fourth tile; beta down the block), against the reference's
    token-by-token recurrence: whole blocks; a last chunk with 16 real
    rows (beta = g = 0 behind them); a chunk with no real row, which
    leaves S bit for bit; S from zero and not; one head, two and eight a
    grid step; heads of 128 x 128 in blocks of 64, four strips each, with
    every gate at the lower bound."""
    T, block, H, real, from_zero, gate, heads, span = CHUNK_CASES[case]
    wide = case.startswith("heads_of_128")
    q, k, v, g, beta, S = _rule_inputs(T, H=H, Dk=128 if wide else 8,
                                       Dv=128 if wide else 6, gate=gate)
    live = (jnp.arange(T) < real)[:, None]
    g = jnp.where(live[..., None], g, 0.0)
    beta = jnp.where(live, beta, 0.0)
    S = jnp.zeros_like(S) if from_zero else S
    want_o, want_S, _ = reference.recurrence(q, k, v, g, beta, S, T)
    chunk = functools.partial(K.state_chunk, interpret=True, heads=heads,
                              span=span)
    o, new = jax.jit(lambda q, k, v, g, beta, S: lf.kda_chunk(
        q, k, v, g, beta, SlotState(S, chunk), block))(
            q[None], k[None], v[None], g[None], beta[None], S[None])
    assert o.shape == (1, T, H, v.shape[-1]) and new.shape == S[None].shape
    for got, want in ((o[0, :real], want_o[:real]), (new[0], want_S)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
    if not real:
        np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(S))


@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
def test_the_state_step_kernel_is_the_rule_in_place(layer):
    """``dstpu_state_step`` (interpret mode) against the family's jnp
    rule on the same operands, its decay a vector down the state's rows
    beside q and k: equal to f32 rounding, every other layer bit for bit
    as it was, a masked slot's state (beta = g = 0) bit for bit."""
    slots, H, Dk, Dv = 5, 4, 32, 128
    q, k, v, g, beta, _ = _rule_inputs(slots, H=H, Dk=Dk, Dv=Dv)
    g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
    vectors = (q[..., None], k[..., None], v[..., None, :],
               jnp.exp(g)[..., None], beta[..., None, None])
    state = jax.random.normal(jax.random.PRNGKey(1), (3, slots, H, Dk, Dv))
    o, new = jax.jit(lambda state, layer, *v: K.state_step(
        lf.kda_rule, state, layer, v, interpret=True))(
            state, layer, *vectors)
    want_o, want_S = lf.kda_rule(state[layer], *vectors)
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


# ---------------------------------------------------- (iv) the share
def test_eight_ranks_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 8 ranks, a group of the router's eight
    each, with what every rank computes alike (the shared expert)
    counted once, are the uncut layer: the program's expert layer on
    each rank's two experts against the reference's on all sixteen."""
    kw = dict(n_routed_experts=16, n_group=8, topk_group=4, top_k=4)
    whole = lf.LingFlashConfig.tiny(experts_held=(0, 16), **kw)
    full = lf.init_params(jax.random.PRNGKey(1), whole)
    lp = jax.tree.map(lambda a: a[0], full["kda_blocks"])
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 24, whole.dim))
    ref = bench_family._ref_kw(whole)
    w, idx, _ = reference.route(h[0], lp["gate"], lp["gate_bias"],
                                ref["top_k"], ref["groups"], ref["scale"],
                                ref["normalize"])
    shared = reference._swiglu(h[0], lp["sw1"], lp["sw3"], lp["sw2"])
    stack = {n: full["kda_blocks"][n] for n in ("w1", "w3", "w2")}
    want = reference.held_part(h[0], stack, 0, w, idx, 0) + shared
    routed, rows, fed = 0.0, 0, []
    for rank in range(8):
        cfg = lf.LingFlashConfig.tiny(experts_held=(2 * rank, 2), **kw)
        mine = dict(lp, **{n: lp[n][2 * rank:2 * rank + 2]
                           for n in ("w1", "w3", "w2")})
        y, n = lf.expert_layer(cfg, h, mine)
        routed = routed + (y[0] - shared)
        rows += int(n.sum())
        fed.append(int(n.sum()))
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(want), atol=2e-5, rtol=2e-5)
    assert rows == 24 * whole.top_k             # every pair, once
    # a rank is its group: a token sends rows to 4 of the 8 ranks at most
    kept = np.asarray(idx) // 2
    assert all(len(set(row)) <= 4 for row in kept.tolist())
    assert fed == [int((kept == r).sum()) for r in range(8)]


def _hand_router(s, bias, top_k, n, keep):
    """The group-limited choice in numpy float32, a row at a time: the
    lower index wins a tie, among groups as among experts."""
    out = []
    for row in (s + bias).astype(np.float32):
        groups = row.reshape(n, -1)
        score = np.sort(groups, -1)[:, -2:].sum(-1, dtype=np.float32)
        kept = sorted(sorted(range(n), key=lambda g: (-score[g], g))[:keep])
        open_ = [e for g in kept for e in range(g * groups.shape[1],
                                                (g + 1) * groups.shape[1])]
        out.append(sorted(open_, key=lambda e: (-row[e], e))[:top_k])
    return np.asarray(out)


def test_the_group_limit_is_the_hand_written_router_ties_and_all():
    """Scores on a coarse grid (sixteenths, so that experts and groups
    tie often and every sum is exact in float32) through
    ``sigmoid_topk_route(groups=)`` against a router written by hand:
    the same experts in the same order, the lower index winning a tie
    among groups as among experts; weights by ``s`` alone (the bias moves
    the choice), normalised and scaled; without the limit other experts
    are chosen."""
    rng = np.random.default_rng(0)
    N, E, n, keep, k = 64, 32, 8, 4, 6
    s = rng.integers(1, 16, (N, E)).astype(np.float32) / 16
    bias = rng.integers(-2, 3, E).astype(np.float32) / 16
    logit = np.log(s / (1 - s)).astype(np.float32)
    # h = the logits themselves through an identity gate: s exactly
    w, idx = moe.sigmoid_topk_route(jnp.asarray(logit), jnp.eye(E), k, 2.5,
                                    True, bias=jnp.asarray(bias),
                                    groups=(n, keep))
    s32 = np.asarray(jax.nn.sigmoid(jnp.asarray(logit)))
    want = _hand_router(s32, bias, k, n, keep)
    np.testing.assert_array_equal(np.asarray(idx), want)
    chosen = np.take_along_axis(s32, want, 1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-6)
    assert all(len(set(row // (E // n))) <= keep for row in want)
    _, free = moe.sigmoid_topk_route(jnp.asarray(logit), jnp.eye(E), k, 2.5,
                                     True, bias=jnp.asarray(bias))
    assert not np.array_equal(np.asarray(free), want)
    # and the reference's float32 router chooses the same
    _, ref_idx, _ = reference.route(jnp.asarray(logit), jnp.eye(E),
                                    jnp.asarray(bias), k, (n, keep), 2.5,
                                    True)
    np.testing.assert_array_equal(np.asarray(ref_idx), want)
