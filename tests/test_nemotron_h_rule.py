"""The nemotron_h family's grouped state-space rule by itself
(``test_nemotron_h.py`` holds the family on the serving path, against the
reference): the grouped scan against the token-by-token rule, one group
against granite's numbers, the reference in blocks against the reference
whole, and the gated norm over a group's channels.  Toy widths, seeded
inputs, CPU."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import nemotron_h as bench_family  # noqa: E402
from benchmark.reference import granite_hybrid as granite_reference  # noqa: E402
from benchmark.reference import nemotron_h as reference  # noqa: E402
from deepspeed_tpu.models import granite_hybrid as gh  # noqa: E402

from test_nemotron_h import CFG, TOL, params


# ------------------------------------------- (iii) the grouped state-space
@pytest.mark.parametrize("block", [1, 3, 8, 64])
@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_grouped_scan_is_the_token_by_token_rule(block, groups):
    B, T, H, P, N = 2, 19, 4, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(groups), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    shape = (B, T, N) if groups == 1 else (B, T, groups, N)
    Bm, Cm = (jax.random.normal(k, shape) for k in ks[3:5])
    S0 = jax.random.normal(ks[5], (B, H, P, N))
    o, S = gh.ssm_chunk_scan(x, dt, A, Bm, Cm, S0, block)
    want_o, S1 = [], S0
    for t in range(T):
        ot, S1 = gh.ssm_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], S1)
        want_o.append(ot)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(jnp.stack(want_o, 1)), **TOL)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S1), **TOL)
    # and both are the reference's recurrence, a group's B and C handed
    # to each of its heads
    a_head = lambda v: jnp.repeat(v.reshape(T, groups, N), H // groups, 1)
    ref_o, ref_S, _ = reference.recurrence(
        x[0], dt[0], A, a_head(Bm[0]), a_head(Cm[0]), S0[0], T)
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(ref_o), **TOL)
    np.testing.assert_allclose(np.asarray(S[0]), np.asarray(ref_S), **TOL)


def test_one_group_gives_granites_numbers():
    """The mixer stated once: with one group this family's reference
    layer is Granite's, and the program's mixer both."""
    cfg = gh.GraniteHybridConfig.tiny()
    lp = jax.tree.map(lambda a: a[0], gh.init_params(
        jax.random.PRNGKey(2), cfg)["ssm_blocks"])
    x = jax.random.normal(jax.random.PRNGKey(3), (12, cfg.dim))
    want, S_want = granite_reference._mamba(
        x, lp, heads=cfg.ssm_heads, state=cfg.ssm_state, eps=cfg.norm_eps)
    stack = jax.tree.map(lambda a: a[None], lp)
    rows = jnp.zeros((cfg.conv_kernel - 1, cfg.conv_channels))
    S0 = jnp.zeros((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
    got, (_, S_got), _ = reference._mamba(
        x, stack, 0, (rows, S0), 12, heads=cfg.ssm_heads, groups=1,
        state=cfg.ssm_state, eps=cfg.norm_eps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    np.testing.assert_allclose(np.asarray(S_got), np.asarray(S_want), **TOL)
    y, (_, S) = gh.ssm_mix(cfg, x[None], lp, (rows[None], S0[None]),
                           jnp.full((1,), 12, jnp.int32))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), **TOL)
    np.testing.assert_allclose(np.asarray(S[0]), np.asarray(S_want), **TOL)


@pytest.mark.parametrize("start", [0, 7, 16, 29, 41, 64])
def test_the_reference_in_blocks_is_the_reference_whole(
        params, monkeypatch, start):
    """The check pads a 16,384-token prompt with its answer to 32,768
    rows, and a Mamba-2 layer's float32 rows of that length do not fit
    the chip beside the weights: the reference gives such a layer
    ``M_BLOCK`` rows at a time.  Whatever the block (one that divides
    the stretch, one that leaves a row over) and wherever ``start``
    falls (a block's first row, its last, the stretch's end), what
    ``carry`` keeps and the logits run from it are the whole stretch's:
    float32 on both sides, the same sums in the same order."""
    kw = bench_family._ref_kw(CFG)
    tokens = jnp.asarray(np.random.default_rng(11).integers(
        0, CFG.vocab_size, 64), jnp.int32)
    count = min(8, 64 - start)

    def run():
        held = reference.carry(params, tokens, start, **kw)
        if not count:
            return held, None
        none = jnp.zeros((CFG.n_expert_layers, count), bool)
        return held, reference.logits(params, tokens, held, start, count,
                                      none, **kw)[0]

    held_whole, whole = run()
    for block in (16, 21):
        monkeypatch.setattr(reference, "M_BLOCK", block)
        held, got = run()
        for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(held_whole)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
        if count:
            np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                                       **TOL)


def test_the_gated_norm_runs_over_a_groups_channels():
    o = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 32))
    z = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 32))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(6), (32,))
    g = np.asarray(o * jax.nn.silu(z)).reshape(2, 3, 4, 8)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)).reshape(
        2, 3, 32) * np.asarray(w)
    apart = lambda a: a.reshape(a.shape[:-1] + (4, 8))
    np.testing.assert_allclose(
        np.asarray(gh._gated_norm(apart(o), apart(z), apart(w),
                                  1e-5)).reshape(2, 3, 32), want, **TOL)
    assert not np.allclose(np.asarray(gh._gated_norm(o, z, w, 1e-5)), want,
                           atol=1e-2)
