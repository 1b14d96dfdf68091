"""The ledger's token count and the sparse reference's two passes."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.serve import Ledger
from benchmark.reference import mixtral


def test_a_token_is_credited_once_and_a_failed_request_never():
    led = Ledger()
    # request 1: prefilled to 64, preempted, prefilled again, then 2 tokens
    led.absorbed[1] = [(1.0, 32), (2.0, 64)]      # the repeat left no mark
    led.stamps[1] = [5.0, 6.0]
    led.ended[1] = (6.0, "completed")
    # request 2: its prompt went through before the window opened
    led.absorbed[2] = [(0.5, 100)]
    led.stamps[2] = [1.5, 2.5, 9.0]
    # request 3 failed after the window: nothing of it counts
    led.absorbed[3] = [(1.0, 500)]
    led.stamps[3] = [2.0]
    led.ended[3] = (20.0, "failed")
    assert led.tokens_through(0.9, 8.0) == 64 + 2 + 2
    assert led.tokens_through(1.0, 8.0) == 32 + 2 + 2
    assert led.tokens_through(0.0, 10.0) == 64 + 2 + 100 + 3


def _toy(key, L=2, E=4, d=32, f=64, V=50, H=4, KV=2):
    ks = iter(jax.random.split(key, 16))
    n = lambda *s: jax.random.normal(next(ks), s, jnp.float32) * 0.3
    hd = d // H
    return {"embed": n(V, d), "final_norm": jnp.ones(d), "lm_head": n(d, V),
            "blocks": {"attn_norm": jnp.ones((L, d)),
                       "mlp_norm": jnp.ones((L, d)), "wq": n(L, d, d),
                       "wk": n(L, d, KV * hd), "wv": n(L, d, KV * hd),
                       "wo": n(L, d, d), "gate": n(L, d, E),
                       "w1": n(L, E, d, f), "w3": n(L, E, d, f),
                       "w2": n(L, E, f, d)}}


KW = dict(n_heads=4, n_kv_heads=2, top_k=2, rope_theta=1e4, eps=1e-5)


def test_a_stretch_against_the_kept_keys_is_the_whole_sequence():
    params = _toy(jax.random.PRNGKey(0))
    tokens = jnp.arange(24) % 50
    cache = mixtral.keys_values(params, tokens, **KW)
    stay = lambda n: jnp.zeros((2, n), bool)
    whole, _ = mixtral.logits(params, tokens, cache, 0, 24, stay(24), **KW)
    tail, margins = mixtral.logits(params, tokens, cache, 16, 8, stay(8),
                                   **KW)
    np.testing.assert_allclose(tail, whole[16:], rtol=1e-5, atol=1e-5)
    assert margins.shape == (2, 8) and (margins >= 0).all()


def test_a_swap_moves_its_position_and_none_before_it():
    params = _toy(jax.random.PRNGKey(1))
    tokens = (jnp.arange(24) * 7) % 50
    cache = mixtral.keys_values(params, tokens, **KW)
    stay = jnp.zeros((2, 8), bool)
    plain, _ = mixtral.logits(params, tokens, cache, 16, 8, stay, **KW)
    swapped, _ = mixtral.logits(params, tokens, cache, 16, 8,
                                stay.at[0, 3].set(True), **KW)
    np.testing.assert_allclose(swapped[:3], plain[:3], rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(swapped[3] - plain[3])).max() > 1e-3
