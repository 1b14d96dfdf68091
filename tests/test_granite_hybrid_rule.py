"""The granite_hybrid family's recurrence by itself (``test_granite_hybrid.py``
holds the family on the serving path, against the reference): the chunked
scan and the one-token step against the token-by-token rule, the
state-step kernel in interpret mode against the rule in place, and a
decode step over every slot.  Toy widths, seeded inputs, CPU."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import granite_hybrid as reference  # noqa: E402
from deepspeed_tpu.inference import kernels as K  # noqa: E402
from deepspeed_tpu.inference.paged_forward import forward_paged  # noqa: E402
from deepspeed_tpu.models import granite_hybrid as gh  # noqa: E402

from test_granite_hybrid import CFG, _cache, _engine, params


# ------------------------------------------ (iv) the recurrence itself
def _scan_inputs(T, H=3, P=6, N=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, H, P))
    dt = jnp.exp(jax.random.uniform(ks[1], (T, H), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    Bm, Cm = (jax.random.normal(k, (T, N)) for k in ks[3:5])
    S = jax.random.normal(ks[5], (H, P, N))
    return x, dt, A, Bm, Cm, S


@pytest.mark.parametrize("T,block", [(48, 4), (48, 16), (48, 48), (37, 5),
                                     (20, 64)])
def test_the_chunked_scan_is_the_recurrence(T, block):
    """Blocks of 4, of 16 and of the sequence's length, a block that
    does not divide it and one longer than it: the outputs and the state
    the blocks leave are the token-by-token recurrence's (the
    reference's)."""
    x, dt, A, Bm, Cm, S = _scan_inputs(T)
    want_o, want_S = reference.recurrence(x, dt, A, Bm, Cm, S)
    o, S1 = gh.ssm_chunk_scan(x[None], dt[None], A, Bm[None], Cm[None],
                              S[None], block)
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want_o),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(S1[0]), np.asarray(want_S),
                               atol=2e-5, rtol=2e-5)


def test_the_step_is_the_recurrence_and_holds_still_at_dt_zero():
    x, dt, A, Bm, Cm, S = _scan_inputs(12)
    want_o, want_S = reference.recurrence(x, dt, A, Bm, Cm, S)
    S2 = S[None]
    for t in range(12):
        o_t, S2 = gh.ssm_step(x[None, t], dt[None, t], A, Bm[None, t],
                              Cm[None, t], S2)
        np.testing.assert_allclose(np.asarray(o_t[0]),
                                   np.asarray(want_o[t]), atol=2e-5,
                                   rtol=2e-5)
    np.testing.assert_allclose(np.asarray(S2[0]), np.asarray(want_S),
                               atol=2e-5, rtol=2e-5)
    _, S3 = gh.ssm_step(x[None, 0], jnp.zeros_like(dt[None, 0]), A,
                        Bm[None, 0], Cm[None, 0], S2)
    np.testing.assert_array_equal(np.asarray(S3), np.asarray(S2))


def _state_step_operands(slots=5, H=12, P=16, N=128, layers=3, seed=0):
    """Every slot's vectors of one decode step as ``ssm_step`` hands them
    to the rule (slot 1 masked: dt = 0), and a carried state."""
    x, dt, A, Bm, Cm, _ = _scan_inputs(slots, H=H, P=P, N=N, seed=seed)
    dt = dt.at[1].set(0.0)
    vectors = ((dt[..., None] * x)[..., None],
               jnp.exp(dt * A)[..., None, None], Bm[:, None, None, :],
               Cm[:, None, None, :])
    state = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (layers, slots, H, P, N))
    return vectors, state


@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
@pytest.mark.parametrize("tile_heads", [None, 8],
                         ids=["whole_slots", "6_of_12_heads"])
def test_the_state_step_kernel_is_the_rule_in_place(layer, tile_heads):
    """``dstpu_state_step`` (interpret mode) against the family's jnp
    rule on the same operands: equal to f32 rounding, every other layer
    bit for bit as it was, a masked slot's state bit for bit; whole
    slots a tile, and room for 8 heads, which do not divide the 12: tiles
    of 6."""
    vectors, state = _state_step_operands()
    P, N = state.shape[-2:]
    assert K._state_tile(5, 12, P * N * 4, 8 * P * N * 4) == (1, 6)
    assert K._state_tile(5, 12, P * N * 4, K._STATE_TILE_BYTES) == (5, 12)
    o, new = jax.jit(lambda state, layer, *v: K.state_step(
        gh.ssm_rule, state, layer, v, interpret=True,
        tile_bytes=tile_heads and tile_heads * P * N * 4))(
            state, layer, *vectors)
    want_o, want_S = gh.ssm_rule(state[layer], *vectors)
    assert o.shape == want_o.shape == state.shape[1:4] + (1,)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new[layer]), np.asarray(want_S),
                               atol=1e-6, rtol=1e-6)
    others = [l for l in range(state.shape[0]) if l != layer]
    np.testing.assert_array_equal(np.asarray(new)[others],
                                  np.asarray(state)[others])
    np.testing.assert_array_equal(np.asarray(new[layer, 1]),
                                  np.asarray(state[layer, 1]))


def test_a_decode_step_over_every_slot_steps_the_carried_state(
        params, monkeypatch):
    """What the seam hands ``ssm_mix``: the carried buffer and the layer
    in a decode step over every slot on one device, the rows' state
    under a mesh (``tp``) and in a chunk's one-slot view; the logits of
    the two decode steps agree."""
    from deepspeed_tpu.inference import paged_forward
    from deepspeed_tpu.models.family import CarriedState

    carried = []

    def mix(cfg, x, lp, state, valid, *positions):
        carried.append(isinstance(state[1], CarriedState))
        return gh.ssm_mix(cfg, x, lp, state, valid, *positions)

    fam = dataclasses.replace(gh.FAMILY, recurrent=dataclasses.replace(
        gh.FAMILY.recurrent, mix=mix))
    monkeypatch.setattr(paged_forward, "decoder_family", lambda cfg: fam)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, CFG.vocab_size, (2, 16)), jnp.int32)
    logits = {}
    for tp in (False, True):
        cache = _cache(CFG, 2, 2, 32)._replace(
            real=jnp.ones((2,), jnp.int32))
        logits[tp], _ = forward_paged(params, tokens[:, :1], CFG, cache,
                                      tp=tp)
        assert carried and all(c == (not tp) for c in carried), (tp, carried)
        del carried[:]
    np.testing.assert_allclose(np.asarray(logits[False]),
                               np.asarray(logits[True]), atol=2e-4,
                               rtol=2e-4)
    view = _cache(CFG, 2, 1, 32, slot=jnp.zeros((1,), jnp.int32))
    forward_paged(params, tokens[:1], CFG, view)
    assert carried and not any(carried)


def test_the_policy_names_the_state_stepper(params):
    """``/statusz`` ``kernels.state_step``: ``pallas`` on one device;
    under a mesh ``xla``, with a ``fallbacks`` row; a family with no
    recurrent layer reads ``xla`` and no row."""
    eng = _engine(params)
    kernels = eng.statusz()["kernels"]
    assert kernels["state_step"] == "pallas" and kernels["fallbacks"] == []
    demoted = K.resolve_serving_kernels(tp=True, recurrent=True)
    assert demoted.state_step == "xla"
    assert [(f, d) for f, d, _ in demoted.fallbacks] == [
        ("state_step=pallas", "xla")]
    assert "tp" in demoted.as_dict()["fallbacks"][0]["reason"]
    plain = K.resolve_serving_kernels(tp=True)
    assert plain.state_step == "xla" and plain.fallbacks == ()
