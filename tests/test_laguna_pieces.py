"""The laguna family's stated pieces, one at a time (``test_laguna.py``
holds the family on the serving path, against the reference): each piece
of the model's statement left out fails the comparison, the YaRN table is
the formula, a rank's share of the experts, and the band's kernel alone,
in interpret mode against the band in blocks.  Toy widths, seeded
weights, CPU."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import laguna as bench_family  # noqa: E402
from benchmark.reference import laguna as reference  # noqa: E402
from deepspeed_tpu.models import laguna as lg  # noqa: E402

from test_laguna import (CFG, TOL, W, _chunks_then_steps, _reference_logits,
                         params)


# ------------------------------ (ii) each stated piece is load-bearing
_ROPE_TABLES = lg.rope_tables


def _other_table(cfg, positions):
    cf, sf, cs, ss = _ROPE_TABLES(cfg, positions)
    half = cf.shape[-1]
    return cs[..., :half], ss[..., :half], cs, ss


@pytest.mark.parametrize("piece,cfg_kw,patch", [
    ("attention_factor", dict(attention_factor=1.0), None),
    ("yarn", dict(yarn_factor=1.0), None),
    ("window", dict(sliding_window=16), None),
    ("the_other_kinds_table", {}, ("rope_tables", _other_table)),
    ("gate", {}, ("_gated_out", lambda cfg, x, attn, lp: x + attn @ lp["wo"])),
    ("routed_scale", dict(routed_scaling_factor=1.0), None),
    ("norm_topk_prob", dict(norm_topk_prob=False), None),
], ids=lambda v: v if isinstance(v, str) else "")
def test_each_stated_piece_fails_the_comparison_when_left_out(
        params, monkeypatch, piece, cfg_kw, patch):
    """The program with one piece of the model's statement left out or
    replaced (the attention factor, YaRN's division, another window, the
    sliding layers' table in the full layers, the gate, the routed
    scale, the normalised top-k) no longer agrees with the reference,
    which has them all."""
    seq = np.random.default_rng(2).integers(0, CFG.vocab_size, 40)
    want = _reference_logits(params, seq)
    cfg = dataclasses.replace(CFG, **cfg_kw)
    if patch is not None:
        monkeypatch.setattr(lg, *patch)
    got = _chunks_then_steps(params, cfg, seq, 29)
    assert np.abs(got - want).max() > 20 * TOL["atol"], piece


def test_the_yarn_table_is_the_formula(params):
    """The program's full-layer frequencies against a direct evaluation,
    at the published numbers: frequencies 0-8 left alone, 18-31 divided
    by 128, a ramp between; cos and sin carry the attention factor, the
    sliding table neither."""
    cfg = lg.LagunaConfig(n_layers=13)
    inv = lg.yarn_inv_freq(cfg)
    R, theta = 64, 500000.0
    c = lambda n: R * np.log(8192 / (2 * np.pi * n)) / (2 * np.log(theta))
    lo, hi = int(np.floor(c(32))), int(np.ceil(c(1)))
    assert (lo, hi) == (9, 18)
    f = theta ** (-2.0 * np.arange(32) / R)
    r = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    np.testing.assert_allclose(inv, f / 128 * r + f * (1 - r), rtol=1e-6)
    np.testing.assert_allclose(inv[:10], f[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], f[18:] / 128, rtol=1e-6)
    np.testing.assert_allclose(
        inv, np.asarray(reference.yarn_inv_freq(R, theta, 128.0, 8192,
                                                32.0, 1.0)), rtol=1e-6)
    pos = jnp.asarray([0, 1, 777, 16383])
    cf, sf, cs, ss = lg.rope_tables(cfg, pos)
    assert cf.shape == (4, 32) and cs.shape == (4, 64)
    np.testing.assert_allclose(np.asarray(cf * cf + sf * sf),
                               cfg.attention_factor ** 2, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(cs * cs + ss * ss), 1.0, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(cs[2]), np.cos(777 * 10000.0 ** (-np.arange(64) / 64)),
        atol=2e-4)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Four shares of two experts each: their routed parts (the
    program's ``held_experts_ffn`` behind the full router) and the
    shared expert counted once add up to the reference's layer with all
    eight experts held."""
    lp = jax.tree.map(lambda a: a[0], params["win_blocks"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, CFG.dim))
    h = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + CFG.norm_eps)
    kw = {k: v for k, v in bench_family._ref_kw(CFG).items()
          if k in ("top_k", "scale", "normalize", "eps")}
    ones = dict(params["win_blocks"],
                mlp_norm=jnp.ones_like(params["win_blocks"]["mlp_norm"]))
    with jax.default_matmul_precision("highest"):
        whole = reference.expert_ffn(x[0], reference._layer(ones, 0), 0,
                                     None, first=0, **kw)[0] - x[0]
    total = 0
    for rank in range(4):
        cfg = dataclasses.replace(CFG, experts_held=(2 * rank, 2))
        share = dict(lp, **{n: lp[n][2 * rank:2 * rank + 2]
                            for n in ("w1", "w3", "w2")})
        with jax.default_matmul_precision("highest"):
            y, rows = lg.expert_layer(cfg, h, share)
            shared = lg.expert_layer(cfg, h, dict(
                share, **{n: jnp.zeros_like(share[n])
                          for n in ("w1", "w3", "w2")}))[0]
        total = total + (y - shared)
        assert rows.shape == (2,)
    np.testing.assert_allclose(np.asarray((total + shared)[0]),
                               np.asarray(whole), atol=2e-5, rtol=2e-5)


# ------------------------------------ (ii b) the band's kernel, alone
def _band_case(B, T, Wk, starts, dtype, seed=0, KV=2, G=3, Dh=128):
    """Operands of a chunk's band at toy widths that meet the kernel's
    shape rule (whole 128-row blocks, heads of 128): ``(cfg, q, rows,
    ring, start)``.  The ring holds what the slot kept: row ``p mod W``
    the ``[K | V]`` of position ``p`` for the last W positions under
    ``start``, and large stale numbers where there was none."""
    cfg = lg.LagunaConfig.tiny(sliding_window=Wk, n_kv_heads=KV,
                               head_dim=Dh, n_heads_sliding=KV * G,
                               n_heads_full=KV)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, KV * G, Dh), jnp.float32)
    rows = jax.random.normal(ks[1], (B, T, 2 * KV * Dh), jnp.float32)
    start = np.asarray(starts, np.int32)
    past = np.asarray(jax.random.normal(
        ks[2], (B, max(int(start.max()), 1), 2 * KV * Dh), jnp.float32))
    ring = np.full((B, Wk, 2 * KV * Dh), 30.0, np.float32)
    for b in range(B):
        for pos in range(max(0, start[b] - Wk), start[b]):
            ring[b, pos % Wk] = past[b, pos]
    cast = lambda a: jnp.asarray(a).astype(dtype)
    return cfg, cast(q), cast(rows), cast(ring), jnp.asarray(start)


def _drop_the_lower_edge(qpos, kpos, window):
    return (kpos <= qpos) & (kpos >= 0)


@pytest.mark.parametrize("B,T,Wk,starts,real,dtype,fault", [
    (1, 128, 128, (0,), 128, jnp.float32, None),
    (1, 128, 128, (75,), 128, jnp.float32, None),
    (1, 256, 128, (333,), 70, jnp.float32, None),
    (2, 128, 128, (300, 5), 128, jnp.float32, None),
    (1, 256, 256, (1000,), 256, jnp.float32, None),
    (1, 256, 128, (128,), 256, jnp.float32, None),
    (1, 256, 128, (201,), 256, jnp.bfloat16, None),
    (1, 256, 128, (201,), 256, jnp.float32, _drop_the_lower_edge),
], ids=["first_chunk", "ring_out_of_order", "padded_last_chunk",
        "two_rows_two_starts", "T_equals_W", "T_twice_W", "bfloat16",
        "planted_no_lower_edge"])
def test_the_band_kernel_is_the_band_in_blocks(
        monkeypatch, B, T, Wk, starts, real, dtype, fault):
    """``dstpu_window_flash_fwd`` (interpret mode) against the band as
    XLA runs it (``_band_in_blocks`` over ``_by_kv_head``: the same f32
    scores and softmax, probabilities rounded for the value product) on
    a slot's ring as it lies: float32 operands to 1e-5, bfloat16 ones to
    the outputs' rounding.  With the window's lower edge dropped from
    the kernel's own mask the comparison must fail: a band that sees
    too far does not pass."""
    from deepspeed_tpu.ops import attention_pallas as AP

    cfg, q, rows, ring, start = _band_case(B, T, Wk, starts, dtype)
    want = jax.jit(lambda *a: lg._band_in_blocks(cfg, *a))(
        q, rows, ring, start)
    if fault is not None:
        monkeypatch.setattr(AP, "_band_seen", fault)
    got = jax.jit(lambda *a: AP.window_flash_attention_tpu(
        *a, interpret=True))(q, rows, ring, start)
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 \
        else dict(atol=2e-2, rtol=2e-2)
    close = lambda: np.testing.assert_allclose(
        np.asarray(got[:, :real], np.float32),
        np.asarray(want[:, :real], np.float32), **tol)
    if fault is None:
        close()
    else:
        with pytest.raises(AssertionError):
            close()


@pytest.mark.parametrize("tokens,window,head,interpret,reader", [
    (1024, 512, 128, False, "pallas"), (128, 128, 128, False, "pallas"),
    (1024, 512, 128, True, "xla"), (1, 512, 128, False, "xla"),
    (0, 512, 128, False, "xla"), (1000, 512, 128, False, "xla"),
    (1024, 8, 128, False, "xla"), (1024, 512, 64, False, "xla")])
def test_the_band_reader_is_a_rule_of_backend_and_shapes(
        tokens, window, head, interpret, reader):
    from deepspeed_tpu.ops.attention import window_reader

    got, why = window_reader(tokens=tokens, window=window, head_dim=head,
                             interpret=interpret)
    assert got == reader and why
    cfg = lg.LagunaConfig.tiny(sliding_window=window, head_dim=head)
    assert lg.FAMILY.recurrent.chunk_reader(cfg, tokens, interpret) \
        == (got, why)


def test_widths_the_rule_refuses_take_the_band_in_blocks(monkeypatch):
    """On a TPU backend the tiny preset's chunk (16 tokens, window 8,
    heads of 16) still runs XLA's band: the kernel is not reached, and
    the chunk's output and ring are what they are on the CPU."""
    from deepspeed_tpu.ops import attention_pallas as AP

    def never(*a, **kw):
        raise AssertionError("the kernel at widths its rule refuses")

    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    B, T, KV, H, Dh = 1, 16, CFG.n_kv_heads, CFG.n_heads_sliding, \
        CFG.head_dim
    q = jax.random.normal(ks[0], (B, T, H, Dh))
    k, v = (jax.random.normal(kk, (B, T, KV, Dh)) for kk in ks[1:3])
    ring = jax.random.normal(ks[3], (B, W, 2 * KV * Dh))
    args = (q, k, v, ring, jnp.asarray([13], jnp.int32),
            jnp.asarray([11], jnp.int32))
    want = lg.window_chunk(CFG, *args)
    monkeypatch.setattr(AP, "window_flash_attention_tpu", never)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = lg.window_chunk(CFG, *args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_a_chunk_at_the_kernels_widths_calls_it_on_a_tpu(monkeypatch):
    """``window_chunk`` hands the kernel q, the chunk's rows and the
    ring as they lie, and writes the ring itself whichever reader ran."""
    from deepspeed_tpu.ops import attention_pallas as AP

    cfg, q, rows, ring, start = _band_case(1, 128, 128, (75,), jnp.float32)
    KV, Dh = cfg.n_kv_heads, cfg.head_dim
    k = rows[..., :KV * Dh].reshape(1, 128, KV, Dh)
    v = rows[..., KV * Dh:].reshape(1, 128, KV, Dh)
    valid = jnp.asarray([100], jnp.int32)
    want_o, want_ring = lg.window_chunk(cfg, q, k, v, ring, start, valid)
    kernel = AP.window_flash_attention_tpu
    monkeypatch.setattr(AP, "window_flash_attention_tpu",
                        lambda *a: kernel(*a, interpret=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert lg.window_reader(cfg, 128, False)[0] == "pallas"
    o, new_ring = lg.window_chunk(cfg, q, k, v, ring, start, valid)
    np.testing.assert_allclose(np.asarray(o[:, :100]),
                               np.asarray(want_o[:, :100]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(new_ring),
                                  np.asarray(want_ring))
