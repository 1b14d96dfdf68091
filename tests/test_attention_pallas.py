"""Pallas flash attention vs jnp reference (interpret mode on CPU)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import attention_pallas as AP
from deepspeed_tpu.ops.attention import _reference
from deepspeed_tpu.ops.attention_pallas import flash_attention_tpu
from deepspeed_tpu.ops.attention_pallas_bwd import flash_backward


def _inputs(B=2, T=256, H=2, KV=2, D=128, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _inputs()
    out = flash_attention_tpu(q, k, v, causal=causal, interpret=True)
    ref = _reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_gqa_forward():
    q, k, v = _inputs(H=4, KV=2)
    out = flash_attention_tpu(q, k, v, causal=True, interpret=True)
    ref = _reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


# (id, B, T, S, H, KV, causal, packed, dtype) -> the path the rule answers.
# The first five are the file's backward cases as they stood (MHA causal;
# GQA causal / full; packed GQA causal / full); the rest came with the
# fused kernel (PR 62): the training cell's shape cut to one head, at
# both heights and in both dtypes, the fused body without a mask, and
# the refusals nothing covered (T != S, packed MHA, a T of odd blocks).
_F32, _BF16 = jnp.float32, jnp.bfloat16
BACKWARD_CASES = [
    ("fused-mha-causal", 1, 256, 256, 1, 1, True, False, _F32, "fused"),
    ("split-gqa-causal", 2, 256, 256, 4, 2, True, False, _F32, "split"),
    ("split-gqa-full", 2, 256, 256, 4, 2, False, False, _F32, "split"),
    ("split-gqa-packed-causal", 2, 256, 256, 4, 2, True, True, _F32, "split"),
    ("split-gqa-packed-full", 2, 256, 256, 4, 2, False, True, _F32, "split"),
    ("fused-cell-512-f32", 1, 512, 512, 1, 1, True, False, _F32, "fused"),
    ("fused-cell-512-bf16", 1, 512, 512, 1, 1, True, False, _BF16, "fused"),
    ("fused-cell-1024-f32", 1, 1024, 1024, 1, 1, True, False, _F32, "fused"),
    ("fused-cell-1024-bf16", 1, 1024, 1024, 1, 1, True, False, _BF16,
     "fused"),
    ("fused-mha-full", 1, 512, 512, 2, 2, False, False, _F32, "fused"),
    ("split-cross-lengths", 1, 256, 128, 2, 2, False, False, _F32, "split"),
    ("split-mha-packed", 2, 256, 256, 2, 2, True, True, _F32, "split"),
    ("split-odd-blocks", 1, 384, 384, 1, 1, True, False, _F32, "split"),
]


def _split_pair_grads(q, k, v, causal):
    """dQ, dK, dV of ``sum(out ** 2)`` from the two split kernels on the
    same operands ([B, T, H, D], MHA), whatever the rule answers."""
    (B, T, H, D), flat = q.shape, lambda a: a.transpose(0, 2, 1, 3).reshape(
        -1, a.shape[1], a.shape[3])
    blocks = dict(zip(("block_q", "block_k"), AP._pick_blocks(T, T)),
                  causal=causal, heads=H, kv_heads=H, interpret=True)
    out, lse = AP._flash_fwd_impl(flat(q), flat(k), flat(v), None, **blocks)
    do = (2 * out.astype(jnp.float32)).astype(out.dtype)
    return [g.reshape(B, H, T, D).transpose(0, 2, 1, 3)
            for g in AP._flash_bwd_impl(flat(q), flat(k), flat(v), None, out,
                                        lse, do, **blocks)]


def _rms(a):
    return float(np.sqrt(np.mean(np.square(np.asarray(a, np.float32)))))


@pytest.mark.parametrize("case", BACKWARD_CASES, ids=lambda c: c[0])
def test_backward_matches_reference(case):
    """dQ, dK, dV of the path the rule of the shapes answers against the
    reference's: the fused kernel where a head lies whole in the vector
    memory, the two split kernels elsewhere (GQA: dK/dV must sum over the
    G query heads sharing each kv head).  bf16 operands are held to the
    f32 reference on the same numbers at bf16's tolerance, which is one
    step of the largest entries' last bit and would pass a coarser
    operand inside the kernel; so the fused kernel's bf16 gradients are
    ALSO held, as a whole, to the split pair's (which feed ``p`` and
    ``ds`` to their products in f32: exact here in interpret mode, one
    bf16 pass of the matrix unit on a chip, where the two paths' errors
    are equal, PERF.md 7): the RMS of the difference, and of
    the fused error against the reference, under 5e-3 of the gradient's
    RMS (they read 1.4e-3 to 3.3e-3, and the split pair's own error
    against the reference 1.7e-3 to 3.4e-3)."""
    _, B, T, S, H, KV, causal, packed, dtype, path = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, 128), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, 128), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, 128), dtype)
    seg = _packed_segments(B, T, seed=11) if packed else None
    assert flash_backward(T, S, 128, H, KV, packed,
                          jnp.dtype(dtype).itemsize)[0] == path

    def grads(f, *operands):
        return jax.grad(lambda q, k, v: jnp.sum(
            f(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2))(*operands)

    flash = functools.partial(flash_attention_tpu, causal=causal,
                              segment_ids=seg, interpret=True)
    g1 = grads(flash, q, k, v)
    g2 = grads(functools.partial(_reference, causal=causal, segment_ids=seg),
               *(a.astype(jnp.float32) for a in (q, k, v)))
    kernels = set(re.findall(r"name=(dstpu_flash_bwd\w*)", str(
        jax.make_jaxpr(lambda *a: grads(flash, *a))(q, k, v))))
    assert kernels == ({"dstpu_flash_bwd"} if path == "fused" else
                       {"dstpu_flash_bwd_dq", "dstpu_flash_bwd_dkv"})
    tol = 5e-3 if dtype == _F32 else 3e-2
    for a, b, name in zip(g1, g2, "qkv"):
        assert a.shape == b.shape and a.dtype == dtype, name
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), rtol=tol, atol=tol,
            err_msg=f"grad d{name} mismatch")
    if path == "fused" and dtype == _BF16:
        for a, b, c, name in zip(g1, g2, _split_pair_grads(q, k, v, causal),
                                 "qkv"):
            a, c = np.asarray(a, np.float32), np.asarray(c, np.float32)
            assert _rms(a - c) < 5e-3 * _rms(c), (name, _rms(a - c), _rms(c))
            assert _rms(a - b) < 5e-3 * _rms(b), (name, _rms(a - b), _rms(b))


@pytest.mark.parametrize("shapes,path,why", [
    ((1024, 1024, 128, 16, 16, False), "fused", "head resident"),
    ((2048, 2048, 128, 32, 32, False), "fused", "head resident"),
    ((1024, 1024, 128, 16, 16, False, 4), "fused", "head resident"),
    ((2048, 2048, 64, 16, 16, False), "fused", "head resident"),
    ((4096, 4096, 128, 16, 16, False), "split", "does not fit"),
    # the bytes a head takes in the vector memory, not its numbers: f32
    # operands, and a head of 64 padded to the 128 lanes
    ((2048, 2048, 128, 32, 32, False, 4), "split", "does not fit"),
    ((4096, 4096, 64, 16, 16, False), "split", "does not fit"),
    ((2048, 2048, 128, 32, 8, False), "split", "GQA"),
    ((1024, 512, 128, 16, 16, False), "split", "T != S"),
    ((1024, 1024, 128, 16, 16, True), "split", "packed segments"),
    ((384, 384, 128, 16, 16, False), "split", "whole blocks"),
], ids=lambda x: x if isinstance(x, str) else None)
def test_flash_backward_rule(shapes, path, why):
    """Which backward a build runs is a rule of the shapes, with a reason
    (as ``window_reader`` and ``paged_reader`` are): both answers."""
    answer, reason = flash_backward(*shapes)
    assert answer == path and why in reason, (answer, reason)


def test_cross_lengths_T_ne_S():
    # T=256 picks block_q=256; S=128 must pick block_k=128 (not 256,
    # which would give an empty k grid and garbage output)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 128))
    k = jax.random.normal(ks[1], (1, 128, 2, 128))
    v = jax.random.normal(ks[2], (1, 128, 2, 128))
    out = flash_attention_tpu(q, k, v, causal=False, interpret=True)
    ref = _reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_bf16_forward():
    q, k, v = _inputs(dtype=jnp.bfloat16)
    out = flash_attention_tpu(q, k, v, causal=True, interpret=True)
    ref = _reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def _packed_segments(B, T, seed=7):
    """Random packed layout: 2-4 documents per row, contiguous ids."""
    rng = np.random.default_rng(seed)
    seg = np.zeros((B, T), np.int32)
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(1, T), rng.integers(1, 4),
                                  replace=False))
        seg[b] = np.searchsorted(cuts, np.arange(T), side="right")
    return jnp.asarray(seg)


@pytest.mark.parametrize("causal", [True, False])
def test_segment_ids_forward_matches_reference(causal):
    """Packed-sequence masking: the kernel must attend within segments
    only — including key blocks that are ENTIRELY cross-segment for a
    query block (the m == NEG_INF corner the causal path never hits)."""
    q, k, v = _inputs(T=256)
    seg = _packed_segments(2, 256)
    out = flash_attention_tpu(q, k, v, causal=causal, segment_ids=seg,
                              interpret=True)
    ref = _reference(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_segment_ids_isolation():
    """Perturbing document 2's keys must not change document 1's rows."""
    B, T = 1, 256
    q, k, v = _inputs(B=B, T=T)
    seg = jnp.asarray(np.concatenate([np.zeros((1, 128), np.int32),
                                      np.ones((1, 128), np.int32)], 1))
    base = flash_attention_tpu(q, k, v, causal=True, segment_ids=seg,
                               interpret=True)
    k2 = k.at[:, 128:].add(100.0)
    v2 = v.at[:, 128:].add(100.0)
    pert = flash_attention_tpu(q, k2, v2, causal=True, segment_ids=seg,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(pert[:, :128]),
                               np.asarray(base[:, :128]), atol=1e-5)
    assert not np.allclose(np.asarray(pert[:, 128:]),
                           np.asarray(base[:, 128:]))
