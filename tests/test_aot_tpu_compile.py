"""Main-path Pallas kernels compiled for a described (not attached) v5e.

Interpret mode, which every other kernel test uses on the CPU, checks
the arithmetic and none of Mosaic's layout rules.  The TPU compiler is
installed here and compiles for a chip it is only told about, so these
cases raise what a real chip would raise at the widths the chip runs:
GPT-2 1.3B (16 heads of 128, T 1024 — ``chip_smoke.py``'s model) and a
GQA layout (32 query / 8 KV heads of 128, T 2048).  Nothing executes;
results are the interpret-mode tests' job.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference import kernels as K
from deepspeed_tpu.ops.attention_pallas import flash_attention_tpu
from deepspeed_tpu.ops.sampling_pallas import fused_greedy_rows

# (name, heads, kv_heads, batch, seq) — head_dim is 128 in both
GPT2_1_3B = ("gpt2_1_3b", 16, 16, 4, 1024)
GQA_32_8 = ("gqa_32_8", 32, 8, 2, 2048)
LAYOUTS = [GPT2_1_3B, GQA_32_8]
DH, PAGE, TABLE_TOKENS = 128, 16, 4096


@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e device; the persistent compilation
    cache stays off, because an entry written for a described chip
    cannot be read back without one and the next compile warns."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"TPU topology cannot be described here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _qkv(layout):
    _, H, KV, B, T = layout
    bf = jnp.bfloat16
    return [((B, T, H, DH), bf), ((B, T, KV, DH), bf), ((B, T, KV, DH), bf)]


def _pages(layout, dtype=jnp.bfloat16):
    """Decode batch 8 over a pool that holds every row's full table."""
    _, H, KV, _, _ = layout
    B, mp = 8, TABLE_TOKENS // PAGE
    kv = ((KV, B * mp + 1, PAGE, DH), dtype)
    return B, H, kv, ((B, mp), jnp.int32), ((B,), jnp.int32)


@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda l: l[0])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention(chip, layout, grad):
    def fwd(q, k, v):
        return flash_attention_tpu(q, k, v, causal=True)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    _compile(fwd_bwd if grad else fwd, chip, *_qkv(layout))


@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda l: l[0])
def test_flash_attention_packed(chip, layout):
    """The packed-sequence variant, forward and backward: refused before
    the segment ids were given a unit middle axis ("last two dimensions
    of your block shape are divisible by 8 and 128 ... block shape
    (1, 512), array shape (2, 2048)")."""
    _, _, _, B, T = layout

    def fwd_bwd(q, k, v, seg):
        return jax.grad(
            lambda *a: flash_attention_tpu(
                *a, causal=True, segment_ids=seg).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    _compile(fwd_bwd, chip, *_qkv(layout), ((B, T), jnp.int32))


@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda l: l[0])
def test_paged_decode_v2(chip, layout):
    B, H, kv, table, lens = _pages(layout)
    _compile(lambda q, k, v, t, n: K.paged_decode_attention_v2(q, k, v, t, n),
             chip, ((B, H, DH), jnp.bfloat16), kv, kv, table, lens)


@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda l: l[0])
def test_paged_chunk_v2(chip, layout):
    B, H, kv, table, lens = _pages(layout)
    _compile(lambda q, k, v, t, n: K.paged_chunk_attention_v2(q, k, v, t, n),
             chip, ((B, 128, H, DH), jnp.bfloat16), kv, kv, table, lens)


@pytest.mark.parametrize("vocab", [50257, 128256])
def test_fused_greedy_rows(chip, vocab):
    _compile(fused_greedy_rows, chip, ((8, vocab), jnp.float32))


def test_quant_resident_kernel_is_refused_by_the_compiler(chip):
    """The int8-resident decode kernel does not compile for the chip.
    When this stops raising, the kernel was repaired: lift the refusal
    in ``resolve_serving_kernels`` in the same change."""
    B, H, kv, table, lens = _pages(GQA_32_8, jnp.int8)
    scale = (kv[0][:3] + (1,), jnp.float32)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(lambda q, kq, ks, vq, vs, t, n:
                 K.paged_decode_attention_v2_quant(q, kq, ks, vq, vs, t, n),
                 chip, ((B, H, DH), jnp.bfloat16), kv, scale, kv, scale,
                 table, lens)


@pytest.mark.parametrize("paged,want", [
    ("pallas_v2", K.ServingKernelRefused), ("auto", "xla"), ("xla", "xla")])
def test_quant_resident_policy_on_chip(paged, want):
    """What the engine build does about it (no compile involved): a
    forced Pallas kernel over an int8-resident cache on a chip is a
    typed error at build, auto resolves to xla with a visible row."""
    resolve = lambda: K.resolve_serving_kernels(
        {"paged_attention": paged}, interpret=False,
        quantized_resident=True)
    if want is K.ServingKernelRefused:
        with pytest.raises(K.ServingKernelRefused, match="aligned to tiling"):
            resolve()
        return
    policy = resolve()
    assert policy.paged_attention == want
    assert bool(policy.fallbacks) == (paged == "auto")
    # interpret mode (the CPU tests) keeps the forced kernel
    assert K.resolve_serving_kernels(
        {"paged_attention": "pallas_v2"}, interpret=True,
        quantized_resident=True).paged_attention == "pallas_v2"
