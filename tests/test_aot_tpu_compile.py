"""Main-path Pallas kernels compiled for a described (not attached) v5e.

Interpret mode, which every other kernel test uses on the CPU, checks
the arithmetic and none of Mosaic's layout rules.  The TPU compiler is
installed here and compiles for a chip it is only told about, so these
cases raise what a real chip would raise at the widths the chip runs:
GPT-2 1.3B (16 heads of 128, T 1024 — ``chip_smoke.py``'s model) and a
GQA layout (32 query / 8 KV heads of 128, T 2048).  Nothing executes;
results are the interpret-mode tests' job.

This file holds the kernels alone; the cells' whole programs are in
``test_aot_tpu_compile_pool.py`` (GPT-2's and Mixtral's, the ZeRO-3
step) and in a file a family, ``test_aot_tpu_compile_<family>.py``.
"""

import math
import re

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import kernels as K
from deepspeed_tpu.ops.attention_pallas import flash_attention_tpu
from deepspeed_tpu.ops.attention_pallas_bwd import flash_backward

from _aot import (DH, PAGE, TABLE_TOKENS, _compile, _flash_kernels,
                  _pool_sized_ops)

# (name, heads, kv_heads, batch, seq) — head_dim is 128 in both
GPT2_1_3B = ("gpt2_1_3b", 16, 16, 4, 1024)
GQA_32_8 = ("gqa_32_8", 32, 8, 2, 2048)
LAYOUTS = [GPT2_1_3B, GQA_32_8]


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

    hlo = _compile(fwd_bwd if grad else fwd, chip, *_qkv(layout)).as_text()
    kernels = set(_flash_kernels(hlo))
    # which backward a layout compiled is the rule's answer for its
    # shapes: GPT-2's head (1024 x 128) lies whole in the vector memory
    # (Mosaic's own check of it ran just now); the GQA layout's dK and
    # dV sum over four query heads, which the split sweep does
    _, H, KV, _, T = layout
    path, why = flash_backward(T, T, DH, H, KV)
    assert (path, why[:3]) == {"gpt2_1_3b": ("fused", "a h"),
                               "gqa_32_8": ("split", "GQA")}[layout[0]]
    backward = {"fused": {"dstpu_flash_bwd"},
                "split": {"dstpu_flash_bwd_dq", "dstpu_flash_bwd_dkv"}}[path]
    assert kernels == {"dstpu_flash_fwd"} | (backward if grad else set())


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("T,D,dtype", [
    (2048, 128, jnp.bfloat16), (1024, 128, jnp.float32),
    (2048, 64, jnp.bfloat16)], ids=["2048x128_bf16", "1024x128_f32",
                                    "2048x64_bf16"])
def test_fused_flash_backward_at_the_rules_edge(chip, T, D, dtype, causal):
    """The largest heads ``flash_backward`` admits, a dtype and a head
    width: Mosaic's own count of the vector memory runs on each, with the
    mask and without (without, every key block meets every query row and
    the body's temporaries are at their most: 14.3 MiB of the 32 asked
    for at 2048 x 128 in bf16).  One row more and the rule answers
    ``split``."""
    size = jnp.dtype(dtype).itemsize
    assert flash_backward(T, T, D, 2, 2, False, size)[0] == "fused"
    assert flash_backward(2 * T, 2 * T, D, 2, 2, False, size)[0] == "split"
    hlo = _compile(
        lambda *a: jax.grad(lambda *a: flash_attention_tpu(
            *a, causal=causal).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(*a),
        chip, *[((1, T, 2, D), dtype)] * 3).as_text()
    assert set(_flash_kernels(hlo)) == {"dstpu_flash_fwd", "dstpu_flash_bwd"}


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
    """The decode reader as a step calls it: it takes the step's new K/V
    row a slot, attends to it from VMEM and copies it to its page, by the
    tile of 8 rows (a copy of one or two rows of a packed page is refused:
    "Slice shape along dimension 3 must be aligned to tiling (8)"), and
    gives the pool back in the buffers it came in."""
    B, H, kv, table, lens = _pages(layout)
    pool = ((2,) + kv[0], kv[1])
    new = ((B, kv[0][0], DH), jnp.bfloat16)
    step = lambda q, nk, nv, k, v, t, n: K.paged_decode_attention_v2(
        q, k, v, t, n, layer=1, new_k=nk, new_v=nv)
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
        ((B, H, DH), jnp.bfloat16), new, new, pool, pool, table, lens)]
    compiled = jax.jit(step, donate_argnums=(3, 4)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _pool_sized_ops(compiled.as_text(), pool[0]) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * 2 * math.prod(pool[0])


@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda l: l[0])
def test_paged_chunk_v2(chip, layout):
    B, H, kv, table, lens = _pages(layout)
    _compile(lambda q, k, v, t, n: K.paged_chunk_attention_v2(q, k, v, t, n),
             chip, ((B, 128, H, DH), jnp.bfloat16), kv, kv, table, lens)


# (N rows, k, held, experts, d, f): docqa-sat's chunk (gated, 64 of 512
# experts of 2048 x 512: one block of f, a tile a step) and Nemotron's
# (two matrices of 2688 x 1,920 stored columns, 16 of 128); Mixtral's
# chunk (all 8 experts, f in blocks, four products a step) rides in
# ``test_mixtral_chunk_program_groups_the_rows_by_expert``
@pytest.mark.parametrize("shape,gated", [
    ((1024, 10, 64, 512, 2048, 512), True),
    ((1024, 6, 16, 128, 2688, 1920), False)], ids=["gated", "two_matrices"])
def test_held_ffn_kernel(chip, monkeypatch, shape, gated):
    """``dstpu_held_ffn`` through ``held_experts_ffn`` at a share's chunk
    shape, a layer of whole stacks: the pass loop is one Mosaic call, no
    layer is sliced out of a stack and no [C, d] buffer of rows is left."""
    from deepspeed_tpu.parallel import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    N, k, Eh, E, d, f = shape
    bf, L = jnp.bfloat16, 3
    relu2 = lambda a: jnp.square(jax.nn.relu(a))

    def fn(h, w, e, w1, w3, w2, layer):
        return moe.held_experts_ffn(
            h, w, e, w1, w3 if gated else None, w2, first=Eh, layer=layer,
            n_experts=E, act=None if gated else relu2)

    hlo = _compile(
        fn, chip, ((N, d), bf), ((N, k), jnp.float32), ((N, k), jnp.int32),
        ((L, Eh, d, f), bf), ((L, Eh, d, f), bf), ((L, Eh, f, d), bf),
        ((), jnp.int32)).as_text()
    assert len(re.findall(r"%dstpu_held_ffn[\w.]* = .*tpu_custom_call",
                          hlo)) == 1
    C = moe._pair_buffer_rows(N, k, Eh, E)
    assert f"bf16[{C},{d}]" not in hlo and f"bf16[{C},{f}]" not in hlo
    assert f"bf16[{Eh},{d},{f}]" not in hlo         # no layer's slice


def test_int8_resident_pages_are_gathered_on_the_chip(chip):
    """Over int8-resident pages (``kv_tier.quantized_resident``) the
    rule answers the gather in both phases on a chip, and a decode step
    over codes and scale planes compiles for it with no Mosaic call: a
    page copy of the ``[KV, P, ps, 1]`` scale planes is a 1-wide slice
    of a 128-lane tile, which the compiler refuses ("Slice shape along
    dimension 3 must be aligned to tiling (128), but is 1", AOT, PR 28),
    so no kernel reads them."""
    readers = {decode: K.paged_reader(
        decode=decode, tp=False, interpret=False, quant=True, tokens=1024,
        head_dim=DH) for decode in (True, False)}
    assert set(readers.values()) == {("xla", "int8-resident pages")}
    B, H, kv, table, lens = _pages(GQA_32_8, jnp.int8)
    pool = ((1,) + kv[0], jnp.int8)
    scale = (pool[0][:4] + (1,), jnp.float32)
    KV = kv[0][0]

    def step(q, k, v, kq, ks, vq, vs, t, n):
        return K.paged_attention_step(
            q, k, v, kq, vq, 0, t, n, continuation=False, prefill=False,
            reader=readers[True][0], flash_force_reference=False, kps=ks,
            vps=vs)

    new = ((B, 1, KV, DH), jnp.bfloat16)
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
        ((B, 1, H, DH), jnp.bfloat16), new, new, pool, scale, pool, scale,
        table, lens)]
    hlo = jax.jit(step).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in hlo


def test_mla_decode_kernel(chip):
    """128 heads over pages of 16 rows of 576 numbers stored in 640
    lanes; declared 576 wide Mosaic refuses the page copy."""
    B, H, mp = 8, 128, TABLE_TOKENS // PAGE
    bf = jnp.bfloat16
    call = lambda width: _compile(
        lambda q, pool, table, lens: K.latent_decode_attention(
            q, pool, table, lens, 192 ** -0.5, 512, layer=1),
        chip, ((B, H, 576), bf), ((2, 1, B * mp + 1, PAGE, width), bf),
        ((B, mp), jnp.int32), ((B,), jnp.int32))
    assert "dstpu_mla_decode" in call(640).as_text()
    with pytest.raises(Exception, match="aligned to tiling"):
        call(576)


def test_latent_flash_kernel(chip):
    """A chunk of 1,024 queries at an offset into 4,096 expanded keys:
    q/k of 128 + 64 with the 64 shared by the 128 heads, v of 128."""
    from deepspeed_tpu.ops.attention_pallas import latent_flash_attention_tpu

    bf, T, S, H = jnp.bfloat16, 1024, 4096, 128
    compiled = _compile(
        lambda *a: latent_flash_attention_tpu(*a, 192 ** -0.5), chip,
        ((1, T, H, 128), bf), ((1, T, H, 64), bf), ((1, S, H, 128), bf),
        ((1, S, 64), bf), ((1, S, H, 128), bf), ((1,), jnp.int32))
    assert "dstpu_latent_flash_fwd" in compiled.as_text()


# ------------------------------------ the recurrent family's cell (PR 35)
# (layers, slots, heads, R, C) of the carried state and the rule's
# vectors' last two dimensions, at the two cells' sizes
STATE_STEPS = {
    "mamba2": ((36, 96, 64, 64, 128),
               [(64, 64, 1), (64, 1, 1), (1, 1, 128), (1, 1, 128)]),
    "delta_rule": ((9, 96, 32, 128, 128),
                   [(32, 128, 1), (32, 128, 1), (32, 1, 128), (32, 1, 1),
                    (32, 1, 1)]),
}


@pytest.mark.parametrize("family", STATE_STEPS)
def test_state_step_kernel(chip, family):
    """``dstpu_state_step`` alone with each family's own rule, at its
    cell's sizes: a whole slot's state a tile (2 MiB), the buffer's
    result aliased to its operand and nothing of its size beside it."""
    from deepspeed_tpu.models import granite_hybrid, qwen3_next

    rule = {"mamba2": granite_hybrid.ssm_rule,
            "delta_rule": qwen3_next.gdn_rule}[family]
    state, vectors = STATE_STEPS[family]
    assert K._state_tile(state[1], state[2], state[3] * state[4] * 4,
                         K._STATE_TILE_BYTES) == (1, state[2])
    f32 = jnp.float32
    compiled = _compile(
        lambda state, layer, *v: K.state_step(rule, state, layer, v), chip,
        (state, f32), ((), jnp.int32),
        *[((state[1],) + v, f32) for v in vectors])
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert re.search(r"%dstpu_state_step[\w.]* = .*tpu_custom_call", hlo)
    assert "output_to_operand_aliasing={{1}: (" in hlo
    assert memory.temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("block", [64, 128])
def test_state_chunk_kernel(chip, block):
    """``dstpu_state_chunk`` alone under the delta rule's block, at the
    recurrent cell's sizes (a slot's 32 states of 128 x 128, 16 key
    heads, a chunk of 1,024 tokens) and both blocks the family may run:
    Mosaic takes the rule's arithmetic (products of bf16 pairs with f32
    accumulation, the inverse by blocks, a product over the rows of both
    operands), the rows' result is aliased to its operand, and nothing
    the rule makes on its way is a value of the program's."""
    from deepspeed_tpu.models import qwen3_next
    from deepspeed_tpu.models.family import SlotState

    f32, T, Hk, Hv, D = jnp.float32, 1024, 16, 32, 128
    compiled = _compile(
        lambda q, k, v, g, beta, S: qwen3_next.gdn_chunk_kernel(
            q, k, v, g, beta, SlotState(S, K.state_chunk), block), chip,
        ((1, T, Hk, D), f32), ((1, T, Hk, D), f32), ((1, T, Hv, D), f32),
        ((1, T, Hv), f32), ((1, T, Hv), f32), ((1, Hv, D, D), f32))
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert re.search(r"%dstpu_state_chunk[\w.]* = .*tpu_custom_call", hlo)
    assert "output_to_operand_aliasing={{1}: (0, {})}" in hlo
    assert f"f32[{T // block},1,{Hv},{block}" not in hlo
    assert memory.temp_size_in_bytes < 8 << 20


def test_window_flash_kernel(chip):
    """``dstpu_window_flash_fwd`` alone at the window cell's widths: a
    chunk of 1,024 queries of 72 heads over 8 K/V heads of 128, the
    slot's ring of 512 rows before it.  A K/V head's 1,536 rows of K and
    of V and a block's ``[9 x 128, 640]`` f32 scores fit the fast memory
    a kernel has without asking, and the scores are no value of the
    program's."""
    from deepspeed_tpu.ops.attention_pallas import window_flash_attention_tpu

    bf, T, W, H, KV = jnp.bfloat16, 1024, 512, 72, 8
    compiled = _compile(
        window_flash_attention_tpu, chip, ((1, T, H, DH), bf),
        ((1, T, 2 * KV * DH), bf), ((1, W, 2 * KV * DH), bf),
        ((1,), jnp.int32))
    hlo = compiled.as_text()
    assert re.search(r"%dstpu_window_flash_fwd[\w.]* = .*tpu_custom_call",
                     hlo)
    assert not re.search(r"f32\[[0-9,]*,(640|1024)\]", hlo)


# ---------------- the channel-gated family's cell (PR 51): KDA beside MLA
def test_state_step_kernel_under_a_decay_down_the_rows(chip):
    """``dstpu_state_step`` alone under the channel-gated delta rule, at
    its cell's sizes (ten layers of 96 slots' 32 states of 128 x 128):
    the decay is a third vector down the state's rows beside q and k,
    turned on the spot as they are; a whole slot's state a tile, the
    buffer's result aliased to its operand."""
    from deepspeed_tpu.models import ling_flash

    state = (10, 96, 32, 128, 128)
    vectors = [(32, 128, 1), (32, 128, 1), (32, 1, 128), (32, 128, 1),
               (32, 1, 1)]
    f32 = jnp.float32
    compiled = _compile(
        lambda state, layer, *v: K.state_step(ling_flash.kda_rule, state,
                                              layer, v), chip,
        (state, f32), ((), jnp.int32),
        *[((state[1],) + v, f32) for v in vectors])
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert re.search(r"%dstpu_state_step[\w.]* = .*tpu_custom_call", hlo)
    assert "output_to_operand_aliasing={{1}: (" in hlo
    assert memory.temp_size_in_bytes < 8 << 20


def test_state_chunk_kernel_under_the_channel_gated_block(chip):
    """``dstpu_state_chunk`` alone under the KDA block rule, at the
    cell's sizes (a slot's 32 states of 128 x 128, a chunk of 1,024
    tokens in blocks of 64): the running decay goes in as a FOURTH tile
    of 128 numbers a token and head beside q, k and v, beta as the one
    column; Mosaic takes the strips' sliced rows, their products over the
    lanes of both operands, the rows put together again and the
    diagonal that turns ``e^c_C`` down the state's rows; the rows'
    result is aliased to its operand."""
    from deepspeed_tpu.models import ling_flash
    from deepspeed_tpu.models.family import SlotState

    f32, T, H, D = jnp.float32, 1024, 32, 128
    compiled = _compile(
        lambda q, k, v, g, beta, S: ling_flash.kda_chunk(
            q, k, v, g, beta, SlotState(S, K.state_chunk), 64), chip,
        *[((1, T, H, D), f32)] * 4, ((1, T, H), f32), ((1, H, D, D), f32))
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    call = re.search(r"%dstpu_state_chunk[\w.]* = .*tpu_custom_call.*", hlo)
    assert call and "output_to_operand_aliasing={{1}: (0, {})}" in hlo
    assert call.group(0).count("f32[1,1024,4096]") >= 5     # 4 in and o
    assert memory.temp_size_in_bytes < 8 << 20
