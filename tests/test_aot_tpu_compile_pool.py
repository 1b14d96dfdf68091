"""GPT-2's and Mixtral's serving programs, and the ZeRO-3 step, compiled
whole for a described v5e (``test_aot_tpu_compile.py`` says how, and holds
the kernels alone): what the compiler made of them is read from the
optimized HLO.  Nothing executes."""

import dataclasses
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import kernels as K
from deepspeed_tpu.inference.paged_forward import forward_paged
from deepspeed_tpu.inference.serving import _sample_rows, serving_programs
from deepspeed_tpu.models import gpt2, mixtral

from _aot import (DH, PAGE, _blocked_chunk_reader, _flash_kernels,
                  _pool_scatters, _pool_sized_ops, _shaped_like)


# ------------------------------------------- the K/V pool stays in place
# The serving programs at the benchmark's widths, four layers deep, under
# the build's rule (``kernels.paged_reader``): (family, config, pool pages, decode rows,
# table entries, bound on the decode program's temporaries in GiB:
# PERF.md 4, AOT, PR 25; the chats' 0.017 is of the engine's whole decode
# program, 64 rows of the sampler's f32 logits included: 15.8 MiB, AOT,
# PR 31).  The engines are the benchmark cells': chat-0.8knee, chat-sat
# and docs-sat.
_GPT2 = lambda: dataclasses.replace(gpt2.GPT2Config.gpt2_1_3b(), n_layers=4)
_MIXTRAL = lambda: dataclasses.replace(mixtral.MixtralConfig.mixtral_8x7b(),
                                       n_layers=4)
POOLS = {
    "gpt2_1_3b": (gpt2, _GPT2, 1793, 28, 64, 0.12),
    "mixtral_chat": (mixtral, _MIXTRAL, 4097, 64, 64, 0.017),
    "mixtral_docs": (mixtral, _MIXTRAL, 3121, 6, 520, 0.015),
}
# phase -> (rows, tokens, continuation); None rows = the decode batch.
# Prefill and chunk run one row at a time, as the engine dispatches them.
PHASES = {"decode": (None, 1, False), "prefill": (1, 256, False),
          "chunk": (1, 128, True)}


def test_pool_sized_ops_reads_the_old_shape_of_the_loop():
    """The reader itself, on the operations the scan-over-the-pool loop
    compiled to (PERF.md 5, PR 24) and on what may stay."""
    hlo = """
%fused_computation.6 (p: bf16[2,4,9,8,16]) -> bf16[2,4,9,8,16] {
  %p = bf16[2,4,9,8,16]{4,3,2,1,0} parameter(0)
  ROOT %scatter.1 = bf16[2,4,9,8,16]{4,3,2,1,0} scatter(%p, %i, %u)
}
%fused_computation.7 (p: bf16[2,4,9,8,16]) -> bf16[4,9,8,16] {
  %p.1 = bf16[2,4,9,8,16]{4,3,2,1,0} parameter(0)
  ROOT %ds = bf16[4,9,8,16]{3,0,2,1} dynamic-slice(%p.1, %l)
}
%body (c: (bf16[2,4,9,8,16])) -> (bf16[2,4,9,8,16]) {
  %g = bf16[2,4,9,8,16]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%c), index=0
  %fusion.1 = bf16[2,4,9,8,16]{4,3,2,1,0:T(8,128)(2,1)} fusion(%g), kind=kCustom, calls=%fused_computation.6
  %copy_bitcast_fusion.4 = bf16[4,9,8,16]{3,0,2,1:T(8,128)(2,1)} fusion(%g), kind=kLoop, calls=%fused_computation.7
  %att = bf16[64,8,128]{2,1,0} custom-call(%q, %fusion.1), custom_call_target="tpu_custom_call"
  ROOT %t = (bf16[2,4,9,8,16]{4,3,2,1,0}) tuple(%fusion.1)
}
ENTRY %main (k: bf16[2,4,9,8,16]) -> bf16[2,4,9,8,16] {
  %k = bf16[2,4,9,8,16]{4,3,2,1,0} parameter(0)
  %w = (bf16[2,4,9,8,16]{4,3,2,1,0}) while(%k), condition=%cond, body=%body
  ROOT %copy.54 = bf16[2,4,9,8,16]{4,3,2,1,0} copy(%k)
}
"""
    assert _pool_sized_ops(hlo, (2, 4, 9, 8, 16)) == [
        "copy_bitcast_fusion.4 = bf16[4,9,8,16] fusion",
        "copy.54 = bf16[2,4,9,8,16] copy"]
    assert _pool_scatters(hlo, (2, 4, 9, 8, 16)) == ["scatter.1"]


@pytest.mark.parametrize("phase", PHASES)
@pytest.mark.parametrize("pool", POOLS)
def test_serving_program_leaves_the_pool_in_place(chip, pool, phase):
    """The engine's decode, whole-prompt prefill and chunk programs
    (``serving_programs`` over ``forward_paged``, with the operand lists
    ``ServingEngine`` dispatches: a prefill's last real position; a
    decode's base key and dispatch ordinal) hold no copy of the K/V pool
    or of one layer of it: the pool is a carry of the layer loop, the
    writers scatter rows into it and the readers take a layer by its
    index.  None returns ``[1, T, V]`` logits: a prefill's result is the
    one row its first token is sampled from, a decode's its tokens.

    A decode program reads live pages only: under the rule it
    holds the Mosaic decode kernel at every engine (28 x 64 table
    entries, 64 x 64, 6 x 520) and nothing shaped like the gathered copy
    of every slot's whole table row ``[B, KV, max_pages * ps, Dh]``.
    The chunk program (128 rows, heads of 128) holds the blocked chunk
    reader and no f32 value over the table's keys; a whole-prompt
    prefill reads no page."""
    family, make_cfg, pages, batch, table, decode_temp_gib = POOLS[pool]
    rows, T, continuation = PHASES[phase]
    rows = rows or batch
    cfg = make_cfg()
    shape = (cfg.n_layers, cfg.n_kv_heads, pages, PAGE, DH)
    on_chip = lambda tree: jax.tree.map(       # page_size stays an int
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
        if hasattr(x, "shape") else x, tree)
    params = jax.eval_shape(lambda: family.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    kv = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    cache = K.PagedKVCache(
        k=kv, v=kv, table=jax.ShapeDtypeStruct((rows, table), jnp.int32),
        seq_lens=jax.ShapeDtypeStruct((rows,), jnp.int32), page_size=PAGE)

    forward = lambda continuation: lambda params, tokens, cache: \
        forward_paged(params, tokens, cfg, cache, interpret=False,
                      tp=False, continuation=continuation)
    prefill, chunk, _, _, decode = serving_programs(
        forward(False), forward(False), forward(True), _sample_rows,
        decode_chunk=1, max_batch=rows)
    last = (jax.ShapeDtypeStruct((1,), jnp.int32),)
    program, operands = {
        "prefill": (prefill, last), "chunk": (chunk, last),
        "decode": (decode, (jax.ShapeDtypeStruct((2,), jnp.uint32),
                            jax.ShapeDtypeStruct((), jnp.int32),
                            jax.ShapeDtypeStruct((rows,), jnp.float32))),
    }[phase]
    compiled = jax.jit(program, donate_argnums=(2,)).lower(*on_chip((
        params, jax.ShapeDtypeStruct((rows, T), jnp.int32),
        cache, *operands))).compile()
    hlo = compiled.as_text()
    memory = compiled.memory_analysis()
    temp = memory.temp_size_in_bytes
    # one row or the tokens, and the cache (aliased to its donated
    # argument), are all a program returns
    assert memory.output_size_in_bytes - memory.alias_size_in_bytes \
        <= 4 * cfg.vocab_size + 2048
    assert _pool_sized_ops(hlo, shape) == []
    if phase == "decode":
        assert re.search(r"%dstpu_paged_decode[\w.]* = .*tpu_custom_call",
                         hlo)
        assert _shaped_like(hlo, rows, cfg.n_kv_heads, table * PAGE,
                            DH) == []
        assert temp <= decode_temp_gib * 2 ** 30
        # the reader writes the step's rows: no row scatter walks the pool
        assert _pool_scatters(hlo, shape) == []
    elif phase == "chunk":
        _blocked_chunk_reader(hlo, table * PAGE)
    else:
        assert "dstpu_paged" not in hlo
    pool_bytes = 2 * math.prod(shape) * 2               # K and V, bf16
    assert temp < pool_bytes / 2


# ------------------------------------- docs-sat's chunk of 1,024 tokens
# table entries -> bound on the temporaries in GiB.  Over the chunk's own
# 64 pages the every-expert-every-row program held 0.228 GiB and the
# grouped one 0.126; over the full table both held the f32 scores of
# 1,024 queries against 8,320 gathered keys, 1.040 and 1.049 GiB (AOT, PR
# 34).  Since PR 46 the blocked chunk reader keeps the scores on the chip
# and both tables' programs hold 0.123 GiB, what the FFN leaves (AOT).
@pytest.mark.parametrize("table,temp_gib", [(64, 0.14), (520, 0.14)],
                         ids=["first_chunk", "full_table"])
def test_mixtral_chunk_program_groups_the_rows_by_expert(
        chip, monkeypatch, table, temp_gib):
    """``mixtral-8x7b-d4.serve.docs-sat``'s chunk program as the engine
    builds it (the experts' rows counted): each layer's FFN is one
    Mosaic call (``dstpu_held_ffn``, PR 52) over the 2,048 (row, expert)
    pairs the router chose, read out of the whole stack in place; nothing
    shaped like every expert's answer for every row ``[8, 1024, 14336]``
    is left and no layer's 2.8 GB of experts is copied out of the stack.
    Its attention over history is the blocked chunk reader's, and no f32
    value over the table's 8,320 keys is left."""
    # the grouped product asks the backend which kernel to run; the
    # described chip is not the default backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, make_cfg, pages, _, _, _ = POOLS["mixtral_docs"]
    cfg, T = make_cfg(), 1024
    shape = (cfg.n_layers, cfg.n_kv_heads, pages, PAGE, DH)
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
        if hasattr(x, "shape") else x, tree)
    params = jax.eval_shape(lambda: mixtral.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    kv = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    cache = K.PagedKVCache(
        k=kv, v=kv, table=jax.ShapeDtypeStruct((1, table), jnp.int32),
        seq_lens=jax.ShapeDtypeStruct((1,), jnp.int32), page_size=PAGE,
        expert_rows=jax.ShapeDtypeStruct((cfg.num_experts,), jnp.int32))
    forward = lambda continuation: lambda params, tokens, cache: \
        forward_paged(params, tokens, cfg, cache, interpret=False,
                      tp=False, continuation=continuation)
    _, chunk, _, _, _ = serving_programs(
        forward(False), forward(False), forward(True), _sample_rows,
        decode_chunk=1, max_batch=1, expert_rows=True)
    compiled = jax.jit(chunk, donate_argnums=(2,)).lower(*on_chip((
        params, jax.ShapeDtypeStruct((1, T), jnp.int32), cache,
        jax.ShapeDtypeStruct((1,), jnp.int32)))).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert len(re.findall(r"%dstpu_held_ffn[\w.]* = .*tpu_custom_call",
                          hlo)) == 1
    assert _shaped_like(hlo, cfg.num_experts, T, cfg.ffn_dim) == []
    assert _shaped_like(hlo, cfg.num_experts, cfg.dim, cfg.ffn_dim) == []
    assert "dynamic-slice_bitcast_fusion" not in hlo
    assert _pool_sized_ops(hlo, shape) == []
    _blocked_chunk_reader(hlo, table * PAGE if table * PAGE != T else None)
    assert memory.temp_size_in_bytes <= temp_gib * 2 ** 30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30


# ------------------------------------------- ZeRO-3 over the four chips
def test_zero3_step_gathers_a_layer_and_scatters_its_gradient(
        topo, monkeypatch):
    """``gpt2-1.3b.train.zero3-x4``'s loss and gradient at the published
    widths (two layers), compiled for the 2x2: the flash forward kernel
    runs once a layer and one backward kernel a head; a layer's weights arrive
    by bf16 all-gathers and nothing activation-shaped moves inside the
    layer loop; the four matrices' gradients leave as reduce-scatter
    fusions (the TPU compiler's spelling); the one all-to-all left is the
    embedding lookup's gradient, outside the loop, which the compiler
    prefers to reducing a table-sized partial sum.  The CPU mesh of
    tests/test_zero_engine.py cannot show the reduce-scatter: its compiler
    writes all-reduce + dynamic-slice."""
    from deepspeed_tpu import topology, zero
    from deepspeed_tpu.comm.digest import analyze_collectives
    from deepspeed_tpu.topology import MeshSpec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ms = MeshSpec.build({"data": 4}, devices=topo.devices)
    monkeypatch.setattr(topology, "_CURRENT_MESH", ms)
    monkeypatch.setattr(topology, "_CURRENT_ZERO_STAGE", 3)
    cfg = dataclasses.replace(
        gpt2.GPT2Config.gpt2_1_3b(remat="save_dots"), n_layers=2)
    B, T, d = 16, 1024, cfg.dim
    shapes = jax.eval_shape(
        lambda: gpt2.init_params(jax.random.PRNGKey(0), cfg))
    layout = zero.param_shardings(shapes, ms, 3)
    loss = gpt2.loss_fn(cfg)

    def grads(params, tokens):
        cast = lambda p: jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        g = jax.grad(lambda p: loss(cast(p), {"tokens": tokens}))(params)
        return zero.grad_constraint(g, ms, 3)

    compiled = jax.jit(grads, out_shardings=layout).lower(
        jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), shapes, layout),
        jax.ShapeDtypeStruct((B, T + 1), jnp.int32, sharding=ms.sharding(
            ms.batch_spec()))).compile()
    hlo = compiled.as_text()
    # the flash forward kernel once, in the forward's layer body: its
    # context and log-sum survive ``save_dots`` (before PR 62 the
    # backward's body ran it again), and one backward kernel a head
    assert _flash_kernels(hlo) == ["dstpu_flash_bwd", "dstpu_flash_fwd"]
    # two layers' step: 1.70 GiB of temporaries (PERF.md 6, PR 62)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.9 * 2 ** 30

    lines = [l for l in hlo.splitlines() if re.search(
        r" (all-gather|all-to-all|collective-permute)(-start)?\(", l)]
    moved = [l for l in lines if re.search(rf"\[[\d,]*\b{T}\b[\d,]*\]", l)
             and f"[{T},{d}]" not in l]               # [T, d]: wpe
    assert all(re.search(r"transpose\(jvp\([^\"]*\bembed\b", l)
               for l in moved), moved
    assert len([l for l in moved if "all-to-all" in l]) <= 1
    gathers = [l for l in lines if " all-gather" in l and l not in moved]
    assert gathers and all(re.search(r"= \(?bf16\[", l) for l in gathers)
    scattered = re.findall(
        r"= bf16\[([\d,]+)\]\S* fusion\([^)]*\), kind=kCustom, "
        r"calls=%all-reduce-scatter", hlo)
    elements = sorted(int(np.prod([int(x) for x in s.split(",")]))
                      for s in scattered)
    # qkv, proj, fc, out: a quarter each (rows padded to a tile's multiple)
    want = sorted(n // 4 for n in (3 * d * d, d * d, 4 * d * d, 4 * d * d))
    big = [e for e in elements if e >= want[0]]
    assert len(big) >= 4 and all(
        w <= e <= 1.05 * w for e, w in zip(big[:4], want)), elements
    digest = analyze_collectives(hlo)["per_kind"]
    assert digest.get("all-reduce", {"bytes": 0})["bytes"] < 2 * d * d
