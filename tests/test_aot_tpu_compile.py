"""Main-path Pallas kernels compiled for a described (not attached) v5e.

Interpret mode, which every other kernel test uses on the CPU, checks
the arithmetic and none of Mosaic's layout rules.  The TPU compiler is
installed here and compiles for a chip it is only told about, so these
cases raise what a real chip would raise at the widths the chip runs:
GPT-2 1.3B (16 heads of 128, T 1024 — ``chip_smoke.py``'s model) and a
GQA layout (32 query / 8 KV heads of 128, T 2048).  Nothing executes;
results are the interpret-mode tests' job.
"""

import dataclasses
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference import kernels as K
from deepspeed_tpu.inference.paged_forward import forward_paged
from deepspeed_tpu.inference.serving import _sample_rows, serving_programs
from deepspeed_tpu.models import gpt2, mixtral
from deepspeed_tpu.ops.attention_pallas import flash_attention_tpu

# (name, heads, kv_heads, batch, seq) — head_dim is 128 in both
GPT2_1_3B = ("gpt2_1_3b", 16, 16, 4, 1024)
GQA_32_8 = ("gqa_32_8", 32, 8, 2, 2048)
LAYOUTS = [GPT2_1_3B, GQA_32_8]
DH, PAGE, TABLE_TOKENS = 128, 16, 4096


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2; the persistent compilation cache stays off,
    because an entry written for a described chip cannot be read back
    without one and the next compile warns."""
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """Sharding on one described v5e device."""
    return SingleDeviceSharding(topo.devices[0])


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
_NOT_OPS = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
            "call", "conditional"}


def _pool_sized_ops(hlo, pool_shape):
    """Instructions of the optimized HLO, fusion bodies aside, whose
    result has the element count of the pool or of one layer of it and
    is not the in-place scatter (or its fusion) or a Mosaic call."""
    sizes = {math.prod(pool_shape), math.prod(pool_shape[1:])}
    bodies, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if m:
            cur = bodies.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    called = lambda line: re.search(r"calls=%([\w.\-]+)", line).group(1)
    fusion_bodies = {called(l) for ls in bodies.values() for l in ls
                     if " fusion(" in l}
    found = []
    for comp, lines in bodies.items():
        if comp in fusion_bodies:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) "
                         r"([a-z][\w\-]*)\(", line)
            if not m or m.group(3) in _NOT_OPS:
                continue
            name, result, op = m.groups()
            counts = {math.prod(int(d) for d in dims.split(",") if d)
                      for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", result)}
            if not counts & sizes:
                continue
            if op == "scatter" or "tpu_custom_call" in line or (
                    op == "fusion" and any(" scatter(" in l
                                           for l in bodies[called(line)])):
                continue
            found.append(f"{name} = {result.split('{')[0]} {op}")
    return found


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


def _pool_scatters(hlo, pool_shape):
    """Scatters anywhere in the HLO, fusion bodies included, whose result
    has the pool's shape: the row writers (``kernels._scatter_rows``),
    which :func:`_pool_sized_ops` lets pass."""
    dims = ",".join(map(str, pool_shape))
    return re.findall(rf"%([\w.\-]+) = \w+\[{dims}\]\S* scatter\(", hlo)


def _shaped_like(hlo, *dims):
    """Results anywhere in the HLO, fusion bodies included, with the
    element count of ``dims`` and their last dim (a weight stack can
    share the count, never the head dim)."""
    want = math.prod(dims)
    return sorted({f"{t}[{d}]" for t, d in
                   re.findall(r"\b([a-z]\w*)\[([\d,]+)\]", hlo)
                   if math.prod(int(x) for x in d.split(",")) == want
                   and d.endswith(f",{dims[-1]}")})


def _blocked_chunk_reader(hlo, table_rows=None):
    """A chunk program's attention over K/V pages runs in the blocked
    Mosaic reader, by name; and, over a table of ``table_rows`` keys,
    no f32 value has that count as a dimension: the gathered reader's
    scores (and its gathered K and V) are gone from the program."""
    assert re.search(r"%dstpu_paged_chunk_v2[\w.]* = .*tpu_custom_call", hlo)
    if table_rows:
        assert not re.search(
            rf"f32\[(?:[0-9]+,)*{table_rows}(?:,[0-9]+)*\]", hlo)


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


# ---------------------------------------------- the latent family's cell
# openpangu-ultra-moe-718b-ep16-d5.serve.think-sat as the benchmark builds
# it: 1 dense + 4 expert layers at the published widths, 16 of 256 experts
# held, an eighth of the vocabulary; 128 slots over 40,961 pages of 16.
_PANGU = dict(vocab_size=19200, n_layers=5, n_dense_layers=1,
              experts_held=(0, 16))
_PANGU_PAGES, _PANGU_SLOTS, _PANGU_TABLE = 40961, 128, 12288 // PAGE
# program -> (rows, tokens, table entries, bound on its temporaries in
# GiB: AOT, PR 40, reads 0.072, 0.856 and 0.155; the last 0.268 while
# the pair buffer was 8,192 rows tall)
PANGU_PROGRAMS = {"decode": (_PANGU_SLOTS, 1, _PANGU_TABLE, 0.1),
                  "chunk_full_table": (1, 1024, _PANGU_TABLE, 0.95),
                  "chunk_first": (1, 1024, 64, 0.2)}


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


@pytest.mark.parametrize("program", PANGU_PROGRAMS)
def test_latent_cell_programs_fit_and_leave_the_pool_in_place(
        chip, monkeypatch, program):
    """The decode and chunk programs of the latent family's cell, at the
    cell's sizes: they compile for the described v5e (the 9.16 GiB of
    weights and the 3.91 GiB pool beside their temporaries, inside
    15.75 GiB), hold no copy of the pool or of a layer's 1.5 GB of
    experts, and run the kernels by name; a chunk's pair buffer is the
    1,024 rows that bound the pairs held here, not the 8,192 there
    are."""
    from deepspeed_tpu.models import pangu_ultra_moe as pangu

    # the family asks the backend which attention and grouped product to
    # run; the described chip is not the default backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, T, table, temp_gib = PANGU_PROGRAMS[program]
    cfg = pangu.PanguUltraMoEConfig(**_PANGU)
    shape = (cfg.n_layers, 1, _PANGU_PAGES, PAGE, cfg.head_dim)
    assert cfg.head_dim == 640
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
        if hasattr(x, "shape") else x, tree)
    params = jax.eval_shape(lambda: pangu.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    cache = K.PagedKVCache(
        k=jax.ShapeDtypeStruct(shape, jnp.bfloat16), v=None,
        table=jax.ShapeDtypeStruct((rows, table), jnp.int32),
        seq_lens=jax.ShapeDtypeStruct((rows,), jnp.int32), page_size=PAGE,
        expert_rows=jax.ShapeDtypeStruct((16 + 1,), jnp.int32))
    forward = lambda continuation: lambda params, tokens, cache: \
        forward_paged(params, tokens, cfg, cache, interpret=False,
                      tp=False, continuation=continuation)
    _, chunk, _, _, decode = serving_programs(
        forward(False), forward(False), forward(True), _sample_rows,
        decode_chunk=1, max_batch=rows, expert_rows=True)
    run, operands = (
        (decode, (jax.ShapeDtypeStruct((2,), jnp.uint32),
                  jax.ShapeDtypeStruct((), jnp.int32),
                  jax.ShapeDtypeStruct((rows,), jnp.float32)))
        if program == "decode"
        else (chunk, (jax.ShapeDtypeStruct((1,), jnp.int32),)))
    compiled = jax.jit(run, donate_argnums=(2,)).lower(*on_chip((
        params, jax.ShapeDtypeStruct((rows, T), jnp.int32), cache,
        *operands))).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= temp_gib * 2 ** 30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    assert _pool_sized_ops(hlo, shape) == []
    # a layer's experts are read in place, not sliced out of the stack
    assert "dynamic-slice_bitcast_fusion" not in hlo
    kernel = "dstpu_mla_decode" if program == "decode" \
        else "dstpu_latent_flash_fwd"
    assert re.search(rf"%{kernel}[\w.]* = .*tpu_custom_call", hlo)
    if program != "decode":
        assert re.search(r"%dstpu_held_ffn[\w.]* = .*tpu_custom_call", hlo)
        assert "bf16[8192,7680]" not in hlo and "f32[1024,8,7680]" not in hlo
        # the pass loop hands the Mosaic call the stack it was handed
        for stack in ((64, 7680, 2048), (64, 2048, 7680)):
            assert _top_level_results(hlo, stack) == []


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


# qwen3-next-80b-a3b-ep8-d12.serve.docqa-sat as the benchmark builds it:
# three periods of three Gated DeltaNet layers and one gated attention
# layer at the published widths, 64 of 512 experts held, an eighth of the
# vocabulary; 96 slots, each with a state beside its pages, over 65,537
# pages of 16 in a pool of the THREE attention layers.
_QWEN = dict(vocab_size=18992, n_layers=12, experts_held=(0, 64))
_QWEN_PAGES, _QWEN_SLOTS, _QWEN_TABLE = 65537, 96, 17408 // PAGE
# program -> (rows, tokens, table entries, bound on its temporaries in
# GiB: AOT, PR 46, reads 0.066, 0.339 and 0.340: with the blocked chunk
# reader a chunk program holds what the recurrent rule and the FFN leave
# at every table width (PR 40: 0.070, 1.266 and 0.340: the attention
# layers' gathered K/V and f32 scores were the widest program's peak);
# 0.26-0.29, 1.26 and 0.55-0.61 while the outer loop sliced a period of
# the linear layers' weights out of their stack; AOT, PR 50, reads 0.066,
# 0.246 and 0.246: the chunked rule's [16, 1, 32, 64, 64] matrices and
# its re-blocked q, k and v are gone with it, 0.09 GiB of the 0.34)
QWEN_PROGRAMS = {"decode": (_QWEN_SLOTS, 1, _QWEN_TABLE, 0.1),
                 "chunk_full_table": (1, 1024, _QWEN_TABLE, 0.26),
                 "chunk_first": (1, 1024, 64, 0.26)}


def _top_level_results(hlo, dims):
    """(name, opcode, called computation's lines) of the instructions,
    fusion bodies aside, one of whose results has exactly ``dims``."""
    bodies, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if m:
            cur = bodies.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    called = lambda line: re.search(r"calls=%([\w.\-]+)", line)
    fused = {called(l).group(1) for ls in bodies.values() for l in ls
             if " fusion(" in l}
    want = "[" + ",".join(map(str, dims)) + "]"
    found = []
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) "
                         r"([a-z][\w\-]*)\(", line)
            if m and m.group(3) not in _NOT_OPS and want in m.group(2):
                body = bodies.get(called(line).group(1), []) \
                    if called(line) else []
                found.append((m.group(1), m.group(3), body))
    return found


def _state_stepped_in_place(hlo, state_shape, program, unrolled_lead=False):
    """The whole state is only ever the carried buffer, and one layer of
    it is never a value of its own.  A chunk program updates its slot's
    rows in place (a dynamic-update-slice, or the fusion that ends in
    one); a decode program hands the buffer to ``dstpu_state_step``,
    whose result aliases it, and nothing else of the state's shape is
    computed: no copy of it, no slice of a layer, no reduction fusion
    that reads one."""
    results = _top_level_results(hlo, state_shape)
    if program == "decode":
        assert results and all(
            op == "custom-call" and name.startswith("dstpu_state_step")
            for name, op, _ in results), [(n, o) for n, o, _ in results]
        aliased = re.findall(
            r"%(dstpu_state_step[\w.]*) = .*?custom-call\((.*?)\), "
            r"custom_call_target=\"tpu_custom_call\".*?"
            r"output_to_operand_aliasing=\{\{1\}: \((\d+), \{\}\)\}", hlo)
        assert len(aliased) == len(results)
        shaped = "f32[" + ",".join(map(str, state_shape)) + "]"
        for _, operands, at in aliased:
            # the aliased operand is the carried buffer itself: a loop's
            # tuple element, or (``unrolled_lead``) the entry's own
            # parameter where a leading stack's loop of one layer was
            # unrolled
            operand = operands.split(", ")[int(at)].split("*/")[-1]
            carried = "(get-tuple-element|parameter)" if unrolled_lead \
                else "get-tuple-element"
            assert re.search(
                re.escape(operand) + r" = " + re.escape(shaped)
                + r"\S* " + carried + r"\(", hlo), operand
        # and no fusion takes the buffer (to slice a layer out and reduce
        # it, as the parent's two a layer did): the entry's own parameter
        # aside, it is only ever a loop's tuple element
        assert re.findall(r"%(?!cache)[\w.\-]+ = " + re.escape(shaped)
                          + r"\S* parameter\(", hlo) == []
    else:
        for name, op, body in results:
            assert op == "dynamic-update-slice" or (
                op == "fusion" and any(
                    "ROOT" in l and " dynamic-update-slice(" in l
                    for l in body)), (name, op)
    assert _top_level_results(hlo, state_shape[1:]) == []
    assert _top_level_results(hlo, (1,) + state_shape[1:]) == []
    # and it is updated once a layer, never rematerialised: with the
    # three linear layers of a period unrolled in one loop body the
    # compiler recomputed a layer's in-place update from the buffer it
    # had already overwritten, under a full chip's memory pressure only,
    # and the state moved twice a step (v5e, PR 35)
    assert "remat" not in " ".join(name for name, _, _ in results)


@pytest.mark.parametrize("program", QWEN_PROGRAMS)
def test_recurrent_cell_programs_fit_and_keep_pool_and_state_in_place(
        chip, monkeypatch, program):
    """The decode and chunk programs of the recurrent family's cell, at
    the cell's sizes: they compile for the described v5e (5.46 GiB of
    weights, a 6.0 GiB pool and 1.73 GiB of per-slot state beside their
    temporaries, inside 15.75 GiB); they hold no copy of the pool, whose
    leading dimension is the three attention layers, nor of the state or
    of one layer of it: a decode step hands the carried buffer to
    ``dstpu_state_step``, which reads and writes a layer's 96 states in
    place, a tile at a time; a layer's experts are read in place; the
    kernels run by name, a chunk's attention over its history (heads of
    256, groups of 8) in the blocked chunk reader with no f32 value over
    the table's 17,408 keys, its delta rule in ``dstpu_state_chunk``."""
    from deepspeed_tpu.models import qwen3_next as qn

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, T, table, temp_gib = QWEN_PROGRAMS[program]
    cfg = qn.Qwen3NextConfig(**_QWEN)
    sr = qn.FAMILY.recurrent.state_row(cfg)
    shape = (cfg.n_full_layers, cfg.n_kv_heads, _QWEN_PAGES, PAGE,
             cfg.head_dim)
    state_shape = (sr.layers, _QWEN_SLOTS) + sr.state
    assert shape[0] == 3 and state_shape == (9, 96, 32, 128, 128)
    S = jax.ShapeDtypeStruct
    on_chip = lambda tree: jax.tree.map(
        lambda x: S(x.shape, x.dtype, sharding=chip)
        if hasattr(x, "shape") else x, tree)
    params = jax.eval_shape(lambda: qn.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == 2_929_374_400
    cache = K.PagedKVCache(
        k=S(shape, jnp.bfloat16), v=S(shape, jnp.bfloat16),
        table=S((rows, table), jnp.int32), seq_lens=S((rows,), jnp.int32),
        page_size=PAGE, expert_rows=S((64 + 1,), jnp.int32),
        conv=S((sr.layers, _QWEN_SLOTS) + sr.conv, jnp.bfloat16),
        state=S(state_shape, K.STATE_DTYPE),
        slot=None if program == "decode" else S((1,), jnp.int32))
    forward = lambda continuation: lambda params, tokens, cache: \
        forward_paged(params, tokens, cfg, cache, interpret=False,
                      tp=False, continuation=continuation)
    _, chunk, _, _, decode = serving_programs(
        forward(False), forward(False), forward(True), _sample_rows,
        decode_chunk=1, max_batch=rows, expert_rows=True, state=True)
    run, operands = (
        (decode, (S((2,), jnp.uint32), S((), jnp.int32),
                  S((rows,), jnp.float32)))
        if program == "decode" else (chunk, (S((1,), jnp.int32),)))
    compiled = jax.jit(run, donate_argnums=(2,)).lower(*on_chip((
        params, S((rows, T), jnp.int32), cache, *operands))).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= temp_gib * 2 ** 30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    assert 13.1 * 2 ** 30 < memory.argument_size_in_bytes < 13.3 * 2 ** 30
    assert _pool_sized_ops(hlo, shape) == []
    _state_stepped_in_place(hlo, state_shape, program)
    if program != "decode":
        # a chunk's grouped product reads a layer's 64 experts in the
        # stack; a decode step (96 rows: every held expert on every row)
        # slices them out, as Mixtral's and the latent family's do
        for experts in ((64, 2048, 512), (64, 512, 2048)):
            assert _top_level_results(hlo, experts) == []
        # nor does the pass loop copy the stack it hands the Mosaic call
        # (the attention layers' [3, 64, ...], the linear layers' [9, ...])
        for stack in ((192, 2048, 512), (192, 512, 2048),
                      (576, 2048, 512), (576, 512, 2048)):
            assert _top_level_results(hlo, stack) == []
        # a pass takes 4,096 sorted pairs, a bound on the ~1,280 of the
        # chunk's 10,240 pairs that are held here, and since PR 52 holds
        # no buffer of their rows' products ([C, f]); no pair that
        # another rank computes is gathered, re-laid out or summed
        for gone in ("bf16[4096,512]", "bf16[16384,2048]",
                     "bf16[10240,2048]", "f32[1024,10,2048]",
                     "f32[10240,2048]"):
            assert gone not in hlo, gone
    if program == "decode":
        assert re.search(r"%dstpu_paged_decode[\w.]* = .*tpu_custom_call",
                         hlo)
    else:
        assert re.search(r"%dstpu_held_ffn[\w.]* = .*tpu_custom_call", hlo)
        _blocked_chunk_reader(hlo, table * PAGE if table * PAGE != T
                              else None)
        # a chunk's delta rule is one Mosaic call a layer (PR 50), handed
        # the slot's rows (aliased to its result), q and k at the 16 key
        # heads' width, not repeated to the 32 value heads, and v; no
        # value is left that holds a block's matrices or its re-blocked
        # operands for every block and head at once
        call = re.search(
            r"%dstpu_state_chunk[\w.]* = .*tpu_custom_call.*?"
            r"operand_layout_constraints=\{([^}]*\}[^}]*)*?\}, "
            r"output_to_operand_aliasing=\{\{1\}: \(0, \{\}\)\}", hlo)
        assert call, "dstpu_state_chunk"
        assert ("f32[1,32,128,128]{3,2,1,0}, f32[1,1024,2048]{2,1,0}, "
                "f32[1,1024,2048]{2,1,0}, f32[1,1024,4096]{2,1,0}"
                ) in call.group(0)
        for gone in ("f32[16,1,32,64", "f32[8,1,32,128"):
            assert gone not in hlo, gone


# v42.granite-4.0-h-micro.serve.assist-sat as the benchmark builds it:
# the whole model (four periods of m m m m m A m m m m at the published
# widths, the whole vocabulary, tied head), 96 slots each with 72.9 MiB
# of state beside its pages, over 6,145 pages of 16 in a pool of the FOUR
# attention layers whose rows of 64 numbers take a 128-lane tile.
_GRANITE_PAGES, _GRANITE_SLOTS, _GRANITE_TABLE = 6145, 96, 2048 // PAGE
# program -> (rows, tokens, bound on its temporaries in GiB: AOT, PR 43,
# reads 0.002 and 0.114 (PR 42: 0.020 and 0.114; 0.114 for the decode
# program while a vector shared by the heads reached the state's kernel
# as [slots, 1, width]: that layout went back through the convolution to
# the carried buffer of its rows, re-laid on its way in and out, 2.7 ms
# a step by the compiler's count); 1.24 and 5.98 (which does not fit) while the
# Mamba-2 input projection was one stack of 8,512 columns, which the chip
# keeps rows-minor and each program re-laid whole, 1.17 GB a step)
GRANITE_PROGRAMS = {"decode": (_GRANITE_SLOTS, 1, 0.01),
                    "chunk_full_table": (1, 256, 0.16)}


@pytest.mark.parametrize("program", GRANITE_PROGRAMS)
def test_state_space_cell_programs_fit_and_keep_pool_and_state_in_place(
        chip, monkeypatch, program):
    """The decode and the widest chunk program of the state-space
    family's cell, at the cell's sizes: they compile for the described
    v5e (5.94 GiB of weights, 6.83 GiB of per-slot state and a 1.5 GiB
    pool beside their temporaries, inside the 15.0 GiB the cell allows
    itself); they hold no copy of the pool, whose leading dimension is
    the four attention layers, nor of the state or of one layer of it
    (a layer's 96 states are 192 MiB: a copy would show in the
    temporaries; the decode step's are 2 MiB since ``dstpu_state_step``
    steps the carried buffer in place), nor of the convolution's rows or
    of a weight stack; the decode kernel runs by name over rows of 128
    lanes."""
    from deepspeed_tpu.models import granite_hybrid as gh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, T, temp_gib = GRANITE_PROGRAMS[program]
    cfg = gh.GraniteHybridConfig()
    sr = gh.FAMILY.recurrent.state_row(cfg)
    row = gh.FAMILY.cache_row(cfg)
    shape = (cfg.n_attn_layers, row.n_kv, _GRANITE_PAGES, PAGE,
             row.pool_width)
    state_shape = (sr.layers, _GRANITE_SLOTS) + sr.state
    assert shape[0] == 4 and shape[-1] == 128
    assert state_shape == (36, 96, 64, 64, 128) and sr.conv == (3, 4352)
    S = jax.ShapeDtypeStruct
    on_chip = lambda tree: jax.tree.map(
        lambda x: S(x.shape, x.dtype, sharding=chip)
        if hasattr(x, "shape") else x, tree)
    params = jax.eval_shape(lambda: gh.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == 3_191_396_096
    cache = K.PagedKVCache(
        k=S(shape, jnp.bfloat16), v=S(shape, jnp.bfloat16),
        table=S((rows, _GRANITE_TABLE), jnp.int32),
        seq_lens=S((rows,), jnp.int32), page_size=PAGE,
        conv=S((sr.layers, _GRANITE_SLOTS) + sr.conv, jnp.bfloat16),
        state=S(state_shape, K.STATE_DTYPE),
        slot=None if program == "decode" else S((1,), jnp.int32))
    forward = lambda continuation: lambda params, tokens, cache: \
        forward_paged(params, tokens, cfg, cache, interpret=False,
                      tp=False, continuation=continuation)
    _, chunk, _, _, decode = serving_programs(
        forward(False), forward(False), forward(True), _sample_rows,
        decode_chunk=1, max_batch=rows, state=True)
    run, operands = (
        (decode, (S((2,), jnp.uint32), S((), jnp.int32),
                  S((rows,), jnp.float32)))
        if program == "decode" else (chunk, (S((1,), jnp.int32),)))
    compiled = jax.jit(run, donate_argnums=(2,)).lower(*on_chip((
        params, S((rows, T), jnp.int32), cache, *operands))).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= temp_gib * 2 ** 30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.0 * 2 ** 30
    assert 14.2 * 2 ** 30 < memory.argument_size_in_bytes < 14.4 * 2 ** 30
    assert _pool_sized_ops(hlo, shape) == []
    _state_stepped_in_place(hlo, state_shape, program)
    # no stack of the large weights is re-laid or copied, and no layer
    # of one is a value of its own
    for stack in ((36, 2048, 8448), (36, 4096, 2048), (36, 2048, 16384),
                  (36, 8192, 2048), (4, 2048, 16384), (100352, 2048)):
        assert _top_level_results(hlo, stack) == [], stack
    # nor, in the decode program, are the convolution's rows beside the
    # state: only ever the carried buffer, updated in place in the
    # layout it came in (the chunk program re-lays them on their way in
    # and out, its 0.114 GiB of temporaries, as it did at PR 42)
    for name, op, body in _top_level_results(
            hlo, (sr.layers, _GRANITE_SLOTS) + sr.conv):
        assert program != "decode" or op == "dynamic-update-slice" or (
            op == "fusion" and any(
                "ROOT" in l and " dynamic-update-slice(" in l
                for l in body)), (name, op)
    if program == "decode":
        assert re.search(r"%dstpu_paged_decode[\w.]* = .*tpu_custom_call",
                         hlo)
    else:
        # a head is 64 numbers in a 128-lane tile (``CacheRow.head_width``):
        # the shape rule leaves this chunk program the gather, as it was
        assert "dstpu_paged_chunk_v2" not in hlo


# v48.nemotron-3-nano-30b-a3b-ep8.serve.code-sat as the benchmark builds
# it: rank 0's share of all 52 layers (23 Mamba-2 mixers of 8 groups, 6
# attention layers, 23 expert layers of 16 held two-matrix experts stored
# 1,920 wide), 64 slots each with 46.8 MiB of state, and a pool of the
# SIX attention layers alone (6 KiB a token).  Pages and slots are the
# cell's own file's.
def _nemotron_cell():
    import json

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "workloads",
        "v48.nemotron-3-nano-30b-a3b-ep8.serve.code-sat.json")
    with open(path) as f:
        return json.load(f)["engine"]


# program -> (rows, tokens, table pages, bound on its temporaries in
# GiB: AOT, PR 48, reads 0.028 and 0.207 at every one of the six table
# widths the engine dispatches, 64 to 1,152 pages)
NEMOTRON_PROGRAMS = {"decode": (None, 1, 18432 // PAGE, 0.05),
                     "chunk_narrowest": (1, 1024, 1024 // PAGE, 0.25),
                     "chunk_full_table": (1, 1024, 18432 // PAGE, 0.25)}


@pytest.mark.parametrize("program", NEMOTRON_PROGRAMS)
def test_sectioned_cell_programs_fit_and_keep_pool_state_and_experts_in_place(
        chip, monkeypatch, program):
    """The decode program and the narrowest and widest chunk programs of
    the sectioned family's cell, at the cell's sizes: they compile for
    the described v5e (10.03 GiB of weights as stored, 2.93 GiB of
    per-slot state and the pool beside their temporaries, under 15.4 of
    15.75 GiB); four sections of two periods run over one pool, whose
    leading dimension is the six attention layers, and one state buffer,
    neither copied: a decode step hands the carried buffer to
    ``dstpu_state_step`` (B and C a head: eight groups), a chunk updates
    its slot's rows in place; no stack of the mixers', the attention's
    or the experts' weights is re-laid or copied, and a chunk's grouped
    product reads a layer's 16 experts in the stack through the Mosaic
    kernel at the stored 1,920 columns."""
    from deepspeed_tpu.models import nemotron_h as nm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine = _nemotron_cell()
    slots, pages = engine["max_batch"], engine["num_pages"]
    rows, T, table, temp_gib = NEMOTRON_PROGRAMS[program]
    rows = rows or slots
    cfg = nm.NemotronHConfig(vocab_size=16384, experts_held=(0, 16))
    sr = nm.FAMILY.recurrent.state_row(cfg)
    row = nm.FAMILY.cache_row(cfg)
    shape = (cfg.n_attn_layers, row.n_kv, pages, PAGE, row.pool_width)
    state_shape = (sr.layers, slots) + sr.state
    assert shape[0] == 6 and shape[-1] == 128 and slots >= 48
    assert state_shape[2:] == (64, 64, 128) and sr.conv == (3, 6144)
    S = jax.ShapeDtypeStruct
    on_chip = lambda tree: jax.tree.map(
        lambda x: S(x.shape, x.dtype, sharding=chip)
        if hasattr(x, "shape") else x, tree)
    params = jax.eval_shape(lambda: nm.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    stored = sum(math.prod(a.shape) for a in jax.tree.leaves(params))
    assert stored == 5_385_036_096 and nm.param_count(cfg) == 5_258_420_544
    cache = K.PagedKVCache(
        k=S(shape, jnp.bfloat16), v=S(shape, jnp.bfloat16),
        table=S((rows, table), jnp.int32), seq_lens=S((rows,), jnp.int32),
        page_size=PAGE, expert_rows=S((16 + 1,), jnp.int32),
        conv=S((sr.layers, slots) + sr.conv, jnp.bfloat16),
        state=S(state_shape, K.STATE_DTYPE),
        slot=None if program == "decode" else S((1,), jnp.int32))
    forward = lambda continuation: lambda params, tokens, cache: \
        forward_paged(params, tokens, cfg, cache, interpret=False,
                      tp=False, continuation=continuation)
    _, chunk, _, _, decode = serving_programs(
        forward(False), forward(False), forward(True), _sample_rows,
        decode_chunk=1, max_batch=rows, expert_rows=True, state=True)
    run, operands = (
        (decode, (S((2,), jnp.uint32), S((), jnp.int32),
                  S((rows,), jnp.float32)))
        if program == "decode" else (chunk, (S((1,), jnp.int32),)))
    compiled = jax.jit(run, donate_argnums=(2,)).lower(*on_chip((
        params, S((rows, T), jnp.int32), cache, *operands))).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= temp_gib * 2 ** 30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.4 * 2 ** 30
    weights_and_state = stored * 2 + math.prod(state_shape) * 4 \
        + sr.layers * slots * math.prod(sr.conv) * 2
    assert 0 < memory.argument_size_in_bytes - weights_and_state \
        - 2 * math.prod(shape) * 2 < 2 ** 24
    assert _pool_sized_ops(hlo, shape) == []
    if program == "decode":
        _state_stepped_in_place(hlo, state_shape, program)
        assert re.search(r"%dstpu_paged_decode[\w.]* = .*tpu_custom_call",
                         hlo)
    else:
        # a chunk updates its slot's rows of the carried state in place:
        # every value of the state's shape is a dynamic-update-slice or a
        # fusion that ends in one (some the scheduler's second writes of
        # the same rows, ``.remat``: the buffer is never copied, 0.2 GiB
        # of temporaries beside 5.9 GiB of state)
        results = _top_level_results(hlo, state_shape)
        assert results
        for name, op, body in results:
            assert op == "dynamic-update-slice" or (
                op == "fusion" and any(
                    "ROOT" in l and " dynamic-update-slice(" in l
                    for l in body)), (name, op)
        assert _top_level_results(hlo, state_shape[1:]) == []
        assert re.search(r"%dstpu_held_ffn[\w.]* = .*tpu_custom_call", hlo)
        _blocked_chunk_reader(hlo, table * PAGE if table * PAGE != T
                              else None)
    L = sr.layers
    for stack in ((L, 2688, 10240), (L, 4096, 2688), (6, 2688, 4608),
                  (6, 4096, 2688), (L, 16, 2688, 1920), (L, 16, 1920, 2688),
                  (L * 16, 2688, 1920), (L * 16, 1920, 2688),
                  (L, 2688, 3712), (L, 3712, 2688), (16384, 2688)):
        assert _top_level_results(hlo, stack) == [], stack


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


# v44.laguna-s-2.1-ep16-d13.serve.code-sat as the benchmark builds it:
# layer 0 and three periods S S S F at the published widths, 16 of 256
# experts, an eighth of the vocabulary; 96 slots each with 18 MiB of rings
# (9 sliding layers x 512 rows of [K | V]) beside a pool of the FOUR full
# layers over 30,721 pages of 16.
_LAGUNA_PAGES, _LAGUNA_SLOTS, _LAGUNA_TABLE = 30721, 96, 18432 // PAGE
# program -> (rows, tokens, table pages, bound on its temporaries in GiB:
# AOT, PR 46, reads 0.030 and 0.107 at all three table widths: the full
# layers' scores stay on the chip in the blocked chunk reader, as the
# band's do since PR 45, and what is left is the FFN's (PR 45: 0.030,
# 0.570, 0.813 and 0.274, the gathered reader's f32 scores: every head's
# over 256 pages, a K/V head's at a time from 512 pages on, under
# ``kernels._CHUNK_SCORE_BYTES``, which no cell's program reaches now).
# As first built: 1.07 at decode (a transposed copy of W_q's stacks, 0.6
# GiB, and a layer's 192 MiB of rings sliced out whole); the widest chunk
# program did not fit (3.4 GiB of float32 scores, 48 x 1,024 x 18,432)
LAGUNA_PROGRAMS = {"decode": (_LAGUNA_SLOTS, 1, _LAGUNA_TABLE, 0.05),
                   "chunk_full_table": (1, 1024, _LAGUNA_TABLE, 0.12),
                   "chunk_256_pages": (1, 1024, 256, 0.12),
                   "chunk_512_pages": (1, 1024, 512, 0.12)}


@pytest.mark.parametrize("program", LAGUNA_PROGRAMS)
def test_window_cell_programs_fit_and_keep_pool_and_rings_in_place(
        chip, monkeypatch, program):
    """The decode program and three chunk programs (the widest table
    and two narrower buckets) of the window family's cell, at the
    cell's sizes: they compile for the described v5e (5.35 GiB of
    weights, 1.69 GiB of rings and a 7.5 GiB pool beside their
    temporaries); they hold no copy of the pool, whose leading dimension
    is the four full layers; the rings are only ever the carried buffer,
    updated in place, and one layer of them (192 MiB) is never a value
    of its own; no stack of the large weights is copied; no float32
    value is as large as one K/V head's scores over the whole table; a
    chunk program's band runs in ``dstpu_window_flash_fwd`` (one call,
    in the sliding layers' loop) and its full layers' attention over
    history in ``dstpu_paged_chunk_v2`` (one call, in theirs), their
    scores no value of the program's, where the decode program has
    neither call."""
    from deepspeed_tpu.models import laguna as lg

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, T, width, temp_gib = LAGUNA_PROGRAMS[program]
    cfg = lg.LagunaConfig(n_layers=13, experts_held=(0, 16),
                          vocab_size=12544)
    sr = lg.FAMILY.recurrent.state_row(cfg)
    row = lg.FAMILY.cache_row(cfg)
    shape = (cfg.n_full_layers, row.n_kv, _LAGUNA_PAGES, PAGE,
             row.pool_width)
    rings = (sr.layers, _LAGUNA_SLOTS) + sr.conv
    assert shape[0] == 4 and rings == (9, 96, 512, 2048)
    assert sr.state is None
    S = jax.ShapeDtypeStruct
    on_chip = lambda tree: jax.tree.map(
        lambda x: S(x.shape, x.dtype, sharding=chip)
        if hasattr(x, "shape") else x, tree)
    params = jax.eval_shape(lambda: lg.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == 2_869_994_496
    held = lg.FAMILY.expert_rows(cfg)[0]
    cache = K.PagedKVCache(
        k=S(shape, jnp.bfloat16), v=S(shape, jnp.bfloat16),
        table=S((rows, width), jnp.int32),
        seq_lens=S((rows,), jnp.int32), page_size=PAGE,
        expert_rows=S((held + 1,), jnp.int32),
        conv=S(rings, jnp.bfloat16), state=None,
        slot=None if program == "decode" else S((1,), jnp.int32))
    forward = lambda continuation: lambda params, tokens, cache: \
        forward_paged(params, tokens, cfg, cache, interpret=False,
                      tp=False, continuation=continuation)
    _, chunk, _, _, decode = serving_programs(
        forward(False), forward(False), forward(True), _sample_rows,
        decode_chunk=1, max_batch=rows, expert_rows=True, state=True)
    run, operands = (
        (decode, (S((2,), jnp.uint32), S((), jnp.int32),
                  S((rows,), jnp.float32)))
        if program == "decode" else (chunk, (S((1,), jnp.int32),)))
    compiled = jax.jit(run, donate_argnums=(2,)).lower(*on_chip((
        params, S((rows, T), jnp.int32), cache, *operands))).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= temp_gib * 2 ** 30
    assert 14.5 * 2 ** 30 < memory.argument_size_in_bytes < 14.6 * 2 ** 30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.4 * 2 ** 30
    assert _pool_sized_ops(hlo, shape) == []
    # the rings: the whole buffer only as the in-place update's result
    # (a scatter of one row a slot at decode, a slot's rows in a chunk),
    # and no layer of it (nor every slot's rows of a layer) on its own
    for name, op, body in _top_level_results(hlo, rings):
        assert op == "fusion" and any(
            "ROOT" in l and (" scatter(" in l or " dynamic-update-slice("
                             in l or " tuple(" in l) for l in body), \
            (name, op)
    assert _top_level_results(hlo, rings[1:]) == []
    assert _top_level_results(hlo, (1,) + rings[1:]) == []
    # no stack of the large weights is re-laid or copied, and no layer
    # of one is a value of its own (W_q and W_o share a shape)
    for stack in ((9, 72, 128, 3072), (9, 9216, 3072), (3, 48, 128, 3072),
                  (3, 6144, 3072), (9, 16, 3072, 1024), (9, 16, 1024, 3072),
                  (3, 16, 3072, 1024), (3, 16, 1024, 3072),
                  (1, 3072, 12288), (1, 12288, 3072), (12544, 3072),
                  (1, 72, 128, 3072), (72, 128, 3072), (1, 9216, 3072),
                  (9216, 3072), (1, 48, 128, 3072), (48, 128, 3072),
                  (6144, 3072)):
        assert [(n, o) for n, o, _ in _top_level_results(hlo, stack)
                if not o.startswith(("copy-start", "copy-done"))] == [], \
            stack
    # every head's scores over the whole table would be 3.4 GiB and one
    # K/V head's query heads over it 0.42: neither is held (the largest
    # f32 value is a chunk's logits, 1,024 x 12,544: 0.048 GiB, AOT, PR 46)
    sizes = [math.prod(int(d) for d in dims.split(",") if d)
             for dims in re.findall(r"f32\[([0-9,]+)\]", hlo)]
    assert max(sizes) * 4 <= 0.1 * 2 ** 30
    # the band: in the kernel, and nowhere an f32 value of its scores (a
    # K/V head's nine query heads over a block pair, or one head's)
    band = re.findall(r"%dstpu_window_flash_fwd[\w.]* = .*tpu_custom_call",
                      hlo)
    assert len(band) == (0 if program == "decode" else 1)
    assert not re.search(r"f32\[[0-9,]*(4608|512),1024\]", hlo)
    if program == "decode":
        assert re.search(r"%dstpu_paged_decode[\w.]* = .*tpu_custom_call",
                         hlo)
    else:
        _blocked_chunk_reader(hlo, width * PAGE)


# ------------------------------------------- ZeRO-3 over the four chips
def test_zero3_step_gathers_a_layer_and_scatters_its_gradient(
        topo, monkeypatch):
    """``gpt2-1.3b.train.zero3-x4``'s loss and gradient at the published
    widths (two layers), compiled for the 2x2: a layer's weights arrive
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

    hlo = jax.jit(grads, out_shardings=layout).lower(
        jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), shapes, layout),
        jax.ShapeDtypeStruct((B, T + 1), jnp.int32, sharding=ms.sharding(
            ms.batch_spec()))).compile().as_text()

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


# v51.ling-3.0-flash-vl-ep8-d12.serve.docqa-sat as the benchmark builds it:
# two periods of five KDA layers and one latent-attention layer at the
# published widths (layer 0 with the dense FFN), 64 of 512 experts held,
# an eighth of the vocabulary; 96 slots, each with ten layers of state
# beside its pages, over 65,537 pages of 16 in a pool of the TWO latent
# layers, 640 lanes a row.
_LING = dict(vocab_size=19648, n_layers=12, n_dense_layers=1,
             experts_held=(0, 64))
_LING_PAGES, _LING_SLOTS, _LING_TABLE = 65537, 96, 17408 // PAGE
# program -> (rows, tokens, table entries, bound on its temporaries in
# GiB: AOT, PR 51, reads 0.072, 0.515 and 0.221 (0.240, 0.691 and 0.485
# while the gate's projection was held [d, outputs]: both programs copied
# its stack whole, 188 MB, to read it in float32)
LING_PROGRAMS = {"decode": (_LING_SLOTS, 1, _LING_TABLE, 0.1),
                 "chunk_full_table": (1, 1024, _LING_TABLE, 0.55),
                 "chunk_first": (1, 1024, 64, 0.25)}


@pytest.mark.parametrize("program", LING_PROGRAMS)
def test_channel_gated_cell_programs_fit_and_keep_pool_and_state_in_place(
        chip, monkeypatch, program):
    """The decode program and the narrowest and widest chunk programs of
    the cell, at its sizes: they compile for the described v5e (9.26 GiB
    of weights, a 2.50 GiB latent pool and 1.94 GiB of per-slot state
    beside their temporaries, inside 15.75 GiB), arguments and
    temporaries pinned; no copy of the pool (its leading dimension the
    two latent layers), of the state or of a layer of it, nor of any
    weight stack at the program's entry; a decode step hands the carried
    buffer to ``dstpu_state_step`` once a KDA layer group and reads the
    latent rows in ``dstpu_mla_decode``; a chunk's rule is
    ``dstpu_state_chunk`` under four operands of 4,096 lanes, its
    attention ``dstpu_latent_flash_fwd``, its experts the grouped
    product over a pair buffer of 2,048 rows."""
    from deepspeed_tpu.models import ling_flash as lf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, T, table, temp_gib = LING_PROGRAMS[program]
    cfg = lf.LingFlashConfig(**_LING)
    sr = lf.FAMILY.recurrent.state_row(cfg)
    shape = (cfg.n_mla_layers, 1, _LING_PAGES, PAGE, cfg.head_dim)
    state_shape = (sr.layers, _LING_SLOTS) + sr.state
    assert shape[0] == 2 and cfg.head_dim == 640
    assert state_shape == (10, 96, 32, 128, 128)
    S = jax.ShapeDtypeStruct
    on_chip = lambda tree: jax.tree.map(
        lambda x: S(x.shape, x.dtype, sharding=chip)
        if hasattr(x, "shape") else x, tree)
    params = jax.eval_shape(lambda: lf.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == 4_969_904_704
    cache = K.PagedKVCache(
        k=S(shape, jnp.bfloat16), v=None,
        table=S((rows, table), jnp.int32), seq_lens=S((rows,), jnp.int32),
        page_size=PAGE, expert_rows=S((64 + 1,), jnp.int32),
        conv=S((sr.layers, _LING_SLOTS) + sr.conv, jnp.bfloat16),
        state=S(state_shape, K.STATE_DTYPE),
        slot=None if program == "decode" else S((1,), jnp.int32))
    forward = lambda continuation: lambda params, tokens, cache: \
        forward_paged(params, tokens, cfg, cache, interpret=False,
                      tp=False, continuation=continuation)
    _, chunk, _, _, decode = serving_programs(
        forward(False), forward(False), forward(True), _sample_rows,
        decode_chunk=1, max_batch=rows, expert_rows=True, state=True)
    run, operands = (
        (decode, (S((2,), jnp.uint32), S((), jnp.int32),
                  S((rows,), jnp.float32)))
        if program == "decode" else (chunk, (S((1,), jnp.int32),)))
    compiled = jax.jit(run, donate_argnums=(2,)).lower(*on_chip((
        params, S((rows, T), jnp.int32), cache, *operands))).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= temp_gib * 2 ** 30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    assert 13.6 * 2 ** 30 < memory.argument_size_in_bytes < 13.8 * 2 ** 30
    assert _pool_sized_ops(hlo, shape) == []
    _state_stepped_in_place(hlo, state_shape, program, unrolled_lead=True)
    assert "copy(%params" not in hlo            # no stack re-laid whole
    if program == "decode":
        assert re.search(r"%dstpu_mla_decode[\w.]* = .*tpu_custom_call",
                         hlo)
    else:
        assert re.search(r"%dstpu_held_ffn[\w.]* = .*tpu_custom_call", hlo)
        assert re.search(
            r"%dstpu_latent_flash_fwd[\w.]* = .*tpu_custom_call", hlo)
        for experts in ((64, 2560, 768), (64, 768, 2560)):
            assert _top_level_results(hlo, experts) == []
        # no buffer of the pairs' rows, bounded (2,048) or not (PR 52)
        assert "bf16[2048,2560]" not in hlo and "bf16[8192,2560]" not in hlo
        call = re.search(
            r"%dstpu_state_chunk[\w.]* = .*tpu_custom_call.*?"
            r"output_to_operand_aliasing=\{\{1\}: \(0, \{\}\)\}", hlo)
        assert call, "dstpu_state_chunk"
        assert ("f32[1,32,128,128]{3,2,1,0}, f32[1,1024,4096]{2,1,0}, "
                "f32[1,1024,4096]{2,1,0}, f32[1,1024,4096]{2,1,0}, "
                "f32[1,1024,4096]{2,1,0}") in call.group(0)


# v55.phi-4-mini-flash-reasoning.serve.think-sat as the benchmark builds
# it: the whole model (32 layers, the whole tied vocabulary), 128 slots
# each with 9 Mamba-1 states [40, 16, 128] and 8 rings of 512 rows beside
# its pages, and a pool of ONE layer (5 KiB a token).  Pages and slots
# are the cell's own file's.
def _phi_cell():
    import json

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "workloads",
        "v55.phi-4-mini-flash-reasoning.serve.think-sat.json")
    with open(path) as f:
        return json.load(f)["engine"]


# program -> (rows, tokens, table pages, bound on its temporaries in
# GiB: AOT, PR 55, reads 0.099 and 0.111 at every one of the five table
# widths the engine dispatches, 64 to 768 pages)
PHI_PROGRAMS = {"decode": (None, 1, 12288 // PAGE, 0.12),
                "chunk_narrowest": (1, 1024, 64, 0.14),
                "chunk_widest": (1, 1024, 12288 // PAGE, 0.14)}


@pytest.mark.parametrize("program", PHI_PROGRAMS)
def test_hybrid_decoder_cell_programs_fit_and_keep_every_cache_in_place(
        chip, monkeypatch, program):
    """The decode program and the narrowest and widest chunk programs of
    the decoder-hybrid-decoder family's cell, at the cell's sizes: they
    compile for the described v5e (7.18 GiB of weights, 2.88 GiB of
    per-slot states and rings and the pool of one layer beside their
    temporaries, under 14.2 of 15.75 GiB); three kinds of cache ride in
    one carry and none is copied: a decode step hands the carried state
    to ``dstpu_state_step`` under the Mamba-1 rule (the decay's tile an
    operand), the rings are updated where they lie, and the pool's one
    layer is written by the full layer's reader and read by the seven
    cross layers' (``dstpu_paged_decode`` eight times: once in the
    self-decoder's section, once in the loop of the cross-decoder's);
    a chunk program runs the blocked reader and the band's kernel on its
    1,024 rows, then the decode reader on the one row it kept, and makes
    no ``[1, 1024, V]``."""
    from deepspeed_tpu.models import phi4_flash as pf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine = _phi_cell()
    slots, pages = engine["max_batch"], engine["num_pages"]
    rows, T, table, temp_gib = PHI_PROGRAMS[program]
    rows = rows or slots
    cfg = pf.Phi4FlashConfig()
    fam = pf.FAMILY
    sr, row = fam.recurrent.state_row(cfg), fam.cache_row(cfg)
    shape = (fam.pool_layers(cfg), row.n_kv, pages, PAGE, row.pool_width)
    state_shape = (sr.layers, slots) + sr.state
    ring_shape = (sr.ring.layers, slots) + sr.ring.conv
    assert shape[:2] == (1, 10) and shape[-1] == 128
    assert state_shape[2:] == (40, 16, 128) and ring_shape[2:] == (512, 2560)
    S = jax.ShapeDtypeStruct
    on_chip = lambda tree: jax.tree.map(
        lambda x: S(x.shape, x.dtype, sharding=chip)
        if hasattr(x, "shape") else x, tree)
    params = jax.eval_shape(lambda: pf.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    stored = sum(math.prod(a.shape) for a in jax.tree.leaves(params))
    assert stored - 16 == pf.param_count(cfg) == 3_852_562_944
    cache = K.PagedKVCache(
        k=S(shape, jnp.bfloat16), v=S(shape, jnp.bfloat16),
        table=S((rows, table), jnp.int32), seq_lens=S((rows,), jnp.int32),
        page_size=PAGE,
        conv=S((sr.layers, slots) + sr.conv, jnp.bfloat16),
        state=S(state_shape, K.STATE_DTYPE),
        ring=S(ring_shape, jnp.bfloat16),
        slot=None if program == "decode" else S((1,), jnp.int32))
    forward = lambda continuation: lambda params, tokens, cache: \
        forward_paged(params, tokens, cfg, cache, interpret=False,
                      tp=False, continuation=continuation)
    _, chunk, _, _, decode = serving_programs(
        forward(False), forward(False), forward(True), _sample_rows,
        decode_chunk=1, max_batch=rows, state=True)
    run, operands = (
        (decode, (S((2,), jnp.uint32), S((), jnp.int32),
                  S((rows,), jnp.float32)))
        if program == "decode" else (chunk, (S((1,), jnp.int32),)))
    compiled = jax.jit(run, donate_argnums=(2,)).lower(*on_chip((
        params, S((rows, T), jnp.int32), cache, *operands))).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= temp_gib * 2 ** 30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 14.2 * 2 ** 30
    held = stored * 2 + math.prod(state_shape) * 4 \
        + sr.layers * slots * math.prod(sr.conv) * 2 \
        + math.prod(ring_shape) * 2 + 2 * math.prod(shape) * 2
    assert 0 < memory.argument_size_in_bytes - held < 2 ** 24
    assert _pool_sized_ops(hlo, shape) == []
    # the rings are only ever the carried buffer
    for name, op, body in _top_level_results(hlo, ring_shape):
        # (a chunk's is a fusion of two results, the rows it read beside
        # the buffer updated in place)
        assert op in ("dynamic-update-slice", "scatter") or (
            op == "fusion" and any(
                " dynamic-update-slice(" in l or " scatter(" in l
                for l in body)), (name, op)
    assert _top_level_results(hlo, ring_shape[1:]) == []
    assert re.search(r"%dstpu_paged_decode[\w.]* = .*tpu_custom_call", hlo)
    if program == "decode":
        _state_stepped_in_place(hlo, state_shape, program)
        assert "dstpu_paged_chunk_v2" not in hlo
    else:
        results = _top_level_results(hlo, state_shape)
        assert results
        for name, op, body in results:
            assert op == "dynamic-update-slice" or (
                op == "fusion" and any(
                    "ROOT" in l and " dynamic-update-slice(" in l
                    for l in body)), (name, op)
        assert re.search(
            r"%dstpu_window_flash_fwd[\w.]* = .*tpu_custom_call", hlo)
        _blocked_chunk_reader(hlo, table * PAGE if table * PAGE != T
                              else None)
        # the head runs on the row the cut kept
        assert "f32[1,1024,200064]" not in hlo
        assert "f32[1024,200064]" not in hlo
    # no stack of the large weights is re-laid or copied whole
    for stack in ((9, 2560, 10240), (9, 5120, 2560), (8, 2560, 5120),
                  (7, 2560, 5120), (7, 5120, 2560), (9, 2560, 20480),
                  (9, 10240, 2560), (200064, 2560)):
        assert _top_level_results(hlo, stack) == [], stack


# --------------- the retention family's cell (PR 59): no pool layer at all
# v59.brumby-14b-base-d10.serve.docqa-sat as the benchmark builds it: ten
# layers of power retention at the published widths with the embedding and
# the whole head (4,859 M parameters, 9.05 GiB), 16 slots each with ten
# layers of eight K/V heads' state [65 x 136, 128] f32 (345 MiB a slot), no
# page pool.
_BRUMBY_SLOTS = 16
# program -> (rows, tokens, bound on its temporaries in GiB: AOT, PR 59,
# reads 0.0005 and 0.068: arguments 14.447 GiB, the largest program 14.51)
BRUMBY_PROGRAMS = {"decode": (_BRUMBY_SLOTS, 1, 0.01),
                   "chunk": (1, 1024, 0.1)}


def test_state_step_kernel_on_a_state_larger_than_a_tile(chip):
    """``dstpu_state_step`` alone under the retention rule, at its cell's
    sizes (ten layers of 16 slots' 8 states of 8,840 x 128, 4.3 MiB a
    head): one head a tile, the rule in place on the tile's reference (a
    rotation's [136, 128] at a time), the five queries of a state head
    and its key and value handed over as rows of 128 numbers (no phi of
    theirs is an operand), ``o`` a whole tile of 8 rows a head; the
    buffer's result aliased to its operand."""
    from deepspeed_tpu.models import brumby
    from deepspeed_tpu.models.family import CarriedState

    cfg = brumby.BrumbyConfig(n_layers=10)
    state = (10, _BRUMBY_SLOTS) + cfg.state_shape
    assert state == (10, 16, 8, 8840, 128)
    f32 = jnp.float32

    def step(state, layer, q, k, v, g):
        o, S = brumby.ret_step(cfg, q, k, v, g,
                               CarriedState(state, layer, K.state_step))
        return o, S.buffer

    compiled = _compile(
        step, chip, (state, f32), ((), jnp.int32),
        ((_BRUMBY_SLOTS, 40, 128), f32), ((_BRUMBY_SLOTS, 8, 128), f32),
        ((_BRUMBY_SLOTS, 8, 128), f32), ((_BRUMBY_SLOTS, 8), f32))
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    call = re.search(r"%dstpu_state_step[\w.]* = .*tpu_custom_call.*", hlo)
    assert call and "output_to_operand_aliasing={{1}: (" in hlo
    # q as [slots, 8 heads x 8 rows, 128]; nothing 8,320 or 8,840 wide
    # beside the state itself
    assert "f32[16,64,128]" in call.group(0)
    assert not re.search(r"f32\[[0-9,]*,(8320|8256)\]", hlo)
    assert memory.temp_size_in_bytes < 8 << 20


def test_state_chunk_kernel_on_a_state_larger_than_a_tile(chip):
    """``dstpu_state_chunk`` alone under the retention block rule, at the
    cell's sizes (a slot's 8 states of 4.3 MiB, a chunk of 1,024 tokens
    in blocks of 128): one head's state a grid step, in place in VMEM; a
    K/V head's five queries one operand of 640 lanes (an operand of more
    heads than the state), ``o`` as wide; the rows' result aliased to its
    operand."""
    from deepspeed_tpu.models import brumby
    from deepspeed_tpu.models.family import SlotState

    cfg = brumby.BrumbyConfig(n_layers=10)
    f32, T = jnp.float32, 1024
    compiled = _compile(
        lambda q, k, v, logg, S: brumby.ret_chunk_kernel(
            cfg, q, k, v, logg, SlotState(S, K.state_chunk)), chip,
        ((1, T, 40, 128), f32), ((1, T, 8, 128), f32),
        ((1, T, 8, 128), f32), ((1, T, 8), f32),
        ((1,) + cfg.state_shape, f32))
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    call = re.search(r"%dstpu_state_chunk[\w.]* = .*tpu_custom_call.*", hlo)
    assert call and "output_to_operand_aliasing={{1}: (0, {})}" in hlo
    assert call.group(0).count("f32[1,1024,5120]") >= 2     # q in and o
    assert memory.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("program", BRUMBY_PROGRAMS)
def test_no_pool_cell_programs_fit_and_keep_the_state_in_place(
        chip, monkeypatch, program):
    """The decode program and THE chunk program of the cell (a family
    with no pool layer has one: no table width to come in), at its sizes:
    they compile for the described v5e (9.05 GiB of weights and 5.39 GiB
    of per-slot state beside their temporaries, inside 15.75 GiB); they
    take and return NO pool (no operand of a page's shape; the cache's
    ``k`` and ``v`` are None); the state is only ever the carried buffer:
    a decode step hands it to ``dstpu_state_step``, whose result aliases
    it, a chunk updates its slot's rows in place around
    ``dstpu_state_chunk``; no ``[slots, 8, D, 128]`` value of the
    program's and no ``phi(q)`` or ``phi(k)`` in the memory (nothing
    8,320, 8,256 or 65 x 128 wide but the state); no stack of the weights
    is copied."""
    from deepspeed_tpu.models import brumby

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, T, temp_gib = BRUMBY_PROGRAMS[program]
    cfg = brumby.BrumbyConfig(n_layers=10)
    sr = brumby.FAMILY.recurrent.state_row(cfg)
    assert brumby.FAMILY.pool_layers(cfg) == 0 and sr.conv is None
    state_shape = (sr.layers, _BRUMBY_SLOTS) + sr.state
    assert state_shape == (10, 16, 8, 8840, 128)
    S = jax.ShapeDtypeStruct
    on_chip = lambda tree: jax.tree.map(
        lambda x: S(x.shape, x.dtype, sharding=chip)
        if hasattr(x, "shape") else x, tree)
    params = jax.eval_shape(lambda: brumby.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    stored = sum(math.prod(a.shape) for a in jax.tree.leaves(params))
    assert stored == 4_859_358_800
    cache = K.PagedKVCache(
        k=None, v=None, table=S((rows, 17408 // PAGE), jnp.int32),
        seq_lens=S((rows,), jnp.int32), page_size=PAGE,
        state=S(state_shape, K.STATE_DTYPE),
        slot=None if program == "decode" else S((1,), jnp.int32))
    forward = lambda continuation: lambda params, tokens, cache: \
        forward_paged(params, tokens, cfg, cache, interpret=False,
                      tp=False, continuation=continuation)
    _, chunk, _, _, decode = serving_programs(
        forward(False), forward(False), forward(True), _sample_rows,
        decode_chunk=1, max_batch=rows, state=True)
    run, operands = (
        (decode, (S((2,), jnp.uint32), S((), jnp.int32),
                  S((rows,), jnp.float32)))
        if program == "decode" else (chunk, (S((1,), jnp.int32),)))
    compiled = jax.jit(run, donate_argnums=(2,)).lower(*on_chip((
        params, S((rows, T), jnp.int32), cache, *operands))).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= temp_gib * 2 ** 30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.3 * 2 ** 30
    held = stored * 2 + math.prod(state_shape) * 4
    # (b_g is float32: 80 numbers)
    assert 0 <= memory.argument_size_in_bytes - held < 2 ** 22
    assert not re.search(r"bf16\[\d+,\d+,\d+,16,128\]", hlo)    # no page
    _state_stepped_in_place(hlo, state_shape, program)
    assert not re.search(r"f32\[[0-9,]*,(8320|8256|65,128)\]", hlo)
    if program == "decode":
        assert "dstpu_state_chunk" not in hlo
    else:
        assert re.search(r"%dstpu_state_chunk[\w.]* = .*tpu_custom_call",
                         hlo)
        # the head runs on the chunk's last real row alone
        assert "f32[1,1024,151936]" not in hlo
        assert "f32[1024,151936]" not in hlo
    for stack in ((10, 5120, 5120), (10, 5120, 1024), (10, 5120, 17408),
                  (10, 17408, 5120), (151936, 5120), (5120, 151936)):
        assert [(n, o) for n, o, _ in _top_level_results(hlo, stack)
                if not o.startswith(("copy-start", "copy-done"))] == [], \
            stack
