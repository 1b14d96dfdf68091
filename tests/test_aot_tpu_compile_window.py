"""The window family's cell programs compiled whole for a described v5e
(``test_aot_tpu_compile.py`` says how, and holds the kernels alone): they
fit, and what the cell keeps on the chip stays in place.  Nothing
executes."""

import math
import re

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import kernels as K
from deepspeed_tpu.inference.paged_forward import forward_paged
from deepspeed_tpu.inference.serving import _sample_rows, serving_programs

from _aot import (PAGE, _blocked_chunk_reader, _pool_sized_ops,
                  _top_level_results)


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
