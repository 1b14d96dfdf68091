"""The decoder-hybrid-decoder family's cell programs compiled whole for a
described v5e (``test_aot_tpu_compile.py`` says how, and holds the kernels
alone): they fit, and what the cell keeps on the chip stays in place.
Nothing executes."""

import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import kernels as K
from deepspeed_tpu.inference.paged_forward import forward_paged
from deepspeed_tpu.inference.serving import _sample_rows, serving_programs

from _aot import (PAGE, _blocked_chunk_reader, _pool_sized_ops,
                  _state_stepped_in_place, _top_level_results)


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
