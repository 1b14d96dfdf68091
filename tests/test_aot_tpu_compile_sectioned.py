"""The sectioned family's cell programs compiled whole for a described v5e
(``test_aot_tpu_compile.py`` says how, and holds the kernels alone): they
fit, and what the cell keeps on the chip stays in place.  Nothing
executes."""

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
