"""The state-space family's cell programs compiled whole for a described v5e
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

from _aot import (PAGE, _pool_sized_ops, _state_stepped_in_place,
                  _top_level_results)


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
