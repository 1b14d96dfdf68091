"""The channel-gated family's cell programs compiled whole for a described
v5e (``test_aot_tpu_compile.py`` says how, and holds the kernels alone):
they fit, and what the cell keeps on the chip stays in place.  Nothing
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
