"""The recurrent family's cell programs compiled whole for a described v5e
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
                  _state_stepped_in_place, _top_level_results)


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
