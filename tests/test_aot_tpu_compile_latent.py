"""The latent family's cell programs compiled whole for a described v5e
(``test_aot_tpu_compile.py`` says how, and holds the kernels alone): they
fit, and what the cell keeps on the chip stays in place.  Nothing
executes."""

import re

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import kernels as K
from deepspeed_tpu.inference.paged_forward import forward_paged
from deepspeed_tpu.inference.serving import _sample_rows, serving_programs

from _aot import PAGE, _pool_sized_ops, _top_level_results


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
