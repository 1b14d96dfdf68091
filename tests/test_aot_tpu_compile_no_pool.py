"""The retention family's kernels at its cell's sizes and its cell programs
compiled whole for a described v5e (``test_aot_tpu_compile.py`` says how,
and holds the other kernels alone): they fit, and what the cell keeps on
the chip stays in place.  Nothing executes."""

import math
import re

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import kernels as K
from deepspeed_tpu.inference.paged_forward import forward_paged
from deepspeed_tpu.inference.serving import _sample_rows, serving_programs

from _aot import PAGE, _compile, _state_stepped_in_place, _top_level_results


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
