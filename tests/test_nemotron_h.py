"""The nemotron_h family on the serving path, against the benchmark's
plain float32 reference (``benchmark/reference/nemotron_h.py``, which
imports nothing from ``deepspeed_tpu``): every layer a Mamba-2 mixer
(two groups here), an attention without positions or an expert FFN
ALONE, in sections of two periods with a part-period tail; two-matrix
relu^2 experts of a width that is not whole tiles, stored padded; a
selection bias in the router; the seam's refusals (the grouped rule by
itself: ``test_nemotron_h_rule.py``).  Toy widths, seeded weights, CPU."""

import dataclasses
import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import nemotron_h as bench_family  # noqa: E402
from benchmark.reference import nemotron_h as reference  # noqa: E402
from deepspeed_tpu.inference import kernels as K  # noqa: E402
from deepspeed_tpu.inference.paged_forward import forward_paged  # noqa: E402
from deepspeed_tpu.inference.serving import (_sample_rows,  # noqa: E402
                                             serving_engine,
                                             serving_programs)
from deepspeed_tpu.models import granite_hybrid as gh  # noqa: E402
from deepspeed_tpu.models import nemotron_h as nm  # noqa: E402
from deepspeed_tpu.models.family import (decoder_families,  # noqa: E402
                                         decoder_family, sections_of)
from deepspeed_tpu.parallel import moe  # noqa: E402

PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# (MEM*E) x 2, ME, MEM*E, (ME) x 2: the published shape in 21 letters
CFG = nm.NemotronHConfig.tiny()
PAGE = 8
# float32 end to end, the two sides summing in different orders (blocks
# against a token at a time, gathered pages against whole rows, every
# held expert on every row against a scan over the experts): 1e-5 on
# logits of about unit variance, read.  With bfloat16 weights and
# activations the same comparison reads 0.1 and more: the tolerance
# sits twenty times above the one and hundreds of times under the other
TOL = dict(atol=2e-4, rtol=2e-4)


@pytest.fixture(scope="module")
def params():
    return nm.init_params(jax.random.PRNGKey(0), CFG)


_REFERENCE = jax.jit(lambda params, tokens: reference.forward(
    params, tokens, **bench_family._ref_kw(CFG)))


def _reference_logits(params, tokens):
    """The reference's logits of ``tokens``; run at one padded length
    (causal: what follows a position does not reach it), so that it
    compiles once."""
    padded = np.zeros(64, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(_REFERENCE(params, jnp.asarray(padded)))[:len(tokens)]


def _engine(params, cfg=CFG, **kw):
    base = dict(max_batch=3, page_size=PAGE, num_pages=64, max_seq=128,
                cache_dtype=jnp.float32, telemetry=True, prefill_bucket=0,
                prefill_chunk=16)
    base.update(kw)
    return serving_engine(params, cfg, **base)


def _cache(cfg, slots, rows, max_seq, slot=None):
    """A pool of the attention layers alone, the per-slot state beside
    it and the experts' counts; ``rows`` rows of table."""
    fam = decoder_family(cfg)
    sr, row = fam.recurrent.state_row(cfg), fam.cache_row(cfg)
    mp = -(-max_seq // PAGE)
    shape = (cfg.n_attn_layers, row.n_kv, slots * mp + 1, PAGE,
             row.pool_width)
    table = np.arange(slots * mp).reshape(slots, mp)[:rows]
    return K.PagedKVCache(
        k=jnp.zeros(shape), v=jnp.zeros(shape),
        table=jnp.asarray(table, jnp.int32),
        seq_lens=jnp.zeros((rows,), jnp.int32), page_size=PAGE,
        expert_rows=jnp.zeros((cfg.experts_held[1],), jnp.int32),
        conv=jnp.zeros((sr.layers, slots) + sr.conv),
        state=jnp.full((sr.layers, slots) + sr.state, 7.0),   # stale
        slot=slot)


@pytest.fixture(scope="module")
def forwards(params):
    """The paged forward, jitted once a phase (called eagerly its loops
    compile again at every call)."""
    fwd = lambda **kw: jax.jit(lambda toks, c: forward_paged(
        params, toks, CFG, c, tp=False, interpret=True, **kw))
    return {"prefill": fwd(), "chunk": fwd(continuation=True),
            "step": fwd()}


def _chunks_then_steps(forwards, seq, n_prompt, C=16):
    """Logits of every position of ``seq``: its first ``n_prompt``
    tokens through chunks of ``C`` (a padded last chunk), the rest a
    decode step each, over one slot of a paged cache."""
    cache = _cache(CFG, 1, 1, 64)
    got = []
    for done in range(0, n_prompt, C):
        take = min(C, n_prompt - done)
        toks = np.zeros((1, C), np.int32)
        toks[0, :take] = seq[done:done + take]
        logits, cache = forwards["chunk"](jnp.asarray(toks), cache._replace(
            slot=jnp.zeros((1,), jnp.int32),
            seq_lens=jnp.full((1,), done, jnp.int32),
            real=jnp.full((1,), take, jnp.int32)))
        assert cache.real is None
        got.append(np.asarray(logits[0, :take], np.float32))
    for at in range(n_prompt, len(seq)):
        logits, cache = forwards["step"](
            jnp.asarray([[seq[at]]]), cache._replace(
                slot=None, seq_lens=jnp.full((1,), at, jnp.int32),
                real=jnp.ones((1,), jnp.int32)))
        got.append(np.asarray(logits[0], np.float32))
    return np.concatenate(got), cache


# --------------------------------------------- (vi) the letters' sections
def test_the_published_letters_cut_into_four_sections_of_two_periods():
    assert nm.cut_pattern(PUBLISHED) == (
        ("MEMEM*E", 5), ("ME", 1), ("MEMEM*E", 1), ("ME", 4))
    cfg = nm.NemotronHConfig()
    assert cfg.sections == nm.cut_pattern(PUBLISHED)
    assert cfg.pattern == PUBLISHED and cfg.n_layers == 52
    assert (cfg.n_ssm_layers, cfg.n_attn_layers, cfg.n_expert_layers) \
        == (23, 6, 23)
    sr = nm.FAMILY.recurrent.state_row(cfg)
    assert sr == (23, (3, 6144), (64, 64, 128))
    assert nm.FAMILY.pool_layers(cfg) == 6
    assert nm.FAMILY.cache_row(cfg)[:3] == (2, 128, 128)
    assert nm.FAMILY.expert_rows(cfg) == (128, 6 * 23)
    assert nm.FAMILY.router(cfg) == (128, 6)
    assert cfg.moe_ffn_stored == 1920


@pytest.mark.parametrize("pattern, want", [
    ("MEM*EMEM*EMEMEM*EMEME",
     (("MEM*E", 2), ("ME", 1), ("MEM*E", 1), ("ME", 2))),
    ("mmmmmAmmmm" * 4, (("mmmmmAmmmm", 4),)),
    ("SSSF" * 3, (("SSSF", 3),)),
    ("MEM*E", (("MEM*E", 1),)),
])
def test_a_pattern_is_cut_at_the_least_cost(pattern, want):
    """One a section, and a period's letters once however many sections
    run it: whole periods are one section, a part-period tail reuses
    the periods that stand."""
    assert nm.cut_pattern(pattern) == want


def test_the_family_is_registered_and_states_its_sections():
    assert "NemotronHConfig" in [f.name for f in decoder_families()]
    fam = decoder_family(CFG)
    rec = fam.recurrent
    M, A, E = True, False, None
    assert sections_of(rec, CFG) == (
        ((M, E, M, A, E), 2), ((M, E), 1), ((M, E, M, A, E), 1),
        ((M, E), 2))
    assert rec.ffn[0] == "moe_blocks" and rec.key == "ssm_blocks"
    # a family of one section is what it was: its period as often as the
    # pool's layers say
    granite = decoder_family(gh.GraniteHybridConfig.tiny())
    assert sections_of(granite.recurrent, gh.GraniteHybridConfig.tiny(), 2) \
        == (((True, True, False, True), 2),)


# -------------------------------- (i) the paged forward vs the reference
def test_a_prefill_gives_the_reference_logits(params, forwards):
    seq = np.random.default_rng(1).integers(0, CFG.vocab_size, 16)
    logits, cache = forwards["prefill"](
        jnp.asarray(seq[None]), _cache(CFG, 1, 1, 64)._replace(
            slot=jnp.zeros((1,), jnp.int32)))
    np.testing.assert_allclose(np.asarray(logits[0]),
                               _reference_logits(params, seq), **TOL)
    # 9 expert layers x 16 rows x top-3 of 8 experts, all held
    assert int(cache.expert_rows.sum()) == 9 * 16 * 3


@pytest.mark.parametrize("n_prompt", [5, 16, 37])
def test_chunks_then_decode_steps_give_the_reference_logits(
        params, forwards, n_prompt):
    """A prompt in chunks of 16 (a padded last one: the padding must move
    neither the state nor the convolution's rows), then decode steps
    through both caches, from a slot whose state was stale."""
    seq = np.random.default_rng(n_prompt).integers(0, CFG.vocab_size,
                                                   n_prompt + 6)
    got, _ = _chunks_then_steps(forwards, seq, n_prompt)
    np.testing.assert_allclose(got, _reference_logits(params, seq), **TOL)


def test_a_masked_row_moves_no_state_and_the_live_row_is_right(
        params, forwards):
    """A decode step over two slots, one idle (length 0, no real token):
    the idle slot's state and rows stay as they were, bit for bit, and
    the live slot's logits are the reference's."""
    seq = np.random.default_rng(3).integers(0, CFG.vocab_size, 20)
    cache = _cache(CFG, 2, 2, 64)
    _, view = forwards["chunk"](
        jnp.asarray(np.pad(seq[:16], (0, 0))[None]), cache._replace(
            table=cache.table[:1], slot=jnp.zeros((1,), jnp.int32),
            seq_lens=jnp.zeros((1,), jnp.int32),
            real=jnp.full((1,), 16, jnp.int32)))
    cache = cache._replace(k=view.k, v=view.v, conv=view.conv,
                           state=view.state, expert_rows=view.expert_rows)
    before = (np.asarray(cache.conv[:, 1]), np.asarray(cache.state[:, 1]))
    logits, after = forwards["step"](
        jnp.asarray([[seq[16]], [9]]), cache._replace(
            seq_lens=jnp.asarray([16, 0], jnp.int32),
            real=jnp.asarray([1, 0], jnp.int32)))
    np.testing.assert_array_equal(np.asarray(after.conv[:, 1]), before[0])
    np.testing.assert_array_equal(np.asarray(after.state[:, 1]), before[1])
    np.testing.assert_allclose(
        np.asarray(logits[0, 0]), _reference_logits(params, seq[:17])[16],
        **TOL)


def test_the_engine_serves_the_reference_greedy_tokens(params):
    eng = _engine(params)
    rng = np.random.default_rng(0)
    prompts = {i: rng.integers(0, CFG.vocab_size, n).tolist()
               for i, n in enumerate((5, 37, 16, 50))}
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=8)
    out = eng.run()
    for i, p in prompts.items():
        want = _reference_logits(params, out[i]).argmax(-1)
        assert out[i][len(p):] == want[len(p) - 1:-1].tolist(), i
    assert eng.check_leaks() == []
    # the pool has the attention layers alone, the state the mixers';
    # an expert layer keeps nothing, and /statusz says so by name
    status = eng.statusz()
    assert eng.cache.k.shape[0] == CFG.n_attn_layers == 3
    assert eng.cache.state.shape[:2] == (CFG.n_ssm_layers, 3)
    assert status["kv"]["layers"] == 3
    assert status["cache.state"]["layers"] == 9
    assert status["cache.state"]["ffn_alone"] == {"layers": 9, "bytes": 0}
    counters = eng.registry.snapshot()["counters"]
    assert counters["serving_routed_rows"] > 0
    assert sum(v for n, v in counters.items()
               if n.startswith("serving_expert_rows_")) > 0


# ----------------------------------------- (ii) the shares add up, (iv)
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole(params):
    """Each rank's routed part, and the shared expert counted once, give
    the uncut layer: program and reference alike."""
    lp = jax.tree.map(lambda a: a[0], params["moe_blocks"])
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 24, CFG.dim))
    whole, rows = nm.expert_layer(CFG, h, lp)
    kw = {k: v for k, v in bench_family._ref_kw(CFG).items()
          if k in reference._MOE + ("eps",)}
    x = jax.random.normal(jax.random.PRNGKey(6), (24, CFG.dim))
    want = reference._experts(x, params["moe_blocks"], 0, None, **kw)[0]
    parts_ref, parts, counted = 0.0, 0.0, []
    for rank in range(8):
        cut = dataclasses.replace(CFG, experts_held=(rank, 1))
        one = dict(lp, w_up=lp["w_up"][rank:rank + 1],
                   w_down=lp["w_down"][rank:rank + 1])
        y, r = nm.expert_layer(cut, h, one)
        shared = nm.relu2(h[0] @ lp["sw_up"]) @ lp["sw_down"]
        parts = parts + (y[0] - shared)
        counted.append(int(r[0]))
        stack = dict(params["moe_blocks"],
                     w_up=params["moe_blocks"]["w_up"][:, rank:rank + 1],
                     w_down=params["moe_blocks"]["w_down"][:, rank:rank + 1])
        parts_ref = parts_ref + reference._experts(
            x, stack, 0, None, **dict(kw, first=rank), shared=False)[0]
    shared = nm.relu2(h[0] @ lp["sw_up"]) @ lp["sw_down"]
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole[0]), **TOL)
    assert counted == np.asarray(rows).tolist() and sum(counted) == 24 * 3
    # held experts that no row is routed to: the shared expert alone
    only_shared = reference._experts(x, params["moe_blocks"], 0, None,
                                     **dict(kw, first=10 ** 6))[0]
    np.testing.assert_allclose(np.asarray(parts_ref + only_shared),
                               np.asarray(want), **TOL)
    # and the program's layer is the reference's
    h_ref = reference._rms_norm(x, lp["mlp_norm"], CFG.norm_eps)
    got, _ = nm.expert_layer(CFG, h_ref[None], lp)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), **TOL)


@pytest.mark.parametrize("every_row", [True, False])
def test_the_stored_width_gives_the_published_widths_numbers(
        params, monkeypatch, every_row):
    """40 columns stored in 128 with zeros behind, and ``W_down``'s rows
    likewise: ``relu(0)^2 = 0`` meets zero rows.  On the every-row
    branch and on the grouped one (its passes too: a granule of 8 rows)."""
    assert CFG.moe_ffn_stored == 128 and CFG.moe_ffn_dim == 40
    monkeypatch.setattr(moe, "_every_row_pays", lambda *a: every_row)
    monkeypatch.setattr(moe, "_ROW_GRANULE", 8)
    lp = jax.tree.map(lambda a: a[1], params["moe_blocks"])
    assert not np.asarray(lp["w_up"][..., 40:]).any()
    assert not np.asarray(lp["w_down"][:, 40:]).any()
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 16, CFG.dim))
    cut = dict(lp, w_up=lp["w_up"][..., :40], w_down=lp["w_down"][:, :40])
    held = dataclasses.replace(CFG, experts_held=(2, 3))
    share = lambda p: dict(p, w_up=p["w_up"][2:5], w_down=p["w_down"][2:5])
    for cfg, a, b in ((CFG, lp, cut), (held, share(lp), share(cut))):
        stored, rows = nm.expert_layer(cfg, h, a)
        published, rows_p = nm.expert_layer(cfg, h, b)
        np.testing.assert_allclose(np.asarray(stored),
                                   np.asarray(published), **TOL)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows_p))
    assert nm.param_count(CFG) == bench_family.param_count(CFG)
    full = nm.NemotronHConfig(vocab_size=16384, experts_held=(0, 16))
    assert nm.param_count(full) == bench_family.param_count(full) \
        == 5_258_420_544


def test_a_two_matrix_body_is_the_callers_statement():
    """``held_experts_ffn`` with ``w3`` None is ``act(h w1) w2`` on every
    branch, and the gated body is what it was."""
    rng = jax.random.split(jax.random.PRNGKey(8), 6)
    h = jax.random.normal(rng[0], (12, 16))
    w1, w3 = (jax.random.normal(k, (4, 16, 24)) / 4 for k in rng[1:3])
    w2 = jax.random.normal(rng[3], (4, 24, 16)) / 5
    weights = jax.random.uniform(rng[4], (12, 2))
    experts = jax.random.randint(rng[5], (12, 2), 0, 4)
    pick = lambda ys: sum(
        weights[:, j, None] * jnp.take_along_axis(
            ys, experts[:, j][None, :, None], 0)[0] for j in range(2))
    two = pick(jnp.einsum("nf,efd->end", nm.relu2(h @ w1[0]) * 0, w2)
               + jnp.stack([nm.relu2(h @ w1[e]) @ w2[e] for e in range(4)]))
    three = pick(jnp.stack([(jax.nn.silu(h @ w1[e]) * (h @ w3[e])) @ w2[e]
                            for e in range(4)]))
    for grouped in (True, False):
        got, rows = moe.held_experts_ffn(h, weights, experts, w1, None, w2,
                                         grouped=grouped, act=nm.relu2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(two), **TOL)
        got, _ = moe.held_experts_ffn(h, weights, experts, w1, w3, w2,
                                      grouped=grouped)
        np.testing.assert_allclose(np.asarray(got), np.asarray(three), **TOL)
        assert int(rows.sum()) == 24


# ------------------------------------------------- (v) the selection bias
def test_the_selection_bias_moves_the_choice_and_not_the_weight():
    h = jax.random.normal(jax.random.PRNGKey(9), (64, 16))
    gate = jax.random.normal(jax.random.PRNGKey(10), (16, 8)) / 4
    bias = jnp.zeros((8,)).at[3].set(5.0)      # expert 3 is always chosen
    w0, e0 = moe.sigmoid_topk_route(h, gate, 2, 2.5, True)
    w1, e1 = moe.sigmoid_topk_route(h, gate, 2, 2.5, True, bias=bias)
    assert np.asarray((e1 == 3).any(-1)).all()
    assert not np.asarray((e0 == 3).any(-1)).all()
    s = jax.nn.sigmoid(h @ gate)
    chosen = jnp.take_along_axis(s, e1, -1)
    np.testing.assert_allclose(
        np.asarray(w1), np.asarray(2.5 * chosen / chosen.sum(-1,
                                                             keepdims=True)),
        rtol=1e-5)
    # no bias: the call it was
    zero = moe.sigmoid_topk_route(h, gate, 2, 2.5, True,
                                  bias=jnp.zeros((8,)))
    np.testing.assert_array_equal(np.asarray(zero[1]), np.asarray(e0))
    np.testing.assert_allclose(np.asarray(zero[0]), np.asarray(w0),
                               rtol=1e-6)
    # the reference's router agrees, and its drawn bias does move choices
    wr, er, _ = reference.route(h, gate, bias, 2, 2.5, True)
    np.testing.assert_array_equal(np.sort(np.asarray(er)),
                                  np.sort(np.asarray(e1)))


def test_the_drawn_bias_shows_a_choice_by_the_score_alone(params):
    lp = jax.tree.map(lambda a: a[0], params["moe_blocks"])
    h = jax.random.normal(jax.random.PRNGKey(11), (256, CFG.dim))
    _, with_bias = moe.sigmoid_topk_route(h, lp["gate"], CFG.top_k,
                                          bias=lp["gate_bias"])
    _, without = moe.sigmoid_topk_route(h, lp["gate"], CFG.top_k)
    differ = (np.sort(np.asarray(with_bias)) != np.sort(
        np.asarray(without))).any(-1).mean()
    assert differ > 0.05


# ----------------------------------------------------------- the refusals
@pytest.mark.parametrize("mechanism, kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_tier", dict(kv_tier={"host_pool_bytes": 1 << 20})),
    ("speculative", dict(speculative={"draft_tokens": 2})),
    ("zero_inference", dict(zero_inference={"enabled": True})),
])
def test_what_granite_refuses_is_refused_by_name(params, mechanism, kw):
    with pytest.raises(NotImplementedError, match=mechanism):
        _engine(params, **kw)


# ------------------------- (vii) the older families' programs are theirs
# sha256 (16 hex) of the StableHLO text, as lowered on the CPU, of the
# prefill, chunk and decode programs of each older recurrent or sparse
# family's tiny model AT THE PARENT of PR 48 (12fb5ce): a tripwire, not a
# rule.  The seam grew sections and an FFN-alone kind, the expert product
# a second body, the router a bias and the mixer groups, and these
# fifteen programs are what they were.  A PR that means to change one
# writes the new number here and shows the cell's parity on the chip.
PARENT_PROGRAMS = {
    "mixtral": ("16921c0f30df06c6", "d08c907cb41a1127", "11028e46bffd9a8b"),
    "pangu_ultra_moe": ("85dfc1b8a8a7a605", "d10d5ef4e56b3db3",
                        "fc57f888bff92757"),
    "qwen3_next": ("de1a3d67b8c5d7a2", "3a0eae224157ed46",
                   "a40cff5717b7a8e7"),
    "granite_hybrid": ("66ce6a11ec30b472", "73552e7b065314e8",
                       "445e48336d24aaf9"),
    "laguna": ("df36113f20683c58", "37c126f2ae03ab53", "2ed5d8f7a4bd97ca"),
}


def _program_hashes(name):
    import importlib

    mod = importlib.import_module(f"deepspeed_tpu.models.{name}")
    fam = mod.FAMILY
    cfg = fam.config_type.tiny()
    S = jax.ShapeDtypeStruct
    params = jax.eval_shape(
        lambda: mod.init_params(jax.random.PRNGKey(0), cfg))
    row, rec = fam.cache_row(cfg), fam.recurrent
    sr = rec.state_row(cfg) if rec else None
    layers = fam.pool_layers(cfg)
    held, scored = fam.expert_rows(cfg)[0], fam.router(cfg)[0]
    slots, pages, width = 3, 24, 4
    pool = S((layers, row.n_kv, pages, PAGE, row.pool_width), jnp.float32)
    out = []
    for prog, (rows, T) in (("prefill", (1, 16)), ("chunk", (1, 16)),
                            ("decode", (slots, 1))):
        cache = K.PagedKVCache(
            k=pool, v=None if row.values_in_keys else pool,
            table=S((rows, width), jnp.int32),
            seq_lens=S((rows,), jnp.int32), page_size=PAGE,
            expert_rows=S((held + (scored > held),), jnp.int32)
            if held else None,
            conv=S((sr.layers, slots) + sr.conv, jnp.float32)
            if sr else None,
            state=S((sr.layers, slots) + sr.state, K.STATE_DTYPE)
            if sr and sr.state else None,
            slot=S((1,), jnp.int32) if sr and prog != "decode" else None)
        forward = lambda continuation: lambda p, t, c: forward_paged(
            p, t, cfg, c, tp=False, continuation=continuation)
        prefill, chunk, _, _, decode = serving_programs(
            forward(False), forward(False), forward(True), _sample_rows,
            decode_chunk=1, max_batch=rows, expert_rows=bool(held),
            state=sr is not None)
        last = (S((1,), jnp.int32),)
        run, operands = {
            "prefill": (prefill, last), "chunk": (chunk, last),
            "decode": (decode, (S((2,), jnp.uint32), S((), jnp.int32),
                                S((rows,), jnp.float32)))}[prog]
        text = jax.jit(run).lower(params, S((rows, T), jnp.int32), cache,
                                  *operands).as_text()
        out.append(hashlib.sha256(text.encode()).hexdigest()[:16])
    return tuple(out)


@pytest.mark.parametrize("family", sorted(PARENT_PROGRAMS))
def test_an_older_familys_programs_lower_to_the_parents_text(family):
    assert _program_hashes(family) == PARENT_PROGRAMS[family]
