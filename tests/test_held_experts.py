"""Mixtral's serving FFN runs the experts a token chose (ISSUE 34): the
router's choice goes through ``parallel/moe.py::held_experts_ffn`` with
all the experts held, sorted and grouped above the row count where that
pays and every expert on every row below it; the paged forward hands the
experts' stacks over whole where they are plain arrays on one device."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kernels import PagedKVCache
from deepspeed_tpu.inference.paged_forward import forward_paged
from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.parallel import moe

CFG = mixtral.MixtralConfig.tiny()            # 4 experts, top-2, 2 layers
PATHS = {"every_expert_every_row": True, "grouped": False}


@pytest.fixture(scope="module")
def params():
    return mixtral.init_params(jax.random.PRNGKey(0), CFG)


def _pin(monkeypatch, path):
    monkeypatch.setattr(moe, "_every_row_pays",
                        lambda N, k, Eh: PATHS[path])


def _per_token_loop(h, w, experts, w1, w3, w2):
    """A token at a time, an expert at a time, in float64: the gated
    body, or (``w3`` None) the two-matrix body with a squared ReLU; a
    pair whose expert is not one of ``w1``'s adds nothing."""
    h, w, w1, w2 = (np.asarray(a, np.float64) for a in (h, w, w1, w2))
    out = np.zeros_like(h)
    for n, row in enumerate(h):
        for j, e in enumerate(np.asarray(experts)[n]):
            if not 0 <= e < len(w1):
                continue
            a = row @ w1[e]
            a = np.maximum(a, 0) ** 2 if w3 is None else \
                a / (1 + np.exp(-a)) * (row @ np.asarray(w3, np.float64)[e])
            out[n] += w[n, j] * (a @ w2[e])
    return out


def _routing(case, N, rng):
    E, k = CFG.num_experts, CFG.top_k
    if case == "all_rows_on_one_expert":
        experts = np.tile([2, 0], (N, 1))
        w = np.tile([1.0, 0.0], (N, 1))
    elif case == "an_expert_gets_no_row":
        experts = np.stack([rng.permutation([0, 1, 3])[:k]
                            for _ in range(N)])
        w = rng.dirichlet(np.ones(k), N)
    else:
        experts = np.stack([rng.permutation(E)[:k] for _ in range(N)])
        w = rng.dirichlet(np.ones(k), N)
    return jnp.asarray(w, jnp.float32), jnp.asarray(experts, jnp.int32)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("case", ["as_routed", "all_rows_on_one_expert",
                                  "an_expert_gets_no_row"])
def test_the_ffn_is_the_per_token_top_k_loop(monkeypatch, params, path,
                                             case):
    """Both sides of the rows rule against a plain loop, at Mixtral's
    tiny widths: drop-free at any imbalance, and the rows counted are
    the pairs each expert was routed."""
    _pin(monkeypatch, path)
    rng = np.random.default_rng(3)
    lp = {k: params["blocks"][k][1] for k in ("w1", "w3", "w2")}
    h = jnp.asarray(rng.normal(size=(40, CFG.dim)), jnp.float32)
    w, experts = _routing(case, 40, rng)
    y, rows = moe.held_experts_ffn(h, w, experts, lp["w1"], lp["w3"],
                                   lp["w2"])
    np.testing.assert_allclose(
        y, _per_token_loop(h, w, experts, lp["w1"], lp["w3"], lp["w2"]),
        atol=2e-5, rtol=2e-5)
    assert rows.tolist() == np.bincount(
        np.asarray(experts).ravel(), minlength=CFG.num_experts).tolist()
    if case == "all_rows_on_one_expert":
        assert rows.tolist() == [40, 0, 40, 0]
    if case == "an_expert_gets_no_row":
        assert rows[2] == 0


@pytest.mark.parametrize("path", PATHS)
def test_a_layer_of_the_whole_stack_is_the_sliced_call(monkeypatch, params,
                                                       path):
    _pin(monkeypatch, path)
    rng = np.random.default_rng(4)
    blocks = params["blocks"]
    h = jnp.asarray(rng.normal(size=(24, CFG.dim)), jnp.float32)
    w, experts = _routing("as_routed", 24, rng)
    sliced = moe.held_experts_ffn(h, w, experts, blocks["w1"][1],
                                  blocks["w3"][1], blocks["w2"][1])
    whole = moe.held_experts_ffn(h, w, experts, blocks["w1"], blocks["w3"],
                                 blocks["w2"], layer=jnp.int32(1))
    np.testing.assert_allclose(whole[0], sliced[0], atol=1e-6)
    assert whole[1].tolist() == sliced[1].tolist()


# N rows routed k ways over Eh held experts -> every expert on every row?
@pytest.mark.parametrize("N,k,Eh,every_row", [
    (128, 8, 16, True), (1024, 8, 16, False),       # pangu's share, PR 33
    (64, 2, 8, True), (1024, 2, 8, False),          # Mixtral: decode, chunk
    (256, 8, 16, True), (512, 8, 16, False),        # as timed on the chip
    (256, 2, 8, True), (384, 2, 8, False),
    (4096, 2, 2, True)],                            # every expert chosen
    ids=["pangu_decode", "pangu_chunk", "mixtral_decode", "mixtral_chunk",
         "pangu_256", "pangu_512", "mixtral_256", "mixtral_384",
         "all_experts_chosen"])
def test_the_rows_rule_reads_shapes(N, k, Eh, every_row):
    assert moe._every_row_pays(N, k, Eh) is every_row


def _products(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("ragged_dot")


def test_grouped_false_keeps_every_expert_on_every_row(params):
    """A caller whose weights are sharded, dequantised or a layer's slice
    says so: no grouped product at a row count where the rule would
    choose one."""
    lp = {k: params["blocks"][k][0] for k in ("w1", "w3", "w2")}
    N = 4 * moe._ROW_GRANULE
    assert not moe._every_row_pays(N, CFG.top_k, CFG.num_experts)
    h = jnp.zeros((N, CFG.dim), jnp.float32)
    w, experts = _routing("as_routed", N, np.random.default_rng(5))
    run = lambda grouped: _products(
        lambda h: moe.held_experts_ffn(h, w, experts, lp["w1"], lp["w3"],
                                       lp["w2"], grouped=grouped), h)
    assert run(True) and not run(False)


def test_rows_that_fill_no_whole_tile_are_padded_past_the_groups(
        monkeypatch, params):
    """40 pairs against a granule of 16 rows: the buffer grows to 64 rows
    (a power of two of whole granules) and the rows added stand behind every
    group."""
    _pin(monkeypatch, "grouped")
    monkeypatch.setattr(moe, "_ROW_GRANULE", 16)
    rng = np.random.default_rng(6)
    lp = {k: params["blocks"][k][0] for k in ("w1", "w3", "w2")}
    h = jnp.asarray(rng.normal(size=(20, CFG.dim)), jnp.float32)
    w, experts = _routing("as_routed", 20, rng)
    jaxpr = str(jax.make_jaxpr(lambda h: moe.held_experts_ffn(
        h, w, experts, lp["w1"], lp["w3"], lp["w2"]))(h))
    assert f"f32[64,{CFG.dim}]" in jaxpr
    y, _ = moe.held_experts_ffn(h, w, experts, lp["w1"], lp["w3"], lp["w2"])
    np.testing.assert_allclose(
        y, _per_token_loop(h, w, experts, lp["w1"], lp["w3"], lp["w2"]),
        atol=2e-5, rtol=2e-5)


# ------------------------------------------- the paged forward's part
def _cache(rows=2, pages=9, ps=8):
    shape = (CFG.n_layers, CFG.n_kv_heads, pages, ps, CFG.head_dim)
    table = np.arange(rows * 4).reshape(rows, 4) % (pages - 1)
    return PagedKVCache(
        k=jnp.zeros(shape, jnp.float32), v=jnp.zeros(shape, jnp.float32),
        table=jnp.asarray(table, jnp.int32),
        seq_lens=jnp.zeros((rows,), jnp.int32), page_size=ps,
        expert_rows=jnp.zeros((CFG.num_experts,), jnp.int32))


@pytest.mark.parametrize("kw,whole", [
    (dict(tp=False), True), (dict(tp=True), False),
    (dict(tp=False, resident=False), False)],
    ids=["one_device_plain_arrays", "sharded", "dequantised_inside"])
def test_forward_paged_honours_whole_stacks_without_a_lead(
        monkeypatch, params, kw, whole):
    """Mixtral states ``whole_stacks`` and has no leading stack: its
    ``out`` gets the experts [L, E, ...] and the layer's index where they
    are resident on one device, and a layer's slice (no index: every
    expert on every row) elsewhere; the logits are the same."""
    seen = []

    def spy(cfg, x, attn, lp):
        seen.append((lp["w1"].ndim, "layer" in lp))
        return mixtral._out_moe(cfg, x, attn, lp)

    assert mixtral.FAMILY.lead is None
    assert mixtral.FAMILY.whole_stacks == ("w1", "w3", "w2")
    monkeypatch.setattr(mixtral, "FAMILY",
                        dataclasses.replace(mixtral.FAMILY, out=spy))
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, CFG.vocab_size, (2, 16)), jnp.int32)
    logits, cache = forward_paged(params, tokens, CFG, _cache(),
                                  interpret=True, **kw)
    assert seen == [(4, True) if whole else (3, False)]
    np.testing.assert_allclose(
        logits, mixtral.forward_eval(params, tokens, CFG), atol=2e-4,
        rtol=2e-4)
    # 2 x 16 rows, top-2, two layers
    assert int(cache.expert_rows.sum()) == 2 * 16 * 2 * CFG.n_layers


def test_the_engines_counters_add_up(params):
    """All the experts are held: the rows counted for the experts are
    every pair the programs routed.  The counts ride in the decode
    program's own fetch: ``test_step_dispatch.py`` holds a Mixtral
    engine's plain step to one program."""
    eng = serving_engine(params, CFG, max_batch=3, page_size=8,
                         num_pages=40, max_seq=64, prefill_bucket=8,
                         telemetry=True)
    for i, n in enumerate((5, 21, 12)):
        eng.submit(i, list(range(3, 3 + n)), max_new_tokens=5)
    eng.run()
    counters = eng.registry.snapshot()["counters"]
    held = [counters[f"serving_expert_rows_{e}"]
            for e in range(CFG.num_experts)]
    assert sum(held) == counters["serving_routed_rows"] > 0
    assert counters["serving_routed_rows"] % (CFG.top_k * CFG.n_layers) == 0
    assert eng.check_leaks() == []


def test_a_speculating_engine_counts_nothing(params):
    """Its steady program is the verify sweep, which has no fetch for
    the counts to ride in."""
    eng = serving_engine(params, CFG, max_batch=2, page_size=8,
                         num_pages=40, max_seq=64, prefill_bucket=8,
                         telemetry=True, speculative={"draft_tokens": 2})
    assert eng.cache.expert_rows is None
    eng.submit(0, [5, 9, 2, 7], max_new_tokens=4)
    assert len(eng.run()[0]) == 8


# -------------------------------- a share's pair buffer (ISSUE 40)
# (N, k, Eh, E): the two shares' shapes in small.  Against a granule of
# 16 the buffer is 256 rows of Qwen3-Next's 640 pairs (an eighth held if
# the router is even: 80) and 64 rows of openPangu's 512 (a sixteenth: 32)
SHARES = {"k10_an_eighth": (64, 10, 16, 128),
          "k8_a_sixteenth": (64, 8, 8, 128)}
ROUTINGS = ("as_drawn", "every_pair_held", "one_pair_more_than_the_buffer")
TILE, D, F = 16, 32, 16


def _share_case(monkeypatch, share, routing):
    """-> (h, w, experts, (w1, w3, w2), first, C): a rank that holds
    experts ``first .. first + Eh`` of E, on the grouped branch."""
    N, k, Eh, E = SHARES[share]
    _pin(monkeypatch, "grouped")
    monkeypatch.setattr(moe, "_ROW_GRANULE", TILE)
    C = moe._pair_buffer_rows(N, k, Eh, E)
    rng = np.random.default_rng(len(share) + len(routing))
    first = Eh                                  # the second rank's share
    held = first + np.arange(Eh)
    absent = np.setdiff1d(np.arange(E), held)
    if routing == "as_drawn":
        experts = np.stack([rng.permutation(E)[:k] for _ in range(N)])
    elif routing == "every_pair_held":
        experts = np.stack([rng.choice(held, k, replace=Eh < k)
                            for _ in range(N)])
    else:                       # the first C + 1 pairs, and no other
        experts = rng.choice(absent, (N, k))
        flat = experts.reshape(-1)
        flat[:C + 1] = held[np.arange(C + 1) % Eh]
    g = lambda *s: jnp.asarray(rng.normal(size=s) * s[-2] ** -0.5,
                               jnp.float32)
    return (jnp.asarray(rng.normal(size=(N, D)), jnp.float32),
            jnp.asarray(rng.dirichlet(np.ones(k), N), jnp.float32),
            jnp.asarray(experts, jnp.int32),
            (g(Eh, D, F), g(Eh, D, F), g(Eh, F, D)), first, C)


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("share", SHARES)
def test_a_shares_bounded_buffer_is_the_per_token_loop(monkeypatch, share,
                                                       routing):
    """The pair buffer holds a bound on the pairs held here, and what it
    cannot take goes through further passes: no pair is dropped when
    every pair is held (the last pass is full) nor when one more than
    the buffer is (a second pass of one pair), and the passes counted
    are the passes made."""
    N, k, Eh, E = SHARES[share]
    h, w, experts, ws, first, C = _share_case(monkeypatch, share, routing)
    assert C < N * k
    y, rows = moe.held_experts_ffn(h, w, experts, *ws, first=first,
                                   n_experts=E)
    local = np.asarray(experts) - first
    mine = (local >= 0) & (local < Eh)
    np.testing.assert_allclose(
        y, _per_token_loop(h, np.where(mine, w, 0.0),
                           np.where(mine, local, 0), *ws),
        atol=2e-5, rtol=2e-5)
    assert rows.tolist() == np.bincount(local[mine], minlength=Eh).tolist()
    extra = int(moe.extra_pair_passes(rows, N, k, E))
    assert extra == {"as_drawn": 0, "every_pair_held": -(-N * k // C) - 1,
                     "one_pair_more_than_the_buffer": 1}[routing]
    if routing == "as_drawn":
        assert 0 < mine.sum() <= C
    elif routing == "one_pair_more_than_the_buffer":
        assert mine.sum() == C + 1


@pytest.mark.parametrize("share", SHARES)
def test_a_shares_buffer_is_its_bound_and_not_every_pair(monkeypatch, share):
    """The traced call holds a [C, d] row buffer, gathers no [N * k, d]
    rows and never forms [N, k, d]."""
    N, k, Eh, E = SHARES[share]
    h, w, experts, ws, first, C = _share_case(monkeypatch, share, "as_drawn")
    jaxpr = str(jax.make_jaxpr(lambda h: moe.held_experts_ffn(
        h, w, experts, *ws, first=first, n_experts=E))(h))
    assert f"f32[{C},{D}]" in jaxpr and f"f32[{C},{F}]" in jaxpr
    for gone in (f"[{N * k},{D}]", f"[{N * k},{F}]", f"[{N},{k},{D}]",
                 f"[{TILE * 64},{D}]"):
        assert gone not in jaxpr, gone
    assert jaxpr.count("= ragged_dot") == 3        # one trace of the pass


def _before_the_bound(h, weights, experts, w1, w3, w2, first=0):
    """The grouped branch as it was before ISSUE 40, every pair a row."""
    N, k = experts.shape
    Eh = w1.shape[-3]
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < Eh)
    group = jnp.where(held, local, Eh)
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[group].add(1)[:Eh]
    with jax.named_scope("moe_routed"):
        order = jnp.argsort(group)
        tm = moe._ROW_GRANULE
        pad = tm * (1 << (-(-N * k // tm) - 1).bit_length()) - N * k
        x = h[(jnp.pad(order, (0, pad)) if pad else order) // k]
        a = moe._grouped_product(x, w1, sizes, None)
        b = moe._grouped_product(x, w3, sizes, None)
        y = moe._grouped_product(jax.nn.silu(a) * b, w2, sizes, None)
        back = jnp.zeros((N * k,), jnp.int32).at[order].set(
            jnp.arange(N * k, dtype=jnp.int32))
        y = jnp.where(held[:, None], y[back].astype(jnp.float32), 0.0) \
            * weights.reshape(-1, 1)
        return jnp.sum(y.reshape(N, k, -1), axis=1).astype(h.dtype), sizes


@pytest.mark.parametrize("said", [None, CFG.num_experts],
                         ids=["experts_not_said", "all_experts_held"])
def test_all_the_experts_held_trace_as_before_the_bound(monkeypatch, params,
                                                        said):
    """Mixtral holds every expert its router scores: the bound is every
    pair, and the traced call is the one it was, equation for equation."""
    _pin(monkeypatch, "grouped")
    rng = np.random.default_rng(8)
    lp = {k: params["blocks"][k][0] for k in ("w1", "w3", "w2")}
    h = jnp.asarray(rng.normal(size=(300, CFG.dim)), jnp.float32)
    w, experts = _routing("as_routed", 300, rng)
    now = jax.make_jaxpr(lambda h: moe.held_experts_ffn(
        h, w, experts, lp["w1"], lp["w3"], lp["w2"], n_experts=said))(h)
    then = jax.make_jaxpr(lambda h: _before_the_bound(
        h, w, experts, lp["w1"], lp["w3"], lp["w2"]))(h)
    assert str(now) == str(then)


# ------------------ a pass on the chip: dstpu_held_ffn (ISSUE 52)
# The kernel in ``interpret`` mode (the arithmetic, none of Mosaic's
# layout rules: ``test_aot_tpu_compile.py`` holds those) against the
# per-token loop, at widths of whole 128-lane tiles.
KD, KF = 128, 256
RELU2 = lambda a: jnp.square(jax.nn.relu(a))


def _on_the_chip(monkeypatch, granule=None, tiles=None):
    """The grouped branch through the kernel, as on a TPU (here in
    ``interpret`` mode: the backend is still the CPU)."""
    _pin(monkeypatch, "grouped")
    monkeypatch.setattr(moe, "_on_chip", lambda: True)
    if granule:
        monkeypatch.setattr(moe, "_ROW_GRANULE", granule)
    if tiles:
        monkeypatch.setattr(moe, "_held_ffn_tiles",
                            lambda N, *a: moe.HeldTiles(*tiles, 1 << 26, N))


def _kernel_case(rng, N, k, Eh, E, L=None, gated=True):
    g = lambda *s: jnp.asarray(rng.normal(size=s) * s[-2] ** -0.5,
                               jnp.float32)
    lead = () if L is None else (L,)
    return (jnp.asarray(rng.normal(size=(N, KD)), jnp.float32),
            jnp.asarray(rng.dirichlet(np.ones(k), N), jnp.float32),
            (g(*lead, Eh, KD, KF), g(*lead, Eh, KD, KF) if gated else None,
             g(*lead, Eh, KF, KD)))


def _calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("dstpu_held_ffn")


# what the router did -> experts [N, k] over E = 8, the first 4 held
KERNEL_ROUTINGS = {
    "as_drawn": lambda rng, N: np.stack(
        [rng.permutation(8)[:2] for _ in range(N)]),
    "an_expert_with_no_row": lambda rng, N: np.stack(
        [rng.permutation([0, 1, 3, 5, 6])[:2] for _ in range(N)]),
    "an_expert_with_every_row": lambda rng, N: np.stack(
        [[2, rng.integers(3, 8)] for _ in range(N)]),
    "no_pair_held_here": lambda rng, N: rng.integers(4, 8, (N, 2)),
}


@pytest.mark.parametrize("gated", [True, False],
                         ids=["gated", "two_matrices"])
@pytest.mark.parametrize("routing", KERNEL_ROUTINGS)
def test_the_kernel_is_the_per_token_top_k_loop(monkeypatch, routing, gated):
    """Both bodies, 4 of 8 experts held, 37 rows (no expert's rows are
    whole tiles of 16, one expert's are three tiles and a part): one
    Mosaic call, and the rows counted are the pairs held."""
    _on_the_chip(monkeypatch, tiles=(16, 16, KF))
    rng = np.random.default_rng(11)
    h, w, ws = _kernel_case(rng, 37, 2, 4, 8, gated=gated)
    experts = jnp.asarray(KERNEL_ROUTINGS[routing](rng, 37), jnp.int32)
    fn = lambda h: moe.held_experts_ffn(
        h, w, experts, *ws, n_experts=8, act=None if gated else RELU2)
    assert _calls(fn, h) == 1 and not _products(fn, h)
    y, rows = fn(h)
    np.testing.assert_allclose(y, _per_token_loop(h, w, experts, *ws),
                               atol=2e-5, rtol=2e-5)
    assert rows.tolist() == np.bincount(
        np.asarray(experts).ravel(), minlength=8)[:4].tolist()
    if routing == "an_expert_with_every_row":
        assert rows[2] == 37
    if routing == "no_pair_held_here":
        assert not np.asarray(y).any()


@pytest.mark.parametrize("tiles", [(16, 16, KF), (16, 16, 128),
                                   (16, 64, 128), (128, 128, KF)],
                         ids=["a_tile_a_step", "f_in_two_blocks",
                              "four_products_a_step", "one_wide_tile"])
def test_the_kernels_tiles_change_no_number(monkeypatch, tiles):
    """The same pass cut four ways: an expert's rows over several grid
    steps, ``f`` in blocks (the tile's f32 rows add up across them), a
    step of several products, and a tile wider than any expert's rows."""
    _on_the_chip(monkeypatch, tiles=tiles)
    rng = np.random.default_rng(12)
    h, w, ws = _kernel_case(rng, 90, 2, 3, 3)
    experts = jnp.asarray(np.stack([rng.permutation(3)[:2]
                                    for _ in range(90)]), jnp.int32)
    y, _ = moe.held_experts_ffn(h, w, experts, *ws)
    np.testing.assert_allclose(y, _per_token_loop(h, w, experts, *ws),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("gated", [True, False],
                         ids=["gated", "two_matrices"])
def test_the_kernel_takes_a_layer_of_the_whole_stack(monkeypatch, gated):
    """The stacks [L, Eh, ...] go in whole with the layer's index: the
    traced call slices no layer out, and gives the sliced call's rows."""
    _on_the_chip(monkeypatch, tiles=(16, 32, 128))
    rng = np.random.default_rng(13)
    h, w, ws = _kernel_case(rng, 48, 2, 4, 4, L=3, gated=gated)
    experts = jnp.asarray(np.stack([rng.permutation(4)[:2]
                                    for _ in range(48)]), jnp.int32)
    act = None if gated else RELU2
    whole = lambda h, l: moe.held_experts_ffn(h, w, experts, *ws, layer=l,
                                              act=act)
    jaxpr = str(jax.make_jaxpr(whole)(h, jnp.int32(2)))
    assert f"f32[4,{KD},{KF}]" not in jaxpr        # no layer's slice
    y, rows = whole(h, jnp.int32(2))
    sliced = [a if a is None else a[2] for a in ws]
    np.testing.assert_allclose(y, _per_token_loop(h, w, experts, *sliced),
                               atol=2e-5, rtol=2e-5)
    y2, rows2 = moe.held_experts_ffn(h, w, experts, *sliced, act=act)
    np.testing.assert_allclose(y, y2, atol=1e-6)
    assert rows.tolist() == rows2.tolist()


@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_kernels_further_passes_add_into_the_same_sum(monkeypatch,
                                                          routing):
    """A share's bounded buffer on the chip: 16 of 128 experts held, 64
    rows ten ways, a pass of 256 pairs.  Every pair held is three
    passes, one more than the buffer a second pass of one pair: each
    pass is the same Mosaic call (one trace, in the loop) adding into
    the sum the pass before it left."""
    N, k, Eh, E = SHARES["k10_an_eighth"]
    _on_the_chip(monkeypatch, granule=TILE, tiles=(16, 16, KF))
    C = moe._pair_buffer_rows(N, k, Eh, E)
    assert C == 256 < N * k
    rng = np.random.default_rng(14)
    held = Eh + np.arange(Eh)
    if routing == "as_drawn":
        experts = np.stack([rng.permutation(E)[:k] for _ in range(N)])
    elif routing == "every_pair_held":
        experts = np.stack([rng.choice(held, k, replace=False)
                            for _ in range(N)])
    else:
        experts = rng.choice(np.setdiff1d(np.arange(E), held), (N, k))
        experts.reshape(-1)[:C + 1] = held[np.arange(C + 1) % Eh]
    h, w, ws = _kernel_case(rng, N, k, Eh, E)
    fn = lambda h: moe.held_experts_ffn(
        h, w, jnp.asarray(experts, jnp.int32), *ws, first=Eh, n_experts=E)
    assert _calls(fn, h) == 1
    y, rows = fn(h)
    np.testing.assert_allclose(y, _per_token_loop(h, w, experts - Eh, *ws),
                               atol=2e-5, rtol=2e-5)
    assert int(moe.extra_pair_passes(rows, N, k, E)) == {
        "as_drawn": 0, "every_pair_held": 2,
        "one_pair_more_than_the_buffer": 1}[routing]


def test_bf16_rows_meet_bf16_weights_as_the_grouped_product_does(
        monkeypatch):
    """The serving dtype: bf16 operands, f32 sums, the gate in f32 (the
    products are not rounded to bf16 before it, as ``ragged_dot``'s
    are): within bf16's rounding of the plain statement."""
    rng = np.random.default_rng(15)
    h, w, ws = _kernel_case(rng, 64, 2, 4, 4)
    h, ws = h.astype(jnp.bfloat16), [a.astype(jnp.bfloat16) for a in ws]
    experts = jnp.asarray(np.stack([rng.permutation(4)[:2]
                                    for _ in range(64)]), jnp.int32)
    _pin(monkeypatch, "grouped")
    plain, _ = moe.held_experts_ffn(h, w, experts, *ws)
    _on_the_chip(monkeypatch, tiles=(16, 16, KF))
    y, _ = moe.held_experts_ffn(h, w, experts, *ws)
    assert y.dtype == jnp.bfloat16
    want = _per_token_loop(h.astype(jnp.float32), w, experts,
                 *(a.astype(jnp.float32) for a in ws))
    scale = np.abs(want).max()
    assert np.abs(np.asarray(y, np.float32) - want).max() < 2e-2 * scale
    assert np.abs(np.asarray(plain, np.float32) - want).max() < 2e-2 * scale


# (N, k, E, d, f, matrices) -> (tm, span, tf): the five families' chunk
# shapes and Mixtral's shortest grouped prefill, as PERF.md 6 (PR 52) has
# them
@pytest.mark.parametrize("shape,tiles", [
    ((1024, 10, 512, 2048, 512, 3), (32, 32, 512)),      # docqa-sat
    ((1024, 8, 512, 2560, 768, 3), (32, 32, 768)),       # Ling's docqa-sat
    ((1024, 10, 256, 3072, 1024, 3), (64, 64, 1024)),    # Laguna's code-sat
    ((1024, 6, 128, 2688, 1920, 2), (128, 128, 1920)),   # Nemotron's
    ((1024, 8, 256, 7680, 2048, 3), (64, 256, 128)),     # think-sat
    ((1024, 2, 8, 4096, 14336, 3), (128, 512, 896)),     # docs-sat
    ((384, 2, 8, 4096, 14336, 3), (128, 512, 1024)),     # chat-sat
], ids=["docqa", "ling", "laguna", "nemotron", "pangu", "mixtral_chunk",
        "mixtral_384"])
def test_the_kernels_tiles_are_read_from_the_shapes(shape, tiles):
    N, k, E, d, f, mats = shape
    got = moe._held_ffn_tiles(N, k, E, d, f, 2, mats)
    assert tuple(got[:3]) == tiles and got.rows == N
    assert got.vmem <= moe._HELD_VMEM_BYTES
    assert f % got.tf == 0 and got.tf % 128 == 0 and got.span % got.tm == 0


@pytest.mark.parametrize("d,f", [(32, 16), (128, 40), (96, 128)])
def test_widths_off_the_lane_tiles_keep_the_plain_statement(monkeypatch,
                                                            d, f):
    """No kernel where a width is not whole 128-lane tiles: the branch
    keeps ``ragged_dot`` (on the chip too)."""
    assert moe._held_ffn_tiles(1024, 2, 8, d, f, 2, 3) is None
    _on_the_chip(monkeypatch)
    fn = lambda h: moe.held_experts_ffn(
        h, jnp.ones((300, 2)) / 2, jnp.zeros((300, 2), jnp.int32),
        jnp.zeros((4, d, f)), jnp.zeros((4, d, f)), jnp.zeros((4, f, d)))
    h = jnp.zeros((300, d))
    assert not _calls(fn, h) and _products(fn, h)


def test_rows_the_vmem_cannot_hold_go_through_in_halves(monkeypatch):
    """Where [N, d] f32 rows and their sum do not fit the VMEM beside a
    block of the weights, each half of the rows is a call of its own
    (here: a VMEM of 1.5 MiB, 512 rows of 128 numbers, two calls of
    256 rows), and the rows counted are all of them."""
    assert moe._held_ffn_tiles(4096, 2, 8, 4096, 14336, 2, 3).rows == 2048
    assert moe._held_ffn_tiles(4097, 2, 8, 4096, 14336, 2, 3) is None
    _on_the_chip(monkeypatch, granule=TILE)
    monkeypatch.setattr(moe, "_HELD_VMEM_BYTES", 3 << 19)
    monkeypatch.setattr(moe, "_HELD_VMEM_SLACK", 0)
    assert moe._held_ffn_tiles(512, 2, 4, KD, KF, 4, 3).rows == 256
    rng = np.random.default_rng(16)
    h, w, ws = _kernel_case(rng, 512, 2, 4, 4)
    experts = jnp.asarray(np.stack([rng.permutation(4)[:2]
                                    for _ in range(512)]), jnp.int32)
    fn = lambda h: moe.held_experts_ffn(h, w, experts, *ws)
    assert _calls(fn, h) == 2
    y, rows = fn(h)
    np.testing.assert_allclose(y, _per_token_loop(h, w, experts, *ws),
                               atol=2e-5, rtol=2e-5)
    assert rows.tolist() == np.bincount(np.asarray(experts).ravel()).tolist()


# what a build says of its chunk program's experts (/statusz kernels.experts)
@pytest.mark.parametrize("case,on_chip,want", [
    (dict(N=1024, grouped=False), True,
     ("every_row", "the weights are no whole stacks held on one device")),
    (dict(N=96), True, ("every_row", "96 rows: a pass over the held weights")),
    (dict(N=1024), False, ("ragged_dot", "no TPU backend")),
    (dict(N=1024, f=520), True,
     ("ragged_dot", "2048 x 520: not whole 128-lane tiles")),
    (dict(N=1024), True,
     ("dstpu_held_ffn", "products of 32 rows, f in blocks of 512")),
    (dict(N=1 << 15), True,
     ("dstpu_held_ffn",
      "products of 128 rows, f in blocks of 512, 4096 rows a call")),
], ids=["not_grouped", "few_rows", "no_tpu", "off_the_lanes", "the_kernel",
        "the_kernel_in_parts"])
def test_the_product_of_a_program_is_a_rule_of_its_shapes(
        monkeypatch, case, on_chip, want):
    """docqa-sat's experts (64 of 512 of 2048 x 512, k = 10, bf16)."""
    monkeypatch.setattr(moe, "_on_chip", lambda: on_chip)
    kw = dict(dict(N=1024, k=10, Eh=64, E=512, d=2048, f=512, itemsize=2,
                   mats=3), **case)
    product, reason, tiles = moe.held_product(**kw)
    assert (product, reason) == want
    assert (tiles is not None) == (product == "dstpu_held_ffn")


@pytest.mark.parametrize("kw,want", [
    (dict(prefill_chunk=16), {"product": "ragged_dot",
                              "reason": "no TPU backend"}),
    (dict(prefill_chunk=16, weight_dtype="int8"), {
        "product": "every_row",
        "reason": "the weights are no whole stacks held on one device"}),
    (dict(), {"product": "every_row", "reason": "no chunk program"}),
], ids=["a_chunk_off_the_chip", "int8_weights", "no_chunk_program"])
def test_statusz_says_what_the_chunk_programs_experts_run(
        monkeypatch, params, kw, want):
    monkeypatch.setattr(moe, "_every_row_pays", lambda N, k, Eh: N < 16)
    eng = serving_engine(params, CFG, max_batch=2, page_size=8,
                         num_pages=40, max_seq=64, prefill_bucket=8, **kw)
    assert eng.statusz()["kernels"]["experts"] == want
