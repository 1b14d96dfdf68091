"""The names the program gives what runs (ISSUE 24): host spans inside
``step()`` and ``train_batch`` on the profiler's clock, named scopes in
every lowered program, a ``name=`` on every Mosaic kernel."""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmark.harness import scopes, trace
from deepspeed_tpu.devprof import STEP_LEDGER, STEP_SITES
from deepspeed_tpu.inference.kernels import PagedKVCache
from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.models import gpt2, laguna, mixtral

CHILDREN = ("admit", "prefill", "boundary", "grow_pages", "upload",
            "inputs", "dispatch", "token_sync", "append")
# zero-length marks inside a step: edges, not phases
EDGES = ("dstpu/request_admitted", "dstpu/request_first_token",
         "dstpu/dispatch")
ENGINE_KW = dict(max_batch=2, page_size=8, num_pages=32, max_seq=64,
                 prefill_bucket=8)
PROMPTS = {"a": ([5, 9, 2], 6), "b": ([17, 3, 3, 8, 1], 5),
           "c": ([40, 2], 7)}


def _gpt2():
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=64)
    return cfg, gpt2.init_params(jax.random.PRNGKey(0), cfg), gpt2


def _mixtral():
    cfg = mixtral.MixtralConfig.tiny()
    return cfg, mixtral.init_params(jax.random.PRNGKey(0), cfg), mixtral


def _laguna():
    cfg = laguna.LagunaConfig.tiny()
    return cfg, laguna.init_params(jax.random.PRNGKey(0), cfg), laguna


MODELS = {"gpt2": _gpt2, "mixtral": _mixtral, "laguna": _laguna}


def _serve(telemetry):
    cfg, params, _ = _gpt2()
    eng = serving_engine(params, cfg, telemetry=telemetry, **ENGINE_KW)
    for rid, (p, n) in PROMPTS.items():
        eng.submit(rid, p, max_new_tokens=n)
    return eng


def _trainer(telemetry=True):
    cfg, params, mod = _gpt2()
    engine, *_ = deepspeed_tpu.initialize(
        loss_fn=mod.loss_fn(cfg), params=params,
        config={"train_batch_size": 8,
                "telemetry": {"enabled": telemetry},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "gradient_clipping": 1.0,
                "zero_optimization": {"stage": 1}})
    batch = {"tokens": np.arange(8 * 17, dtype=np.int32).reshape(8, 17)
             % cfg.vocab_size}
    return engine, batch


# ---------------------------------------------- (a) spans in a capture
@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One CPU capture, three stretches marked by ``bench/`` spans: a
    toy engine with telemetry, the same without, two training steps.
    Everything is compiled before the profiler starts."""
    on, off = _serve(True), _serve(False)
    trainer, batch = _trainer()
    for eng in (on, off):
        eng.step()
    trainer.train_batch(batch)
    logdir = str(tmp_path_factory.mktemp("capture"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench/on"):
            steps = 0
            while on.has_work:
                on.step()
                steps += 1
        with jax.profiler.TraceAnnotation("bench/off"):
            off.run()
        with jax.profiler.TraceAnnotation("bench/train"):
            for _ in range(2):
                jax.block_until_ready(trainer.train_batch(batch))
        # a build inside the capture: its phases, and one span a
        # warm-up dispatch (ISSUE 37)
        with jax.profiler.TraceAnnotation("bench/build"):
            cfg, params, _ = _gpt2()
            serving_engine(params, cfg, telemetry=True, devprof=True,
                           **ENGINE_KW).shutdown()
            fresh, batch = _trainer(telemetry=True)
            jax.block_until_ready(fresh.train_batch(batch))
    finally:
        jax.profiler.stop_trace()
    scoped = scopes.load(trace.newest_xplane(logdir))
    return scoped, steps, on


def _inside(scoped, outer):
    box = next(s for s in scoped.spans if s.name == outer)
    return [s for s in scoped.spans if s.name.startswith("dstpu/")
            and box.start <= s.start and
            s.start + s.dur <= box.start + box.dur]


def test_serving_spans_nest_in_the_step_and_tile_it(capture):
    scoped, steps, _ = capture
    mine = _inside(scoped, "bench/on")
    per_step = scopes.children(
        scopes.Scoped({}, {}, mine), "dstpu/serving_step")
    assert len(per_step) == steps
    for parent, kids in per_step:
        names = [k.name for k in kids if k.name not in EDGES]
        # one span a phase a step: none repeated per slot or per token
        assert len(names) == len(set(names))
        assert set(names) <= {f"dstpu/serving_{c}" for c in CHILDREN}
    seen = {k.name for _, kids in per_step for k in kids}
    assert {f"dstpu/serving_{c}" for c in CHILDREN} <= seen
    assert scopes.coverage(scopes.Scoped({}, {}, mine)) >= 0.90
    # the tick runs beside the step, once a step
    assert sum(s.name == "dstpu/serving_tick" for s in mine) == steps


def test_step_phases_add_up_to_the_step(capture):
    scoped, steps, _ = capture
    mine = scopes.Scoped({}, {}, _inside(scoped, "bench/on"))
    kids = [f"dstpu/serving_{c}" for c in CHILDREN]
    phases = scopes.step_phases(mine, kids)
    whole = [p.dur for p, _ in scopes.children(mine)]
    assert len(phases) == steps
    assert sum(phases) == pytest.approx(sum(whole), rel=0.10)
    with_tick = scopes.step_phases(mine, ["dstpu/serving_append"],
                                   beside=["dstpu/serving_tick"])
    assert all(a >= b for a, b in zip(
        with_tick, scopes.step_phases(mine, ["dstpu/serving_append"])))


def test_a_captured_step_is_its_row_in_the_ledger(capture):
    """``dstpu/serving_step`` carries the ordinal of its row in the
    step ledger, and every dispatch of the step is an edge inside it
    under its site, with the rows and the tokens the row counts: a
    capture and the ledger are read side by side, by name."""
    scoped, steps, _ = capture
    mine = scopes.Scoped({}, {}, _inside(scoped, "bench/on"))
    per_step = scopes.children(mine, "dstpu/serving_step")
    ordinals = [int(parent.stats["n"]) for parent, _ in per_step]
    assert len(ordinals) == steps
    assert ordinals == list(range(ordinals[0], ordinals[0] + steps))
    rows = {r["n"]: r for r in STEP_LEDGER.snapshot()["rows"]}
    offsets = []
    for (parent, kids), n in zip(per_step, ordinals):
        row = rows[n]
        seen = {}
        for k in kids:
            if k.name == "dstpu/dispatch":
                p = seen.setdefault(k.stats["site"], [0, 0, 0])
                p[0] += 1
                p[1] += int(k.stats["rows"])
                p[2] += int(k.stats["tokens"])
        assert seen == {s: p for s, p in row["programs"].items() if p[0]}
        assert set(seen) <= set(STEP_SITES)
        # the decode's edge lies in its phase, a prefill's in admit
        for k in kids:
            if k.name == "dstpu/dispatch":
                phase = {"decode": "dispatch", "decode_ahead": "dispatch",
                         "prefill": "admit",
                         "chunk": "prefill"}[k.stats["site"]]
                (box,) = [c for c in kids
                          if c.name == f"dstpu/serving_{phase}"]
                assert box.start <= k.start <= box.start + box.dur
        # one clock beside the other: the span and the row are the
        # same interval, a constant apart
        assert parent.dur == pytest.approx(row["t1"] - row["t0"], abs=2e-4)
        offsets.append(parent.start - row["t0"])
    assert "decode" in {k.stats.get("site") for _, kids in per_step
                        for k in kids}
    assert max(offsets) - min(offsets) < 1e-3


def test_a_request_is_marked_once_at_each_edge_under_its_id(capture):
    scoped, _, on = capture
    mine = _inside(scoped, "bench/on")
    for edge in ("request_admitted", "request_first_token"):
        ids = sorted(s.stats["request_id"] for s in mine
                     if s.name == f"dstpu/{edge}")
        # "a" was admitted by the warm-up step, before the capture
        assert set(ids) <= set(PROMPTS) and len(ids) == len(set(ids)) >= 1
    hist = on.registry.snapshot()["histograms"]
    assert hist["serving_queue_wait_seconds"]["count"] == len(PROMPTS)
    for c in CHILDREN + ("tick", "step"):
        assert hist[f"serving_{c}_seconds"]["count"] > 0


def test_without_telemetry_no_program_span_at_all(capture):
    scoped, _, _ = capture
    assert _inside(scoped, "bench/off") == []


def test_train_spans(capture):
    scoped, _, _ = capture
    names = [s.name for s in _inside(scoped, "bench/train")]
    assert names.count("dstpu/train_step") == 2
    assert names.count("dstpu/train_align_batch") == 2


BUILD_SPANS = {"dstpu/build_alloc": 1, "dstpu/build_programs": 2,
               "dstpu/build_warmup": 1, "dstpu/build_program": 15,
               "dstpu/build_state": 1, "dstpu/build_step": 1}
BUILD_COUNTERS = ("build_programs", "build_cache_misses",
                  "build_trace_seconds", "build_lower_seconds",
                  "build_cache_load_seconds", "build_compile_seconds")
BUILD_GAUGES = ("build_seconds", "package_import_seconds")


@pytest.mark.parametrize("name", sorted(BUILD_SPANS))
def test_build_spans(capture, name):
    """The build's phases on the profiler's clock: the serving engine's
    allocation, jits and warm-up with one ``build_program`` a dispatch
    inside it (the site and the shape word as stats), the training
    engine's state placement and first step."""
    scoped, _, _ = capture
    mine = [s for s in _inside(scoped, "bench/build") if s.name == name]
    assert len(mine) == BUILD_SPANS[name]
    if name == "dstpu/build_program":
        (warm,) = [s for s in _inside(scoped, "bench/build")
                   if s.name == "dstpu/build_warmup"]
        assert all(warm.start <= s.start and
                   s.start + s.dur <= warm.start + warm.dur for s in mine)
        words = [(s.stats["site"],
                  {k: int(v) for k, v in s.stats.items() if k != "site"})
                 for s in mine]
        assert words[:8] == [("prefill", {"end": 8 * i})
                             for i in range(1, 9)]
        assert words[8:12] == [("chunk_prefill", {"w": w})
                               for w in (1, 2, 4, 8)]
        assert words[12:] == [("boundary", {}), ("join", {}),
                              ("decode_chunk", {"b": 2})]
    if name == "dstpu/build_step":
        assert mine[0].stats["site"] == "train_step"


@pytest.mark.parametrize("build", ["serving", "training"])
def test_build_counters_and_gauges_are_in_the_registry(build):
    if build == "serving":
        cfg, params, _ = _gpt2()
        eng = serving_engine(params, cfg, telemetry=True, **ENGINE_KW)
        snap = eng.registry.snapshot()
        eng.shutdown()
    else:
        snap = _trainer()[0].registry.snapshot()
    assert set(BUILD_COUNTERS) <= set(snap["counters"])
    assert set(BUILD_GAUGES) <= set(snap["gauges"])
    assert snap["gauges"]["build_seconds"] > 0
    # nothing the sampled half gave is left
    names = [n for kind in ("counters", "gauges", "histograms")
             for n in snap[kind]]
    assert not [n for n in names if n.startswith("devprof_")
                and "compiles" not in n]


# -------------------------------------- (b) scopes in lowered programs
@functools.lru_cache(maxsize=None)
def _paged_programs(name):
    """The lowered prefill, chunk and decode programs of a toy engine,
    as text with locations."""
    cfg, params, _ = MODELS[name]()
    eng = serving_engine(params, cfg, telemetry=False, **ENGINE_KW)
    absx = lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                      if hasattr(x, "shape") else x)
    tm = jax.tree_util.tree_map
    params_a, cache_a = tm(absx, eng.params), tm(absx, eng.cache)
    # the engine's own one-row view: with the slot, where slots keep rows
    view_a = tm(absx, eng._row_view(eng._table_host[0:1], 0, 0))
    B = eng.max_batch
    last = jax.ShapeDtypeStruct((1,), jnp.int32)    # the last real position
    lowered = {
        "prefill": eng._prefill.lower(
            params_a, jax.ShapeDtypeStruct((1, 8), jnp.int32), view_a,
            last),
        "chunk": eng._chunk_prefill.lower(
            params_a, jax.ShapeDtypeStruct((1, 8), jnp.int32), view_a,
            last),
        "boundary": eng._boundary.lower(
            jax.ShapeDtypeStruct((1, cfg.vocab_size), jnp.float32),
            absx(eng._key), jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.float32)),
        # a boundary token written into a decode's token operand
        "join": eng._join.lower(
            jax.ShapeDtypeStruct(eng._out_shape, jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)),
        "decode": eng._decode_chunk_fn.lower(
            params_a, jax.ShapeDtypeStruct((B, 1), jnp.int32), cache_a,
            absx(eng._key), jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.float32)),
    }
    assert isinstance(eng.cache, PagedKVCache)
    return {k: v.as_text(debug_info=True) for k, v in lowered.items()}


BLOCKS = {"gpt2": {"embed", "attn_qkv", "attn_out", "mlp", "final_norm",
                   "lm_head", "kv_write"},
          "mixtral": {"embed", "attn_qkv", "attn_out", "mlp", "moe_router",
                      "moe_ffn", "final_norm", "lm_head", "kv_write"}}
BLOCKS["laguna"] = BLOCKS["mixtral"]
PHASE = {"prefill": {"flash"}, "chunk": {"kv_attend"},
         "decode": {"kv_attend", "sample"}}


def _words(text):
    """Vocabulary words that appear as a component of a location path."""
    found = set()
    for path in re.findall(r'loc\("([^"]*)"', text):
        found.update(w for w in scopes.WORD.findall(path)
                     if w in scopes.VOCABULARY)
    return found


@pytest.mark.parametrize("program", ["prefill", "chunk", "decode",
                                     "boundary", "join"])
@pytest.mark.parametrize("name", ["gpt2", "mixtral", "laguna"])
def test_paged_programs_carry_every_scope_that_applies(name, program):
    text = _paged_programs(name)[program]
    # the boundary sampler runs no model, nor does what writes its
    # token into a decode's operand: their one scope is ``sample``
    want = ({"sample"} if program in ("boundary", "join")
            else BLOCKS[name] | PHASE[program])
    assert want <= _words(text), want - _words(text)
    assert f"dstpu_{program}" in text          # the program's own name


@pytest.mark.parametrize("program", ["prefill", "chunk", "decode"])
def test_a_window_familys_words_nest_in_the_vocabulary(program):
    """The laguna family's own words (a sliding layer's scores and sum,
    its ring's rows, the per-head gate, the routed and the shared part
    of an expert layer) stand INSIDE a word the harness's vocabulary
    knows, in every program: an operation under them is labelled by the
    known word and found by the new one (``benchmark/readers/
    window.py``)."""
    paths = re.findall(r'loc\("([^"]*)"', _paged_programs("laguna")[program])
    for inner, outer in (("win_attend", "kv_attend"),
                         ("win_write", "kv_write"),
                         ("attn_gate", "attn_out"),
                         ("moe_routed", "moe_ffn"),
                         ("moe_shared", "moe_ffn")):
        under = [scopes.WORD.findall(p) for p in paths if inner in p]
        under = [w for w in under if inner in w]
        assert under, inner
        for words in under:
            assert outer in words[:words.index(inner)], (inner, words)
            assert scopes.scope_of("/".join(words))[0] == outer


@pytest.mark.parametrize("name", ["gpt2", "mixtral"])
def test_train_step_carries_model_and_step_scopes(name):
    cfg, params, mod = MODELS[name]()
    engine, *_ = deepspeed_tpu.initialize(
        loss_fn=mod.loss_fn(cfg), params=params, has_aux=name == "mixtral",
        config={"train_batch_size": 8, "gradient_clipping": 1.0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    lowered = engine.lower_step({"tokens": np.zeros((8, 17), np.int32)})
    text = lowered.as_text(debug_info=True)
    want = (BLOCKS[name] - {"kv_write"}) | {"flash", "loss", "grad_clip",
                                            "optimizer"}
    assert want <= _words(text), want - _words(text)
    assert "dstpu_train_step" in text
    if name == "gpt2":
        # what a trace carries is the compiled program's op_name: the
        # backward pass shows as the same words under a transpose
        paths = set(re.findall(r'op_name="([^"]*)"',
                               lowered.compile().as_text()))
        marks = {scopes.scope_of(p) for p in paths}
        assert {("attn_qkv", False), ("attn_qkv", True),
                ("optimizer", False)} <= marks
        assert ("optimizer", True) not in marks


def _pallas_scopes(jaxpr, out):
    """(kernel name, the scope path it was traced under) of every
    ``pallas_call`` in ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            info = eqn.params.get("name_and_src_info")
            out.append((getattr(info, "name", None) or eqn.params.get("name"),
                        str(eqn.source_info.name_stack)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_scopes(sub, out)
    return out


@pytest.mark.parametrize("tokens,kernel", [(128, "dstpu_paged_chunk_v2"),
                                           (1, "dstpu_paged_decode")])
def test_the_paged_readers_run_by_name_under_kv_attend(tokens, kernel):
    """A chunk program traced as the chip would (whole 128-row blocks,
    heads of 128, ``interpret=False``) holds the blocked reader under its
    ``dstpu_`` name inside the scope ``kv_attend``, which is how the
    harness bins it (``benchmark/tests/test_scopes.py`` reads the same
    name) and how ``breakdown.device_ops`` shows that a build engaged
    it; a decode program its own."""
    from deepspeed_tpu.inference.kernels import PagedKVCache
    from deepspeed_tpu.inference.paged_forward import forward_paged
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=64, dim=512, n_layers=2, n_heads=4,
                            n_kv_heads=2, ffn_dim=64, max_seq_len=512)
    S = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: llama.init_params(
        jax.random.PRNGKey(0), cfg))
    kv = S((2, 2, 33, 16, 128), jnp.bfloat16)
    cache = PagedKVCache(k=kv, v=kv, table=S((1, 32), jnp.int32),
                         seq_lens=S((1,), jnp.int32), page_size=16)
    jaxpr = jax.make_jaxpr(lambda p, t, c: forward_paged(
        p, t, cfg, c, interpret=False, tp=False,
        continuation=tokens > 1))(params, S((1, tokens), jnp.int32), cache)
    found = _pallas_scopes(jaxpr.jaxpr, [])
    assert [name for name, _ in found] == [kernel]
    assert "kv_attend" in found[0][1]


def test_every_mosaic_kernel_has_a_name_of_its_own():
    from deepspeed_tpu.inference import kernels as K
    from deepspeed_tpu.ops import attention_pallas, attention_pallas_bwd, quant

    q = jnp.zeros((1, 128, 2, 128), jnp.float32)
    flash = lambda q: attention_pallas.flash_attention_tpu(
        q, q, q, interpret=True).sum()
    pages = jnp.zeros((2, 9, 8, 128), jnp.float32)
    table = jnp.zeros((2, 4), jnp.int32)
    start = jnp.zeros((2,), jnp.int32)
    qc = jnp.zeros((2, 8, 2, 128), jnp.float32)
    latent = jnp.zeros((1, 1, 9, 8, 128), jnp.float32)
    sites = {
        "dstpu_mla_decode": lambda: jax.make_jaxpr(
            lambda: K.latent_decode_attention(
                jnp.zeros((2, 8, 40), jnp.float32), latent, table, start,
                0.2, 32, layer=0, interpret=True))(),
        "dstpu_latent_flash_fwd": lambda: jax.make_jaxpr(
            lambda: attention_pallas.latent_flash_attention_tpu(
                q[..., :16], q[..., :8], q[..., :16], q[:, :, 0, :8],
                q[..., :16], start[:1], 0.2, interpret=True))(),
        "dstpu_flash_fwd": lambda: jax.make_jaxpr(flash)(q),
        "dstpu_flash_bwd_dq": lambda: jax.make_jaxpr(jax.grad(flash))(q),
        "dstpu_flash_bwd_dkv": lambda: jax.make_jaxpr(jax.grad(flash))(q),
        # two blocks of 256 key rows: the rule of the shapes answers fused
        "dstpu_flash_bwd": lambda: jax.make_jaxpr(jax.grad(flash))(
            jnp.zeros((1, 512, 2, 128), jnp.float32)),
        "dstpu_paged_chunk_v2": lambda: jax.make_jaxpr(
            lambda: K.paged_chunk_attention_v2(
                qc, pages, pages, table, start, interpret=True))(),
        "dstpu_paged_decode": lambda: jax.make_jaxpr(
            lambda: K.paged_decode_attention_v2(
                qc[:, 0], pages, pages, table, start, interpret=True))(),
        "dstpu_window_flash_fwd": lambda: jax.make_jaxpr(
            lambda: attention_pallas.window_flash_attention_tpu(
                q, jnp.zeros((1, 128, 256), jnp.float32),
                jnp.zeros((1, 128, 256), jnp.float32), start[:1],
                interpret=True))(),
        "dstpu_state_step": lambda: jax.make_jaxpr(
            lambda: K.state_step(
                lambda S, row: (row, S + row),
                jnp.zeros((2, 2, 2, 8, 128), jnp.float32), 1,
                (jnp.zeros((2, 2, 1, 128), jnp.float32),),
                interpret=True))(),
        "dstpu_state_chunk": lambda: jax.make_jaxpr(
            lambda: K.state_chunk(
                lambda S, x, col, lane: (x + col, S),
                jnp.zeros((1, 2, 8, 128), jnp.float32),
                (jnp.zeros((1, 16, 2, 128), jnp.float32),),
                jnp.zeros((1, 16, 2, 1), jnp.float32),
                jnp.zeros((1, 16, 2, 1), jnp.float32), block=8,
                interpret=True))(),
    }
    for want, make in sites.items():
        names = [n for n, _ in _pallas_scopes(make().jaxpr, [])]
        assert want in names, (want, names)
    # the sources give twelve sites twelve names, none shared
    named = []
    for mod in (K, attention_pallas, attention_pallas_bwd, quant):
        with open(mod.__file__) as f:
            text = f.read()
        assert text.count("pl.pallas_call(") == text.count('name="dstpu_')
        named += re.findall(r'name="(dstpu_[a-z0-9_]+)"', text)
    assert len(named) == len(set(named)) == 12
