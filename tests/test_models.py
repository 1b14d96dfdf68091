"""Model family tests: shapes, TP equivalence, training convergence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.models import bert, cnn, gpt2, llama, mixtral
from deepspeed_tpu.models.family import decoder_family
from deepspeed_tpu.topology import MeshSpec


def _tokens(rng, b, t, v):
    return jnp.asarray(rng.integers(0, v, (b, t)), jnp.int32)


class TestLlama:
    def test_forward_shapes(self):
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        toks = _tokens(np.random.default_rng(0), 2, 16, cfg.vocab_size)
        logits = llama.forward(params, toks, cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_gqa_reference_matches_mha_when_equal_heads(self):
        # with n_kv == n_heads the GQA path must equal plain MHA
        rng = jax.random.PRNGKey(1)
        q = jax.random.normal(rng, (2, 8, 4, 16))
        out1 = llama.reference_attention(q, q, q, causal=True)
        cfgq = llama.LlamaConfig.tiny(n_heads=4, n_kv_heads=4)
        out2 = llama._attention(q, q, q, cfgq)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-5, atol=1e-5)

    def test_causality(self):
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        t1 = _tokens(rng, 1, 16, cfg.vocab_size)
        t2 = t1.at[0, -1].set((t1[0, -1] + 1) % cfg.vocab_size)
        l1 = llama.forward(params, t1, cfg)
        l2 = llama.forward(params, t2, cfg)
        # changing the last token must not affect earlier logits
        np.testing.assert_allclose(np.asarray(l1[:, :-1]),
                                   np.asarray(l2[:, :-1]), rtol=1e-4, atol=1e-4)

    def test_train_loss_drops(self, devices):
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        engine, _, _, _ = dstpu.initialize(
            loss_fn=llama.loss_fn(cfg), params=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "zero_optimization": {"stage": 2},
                    "optimizer": {"type": "adamw", "params": {"lr": 3e-3}}})
        toks = _tokens(np.random.default_rng(0), 16, 33, cfg.vocab_size)
        losses = [float(engine.train_batch({"tokens": toks})) for _ in range(10)]
        assert losses[-1] < losses[0] * 0.8

    @pytest.mark.slow
    def test_tp_matches_single(self, devices):
        """TP=2 + ZeRO-3 forward/backward == replicated run."""
        cfg = llama.LlamaConfig.tiny(dim=64)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        toks = _tokens(np.random.default_rng(0), 8, 33, cfg.vocab_size)

        def run(mesh_sizes, specs, stage):
            ms = MeshSpec.build(mesh_sizes)
            engine, _, _, _ = dstpu.initialize(
                loss_fn=llama.loss_fn(cfg),
                params=jax.tree.map(jnp.copy, params), mesh=ms,
                param_specs=specs,
                config={"train_micro_batch_size_per_gpu": 8 // ms.dp_world,
                        "zero_optimization": {"stage": stage},
                        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                        "mesh": {k: v for k, v in mesh_sizes.items()}})
            return [float(engine.train_batch({"tokens": toks}))
                    for _ in range(3)]

        base = run({"data": 8}, None, 0)
        tp = run({"data": 4, "model": 2}, llama.param_specs(cfg), 3)
        np.testing.assert_allclose(tp, base, rtol=5e-3, atol=5e-3)

    @pytest.mark.parametrize("pol", ["full", "save_attn", "offload_attn"])
    def test_remat_matches(self, pol):
        """Every remat policy — including save_attn (checkpoint_name
        tags) and offload_attn (the reference's cpu_checkpointing:
        residuals parked in pinned_host between fwd and bwd) — computes
        the same grads as no remat."""
        cfg_a = llama.LlamaConfig.tiny()
        cfg_b = llama.LlamaConfig.tiny(remat=pol)
        params = llama.init_params(jax.random.PRNGKey(0), cfg_a)
        toks = _tokens(np.random.default_rng(0), 2, 16, cfg_a.vocab_size)
        f = lambda c: jax.jit(jax.grad(
            lambda p: jnp.sum(llama.forward(p, toks, c)[..., :8])))(params)
        ga, gb = f(cfg_a), f(cfg_b)
        for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_param_count_consistent(self):
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        assert actual == llama.param_count(cfg)


class TestGPT2:
    @pytest.mark.slow
    def test_forward_and_train(self, devices):
        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        toks = _tokens(np.random.default_rng(0), 4, 17, cfg.vocab_size)
        logits = gpt2.forward(params, toks, cfg)
        assert logits.shape == (4, 17, cfg.vocab_size)
        engine, _, _, _ = dstpu.initialize(
            loss_fn=gpt2.loss_fn(cfg), params=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "zero_optimization": {"stage": 1},
                    "optimizer": {"type": "adamw", "params": {"lr": 3e-3}}})
        toks = _tokens(np.random.default_rng(0), 16, 17, cfg.vocab_size)
        losses = [float(engine.train_batch({"tokens": toks})) for _ in range(8)]
        assert losses[-1] < losses[0]


class TestCNN:
    @pytest.mark.slow
    def test_cifar_train(self, devices):
        params = cnn.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"images": jnp.asarray(rng.normal(0, 1, (32, 32, 32, 3)),
                                       jnp.float32),
                 "labels": jnp.asarray(rng.integers(0, 10, (32,)), jnp.int32)}
        engine, _, _, _ = dstpu.initialize(
            loss_fn=cnn.loss_fn, params=params,
            config={"train_batch_size": 32,
                    "optimizer": {"type": "adam", "params": {"lr": 1e-3}}})
        losses = [float(engine.train_batch(batch)) for _ in range(10)]
        assert losses[-1] < losses[0]


# ------------------------------------------------ the decoder-family seam
FAMILIES = {
    "gpt2": (gpt2, gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                                        max_seq_len=64)),
    "llama": (llama, llama.LlamaConfig.tiny(dim=32, n_layers=1, n_heads=2,
                                            n_kv_heads=2)),
    "mixtral": (mixtral, mixtral.MixtralConfig.tiny(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, num_experts=4)),
}


@pytest.mark.parametrize("family", [*FAMILIES, "bert"])
def test_decoder_family_lookup(family):
    """A config resolves to its own file's record; anything else is a
    TypeError that names the supported types."""
    if family in FAMILIES:
        mod, cfg = FAMILIES[family]
        assert decoder_family(cfg) is mod.FAMILY
        assert mod.FAMILY.config_type is type(cfg)
        return
    with pytest.raises(TypeError) as e:
        decoder_family(bert.BertConfig.tiny())
    for _, cfg in FAMILIES.values():
        assert type(cfg).__name__ in str(e.value)


def test_family_seam_is_one_way():
    """``models/`` imports nothing from ``inference/``, and nothing
    outside ``models/family.py`` asks which decoder family a config is."""
    import ast
    import pathlib

    root = pathlib.Path(dstpu.__file__).parent
    upward, chains = [], []
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        for node in ast.walk(ast.parse(path.read_text())):
            names = []
            if isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            if rel.startswith("models/") and any(
                    n.startswith("deepspeed_tpu.inference") for n in names):
                upward.append(f"{rel}:{node.lineno}")
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "id", "") == "isinstance"
                    and rel != "models/family.py"
                    and any(getattr(n, "id", getattr(n, "attr", "")) in
                            ("GPT2Config", "LlamaConfig", "MixtralConfig")
                            for n in ast.walk(node.args[1]))):
                chains.append(f"{rel}:{node.lineno}")
    assert upward == [] and chains == []


@pytest.mark.parametrize("family", FAMILIES)
def test_registry_serves(family, devices):
    """Pin the dispatch itself: a config served through the one builder
    must produce ITS model's tokens (a mis-dispatch would KeyError or
    emit different tokens)."""
    from deepspeed_tpu.inference.generation import paged_generator
    from deepspeed_tpu.inference.serving import serving_engine

    mod, cfg = FAMILIES[family]
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    prompts = {"a": ([5, 9, 2], 6), "b": ([17, 3, 3, 8, 1], 5),
               "c": ([40, 2], 7)}
    eng = serving_engine(params, cfg, max_batch=2, page_size=8,
                         num_pages=32, max_seq=64, prefill_bucket=8)
    for rid, (p, n) in prompts.items():
        eng.submit(rid, p, max_new_tokens=n)
    outs = eng.run()
    oracle = paged_generator(params, cfg, page_size=8)
    for rid, (p, n) in prompts.items():
        want = oracle.generate(jnp.asarray([p], jnp.int32),
                               max_new_tokens=n)
        assert outs[rid] == [int(t) for t in np.asarray(want[0])], rid


def test_registry_refuses_unknown_config():
    from deepspeed_tpu.inference.serving import serving_engine

    with pytest.raises(TypeError, match="MixtralConfig"):
        serving_engine({}, object(), max_batch=1)


@pytest.mark.slow
def test_graft_entry(devices):
    sys_path_hack = __import__("sys").path
    if "/root/repo" not in sys_path_hack:
        sys_path_hack.insert(0, "/root/repo")
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(out)).all()
    ge.dryrun_multichip(8)


def test_loss_fn_packed_segments_match_manual():
    """loss_fn(batch with segment_ids) == hand-built packed loss: ids
    sliced to the input window, cross-document and padding targets
    masked out."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    B, T1 = 2, 33
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, T1)), jnp.int32)
    seg = jnp.asarray(
        np.stack([np.r_[[1] * 10, [2] * 15, [0] * 8],
                  np.r_[[1] * 20, [2] * 13]]), jnp.int32)

    got = llama.loss_fn(cfg)(params, {"tokens": toks, "segment_ids": seg})

    # manual oracle
    x = llama.forward(params, toks[:, :-1], cfg, segment_ids=seg[:, :-1])
    logp = jax.nn.log_softmax(x.astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, toks[:, 1:, None], -1)[..., 0]
    m = ((seg[:, :-1] == seg[:, 1:]) & (seg[:, :-1] > 0)).astype(jnp.float32)
    want = jnp.sum(nll * m) / jnp.sum(m)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
