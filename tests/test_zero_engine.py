"""ZeRO stage equivalence + engine behavior (SURVEY.md §4).

The load-bearing property: stages 0/1/2/3 on an 8-way mesh produce the
same training trajectory as each other (and sensible loss decrease),
because ZeRO on TPU is purely a layout change.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.topology import MeshSpec


def _make_params(rng, din=16, dh=32, dout=4):
    return {
        "w1": jnp.asarray(rng.normal(0, 0.1, (din, dh)), jnp.float32),
        "b1": jnp.zeros((dh,), jnp.float32),
        "w2": jnp.asarray(rng.normal(0, 0.1, (dh, dout)), jnp.float32),
        "b2": jnp.zeros((dout,), jnp.float32),
    }


def _loss_fn(params, batch):
    x, y = batch["x"], batch["y"]
    h = jnp.tanh(x @ params["w1"].astype(x.dtype) + params["b1"].astype(x.dtype))
    logits = h @ params["w2"].astype(x.dtype) + params["b2"].astype(x.dtype)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _data(rng, n=32, din=16, dout=4):
    return {"x": jnp.asarray(rng.normal(0, 1, (n, din)), jnp.float32),
            "y": jnp.asarray(rng.integers(0, dout, (n,)), jnp.int32)}


def _train(stage, rng_seed=0, steps=5, accum=1, dtype_block=None, clip=0.0):
    rng = np.random.default_rng(rng_seed)
    params = _make_params(rng)
    cfg = {
        "train_batch_size": 32,
        "gradient_accumulation_steps": accum,
        "zero_optimization": {"stage": stage},
        "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
        "gradient_clipping": clip,
    }
    if dtype_block:
        cfg.update(dtype_block)
    engine, _, _, _ = dstpu.initialize(loss_fn=_loss_fn, params=params,
                                       config=cfg)
    batch = _data(np.random.default_rng(123))  # fixed batch → loss must drop
    losses = []
    for _ in range(steps):
        losses.append(float(engine.train_batch(batch)))
    return losses, engine


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_match_each_other(stage, devices):
    base, _ = _train(0)
    got, engine = _train(stage)
    np.testing.assert_allclose(got, base, rtol=2e-3, atol=2e-3)
    assert got[-1] < got[0], "loss should decrease"
    # verify the layout really is partitioned for stage>=1
    if stage >= 1:
        m = jax.tree.leaves(engine.state.opt_state.mu)[0]
        assert not m.sharding.is_fully_replicated
    if stage >= 3:
        p = engine.state.params["w1"]
        assert not p.sharding.is_fully_replicated


def test_grad_accumulation_matches(devices):
    base, _ = _train(0, accum=1)
    got, _ = _train(2, accum=4)
    np.testing.assert_allclose(got, base, rtol=2e-3, atol=2e-3)


def test_gradient_clipping_runs(devices):
    losses, engine = _train(2, clip=0.1)
    assert np.isfinite(losses).all()
    assert engine.get_global_grad_norm() >= 0


def test_fp16_loss_scaling(devices):
    losses, engine = _train(
        2, dtype_block={"fp16": {"enabled": True, "initial_scale_power": 4}})
    assert np.isfinite(losses).all()
    assert float(engine.metrics["loss_scale"]) >= 1.0
    assert losses[-1] < losses[0]


def test_torch_idiom_compat(devices):
    rng = np.random.default_rng(0)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=_loss_fn, params=_make_params(rng),
        config={"train_batch_size": 32, "zero_optimization": {"stage": 2}})
    batch = _data(np.random.default_rng(1))
    loss = engine(batch)
    engine.backward(loss)
    engine.step()
    assert engine.global_steps == 1
    with pytest.raises(RuntimeError):
        engine.step()


def test_unshard_params(devices):
    _, engine = _train(3, steps=1)
    full = engine.module_params()
    for leaf in jax.tree.leaves(full):
        assert leaf.sharding.is_fully_replicated


def test_tp_base_spec(devices):
    """ZeRO-3 layered on top of a tensor-parallel base sharding."""
    from jax.sharding import PartitionSpec as P

    ms = MeshSpec.build({"data": 4, "model": 2})
    rng = np.random.default_rng(0)
    params = _make_params(rng)

    def base_spec(leaf):
        if leaf.ndim == 2:
            return P(None, "model")
        return P()

    engine, _, _, _ = dstpu.initialize(
        loss_fn=_loss_fn, params=params,
        config={"train_batch_size": 32, "zero_optimization": {"stage": 3},
                "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
                "mesh": {"data": 4, "model": 2}},
        mesh=ms, param_specs=base_spec)
    base, _ = _train(0)
    batch = _data(np.random.default_rng(123))
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    np.testing.assert_allclose(losses, base, rtol=2e-3, atol=2e-3)


def test_sharded_init_thunk(devices):
    """zero.Init parity: initialize() with a callable params thunk
    materializes state directly into ZeRO shardings and trains the same
    trajectory as eagerly-built params (ref:
    deepspeed/runtime/zero/partition_parameters.py Init)."""
    def make():
        k = jax.random.PRNGKey(7)
        ks = jax.random.split(k, 2)
        return {
            "w1": jax.random.normal(ks[0], (16, 32), jnp.float32) * 0.1,
            "b1": jnp.zeros((32,), jnp.float32),
            "w2": jax.random.normal(ks[1], (32, 4), jnp.float32) * 0.1,
            "b2": jnp.zeros((4,), jnp.float32),
        }

    cfg = {"train_batch_size": 32,
           "zero_optimization": {"stage": 3},
           "optimizer": {"type": "adam", "params": {"lr": 1e-2}}}
    batch = _data(np.random.default_rng(123))

    eng_thunk, _, _, _ = dstpu.initialize(loss_fn=_loss_fn, params=make,
                                          config=dict(cfg))
    # params landed partitioned, equal to the eager tree
    p = eng_thunk.state.params["w1"]
    assert not p.sharding.is_fully_replicated
    np.testing.assert_allclose(np.asarray(p), np.asarray(make()["w1"]),
                               rtol=1e-6, atol=1e-6)

    eng_eager, _, _, _ = dstpu.initialize(loss_fn=_loss_fn, params=make(),
                                          config=dict(cfg))
    lt = [float(eng_thunk.train_batch(batch)) for _ in range(4)]
    le = [float(eng_eager.train_batch(batch)) for _ in range(4)]
    np.testing.assert_allclose(lt, le, rtol=1e-5, atol=1e-5)


def test_sharded_init_helper(devices):
    """Standalone zero.sharded_init: sharded materialization, exact values."""
    from deepspeed_tpu import zero as z

    ms = MeshSpec.build({"data": 8})
    make = lambda: {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 8))}
    got = z.sharded_init(make, ms, stage=3)
    assert not got["w"].sharding.is_fully_replicated
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(make()["w"]),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# ZeRO-3's two statements about a forward pass (zero.gather_at_use,
# zero.pin_to_batch): a layer's weights are gathered where they are used,
# the activations stay on the batch axes.
# ---------------------------------------------------------------------------
T_ACT = 24        # no parameter of a tiny model has a dimension of 24


def _family(name):
    """(cfg, params, loss_fn, has_aux, param_specs) of a tiny model."""
    from deepspeed_tpu.models import gpt2, llama, mixtral

    mod, cfg = {
        "gpt2": (gpt2, gpt2.GPT2Config.tiny(remat="save_dots")),
        "llama": (llama, llama.LlamaConfig.tiny(remat="save_dots")),
        "mixtral": (mixtral, mixtral.MixtralConfig.tiny(remat="save_dots")),
    }[name]
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    return (cfg, params, mod.loss_fn(cfg), name == "mixtral",
            mod.param_specs(cfg))


def _model_engine(name, stage, mesh, specs=False):
    cfg, params, loss_fn, has_aux, param_specs = _family(name)
    n = int(np.prod(list(mesh.values())))
    ms = MeshSpec.build(mesh, devices=jax.devices()[:n])
    config = {"train_batch_size": 8,
              "zero_optimization": {"stage": stage},
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
              "bf16": {"enabled": True}}
    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params, mesh=ms, has_aux=has_aux,
        param_specs=param_specs if specs else None, config=config)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, T_ACT + 1), dtype=np.int32)
    return engine, {"tokens": tokens}, params, cfg


def _collectives(hlo):
    """(kind, [dims of each result], op_name) of every collective line of
    an HLO text."""
    from deepspeed_tpu.comm.digest import _INSTR, _SHAPE

    out = []
    for line in hlo.splitlines():
        m = _INSTR.search(line)
        if m is None:
            continue
        dims = [tuple(int(d) for d in s.split(",") if d)
                for _, s in _SHAPE.findall(m.group(1))]
        name = re.search(r'op_name="([^"]*)"', line)
        out.append((m.group(2).replace("-start", ""), dims,
                    name.group(1) if name else ""))
    return out


def _embedding_backward(op_name):
    """The one place the compiler may still move an activation: the
    gradient of the embedding lookup.  Its table's gradient is stored by
    columns, so GSPMD hands each chip every row's slice of columns (an
    activation's bytes) and lets it add its own columns up, instead of
    reducing a table-sized partial sum: the cheaper of the two, at the
    benchmark's widths by 12x (16 MB against 206)."""
    return re.search(r"transpose\(jvp\(.*\bembed\b", op_name) is not None


@pytest.mark.parametrize("family", ["gpt2", "llama", "mixtral"])
def test_stage3_gathers_weights_and_leaves_activations(family, devices):
    """The compiled stage-3 step on a 4-way data mesh: no all-to-all, no
    collective whose operand is an activation, every parameter gathered
    about twice (forward, and the forward ``save_dots`` recomputes) and
    every gradient reduced once; the trajectory is stage 0's on one
    device."""
    engine, batch, params, cfg = _model_engine(family, 3, {"data": 4})
    digest = engine.comms_digest(batch)["per_kind"]
    assert "all-to-all" not in digest, digest
    ops = _collectives(engine.lower_step(batch).compile().as_text())
    moved = [(k, d, name) for k, dims, name in ops for d in dims
             if T_ACT in d and d[0] != T_ACT        # [T, d]: wpe's rows
             and not _embedding_backward(name)]
    assert moved == [], f"collectives on activations: {moved}"

    # the text names a loop body's collectives once: a layer is one slice
    # of the blocks, gathered in the forward loop and again in the backward
    # loop (``save_dots`` recomputes the forward there); what stands beside
    # the blocks is gathered once or twice.  A gather's result is the whole
    # leaf, of which 3/4 travels.  The slack is for what the partitioner
    # gathers beside parameters (a few rows).
    size = lambda tree: sum(int(np.prod(p.shape))
                            for p in jax.tree.leaves(tree))
    layer = size(params["blocks"]) // cfg.n_layers
    beside = size(params) - size(params["blocks"])
    slack = 0.05 * (layer + beside)
    gathered = sum(int(np.prod(d)) for k, dims, name in ops for d in dims
                   if k == "all-gather" and not _embedding_backward(name))
    assert 2 * layer + beside - slack <= gathered \
        <= 2 * (layer + beside) + slack, (gathered, layer, beside)
    # the CPU compiler spells a reduce-scatter all-reduce + dynamic-slice
    # (tests/test_aot_tpu_compile.py reads the TPU's program): each
    # gradient is reduced once, as itself.  (The capacity dispatch of a
    # sparse layer sums its expert buffers over the batch's chips: the
    # layer's own collective, and no parameter's.)
    reduced = sum(int(np.prod(d)) for k, dims, name in ops for d in dims
                  if k in ("all-reduce", "reduce-scatter")
                  and "/moe_ffn/" not in name)
    assert reduced <= layer + beside + slack, (reduced, layer, beside)

    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    base, batch0, _, _ = _model_engine(family, 0, {"data": 1})
    want = [float(base.train_batch(batch0)) for _ in range(4)]
    np.testing.assert_allclose(losses, want, rtol=2e-3, atol=2e-3)
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("case", ["stage0", "stage1", "stage2", "one_device"])
@pytest.mark.parametrize("family", ["gpt2", "llama", "mixtral"])
def test_helpers_leave_every_other_program_as_it_was(family, case, devices,
                                                     monkeypatch):
    """Below stage 3, and on one device, a model lowers to the program it
    lowered to before the helpers existed: the text with the helpers is
    the text with them patched to the identity."""
    from deepspeed_tpu import zero

    stage, mesh = {"stage0": (0, {"data": 4}), "stage1": (1, {"data": 4}),
                   "stage2": (2, {"data": 4}),
                   "one_device": (3, {"data": 1})}[case]
    engine, batch, _, _ = _model_engine(family, stage, mesh)
    with_helpers = engine.lower_step(batch).as_text()
    monkeypatch.setattr(zero, "gather_at_use", lambda tree, *a, **k: tree)
    monkeypatch.setattr(zero, "pin_to_batch", lambda x: x)
    engine, batch, _, _ = _model_engine(family, stage, mesh)
    assert engine.lower_step(batch).as_text() == with_helpers


def test_helpers_are_inert_inside_local_grad_shardmap(devices, monkeypatch):
    """Inside the ``shard_map`` of the compressed-gradient paths the data
    axis is the body's own: with stage 3 published the helpers still
    return their argument (a constraint that named the axis would not
    trace)."""
    from deepspeed_tpu import comm_compress, topology, zero

    cfg, params, loss_fn, _, _ = _family("gpt2")
    ms = MeshSpec.build({"data": 4}, devices=jax.devices()[:4])
    monkeypatch.setattr(topology, "_CURRENT_MESH", ms)
    monkeypatch.setattr(topology, "_CURRENT_ZERO_STAGE", 3)
    seen, real = [], zero._mesh_at_use
    monkeypatch.setattr(zero, "_mesh_at_use",
                        lambda: seen.append(real()) or seen[-1])
    tokens = jnp.zeros((8, T_ACT + 1), jnp.int32)

    def local_gf(p, mb):
        loss, g = jax.value_and_grad(loss_fn)(p, mb)
        return g, loss

    f = comm_compress.local_grad_shardmap(local_gf, ms, 1)
    jax.jit(f).lower(params, {"tokens": tokens})
    assert seen and all(m is None for m in seen)
    # and outside it, under the same publication, they are live
    seen.clear()
    jax.jit(loss_fn).lower(params, {"tokens": tokens})
    assert seen and all(m is ms for m in seen)


def test_gathered_layer_keeps_its_model_axis(devices, monkeypatch):
    """data 2 x model 2: what is gathered over ``data`` stays split over
    ``model`` (test_tp_base_spec's ground), and the trajectory is stage
    0's."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu import zero

    seen, real = [], zero._gather
    monkeypatch.setattr(
        zero, "_gather",
        lambda x, sh: seen.append((x.shape, sh.spec)) or real(x, sh))
    engine, batch, _, cfg = _model_engine(
        "gpt2", 3, {"data": 2, "model": 2}, specs=True)
    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    d = cfg.dim
    assert ((d, 4 * d), P(None, "model")) in seen      # fc_w, by columns
    assert ((4 * d, d), P("model", None)) in seen      # out_w, by rows
    assert ((cfg.vocab_size, d), P(None, "model")) in seen
    assert all("data" not in str(spec) for _, spec in seen)
    base, batch0, _, _ = _model_engine("gpt2", 0, {"data": 1})
    want = [float(base.train_batch(batch0)) for _ in range(4)]
    np.testing.assert_allclose(losses, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("family", ["gpt2", "llama", "mixtral"])
def test_a_bare_forward_after_a_stage3_engine_meets_no_stage(devices,
                                                             family):
    """The ZeRO stage is a fact of one engine's own trace (ROADMAP D10
    (a)): once a stage-3 engine over the 8-way mesh has stepped and
    evaluated, a model's forward outside any engine, at a batch the
    data axis does not divide, runs as if none had been built (it was a
    ``ValueError`` in ``pin_to_batch`` while the stage outlived the
    trace); the mesh stays published for the readers that had it."""
    from deepspeed_tpu import topology
    from deepspeed_tpu.models import gpt2, llama, mixtral

    engine, batch, params, cfg = _model_engine(family, 3, {"data": 8})
    assert topology.current_zero_stage() == 0       # built, not traced
    engine.train_batch(batch)
    assert topology.current_zero_stage() == 0
    engine.eval_batch(batch)
    assert topology.current_zero_stage() == 0
    assert topology.current_mesh() is engine.mesh
    forward = {"gpt2": gpt2.forward, "llama": llama.forward,
               "mixtral": mixtral.forward}[family]
    tokens = jnp.zeros((1, T_ACT), jnp.int32)       # 1 row over data = 8
    out = forward(params, tokens, cfg)
    logits = out[0] if isinstance(out, tuple) else out
    assert logits.shape == (1, T_ACT, cfg.vocab_size)
