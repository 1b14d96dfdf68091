"""The training runner: ``train_stream``.  Optimizer steps through
``deepspeed_tpu.initialize`` on a seeded corpus, one step kept in flight
so that the device never waits for the host's stamp."""

import gc
import math

import numpy as np

from benchmark.harness import clock, device, generator
from benchmark.harness.profiler import TailTrace


def build_engine(run):
    import jax

    import deepspeed_tpu as dstpu
    from deepspeed_tpu import zero
    from deepspeed_tpu.topology import default_mesh

    mix = run.traffic
    cfg = run.program_config(remat=mix["remat"])
    run.lap("imports")
    # the f32 master weights, made in one jit from the key directly in
    # the layout ZeRO will keep them in: never whole on one chip, and
    # the same program for every seed
    mesh = default_mesh(run.chips)
    init = lambda key: run.family.init_params(cfg, key, "float32")
    key = jax.random.PRNGKey(run.seed32)
    layout = zero.param_shardings(
        jax.eval_shape(init, key), mesh,
        mix["engine"]["zero_optimization"]["stage"])
    params = jax.jit(init, out_shardings=layout)(key)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=run.family.loss_fn(cfg), params=params, mesh=mesh,
        config=dict(mix["engine"], train_micro_batch_size_per_gpu=mix[
            "micro_batch_per_chip"]))
    del params
    run.lap("engine")
    return engine, cfg


def run_training(run):
    import jax
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    mix = run.traffic
    engine, cfg = build_engine(run)
    seq = mix["sequence_tokens"]
    per_step = mix["micro_batch_per_chip"] * run.chips
    data = generator.corpus(run.seed, mix["corpus_sequences"], seq + 1,
                            cfg.vocab_size)
    rows = np.arange(per_step)

    def batch(i):
        with TraceAnnotation("bench/next_batch"):
            return {"tokens": data[(rows + i * per_step) % len(data)]}

    def step(i):
        with StepTraceAnnotation("bench/train_batch", step_num=i):
            return engine.train_batch(batch(i))

    losses = [step(i) for i in range(mix["warm_steps"])]   # compiles
    jax.block_until_ready(losses)
    gc.collect()
    gc.freeze()
    gc.disable()
    compiles0 = run.compiles.programs_built()
    t_open = clock.now()
    setup_s = t_open - run.t_process_start
    run.lap("warm_steps", t_open)
    cache_hits = run.compiles.hits
    tracer = TailTrace(run.trace, run.trace_dir,
                       t_open + run.seconds - mix["trace_seconds"])
    n0 = i = len(losses)
    t = t_open
    while t < t_open + run.seconds:
        tracer.maybe_start(t)
        losses.append(step(i))
        i += 1
        if i - n0 >= 2:                    # one step stays in flight
            jax.block_until_ready(losses[-2])
            t = clock.now()
    jax.block_until_ready(losses[-1])
    t_end = clock.now()
    steps = i - n0
    compiles = run.compiles.programs_built() - compiles0
    traced = tracer.stop()
    gc.unfreeze()
    gc.enable()
    memory = device.memory(run.chips)
    scratch = program_scratch_bytes(engine, batch(0))

    values = [float(x) for x in losses]
    problems = []
    if not all(math.isfinite(x) for x in values):
        problems.append(f"loss not finite: {values[:8]}...")
    elif not values[-1] < values[n0]:
        problems.append(f"loss did not fall over the window: "
                        f"{values[n0]} -> {values[-1]}")
    if compiles:
        problems.append(f"{compiles} programs were built inside the window")
    probe = check_probe(run, engine, cfg, seq)
    problems += probe.pop("problems")
    window = {
        "kind": "train", "t_open": t_open, "t_end": t_end,
        "setup_s": setup_s, "setup_laps": run.laps,
        "compile_cache_hits": cache_hits, "steps": steps,
        "tokens_per_step": per_step * seq, "sequence_tokens": seq,
        "losses": values[n0:], "compiles_steady": compiles,
        "memory": memory, "program_scratch": scratch,
        "program_config": cfg, "probe": probe,
    }
    return {"window": window, "trace": traced, "attempted": steps,
            "failed": 0, "problems": problems}


def program_scratch_bytes(engine, batch):
    """The temporaries of the compiled step, per chip, as the program
    itself reports them (``lower_step`` is the engine's own door for
    that).  The runtime reserves a program's scratch apart from the
    allocator that ``memory_stats()`` counts, so the peak that counts
    live arrays alone (3.7 GiB here: the ZeRO shard of the state) leaves
    out most of what a step holds."""
    try:
        analysis = engine.lower_step(batch).compile().memory_analysis()
        return int(analysis.temp_size_in_bytes)
    except (NotImplementedError, AttributeError, RuntimeError):
        # no analysis on this backend: the note line says so
        return None


def check_probe(run, engine, cfg, seq):
    """``eval_batch`` on a seeded probe batch against the plain float32
    loss at the parameters gathered now.  The bound is 2^-7 relative:
    two bf16 ulps, what PR 21 measured ZeRO-3 on four chips to keep
    against ZeRO-0 on one (5.1e-4); a wrong shard or a lost
    contribution moves the loss by far more."""
    import jax.numpy as jnp

    n = max(2, run.chips)          # the batch axis is split over chips
    tokens = generator.rng_for(run.seed, 5).integers(
        0, cfg.vocab_size, (n, seq + 1), dtype=np.int32)
    got = float(engine.eval_batch({"tokens": tokens}))
    want = float(run.family.reference_loss(cfg)(
        engine.module_params(), jnp.asarray(tokens)))
    tol = 2.0 ** -7
    rel = abs(got - want) / abs(want)
    problems = [] if rel <= tol else [
        f"eval_batch {got} against the reference's {want}: {rel:.2e} "
        f"relative, over {tol:.2e}"]
    return {"eval_batch": got, "reference": want, "relative": rel,
            "tolerance": tol, "problems": problems}
