"""Rank child for the multi-process integration tests.

Spawned by ``deepspeed_tpu.launcher --local_hosts 2 --platform cpu`` (one
process per simulated host, 4 virtual CPU devices each → an 8-device
global mesh across 2 processes, gloo collectives).  Each scenario runs
the SAME global batch on every process (the multi-controller SPMD
contract: identical call sequence, device_put slices out the local
shards) and rank 0 writes the observed losses/digests as JSON for the
parent test to compare against its single-process oracle.

Not a pytest file (no ``test_`` prefix — never collected).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the parent test pops XLA_FLAGS before spawning, so the lane flags are
# (re)applied here, pre-jax, from the same shared helper as conftest.py
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _xla_flags  # noqa: E402

_xla_flags.apply(device_count=4)

import jax

# pinned in code as well as by the parent's environment: this child
# must never reach for an accelerator the parent test process shares
jax.config.update("jax_platforms", "cpu")
# match conftest.py's RNG implementation: partitionable threefry is the
# default on newer JAX but opt-in on the pinned one, and it generates
# DIFFERENT values — a child on the legacy impl would init different
# params than the parent's single-process oracle and fail loss parity
# by bf16-visible margins
jax.config.update("jax_threefry_partitionable", True)
# uncached: conftest.py keeps a compile cache a PROCESS, and the ranks
# would share whatever directory the environment names
jax.config.update("jax_compilation_cache_dir", None)

import numpy as np  # noqa: E402

import jax.numpy as jnp  # noqa: E402


def build_batch(cfg, n):
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (n, 33))
    return {"tokens": jnp.asarray(toks, jnp.int32)}


def scenario_zero3(out):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                 n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng, _, _, _ = dstpu.initialize(
        loss_fn=llama.loss_fn(cfg), params=params,
        config={"train_batch_size": 8,
                "zero_optimization": {"stage": 3},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}})
    batch = build_batch(cfg, 8)
    losses = [float(eng.train_batch(batch)) for _ in range(3)]
    return {"losses": losses, "grad_norm": eng.get_global_grad_norm() and
            float(eng.get_global_grad_norm())}


def scenario_pstream(out):
    import tempfile

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                 n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)

    def build():
        eng, _, _, _ = dstpu.initialize(
            params=llama.layered_model(cfg, params),
            config={"train_batch_size": 8,
                    "zero_optimization": {
                        "stage": 3,
                        "offload_param": {"device": "cpu",
                                          "scheduled": True}},
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "bf16": {"enabled": True}})
        return eng

    eng = build()
    assert eng._pc == 2, f"expected 2 processes, got {eng._pc}"
    batch = build_batch(cfg, 8)
    losses = [float(eng.train_batch(batch)) for _ in range(3)]
    grad_norm = float(eng.get_global_grad_norm())   # step-3 norm
    # collective consolidation: every rank gets the FULL masters
    m = eng.master_params()
    digest = float(sum(np.abs(a).sum() for a in jax.tree.leaves(m)))
    # universal checkpoint across processes + restore
    ckdir = os.path.join(os.path.dirname(out), "mp_pstream_ck")
    eng.save_checkpoint(ckdir)
    e2 = build()
    e2.load_checkpoint(ckdir)
    l_next = float(eng.train_batch(batch))
    l_next2 = float(e2.train_batch(batch))
    return {"losses": losses, "digest": digest,
            "resume_match": abs(l_next - l_next2) < 1e-6,
            "grad_norm": grad_norm}


def scenario_infinity(out):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                 n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng, _, _, _ = dstpu.initialize(
        loss_fn=llama.loss_fn(cfg), params=params,
        config={"train_batch_size": 8,
                "zero_optimization": {
                    "stage": 3,
                    "offload_optimizer": {"device": "cpu",
                                          "scheduled": True}},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}})
    batch = build_batch(cfg, 8)
    losses = [float(eng.train_batch(batch)) for _ in range(2)]
    # the round-4 cross-host consolidation hole: master_params must now
    # gather the [dp, chunk] rows across both processes
    m = eng.master_params()
    digest = float(sum(np.abs(a).sum() for a in jax.tree.leaves(m)))
    return {"losses": losses, "digest": digest}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", required=True,
                    choices=["zero3", "pstream", "infinity"])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from deepspeed_tpu import comm

    comm.init_distributed()          # launcher env contract
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()

    result = {"zero3": scenario_zero3, "pstream": scenario_pstream,
              "infinity": scenario_infinity}[args.scenario](args.out)
    result["process_count"] = jax.process_count()
    if jax.process_index() == 0:
        with open(args.out, "w") as f:
            json.dump(result, f)
    # every rank reaches here or the launcher reports the failure
    print(f"rank {jax.process_index()} done", flush=True)


if __name__ == "__main__":
    main()
