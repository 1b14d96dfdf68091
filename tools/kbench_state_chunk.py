#!/usr/bin/env python3
"""A prompt chunk's gated delta rule alone, on the chip: ms a layer of
``qwen3_next.gdn_chunk_rule`` (XLA), of that rule with one of its pieces
left out (timing only: what each piece costs), and of the Mosaic kernel
``dstpu_state_chunk`` under ``qwen3_next.gdn_block_rule``, at a named
cell's shape, the layers scanned in one jit.  PERF.md 6, PR 50 holds the
table this prints; run it again after touching either.

    chiprun -- python tools/kbench_state_chunk.py [--cell docqa-sat]
        [--only rule|kernel] [--out chiprun_out/kbench_state_chunk.json]

A layer's time is the slope between a scan over ``--layers`` layers and
one over twice as many (the call's own ~1 ms drops out), the least of
``--reps`` timings of each.
"""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.inference import kernels as K  # noqa: E402
from deepspeed_tpu.models import qwen3_next as qn  # noqa: E402
from deepspeed_tpu.models.family import SlotState  # noqa: E402

# cell -> (tokens a chunk, key heads, value heads, Dk, Dv, block)
CELLS = {"docqa-sat": (1024, 16, 32, 128, 128, 64),
         "toy": (64, 2, 4, 16, 16, 8)}


def inputs(layers, T, Hk, Hv, Dk, Dv, seed=0, masked_from=None):
    """A layer's operands as ``gdn_mix`` hands them over, ``layers`` deep:
    q, k [L, 1, T, Hk, Dk] normed, v [L, 1, T, Hv, Dv], g, beta [L, 1, T,
    Hv], S [L, 1, Hv, Dk, Dv]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda t: t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    q = l2(jax.random.normal(ks[0], (layers, 1, T, Hk, Dk))) * Dk ** -0.5
    k = l2(jax.random.normal(ks[1], (layers, 1, T, Hk, Dk)))
    v = jax.random.normal(ks[2], (layers, 1, T, Hv, Dv))
    g = -jnp.exp(jax.random.normal(ks[3], (layers, 1, T, Hv)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (layers, 1, T, Hv)))
    if masked_from is not None:
        real = (jnp.arange(T) < masked_from)[None, None, :, None]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    S = jax.random.normal(ks[5], (layers, 1, Hv, Dk, Dv))
    return q, k, v, g, beta, S


def rule_variant(q, k, v, g, beta, S, block, *, precision="highest",
                 doubling=True, rolled=False):
    """``gdn_chunk_rule`` as written, with switches that leave a piece
    out: FOR TIMING ONLY (without the doubling the numbers are wrong)."""
    mm = functools.partial(jnp.einsum, precision=precision)
    B, T, H, _ = q.shape
    N, C = T // block, block
    blk = lambda a: jnp.moveaxis(
        a.reshape((B, N, C) + a.shape[2:]), (1, 2), (0, 3))
    q, k, v = blk(q), blk(k), blk(v)
    g, beta = blk(g[..., None])[..., 0], blk(beta[..., None])[..., 0]
    c = jnp.cumsum(g, axis=-1)
    i, j = np.arange(C)[:, None], np.arange(C)[None]
    decay = jnp.exp(jnp.where(i >= j, c[..., :, None] - c[..., None, :],
                              -jnp.inf))
    kb = k * beta[..., None]
    X = -jnp.where(i > j, mm("nbhid,nbhjd->nbhij", kb, k) * decay, 0.0)
    inv = jnp.eye(C, dtype=X.dtype) + X
    for _ in range(max(0, (C - 1).bit_length() - 1) if doubling else 0):
        X = mm("nbhij,nbhjk->nbhik", X, X)
        inv = inv + mm("nbhij,nbhjk->nbhik", inv, X)
    value = mm("nbhij,nbhjd->nbhid", inv, v * beta[..., None])
    k_cum = mm("nbhij,nbhjd->nbhid", inv, kb * jnp.exp(c)[..., None])
    qk = mm("nbhid,nbhjd->nbhij", q, k) * decay
    q_in = q * jnp.exp(c)[..., None]
    k_out = k * jnp.exp(c[..., -1:] - c)[..., None]
    last = jnp.exp(c[..., -1])[..., None, None]

    def one(S, b):
        value, k_cum, qk, q_in, k_out, last = b
        u = value - mm("bhik,bhkd->bhid", k_cum, S)
        o = mm("bhik,bhkd->bhid", q_in, S) + mm("bhij,bhjd->bhid", qk, u)
        return last * S + mm("bhik,bhid->bhkd", k_out, u), o

    S, o = jax.lax.scan(one, S, (value, k_cum, qk, q_in, k_out, last),
                        unroll=1 if rolled else True)
    return jnp.moveaxis(o, (0, 3), (1, 2)).reshape(B, N * C, H, -1), S


def scanned(layer_fn):
    """One jit: ``layer_fn`` over the layers, the state donated."""
    def run(q, k, v, g, beta, S):
        def body(_, xs):
            o, S = layer_fn(*xs)
            return None, (o, S)

        return jax.lax.scan(body, None, (q, k, v, g, beta, S))[1]

    return jax.jit(run, donate_argnums=(5,))


def ms_a_layer(layer_fn, shape, layers, reps):
    took = {}
    for n in (layers, 2 * layers):
        fn, best = scanned(layer_fn), float("inf")
        for rep in range(reps + 1):             # the first call compiles
            args = inputs(n, *shape[:5], seed=rep)
            jax.block_until_ready(args)
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            if rep:
                best = min(best, time.perf_counter() - t)
        took[n] = best
    return 1e3 * (took[2 * layers] - took[layers]) / layers, took


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="docqa-sat", choices=sorted(CELLS))
    ap.add_argument("--layers", type=int, default=9)
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--only", choices=("rule", "kernel"))
    ap.add_argument("--interpret", action="store_true",
                    help="the kernel in interpret mode (the CPU; toy cell)")
    ap.add_argument("--out", default="chiprun_out/kbench_state_chunk.json")
    args = ap.parse_args()
    shape = CELLS[args.cell]
    T, Hk, Hv, Dk, Dv, block = shape
    rep = Hv // Hk
    repeated = lambda f: lambda q, k, v, g, beta, S: f(
        jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), v, g, beta,
        S, block)
    as_one = lambda f: lambda q, k, v, g, beta, S: f(
        q[:, :, :1].repeat(Hv, 2), k[:, :, :1].repeat(Hv, 2), v, g, beta, S,
        block)
    rules = {
        "rule (gdn_chunk_rule as gdn_mix calls it)": repeated(
            qn.gdn_chunk_rule),
        "rule, HIGH (3 passes)": repeated(functools.partial(
            rule_variant, precision="high")),
        "rule, DEFAULT (1 pass)": repeated(functools.partial(
            rule_variant, precision="default")),
        "rule, no doubling": repeated(functools.partial(
            rule_variant, doubling=False)),
        "rule, no doubling, DEFAULT": repeated(functools.partial(
            rule_variant, doubling=False, precision="default")),
        "rule, scan rolled": repeated(functools.partial(
            rule_variant, rolled=True)),
        "rule, q and k broadcast from one head (no 16->32 repeat)": as_one(
            qn.gdn_chunk_rule),
    }

    def kernel(block, heads, span):
        chunk = functools.partial(K.state_chunk, heads=heads, span=span,
                                  interpret=args.interpret)
        return lambda q, k, v, g, beta, S: qn.gdn_chunk_kernel(
            q, k, v, g, beta, SlotState(S, chunk), block)

    # (block, heads a grid step, tokens a grid step); None: from the shapes
    variants = [(block, None, None)] + [
        v for v in ((64, 1, None), (64, 2, None), (64, 4, None),
                    (64, 16, None), (64, 8, 64), (64, 8, 1024),
                    (128, 2, None), (128, 4, None), (128, 8, None),
                    (32, 8, None))
        if T % v[0] == 0 and (v[2] is None or T % v[2] == 0)]
    kernels = {f"kernel block={b} heads={h} span={s}": kernel(b, h, s)
               for b, h, s in (variants if args.cell != "toy"
                               else [(block, None, None)])}
    rows = {}
    todo = {**({} if args.only == "kernel" else rules),
            **({} if args.only == "rule" else kernels)}
    for name, fn in todo.items():
        try:
            ms, took = ms_a_layer(fn, shape, args.layers, args.reps)
            rows[name] = {"ms_a_layer": ms, "calls_s": took}
        except Exception as e:                  # a variant Mosaic refuses
            rows[name] = {"error": repr(e)[:400]}
        print(json.dumps({name: rows[name]}), flush=True)
    # what the kernel computes, against the rule at HIGHEST
    q, k, v, g, beta, S = (a[0] for a in inputs(1, *shape[:5], seed=7,
                                                 masked_from=T - T // 8))
    want_o, want_S = jax.jit(repeated(qn.gdn_chunk_rule))(q, k, v, g, beta, S)
    got_o, got_S = jax.jit(kernel(block, None, None))(q, k, v, g, beta, S)
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    rows["kernel against the rule"] = {
        "o_rel": rel(got_o, want_o), "S_rel": rel(got_S, want_S),
        "o_max_abs": float(jnp.abs(got_o - want_o).max()),
        "S_max_abs": float(jnp.abs(got_S - want_S).max())}
    print(json.dumps({"kernel against the rule":
                      rows["kernel against the rule"]}), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"cell": args.cell, "device": str(jax.devices()[0]),
                   "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
