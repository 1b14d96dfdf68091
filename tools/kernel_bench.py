#!/usr/bin/env python
"""Kernel microbenchmarks on the local chip (round-2 verdict task 2):
prove each Pallas kernel WINS against the XLA-lowered reference at
training shapes — or demote it with data.

  1. flash attention fwd and fwd+bwd vs XLA reference attention
  2. Pallas fused Adam single-pass update vs XLA-fused (jit) Adam math
  3. Pallas paged decode attention vs the gather-based reference
  4. flash block-size sweep feeding _pick_blocks

Writes KERNEL_BENCH.json.  Timing goes through a value fetch (on the
v5e it agrees with block_until_ready — chip_smoke.py, PR 21); the host
dispatch loop serializes on-device, so (sum of N dispatches)/N is honest
kernel time.

    python tools/kernel_bench.py            # real chip
    python tools/kernel_bench.py --quick    # fewer shapes/iters
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops import attention_pallas
from deepspeed_tpu.ops.adam_pallas import adam_update_flat
from deepspeed_tpu.inference.kernels import (paged_attention_reference,
                                             paged_decode_attention)


def _sync(o):
    leaves = jax.tree.leaves(o)
    return float(jnp.sum(leaves[0].astype(jnp.float32)))


def bench(fn, *args, iters=20):
    o = fn(*args)
    _sync(o)                       # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        o = fn(*args)
    _sync(o)                       # in-order execution: fences them all
    return (time.perf_counter() - t0) / iters


def xla_ref_attention(q, k, v, causal=True):
    """Plain-XLA attention, the fusion baseline the flash kernel races."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qh = q.reshape(B, T, KV, G, D)
    s = jnp.einsum("btkgd,bskd->bkgts", qh.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool))
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return o.reshape(B, T, H, D).astype(q.dtype)


def attn_inputs(B, T, H, D, KV, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, KV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, KV, D), jnp.bfloat16)
    return q, k, v


def flash_vs_ref(shapes, iters):
    rows = []
    for (B, T, H, D, KV) in shapes:
        q, k, v = attn_inputs(B, T, H, D, KV)
        flops_fwd = 4 * B * H * T * T * D * 0.5      # causal half
        flash_f = jax.jit(lambda q, k, v: attention_pallas
                          .flash_attention_tpu(q, k, v, causal=True))
        ref_f = jax.jit(lambda q, k, v: xla_ref_attention(q, k, v))

        def grad_of(f):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2)))

        row = {"shape": {"B": B, "T": T, "H": H, "D": D, "KV": KV}}
        tf = bench(flash_f, q, k, v, iters=iters)
        tr = bench(ref_f, q, k, v, iters=iters)
        row["fwd"] = {
            "flash_ms": round(1e3 * tf, 3), "xla_ms": round(1e3 * tr, 3),
            "flash_tflops": round(flops_fwd / tf / 1e12, 2),
            "speedup": round(tr / tf, 2)}
        tfb = bench(grad_of(flash_f), q, k, v, iters=max(iters // 2, 3))
        trb = bench(grad_of(ref_f), q, k, v, iters=max(iters // 2, 3))
        row["fwd_bwd"] = {
            "flash_ms": round(1e3 * tfb, 3), "xla_ms": round(1e3 * trb, 3),
            "flash_tflops": round(3.5 * flops_fwd / tfb / 1e12, 2),
            "speedup": round(trb / tfb, 2)}
        rows.append(row)
        print("flash", row)
    return rows


def adam_vs_xla(sizes, iters):
    # the A/B must measure the REAL kernel at every size: below the
    # measured crossover adam_update_flat now demotes itself to XLA
    # (ops/adam_pallas.pallas_adam_gate), which would make the sweep
    # silently compare XLA against XLA
    os.environ["DSTPU_FORCE_ADAM_PALLAS"] = "1"
    rows = []
    for n in sizes:
        k = jax.random.PRNGKey(0)
        g = jax.random.normal(k, (n,), jnp.bfloat16)
        m = jnp.zeros((n,), jnp.float32)
        v = jnp.ones((n,), jnp.float32) * 1e-4
        p = jax.random.normal(k, (n,), jnp.bfloat16)
        step = jnp.int32(10)

        pallas_f = jax.jit(lambda g, m, v, p, s: adam_update_flat(
            g, m, v, p, s, 1e-3))

        @jax.jit
        def xla_f(g, m, v, p, s):
            gf = g.astype(jnp.float32)
            t = s.astype(jnp.float32) + 1.0
            mn = 0.9 * m + 0.1 * gf
            vn = 0.999 * v + 0.001 * gf * gf
            c1 = 1.0 / (1.0 - 0.9 ** t)
            c2 = 1.0 / (1.0 - 0.999 ** t)
            u = -1e-3 * (mn * c1) / (jnp.sqrt(vn * c2) + 1e-8)
            return u, mn, vn

        tp = bench(pallas_f, g, m, v, p, step, iters=iters)
        tx = bench(xla_f, g, m, v, p, step, iters=iters)
        bytes_touched = n * (2 + 4 + 4 + 2 + 4 + 4 + 4)  # r:g,m,v,p w:u,m,v
        rows.append({
            "n_params": n,
            "pallas_ms": round(1e3 * tp, 3), "xla_ms": round(1e3 * tx, 3),
            "pallas_gbps": round(bytes_touched / tp / 1e9, 1),
            "xla_gbps": round(bytes_touched / tx / 1e9, 1),
            "speedup": round(tx / tp, 2)})
        print("adam", rows[-1])
    return rows


def _paged_inputs(B, H, KV, Dh, ps, pages, seq):
    """Shared decode-shape inputs so v1/v2/gather sweeps measure the
    SAME tables and live lengths."""
    mp = -(-seq // ps)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (KV, pages, ps, Dh), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (KV, pages, ps, Dh), jnp.bfloat16)
    rng = np.random.default_rng(0)
    table = jnp.asarray(
        rng.permutation(pages)[:B * mp].reshape(B, mp), jnp.int32)
    lens = jnp.asarray(rng.integers(seq // 2, seq, B), jnp.int32)
    return q, kp, vp, table, lens


def paged_vs_gather(configs, iters):
    rows = []
    for (B, H, KV, Dh, ps, pages, seq) in configs:
        q, kp, vp, table, lens = _paged_inputs(B, H, KV, Dh, ps, pages,
                                               seq)
        pal = jax.jit(lambda q, kp, vp, t, l: paged_decode_attention(
            q, kp, vp, t, l))
        ref = jax.jit(lambda q, kp, vp, t, l: paged_attention_reference(
            q, kp, vp, t, l))
        tp = bench(pal, q, kp, vp, table, lens, iters=iters)
        tr = bench(ref, q, kp, vp, table, lens, iters=iters)
        rows.append({
            "shape": {"B": B, "H": H, "KV": KV, "Dh": Dh, "page": ps,
                      "pages": pages, "seq": seq},
            "pallas_ms": round(1e3 * tp, 3), "gather_ms": round(1e3 * tr, 3),
            "speedup": round(tr / tp, 2)})
        print("paged", rows[-1])
    return rows


def _chunk_inputs(B, C, H, KV, Dh, ps, pages, seq):
    """Shared split-fuse-shape inputs so the v1 and v2 chunk sweeps
    measure the SAME tables and frontiers."""
    mp = -(-seq // ps)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, C, H, Dh), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (KV, pages, ps, Dh), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (KV, pages, ps, Dh), jnp.bfloat16)
    rng = np.random.default_rng(1)
    table = jnp.asarray(
        rng.permutation(pages)[:B * mp].reshape(B, mp), jnp.int32)
    start = jnp.asarray(rng.integers(0, seq - C, B), jnp.int32)
    return q, kp, vp, table, start


def chunk_vs_gather(configs, iters):
    """Chunked-prefill (split-fuse) attention: pallas kernel vs the
    masked-gather reference — decides where the 1<<28 gather-bytes
    threshold in models/llama.py forward_paged should actually sit for
    chunk shapes."""
    from deepspeed_tpu.inference.kernels import (
        paged_chunk_attention, paged_chunk_attention_reference)

    rows = []
    for (B, C, H, KV, Dh, ps, pages, seq) in configs:
        q, kp, vp, table, start = _chunk_inputs(B, C, H, KV, Dh, ps,
                                                pages, seq)
        pal = jax.jit(lambda q, kp, vp, t, s: paged_chunk_attention(
            q, kp, vp, t, s))
        ref = jax.jit(lambda q, kp, vp, t, s:
                      paged_chunk_attention_reference(q, kp, vp, t, s))
        tp = bench(pal, q, kp, vp, table, start, iters=iters)
        tr = bench(ref, q, kp, vp, table, start, iters=iters)
        rows.append({
            "shape": {"B": B, "C": C, "H": H, "KV": KV, "Dh": Dh,
                      "page": ps, "pages": pages, "seq": seq},
            "pallas_ms": round(1e3 * tp, 3), "gather_ms": round(1e3 * tr, 3),
            "speedup": round(tr / tp, 2)})
        print("chunk", rows[-1])
    return rows


def paged_v2_sweep(configs, iters):
    """paged_decode_attention_v2 (multi-page DMA streaming, only live
    pages read) vs v1 and the gather reference, over pages_per_block —
    the measurement that decides whether the pallas paged gate flips
    back on (r5: v1 lost 25x at the big shape, gather became the
    default)."""
    from deepspeed_tpu.inference.kernels import (
        paged_attention_reference, paged_decode_attention,
        paged_decode_attention_v2)

    rows = []
    for (B, H, KV, Dh, ps, pages, seq) in configs:
        q, kp, vp, table, lens = _paged_inputs(B, H, KV, Dh, ps, pages,
                                               seq)
        tr = bench(jax.jit(paged_attention_reference),
                   q, kp, vp, table, lens, iters=iters)
        tv1 = bench(jax.jit(paged_decode_attention),
                    q, kp, vp, table, lens, iters=iters)
        for ppcb in (4, 8, 16):
            try:
                f = jax.jit(functools.partial(paged_decode_attention_v2,
                                              pages_per_block=ppcb))
                t2 = bench(f, q, kp, vp, table, lens, iters=iters)
                row = {"v2_ms": round(1e3 * t2, 3),
                       "v2_vs_gather": round(tr / t2, 2),
                       "v2_vs_v1": round(tv1 / t2, 2)}
            except Exception as e:  # Mosaic lowering risk: record, go on
                row = {"error": str(e)[:160]}
            rows.append({
                "shape": {"B": B, "H": H, "KV": KV, "Dh": Dh, "page": ps,
                          "pages": pages, "seq": seq}, "ppcb": ppcb,
                "gather_ms": round(1e3 * tr, 3),
                "v1_ms": round(1e3 * tv1, 3), **row})
            print("paged_v2", rows[-1], flush=True)
    return rows


def chunk_v2_sweep(configs, iters):
    """paged_chunk_attention_v2 vs v1 vs the gather reference at the
    split-fuse shapes (same A/B contract as paged_v2_sweep)."""
    from deepspeed_tpu.inference.kernels import (
        paged_chunk_attention, paged_chunk_attention_reference,
        paged_chunk_attention_v2)

    rows = []
    for (B, C, H, KV, Dh, ps, pages, seq) in configs:
        q, kp, vp, table, start = _chunk_inputs(B, C, H, KV, Dh, ps,
                                                pages, seq)
        tr = bench(jax.jit(paged_chunk_attention_reference),
                   q, kp, vp, table, start, iters=iters)
        tv1 = bench(jax.jit(paged_chunk_attention),
                    q, kp, vp, table, start, iters=iters)
        for ppcb in (4, 8, 16):
            try:
                f = jax.jit(functools.partial(paged_chunk_attention_v2,
                                              pages_per_block=ppcb))
                t2 = bench(f, q, kp, vp, table, start, iters=iters)
                row = {"v2_ms": round(1e3 * t2, 3),
                       "v2_vs_gather": round(tr / t2, 2),
                       "v2_vs_v1": round(tv1 / t2, 2)}
            except Exception as e:
                row = {"error": str(e)[:160]}
            rows.append({
                "shape": {"B": B, "C": C, "H": H, "KV": KV, "Dh": Dh,
                          "page": ps, "pages": pages, "seq": seq},
                "ppcb": ppcb,
                "gather_ms": round(1e3 * tr, 3),
                "v1_ms": round(1e3 * tv1, 3), **row})
            print("chunk_v2", rows[-1], flush=True)
    return rows


def paged_v2_vs_xla(configs, iters):
    """The XLA gather against the decode kernels: per decode shape,
    the table's K/V footprint, the gather's time, and the v2 arms
    (dense and int8-dequant-fused), per-kernel rows.  No gate reads
    this: ``kernels.paged_reader`` answers from the phase and the
    layout, and the benchmark's serving cells are the measurement.

    Off-chip (CPU) the kernels only run in interpret mode, which
    measures the interpreter, not the kernel — so a CPU stamp records
    the gate verdicts plus interpret-mode IDENTITY errors (the
    correctness half of the contract) and leaves the timing columns to
    a TPU run.  Rows carry ``backend`` so the two never mix."""
    from deepspeed_tpu.inference.kernels import (
        dequantize_pages, paged_attention_reference,
        paged_decode_attention_v2, paged_decode_attention_v2_quant,
        quantize_kv_rows)

    on_tpu = jax.default_backend() == "tpu"
    rows = []
    for (B, H, KV, Dh, ps, pages, seq) in configs:
        q, kp, vp, table, lens = _paged_inputs(B, H, KV, Dh, ps, pages,
                                               seq)
        mp = table.shape[1]
        live_kv = 2 * B * KV * mp * ps * Dh * kp.dtype.itemsize
        kq, ks = quantize_kv_rows(kp)
        vq, vs = quantize_kv_rows(vp)
        row = {
            "backend": jax.default_backend(),
            "shape": {"B": B, "H": H, "KV": KV, "Dh": Dh, "page": ps,
                      "pages": pages, "seq": seq},
            "live_kv_mb": round(live_kv / (1 << 20), 1),
        }
        if on_tpu:
            tr = bench(jax.jit(paged_attention_reference),
                       q, kp, vp, table, lens, iters=iters)
            row["xla_ms"] = round(1e3 * tr, 3)
            try:
                t2 = bench(jax.jit(paged_decode_attention_v2),
                           q, kp, vp, table, lens, iters=iters)
                row["v2_ms"] = round(1e3 * t2, 3)
                row["v2_vs_xla"] = round(tr / t2, 2)
                tq = bench(jax.jit(paged_decode_attention_v2_quant),
                           q, kq, ks, vq, vs, table, lens, iters=iters)
                row["v2_quant_ms"] = round(1e3 * tq, 3)
                row["v2_quant_vs_xla"] = round(tr / tq, 2)
            except Exception as e:   # Mosaic lowering risk: record
                row["error"] = str(e)[:160]
        else:
            # interpret-mode identity arms (the CPU stamp's content):
            # dense v2 vs the gather, quant v2 vs the reference over
            # host-dequantized pages — both must sit at float noise
            ref = paged_attention_reference(q, kp, vp, table, lens)
            got = paged_decode_attention_v2(q, kp, vp, table, lens,
                                            interpret=True)
            row["v2_max_abs_diff"] = float(
                jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
            qref = paged_attention_reference(
                q, dequantize_pages(kq, ks, kp.dtype),
                dequantize_pages(vq, vs, vp.dtype), table, lens)
            qgot = paged_decode_attention_v2_quant(
                q, kq, ks, vq, vs, table, lens, interpret=True)
            row["v2_quant_max_abs_diff"] = float(
                jnp.max(jnp.abs(qgot.astype(jnp.float32)
                                - qref.astype(jnp.float32))))
            row["note"] = ("cpu interpret stamp: identity only — "
                           "timings need a chip re-stamp")
        rows.append(row)
        print("paged_v2_vs_xla", row, flush=True)
    return rows


def fused_sample_vs_xla(shapes, iters):
    """The crossover sweep behind ``pallas_sample_gate``: per (batch,
    vocab) serving shape, rows × vocab, the gate's auto verdict, the
    jitted XLA sampler time, and the FORCED-ON fused kernel arm.  On
    CPU (interpret) the row records the greedy identity mismatch count
    instead of timing — the bit-exactness the serving gates rely on."""
    from deepspeed_tpu.inference.serving import _sample_rows
    from deepspeed_tpu.ops.sampling_pallas import (
        _FUSED_SAMPLE_MIN_ROWS_X_VOCAB, fused_sample_rows,
        pallas_sample_gate)

    on_tpu = jax.default_backend() == "tpu"
    rows = []
    for (B, V) in shapes:
        logits = jax.random.normal(jax.random.PRNGKey(B), (B, V),
                                   jnp.float32)
        keys = jax.random.split(jax.random.PRNGKey(7), B)
        temps = jnp.zeros((B,))          # the greedy serving case
        row = {
            "backend": jax.default_backend(),
            "shape": {"B": B, "V": V}, "rows_x_vocab": B * V,
            "gate_auto_fused": pallas_sample_gate(B, V,
                                                  interpret=False),
            "crossover_rows_x_vocab": _FUSED_SAMPLE_MIN_ROWS_X_VOCAB,
        }
        if on_tpu:
            tx = bench(_sample_rows, logits, keys, temps, iters=iters)
            row["xla_ms"] = round(1e3 * tx, 3)
            try:
                tf = bench(fused_sample_rows, logits, keys, temps,
                           iters=iters)
                row["fused_ms"] = round(1e3 * tf, 3)
                row["fused_vs_xla"] = round(tx / tf, 2)
            except Exception as e:
                row["error"] = str(e)[:160]
        else:
            want = _sample_rows(logits, keys, temps)
            got = fused_sample_rows(logits, keys, temps,
                                    interpret=True)
            row["greedy_mismatches"] = int(jnp.sum(want != got))
            row["note"] = ("cpu interpret stamp: identity only — "
                           "timings need a chip re-stamp")
        rows.append(row)
        print("fused_sample_vs_xla", row, flush=True)
    return rows


def flash_packed_sweep(shapes, iters):
    """Packed-sequence flash attention (segment_ids) vs the masked XLA
    reference — first on-chip validation of the segment kernels' Mosaic
    lowering AND the packed-path speedup measurement."""
    from deepspeed_tpu.ops.attention import _reference

    rows = []
    for (B, T, H, D, KV) in shapes:
        q, k, v = attn_inputs(B, T, H, D, KV)
        rng = np.random.default_rng(0)
        seg = np.zeros((B, T), np.int32)
        for b in range(B):
            cuts = np.sort(rng.choice(np.arange(1, T), 3, replace=False))
            seg[b] = np.searchsorted(cuts, np.arange(T), side="right")
        seg = jnp.asarray(seg)

        def grad_of(f):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2)))

        flash_f = jax.jit(lambda q, k, v: attention_pallas
                          .flash_attention_tpu(q, k, v, causal=True,
                                               segment_ids=seg))
        ref_f = jax.jit(lambda q, k, v: _reference(q, k, v, causal=True,
                                                   segment_ids=seg))
        row = {"shape": {"B": B, "T": T, "H": H, "D": D, "KV": KV},
               "n_docs_per_row": 4}
        try:
            tf = bench(flash_f, q, k, v, iters=iters)
            tr = bench(ref_f, q, k, v, iters=iters)
            row["fwd"] = {"flash_ms": round(1e3 * tf, 3),
                          "xla_ms": round(1e3 * tr, 3),
                          "speedup": round(tr / tf, 2)}
            tfb = bench(grad_of(flash_f), q, k, v, iters=max(iters // 2, 3))
            trb = bench(grad_of(ref_f), q, k, v, iters=max(iters // 2, 3))
            row["fwd_bwd"] = {"flash_ms": round(1e3 * tfb, 3),
                              "xla_ms": round(1e3 * trb, 3),
                              "speedup": round(trb / tfb, 2)}
        except Exception as e:   # Mosaic lowering risk: record, move on
            row["error"] = str(e)[:160]
        rows.append(row)
        print("flash_packed", row, flush=True)
    return rows


def block_sweep(iters):
    """Sweep flash tile sizes at the bench shape; _pick_blocks should
    match the argmin."""
    B, T, H, D, KV = 4, 2048, 16, 128, 8
    q, k, v = attn_inputs(B, T, H, D, KV)
    orig = attention_pallas._pick_blocks
    out = []
    try:
        for bq in (128, 256, 512):
            for bk in (128, 256, 512):
                if T % bq or T % bk:
                    continue
                attention_pallas._pick_blocks = (
                    lambda TT, SS, _bq=bq, _bk=bk: (_bq, _bk))
                f = jax.jit(lambda q, k, v: attention_pallas
                            .flash_attention_tpu(q, k, v, causal=True))
                g = jax.jit(jax.grad(
                    lambda q, k, v: jnp.sum(
                        attention_pallas.flash_attention_tpu(
                            q, k, v, causal=True).astype(jnp.float32)),
                    argnums=(0, 1, 2)))
                try:
                    tf = bench(f, q, k, v, iters=iters)
                    tb = bench(g, q, k, v, iters=max(iters // 2, 3))
                    out.append({"block_q": bq, "block_k": bk,
                                "fwd_ms": round(1e3 * tf, 3),
                                "fwd_bwd_ms": round(1e3 * tb, 3)})
                    print("sweep", out[-1])
                except Exception as e:  # VMEM overflow etc: record, move on
                    out.append({"block_q": bq, "block_k": bk,
                                "error": str(e)[:120]})
    finally:
        attention_pallas._pick_blocks = orig
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--families", default="",
                    help="comma-separated subset of sweep families "
                         "(default: all)")
    ap.add_argument("--json-out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "KERNEL_BENCH.json"))
    args = ap.parse_args()
    iters = 5 if args.quick else 20

    attn_shapes = [(4, 2048, 16, 128, 8), (2, 4096, 16, 128, 8),
                   (8, 1024, 16, 128, 16)]
    adam_sizes = [1 << 22, 1 << 26]
    paged_cfgs = [(8, 16, 4, 128, 16, 512, 1024),
                  (16, 16, 8, 128, 16, 1024, 512),
                  # ABOVE the 1<<28 gather-bytes gate in llama.forward_paged
                  # (2*16*8*256*16*128*6 = 805 MB): the demoted kernel's
                  # winning side, unmeasured until now (round-3 weak #5)
                  (16, 32, 8, 128, 16, 4608, 4096)]
    # (B, C, H, KV, Dh, page, pages, seq): short interactive chunk,
    # serving-default chunk, long-context chunk over a big table
    chunk_cfgs = [(8, 16, 16, 4, 128, 16, 512, 1024),
                  (8, 64, 16, 4, 128, 16, 512, 1024),
                  (4, 64, 16, 4, 128, 16, 2048, 8192)]
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        # a small and a large decode table; one (B, V) below
        # _FUSED_SAMPLE_MIN_ROWS_X_VOCAB, one above
        gate_paged_cfgs = [(8, 16, 4, 128, 16, 512, 1024),
                           (16, 32, 8, 128, 16, 4608, 4096)]
        gate_sample_shapes = [(8, 32000), (256, 128256)]
    else:
        # CPU interpret stamps: identity only, so tiny shapes — the
        # rows record gate verdicts + max-abs-diff, never timings
        gate_paged_cfgs = [(2, 4, 2, 32, 8, 16, 48)]
        gate_sample_shapes = [(4, 512), (8, 1024)]
    if args.quick:
        attn_shapes, adam_sizes = attn_shapes[:1], adam_sizes[:1]
        paged_cfgs, chunk_cfgs = paged_cfgs[:1], chunk_cfgs[:1]

    # incremental commit after every sweep family: a run killed at its
    # time limit must not cost the families that DID complete.  MERGE semantics: seed from
    # the committed file so a --families subset run (e.g. the CPU slow
    # lane stamping only the gate sweeps) cannot clobber TPU rows that
    # this box can't reproduce.
    result = {"backend": jax.default_backend(), "partial": True}
    if os.path.exists(args.json_out):
        try:
            with open(args.json_out) as f:
                prior = json.load(f)
            prior.pop("partial", None)
            # keep the prior top-level backend: it labels the families
            # this run does NOT re-stamp; new rows carry their own
            prior.setdefault("backend", jax.default_backend())
            result = dict(prior, partial=True)
        except (OSError, ValueError) as e:
            print(f"note: not merging {args.json_out}: {e}",
                  file=sys.stderr)
    sweeps = [
        ("flash_vs_xla", lambda: flash_vs_ref(attn_shapes, iters)),
        ("adam_pallas_vs_xla", lambda: adam_vs_xla(adam_sizes, iters)),
        ("paged_decode_vs_gather", lambda: paged_vs_gather(paged_cfgs,
                                                           iters)),
        ("chunk_prefill_vs_gather", lambda: chunk_vs_gather(chunk_cfgs,
                                                            iters)),
        ("paged_decode_v2", lambda: paged_v2_sweep(paged_cfgs, iters)),
        ("chunk_prefill_v2", lambda: chunk_v2_sweep(chunk_cfgs, iters)),
        ("flash_packed", lambda: flash_packed_sweep(attn_shapes[:1], iters)),
        ("flash_block_sweep", lambda: block_sweep(iters)),
        ("paged_v2_vs_xla", lambda: paged_v2_vs_xla(gate_paged_cfgs,
                                                    iters)),
        ("fused_sample_vs_xla",
         lambda: fused_sample_vs_xla(gate_sample_shapes, iters)),
    ]
    picked = [s for s in args.families.split(",") if s]
    if picked:
        unknown = set(picked) - {n for n, _ in sweeps}
        if unknown:
            raise SystemExit(f"unknown families {sorted(unknown)}")
        sweeps = [(n, f) for n, f in sweeps if n in picked]
    from deepspeed_tpu.utils.evidence import atomic_write_json

    for name, fn in sweeps:
        result[name] = fn()
        print(f"--- {name} done", flush=True)
        atomic_write_json(result, args.json_out)
    result.pop("partial")
    atomic_write_json(result, args.json_out)
    print("→", args.json_out)


if __name__ == "__main__":
    main()
