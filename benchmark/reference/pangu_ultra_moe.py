"""openPangu-Ultra-MoE (``model_type: pangu_ultra_moe``), plain, in the
published per-head form only: float32, no cache, no absorbed form, no
kernels.

A layer: ``x += RMS(MLA(RMS(x)))``, ``x += RMS(F(RMS(x)))`` (sandwich
norms).  MLA: ``c_q = RMS(a W_qa)``, ``q = c_q W_qb`` in heads of
``[nope | rope]``; ``[c_kv | k_r] = a W_kva``, ``c = RMS(c_kv)``; per
head ``k_nope = c W_UK``, ``v = c W_UV``; ``k_rope = RoPE(k_r)``, one
for all heads; scores ``(q_nope . k_nope + RoPE(q_rope) . k_rope) /
sqrt(Dn + Dr)``, causal.  RoPE pairs the halves of its 64 numbers
(rotate-half), theta from the config, no scaling.  ``F`` is a SwiGLU in
the leading dense layers; in the expert layers ``s = sigmoid(m W_g)``
over all the experts the router has, the top k, ``w = scale * s /
sum(s)``, and ``y = sum w_i E_i(m) + E_shared(m)`` over the experts
HELD (``first .. first + Eh``, what the tree's stacks hold): the share
of one rank of an expert-parallel deployment, as the program computes
it.  What the absent experts would add is left out.

Parameter names are those of the tree the system is given
(``dense_blocks/*`` and ``blocks/*`` stacked over layers, experts
stacked ``[L, Eh, ...]``).  Two passes, as ``reference/mixtral.py`` and
for its reason: ``latents`` runs the whole sequence and keeps every
layer's ``(c, k_rope)``; ``logits`` runs a stretch of positions against
them and may ``swap`` the k-th expert for the (k+1)-th at chosen
positions of chosen expert layers.
"""

import jax
import jax.numpy as jnp

EXPERT_WEIGHTS = ("w1", "w3", "w2")
Q_BLOCK = 256       # queries a block: scores of 16 heads x 256 x 16k keys
HEAD_GROUP = 16     # heads a pass: the expanded keys of 16k tokens, 0.5 GB

f32 = lambda a: a.astype(jnp.float32)


def _layer(blocks, at):
    """Layer ``at`` of a stack, its experts left in the stack."""
    return {n: a if n in EXPERT_WEIGHTS and a.ndim == 4 else a[at]
            for n, a in blocks.items()}


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * f32(w)


def _rope(x, pos, theta):
    """x: [N, ..., D] at positions ``pos`` [N]; halves paired."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q_nope, q_rope, c, k_rope, w_uk, w_uv, first):
    """q_*: [N, H, D] at positions first..first+N; c [T, C], k_rope
    [T, Dr] of the whole sequence -> [N, H, Dv].  Heads go through in
    groups and queries in blocks, so that neither the expanded keys nor
    the scores of a long sequence are held whole."""
    N, H, Dn = q_nope.shape
    T = c.shape[0]
    G = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    blk = Q_BLOCK if N % Q_BLOCK == 0 else N
    scale = 1.0 / jnp.sqrt(jnp.float32(Dn + q_rope.shape[-1]))
    key_pos = jnp.arange(T)

    def heads(args):
        qn, qr, uk, uv = args            # [N, G, D], [C, G, D]
        k = jnp.einsum("tc,cgd->tgd", c, uk)
        v = jnp.einsum("tc,cgd->tgd", c, uv)

        def block(args):
            qn, qr, at = args
            s = (jnp.einsum("qgd,tgd->gqt", qn, k)
                 + jnp.einsum("qgd,td->gqt", qr, k_rope)) * scale
            seen = key_pos[None, :] <= (at + jnp.arange(blk))[:, None]
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("gqt,tgd->qgd", p, v)

        split = lambda a: a.reshape(N // blk, blk, G, -1)
        out = jax.lax.map(block, (split(qn), split(qr),
                                  first + jnp.arange(N // blk) * blk))
        return out.reshape(N, G, -1)

    by_group = lambda a, axis: jnp.moveaxis(
        a.reshape(a.shape[:axis] + (H // G, G) + a.shape[axis + 1:]),
        axis, 0)
    out = jax.lax.map(heads, (by_group(q_nope, 1), by_group(q_rope, 1),
                              by_group(w_uk, 1), by_group(w_uv, 1)))
    return jnp.moveaxis(out, 0, 1).reshape(N, H, -1)


def _attention(x, lp, pos, cached, *, n_heads, kv_lora_rank, qk_nope_dim,
               rope_theta, eps):
    """x: [N, d] at positions ``pos`` -> (x after attention and its
    norm, this stretch's (c, k_rope)).  ``cached``: None, or a layer's
    (c, k_rope) over the whole sequence, in which this stretch's rows
    are replaced by what is computed here."""
    N = x.shape[0]
    C, H = kv_lora_rank, n_heads
    a = _rms_norm(x, lp["attn_norm"], eps)
    c_q = _rms_norm(a @ f32(lp["wq_a"]), lp["q_norm"], eps)
    q = (c_q @ f32(lp["wq_b"])).reshape(N, H, -1)
    q_nope, q_rope = q[..., :qk_nope_dim], _rope(q[..., qk_nope_dim:], pos,
                                                 rope_theta)
    kv = a @ f32(lp["wkv_a"])
    c = _rms_norm(kv[:, :C], lp["kv_norm"], eps)
    k_rope = _rope(kv[:, C:], pos, rope_theta)
    rows, ropes = c, k_rope
    if cached is not None:
        rows = jax.lax.dynamic_update_slice_in_dim(cached[0], c, pos[0], 0)
        ropes = jax.lax.dynamic_update_slice_in_dim(cached[1], k_rope,
                                                    pos[0], 0)
    o = _attend(q_nope, q_rope, rows, ropes,
                f32(lp["w_uk"]).reshape(C, H, -1),
                f32(lp["w_uv"]).reshape(C, H, -1), pos[0])
    y = o.reshape(N, -1) @ f32(lp["wo"])
    return x + _rms_norm(y, lp["post_attn_norm"], eps), c, k_rope


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ f32(w1)) * (h @ f32(w3))) @ f32(w2)


def _dense_ffn(x, lp, eps):
    h = _rms_norm(x, lp["mlp_norm"], eps)
    return x + _rms_norm(_swiglu(h, lp["w1"], lp["w3"], lp["w2"]),
                         lp["post_mlp_norm"], eps)


def route(h, gate, top_k, scale, normalize, swap=None):
    """-> (weights [N, k], experts [N, k], margin [N]): sigmoid scores
    over all the experts, the top k (the (k+1)-th in the k-th's place
    where ``swap``), divided by their sum and multiplied by ``scale``.
    The margin is the gap between the k-th and the (k+1)-th router logit
    as a share of the largest logit's magnitude."""
    z = h @ f32(gate)
    top, idx = jax.lax.top_k(z, top_k + 1)
    margin = (top[:, top_k - 1] - top[:, top_k]) / jnp.abs(z).max(-1)
    if swap is None:
        top, idx = top[:, :top_k], idx[:, :top_k]
    else:
        last = lambda a: jnp.where(swap, a[:, top_k],
                                   a[:, top_k - 1])[:, None]
        top = jnp.concatenate([top[:, :top_k - 1], last(top)], -1)
        idx = jnp.concatenate([idx[:, :top_k - 1], last(idx)], -1)
    s = jax.nn.sigmoid(top)
    if normalize:
        s = s / (s.sum(-1, keepdims=True) + 1e-20)
    return s * scale, idx, margin


def held_part(h, lp, at, w, idx, first):
    """What the experts held contribute: sum over them of the router's
    weight (zero where it did not choose the expert) times the expert."""
    def expert(y, e):
        share = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        out = _swiglu(h, *(lp[n][at, e] for n in EXPERT_WEIGHTS))
        return y + share[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        jnp.arange(lp["w1"].shape[1]))
    return y


def _expert_ffn(x, lp, at, swap, *, top_k, first, scale, normalize, eps):
    h = _rms_norm(x, lp["mlp_norm"], eps)
    w, idx, margin = route(h, lp["gate"], top_k, scale, normalize, swap)
    y = held_part(h, lp, at, w, idx, first) \
        + _swiglu(h, lp["sw1"], lp["sw3"], lp["sw2"])
    return x + _rms_norm(y, lp["post_mlp_norm"], eps), margin


def _split(kw):
    moe = {k: kw[k] for k in ("top_k", "first", "scale", "normalize")}
    attn = {k: v for k, v in kw.items() if k not in moe
            and k != "qk_rope_dim"}
    return attn, dict(moe, eps=kw["eps"])


def _layers(params):
    return (params["dense_blocks"]["wq_a"].shape[0],
            params["blocks"]["wq_a"].shape[0])


def latents(params, tokens, **kw):
    """tokens: [T] -> every layer's (c [L, T, C], k_rope [L, T, Dr]) of
    the whole sequence, dense layers first, as the router's own choice
    gives them."""
    attn, moe = _split(kw)
    pos = jnp.arange(tokens.shape[0])
    n_dense, n_sparse = _layers(params)

    def dense(x, at):
        lp = _layer(params["dense_blocks"], at)
        x, c, r = _attention(x, lp, pos, None, **attn)
        return _dense_ffn(x, lp, attn["eps"]), (c, r)

    def sparse(x, at):
        lp = _layer(params["blocks"], at)
        x, c, r = _attention(x, lp, pos, None, **attn)
        x, _ = _expert_ffn(x, lp, at, None, **moe)
        return x, (c, r)

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        x, a = jax.lax.scan(dense, x, jnp.arange(n_dense))
        _, b = jax.lax.scan(sparse, x, jnp.arange(n_sparse))
    return tuple(jnp.concatenate([u, v]) for u, v in zip(a, b))


def logits(params, tokens, cache, start, count, swap, **kw):
    """-> (float32 logits [count, V], router margins [expert layers,
    count]) of the ``count`` positions from ``start``, run against
    ``cache`` (what ``latents`` returned) with their own rows computed
    anew.  ``swap``: [expert layers, count] booleans."""
    attn, moe = _split(kw)
    pos = start + jnp.arange(count)
    n_dense, n_sparse = _layers(params)
    cs, rs = cache

    def dense(x, layer):
        at, c, r = layer
        lp = _layer(params["dense_blocks"], at)
        x, _, _ = _attention(x, lp, pos, (c, r), **attn)
        return _dense_ffn(x, lp, attn["eps"]), None

    def sparse(x, layer):
        at, c, r, swap_here = layer
        lp = _layer(params["blocks"], at)
        x, _, _ = _attention(x, lp, pos, (c, r), **attn)
        return _expert_ffn(x, lp, at, swap_here, **moe)

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jax.lax.dynamic_slice_in_dim(
            tokens, start, count)])
        x, _ = jax.lax.scan(dense, x, (jnp.arange(n_dense), cs[:n_dense],
                                       rs[:n_dense]))
        x, margins = jax.lax.scan(
            sparse, x, (jnp.arange(n_sparse), cs[n_dense:], rs[n_dense:],
                        swap))
        x = _rms_norm(x, params["final_norm"], attn["eps"])
        return x @ f32(params["lm_head"]), margins


def forward(params, tokens, **kw):
    """The whole forward, once: tokens [T] -> logits [T, V].  What the
    CPU tests hold the system to."""
    cache = latents(params, tokens, **kw)
    n_sparse = _layers(params)[1]
    none = jnp.zeros((n_sparse, tokens.shape[0]), bool)
    return logits(params, tokens, cache, 0, tokens.shape[0], none, **kw)[0]
