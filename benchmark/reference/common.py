"""What the plain forwards share: blocked causal attention."""

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def causal_attention(q, k, v, start=0):
    """q: [N, H, D] at positions start..start+N, k/v: [T, KV, D] in
    float32 -> [N, H, D].

    Softmax over the keys at or before each query, heads grouped over
    the KV heads.  Queries go through in blocks of ``Q_BLOCK`` so that
    the scores of an 8k-token document are 0.5 GiB, not 8.
    """
    N, H, D = q.shape
    T, KV = k.shape[:2]
    blk = Q_BLOCK if N % Q_BLOCK == 0 else N
    qb = q.reshape(N // blk, blk, KV, H // KV, D)
    key_pos = jnp.arange(T)

    def one(args):
        qi, first = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) / jnp.sqrt(jnp.float32(D))
        seen = key_pos[None, :] <= (first + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(one, (qb, start + jnp.arange(N // blk) * blk))
    return out.reshape(N, H, D)
