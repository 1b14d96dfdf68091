"""Mixtral: ``deepspeed_tpu.models.mixtral`` under the keys of
mistralai/Mixtral-8x7B-v0.1's ``config.json``."""

import jax

from benchmark.reference import mixtral as reference


def program_config(model, **overrides):
    from deepspeed_tpu.models.mixtral import MixtralConfig

    return MixtralConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"],
        num_experts=model["num_local_experts"],
        top_k=model["num_experts_per_tok"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        **overrides)


def toy(model):
    return dict(model, vocab_size=512, hidden_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=256, num_local_experts=4,
                max_position_embeddings=1024)


def init_params(cfg, key, dtype):
    """The program's own initialiser.  ``key`` is an argument of the jit
    that calls this, never a constant in it: a seed baked into the
    program would compile a new program for every ``--seed``."""
    from deepspeed_tpu.models import mixtral

    return mixtral.init_params(key, cfg, dtype)


def _layer_counts(cfg):
    d, f = cfg.dim, cfg.ffn_dim
    kvd = cfg.n_kv_heads * cfg.head_dim
    attn = 2 * d * d + 2 * d * kvd
    expert = 3 * d * f
    return attn, expert


def param_count(cfg):
    attn, expert = _layer_counts(cfg)
    per_layer = attn + cfg.num_experts * (expert + cfg.dim) + 2 * cfg.dim
    return cfg.n_layers * per_layer + 2 * cfg.vocab_size * cfg.dim + cfg.dim


def routed_param_count(cfg):
    """What one token multiplies with: top-k experts, not all of them."""
    attn, expert = _layer_counts(cfg)
    per_layer = attn + cfg.top_k * expert + cfg.num_experts * cfg.dim
    return cfg.n_layers * per_layer + cfg.vocab_size * cfg.dim


def serve_flops_per_token(cfg, context):
    """Forward only, as routed: 2 per weight a token meets, and
    4 L (heads x head size) per token of context attended."""
    return (2 * routed_param_count(cfg)
            + 4 * cfg.n_layers * cfg.n_heads * cfg.head_dim * context)


def weight_bytes(cfg, itemsize=2):
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    return 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * itemsize


# A router margin under this share of the largest router logit is a
# tie: bf16 keeps 8 bits, and a hidden state that has come through
# attention and an expert in bf16 is off by a few of its 2^-9 roundings.
ROUTER_TIE = 2.0 ** -5


def reference_logits(cfg):
    """(params, tokens[T], start, count) -> (logits[count, V] of the
    positions from start, alternatives).  An alternative is (logits,
    where[count]): the logits with the k-th expert swapped for the
    (k+1)-th at every position of one layer whose router is on a tie
    there, and the positions that may claim it: those ties."""
    import jax.numpy as jnp

    kw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
              top_k=cfg.top_k, rope_theta=cfg.rope_theta, eps=cfg.norm_eps)
    keys_values = jax.jit(lambda p, t: reference.keys_values(p, t, **kw))
    logits = jax.jit(lambda p, t, cache, start, swap: reference.logits(
        p, t, cache, start, swap.shape[1], swap, **kw))

    def forward(params, tokens, start, count):
        cache = keys_values(params, tokens)
        none = jnp.zeros((cfg.n_layers, count), bool)
        plain, margins = logits(params, tokens, cache, start, none)
        ties = margins < ROUTER_TIE
        return plain, [
            (logits(params, tokens, cache, start,
                    none.at[layer].set(ties[layer]))[0], ties[layer])
            for layer in range(cfg.n_layers)]

    return forward
