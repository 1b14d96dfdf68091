"""GPT-2: ``deepspeed_tpu.models.gpt2`` under the keys of a Hugging Face
GPT-2 ``config.json`` (``n_embd``, ``n_layer``, ``n_head``,
``n_positions``, ``vocab_size``)."""


import jax

from benchmark.reference import gpt2 as reference


def program_config(model, **overrides):
    from deepspeed_tpu.models.gpt2 import GPT2Config

    return GPT2Config(vocab_size=model["vocab_size"], dim=model["n_embd"],
                      n_layers=model["n_layer"], n_heads=model["n_head"],
                      max_seq_len=model["n_positions"],
                      norm_eps=model["layer_norm_epsilon"], **overrides)


def toy(model):
    """The same family at a size the CPU walks in seconds (--rehearse)."""
    return dict(model, vocab_size=512, n_embd=128, n_layer=2, n_head=2,
                n_positions=256)


def init_params(cfg, key, dtype):
    """The program's own initialiser.  ``key`` is an argument of the jit
    that calls this, never a constant in it: a seed baked into the
    program would compile a new program for every ``--seed``."""
    from deepspeed_tpu.models import gpt2

    return gpt2.init_params(key, cfg, dtype)


def loss_fn(cfg):
    from deepspeed_tpu.models import gpt2

    return gpt2.loss_fn(cfg)


def param_count(cfg):
    d, L = cfg.dim, cfg.n_layers
    per_layer = 12 * d * d + 13 * d
    return L * per_layer + (cfg.vocab_size + cfg.max_seq_len) * d + 2 * d


def train_flops_per_token(cfg, seq):
    """6N for the products with weights, forward and backward, and
    12 L d T for the attention scores and values at sequence length T
    (Kaplan et al. 2020, the PaLM appendix's form).  Recomputation is
    not counted."""
    return 6 * param_count(cfg) + 12 * cfg.n_layers * cfg.dim * seq


def serve_flops_per_token(cfg, context):
    """Forward only: 2N and 4 L d per token of context attended."""
    return 2 * param_count(cfg) + 4 * cfg.n_layers * cfg.dim * context


def weight_bytes(cfg, itemsize=2):
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    return 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * itemsize


def reference_logits(cfg):
    """jitted (params, tokens[T], start, count) -> (logits[count, V] of
    the positions from start, alternatives).  A dense model is
    continuous: there is no alternative."""
    return jax.jit(lambda p, t, start, count: (reference.logits(
        p, t, n_heads=cfg.n_heads, eps=cfg.norm_eps, start=start,
        count=count), []), static_argnums=3)


def reference_loss(cfg):
    return jax.jit(lambda p, t: reference.loss(
        p, t, n_heads=cfg.n_heads, eps=cfg.norm_eps))
