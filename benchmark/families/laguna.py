"""Laguna: ``deepspeed_tpu.models.laguna`` under the keys of
poolside/Laguna-S-2.1's ``config.json``, as ONE RANK's share of an
expert-parallel deployment.

``RANKS`` chips share each layer: attention (both kinds), the shared
expert and the router (over all the experts there are) are replicated,
and a configuration's ``num_experts`` counts the experts held HERE, rank
0's: the published count is ``RANKS`` times it.  The arithmetic below is
of the share: held experts only.

The four per-layer lists (``layer_types``, ``mlp_layer_types``,
``gating_types``, ``num_attention_heads_per_layer``) stand as published,
a value a published layer; this file reads the first
``num_hidden_layers`` of each.

What this file adds to what a sparse share's file holds (``families/
pangu_ultra_moe.py``): ``state_bytes_per_slot``, the sliding layers'
rings, which a slot keeps whatever its length (``readers/window.py``).
"""

import jax

from benchmark.reference import laguna as reference
from benchmark.roofline import window

RANKS = 16

# keys of the source whose value says which layer this program builds;
# any other value is another model
_STATED = {"attention_bias": False, "tie_word_embeddings": False,
           "gating": "per-head", "decoder_sparse_step": 1,
           "mlp_only_layers": [0], "moe_apply_router_weight_on_input": False,
           "moe_router_logit_softcapping": 0}


def _one(values, what):
    if len(set(values)) != 1:
        raise SystemExit(f"laguna builds one {what}, and the configuration "
                         f"says {sorted(set(values))}")
    return values[0]


def program_config(model, **overrides):
    try:
        from deepspeed_tpu.models.laguna import LagunaConfig
    except ImportError:
        # a program from before PR 44: the cell cannot run on it
        raise SystemExit("this program has no family laguna "
                         "(deepspeed_tpu/models/laguna.py): the cell "
                         "needs it")

    for key, value in _STATED.items():
        if model[key] != value:
            raise SystemExit(f"laguna builds {key} = {value!r}, and the "
                             f"configuration says {model[key]!r}")
    n = model["num_hidden_layers"]
    kinds = [k.split("_")[0] for k in model["layer_types"][:n]]
    heads = model["num_attention_heads_per_layer"][:n]
    if kinds[0] != "full" or model["mlp_layer_types"][:n] \
            != ["dense"] + ["sparse"] * (n - 1) \
            or set(model["gating_types"][:n]) != {"per_head"}:
        raise SystemExit("laguna builds a dense full-attention layer 0, "
                         "sparse layers behind it and a per-head gate in "
                         "every layer")
    rest = tuple(kinds[1:])
    period = next(rest[:p] for p in range(1, len(rest) + 1)
                  if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p))
    rope = model["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if full["rope_type"] != "yarn" or sliding["rope_type"] != "default" \
            or sliding["partial_rotary_factor"] != 1:
        raise SystemExit("laguna builds a YaRN table for the full layers "
                         "and a plain one over the whole head for the "
                         "sliding layers")
    held = model["num_experts"]
    return LagunaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=n, period=period,
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        n_heads_full=_one([h for h, k in zip(heads, kinds) if k == "full"],
                          "head count a full layer"),
        n_heads_sliding=_one(
            [h for h, k in zip(heads, kinds) if k == "sliding"],
            "head count a sliding layer"),
        sliding_window=model["sliding_window"],
        ffn_dim=model["intermediate_size"],
        moe_ffn_dim=model["moe_intermediate_size"],
        shared_ffn_dim=model["shared_expert_intermediate_size"],
        n_routed_experts=held * RANKS, experts_held=(0, held),
        top_k=model["num_experts_per_tok"],
        routed_scaling_factor=model["moe_routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"],
        rope_theta_full=float(full["rope_theta"]),
        rotary_full=full["partial_rotary_factor"],
        yarn_factor=float(full["factor"]),
        yarn_original_max=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        attention_factor=full["attention_factor"],
        rope_theta_sliding=float(sliding["rope_theta"]),
        max_seq_len=model["max_position_embeddings"],
        norm_eps=model["rms_norm_eps"], **overrides)


def toy(model):
    """Two periods behind the lead at a size the CPU walks in seconds
    (--rehearse): a window the rehearsal's contexts pass, the two head
    counts over two K/V heads, a YaRN table that ramps inside 1k."""
    n = 9
    kinds = model["layer_types"][:n]
    rope = dict(model["rope_parameters"])
    rope["full_attention"] = dict(rope["full_attention"], factor=8,
                                  original_max_position_embeddings=64)
    return dict(
        model, vocab_size=512, hidden_size=128, num_hidden_layers=n,
        num_key_value_heads=2, head_dim=32, sliding_window=16,
        num_attention_heads_per_layer=[
            4 if k.startswith("full") else 6 for k in kinds]
        + model["num_attention_heads_per_layer"][n:],
        intermediate_size=256, moe_intermediate_size=64,
        shared_expert_intermediate_size=64, num_experts=2,
        num_experts_per_tok=4, rope_parameters=rope,
        max_position_embeddings=1024)


def init_params(cfg, key, dtype):
    """The program's own initialiser; ``key`` is an argument of the jit
    that calls this, never a constant in it."""
    from deepspeed_tpu.models import laguna

    return laguna.init_params(key, cfg, dtype)


def _counts(cfg):
    """Parameters of (a full layer's attention, a sliding layer's, layer
    0's MLP, one expert, the shared expert, the router), the two norms
    aside."""
    d, Dh, KV = cfg.dim, cfg.head_dim, cfg.n_kv_heads
    attn = lambda H: 2 * d * H * Dh + 2 * d * KV * Dh + d * H
    return (attn(cfg.n_heads_full), attn(cfg.n_heads_sliding),
            3 * d * cfg.ffn_dim, 3 * d * cfg.moe_ffn_dim,
            3 * d * cfg.shared_ffn_dim, d * cfg.n_routed_experts)


def param_count(cfg):
    """What this rank holds."""
    full, sliding, mlp, expert, shared, router = _counts(cfg)
    sparse = cfg.experts_held[1] * expert + shared + router
    return (full + mlp + (cfg.n_full_layers - 1) * (full + sparse)
            + cfg.n_sliding_layers * (sliding + sparse)
            + cfg.n_layers * 2 * cfg.dim
            + 2 * cfg.vocab_size * cfg.dim + cfg.dim)


def routed_param_count(cfg):
    """What one token multiplies with on this rank: of its top-k experts
    the held share (top_k x held / all, on average), the shared expert,
    the router, attention's projections and the output head."""
    full, sliding, mlp, expert, shared, router = _counts(cfg)
    here = cfg.top_k * cfg.experts_held[1] / cfg.n_routed_experts
    sparse = here * expert + shared + router
    return (full + mlp + (cfg.n_full_layers - 1) * (full + sparse)
            + cfg.n_sliding_layers * (sliding + sparse)
            + cfg.vocab_size * cfg.dim)


def serve_flops_per_token(cfg, context):
    """Forward only, as routed: 2 per weight a token meets, and per key
    attended 2 x heads x (score + value): ``context`` keys in a full
    layer, the window's at most in a sliding one."""
    return (2 * routed_param_count(cfg)
            + 4 * cfg.head_dim * (
                cfg.n_full_layers * cfg.n_heads_full * context
                + cfg.n_sliding_layers * cfg.n_heads_sliding
                * min(context, cfg.sliding_window)))


def weight_bytes(cfg, itemsize=2):
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of the full layers alone: a sliding layer leaves nothing
    a token in the pool."""
    return cfg.n_full_layers * cfg.n_kv_heads * cfg.head_dim * 2 * itemsize


def state_bytes_per_slot(cfg, itemsize=2):
    """The sliding layers' rings: what a slot keeps beside its pages."""
    return cfg.n_sliding_layers * window.ring_bytes(cfg, itemsize)


def _ref_kw(cfg):
    return dict(
        period=cfg.period, head_dim=cfg.head_dim, window=cfg.sliding_window,
        rotary=cfg.rotary_dim_full, theta_full=cfg.rope_theta_full,
        yarn=(cfg.yarn_factor, cfg.yarn_original_max, cfg.yarn_beta_fast,
              cfg.yarn_beta_slow),
        attention_factor=cfg.attention_factor,
        theta_sliding=cfg.rope_theta_sliding, top_k=cfg.top_k,
        first=cfg.experts_held[0], scale=cfg.routed_scaling_factor,
        normalize=cfg.norm_topk_prob, eps=cfg.norm_eps)


# As ``families/pangu_ultra_moe.py``: top 10 of 256 sit as close as top
# 8 of 256, and 2^-7 of the largest logit is what bf16 hidden states move
# a router logit by.
ROUTER_TIE = 2.0 ** -7
# The token check cannot see the router's precision on a share (a
# flipped 10th expert matters only when it or its rival is one of the 16
# held); the probe looks at the router itself.
ROUTER_PROBE_ROWS = 4096
ROUTER_PROBE_LIMIT = 4


def router_probe(cfg, params, seed, step_rows, chunk_rows):
    """The program's expert layer (the first sliding layer's) on seeded
    hidden states, against the reference's float32 router on the same
    numbers: how many rows the two send to the held experts differently
    (the sum over held experts of the difference of their row counts),
    ``step_rows`` at a time (a decode step: every held expert on every
    row) and ``chunk_rows`` at a time (a chunk: the grouped product); the
    larger difference counts.  As ``families/pangu_ultra_moe.py::
    router_probe``, and a pass of the check's own over the functions the
    programs are built from, not the compiled programs that were timed."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import laguna as program

    first, held = cfg.experts_held
    lp = jax.tree.map(lambda a: a[0], params["win_blocks"])
    h = jax.random.normal(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                          (ROUTER_PROBE_ROWS, cfg.dim), lp["gate"].dtype)

    def counted(rows):
        rows = min(rows, ROUTER_PROBE_ROWS)
        batches = h[:ROUTER_PROBE_ROWS // rows * rows].reshape(
            -1, 1, rows, cfg.dim)
        return jax.jit(lambda hs, lp: jax.lax.map(
            lambda one: program.expert_layer(cfg, one, lp)[1], hs).sum(0))(
                batches, lp), batches.shape[0] * rows

    with jax.default_matmul_precision("highest"):
        _, idx, _ = reference.route(h.astype(jnp.float32), lp["gate"],
                                    cfg.top_k, cfg.routed_scaling_factor,
                                    cfg.norm_topk_prob)
    here = idx[..., None] == first + jnp.arange(held)       # [N, k, Eh]
    differ, routed_here = 0, 0
    for rows in (step_rows, chunk_rows):
        got, n = counted(rows)
        want = here[:n].sum((0, 1))
        differ = max(differ, int(jnp.abs(got - want).sum()))
        routed_here = max(routed_here, int(want.sum()))
    return {"rows": ROUTER_PROBE_ROWS, "by": [step_rows, chunk_rows],
            "routed_here": routed_here, "differ": differ,
            "limit": ROUTER_PROBE_LIMIT}


def reference_logits(cfg):
    """(params, tokens[T], start, count) -> (logits[count, V] of the
    positions from start, alternatives).  An alternative is (logits,
    where[count]): the logits with the k-th expert swapped for the
    (k+1)-th at every position of one expert layer whose router is on a
    tie there, and the positions that may claim it: those ties."""
    import jax.numpy as jnp

    kw = _ref_kw(cfg)
    keys_values = jax.jit(lambda p, t: reference.keys_values(p, t, **kw))
    logits = jax.jit(lambda p, t, cache, start, swap: reference.logits(
        p, t, cache, start, swap.shape[1], swap, **kw))

    def forward(params, tokens, start, count):
        cache = keys_values(params, tokens)
        none = jnp.zeros((cfg.n_expert_layers, count), bool)
        plain, margins = logits(params, tokens, cache, start, none)
        ties = margins < ROUTER_TIE
        return plain, [
            (logits(params, tokens, cache, start,
                    none.at[layer].set(ties[layer]))[0], ties[layer])
            for layer in range(cfg.n_expert_layers)]

    return forward
