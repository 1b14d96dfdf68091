"""openPangu-Ultra-MoE: ``deepspeed_tpu.models.pangu_ultra_moe`` under the
keys of FreedomIntelligence/openPangu-Ultra-MoE-718B's ``config.json``,
as ONE RANK's share of an expert-parallel deployment.

``RANKS`` chips share each layer: attention, the shared expert and the
router (over all the experts there are) are replicated, and a
configuration's ``n_routed_experts`` counts the experts held HERE, rank
0's: the published count is ``RANKS`` times it.  The arithmetic below is
of the share: held experts only.
"""

import jax

from benchmark.reference import pangu_ultra_moe as reference

RANKS = 16


def program_config(model, **overrides):
    from deepspeed_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig

    held = model["n_routed_experts"]
    return PanguUltraMoEConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_dense_layers=model["first_k_dense_replace"],
        n_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        ffn_dim=model["intermediate_size"],
        moe_ffn_dim=model["moe_intermediate_size"],
        n_routed_experts=held * RANKS, experts_held=(0, held),
        top_k=model["num_experts_per_tok"],
        n_shared_experts=model["n_shared_experts"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"], **overrides)


def toy(model):
    return dict(model, vocab_size=512, hidden_size=128, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=64,
                qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                intermediate_size=256, moe_intermediate_size=64,
                n_routed_experts=2, max_position_embeddings=1024)


def init_params(cfg, key, dtype):
    """The program's own initialiser; ``key`` is an argument of the jit
    that calls this, never a constant in it."""
    from deepspeed_tpu.models import pangu_ultra_moe

    return pangu_ultra_moe.init_params(key, cfg, dtype)


def _counts(cfg):
    """Parameters of (attention, a dense layer's MLP, one expert, the
    shared expert, the router), a layer."""
    d, H = cfg.dim, cfg.n_heads
    attn = (d * cfg.q_lora_rank
            + cfg.q_lora_rank * H * (cfg.qk_nope_dim + cfg.qk_rope_dim)
            + d * (cfg.kv_lora_rank + cfg.qk_rope_dim)
            + cfg.kv_lora_rank * H * (cfg.qk_nope_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * d)
    expert = 3 * d * cfg.moe_ffn_dim
    return (attn, 3 * d * cfg.ffn_dim, expert,
            expert * cfg.n_shared_experts, d * cfg.n_routed_experts)


def _norms(cfg):
    return 4 * cfg.dim + cfg.q_lora_rank + cfg.kv_lora_rank


def param_count(cfg):
    """What this rank holds."""
    attn, mlp, expert, shared, router = _counts(cfg)
    held = cfg.experts_held[1]
    return (cfg.n_dense_layers * (attn + mlp + _norms(cfg))
            + cfg.n_expert_layers * (attn + held * expert + shared + router
                                     + _norms(cfg))
            + 2 * cfg.vocab_size * cfg.dim + cfg.dim)


def routed_param_count(cfg):
    """What one token multiplies with on this rank: of its top-k experts
    the held share (top_k x held / all, on average), the shared expert,
    the router, attention's projections and the output head."""
    attn, mlp, expert, shared, router = _counts(cfg)
    here = cfg.top_k * cfg.experts_held[1] / cfg.n_routed_experts
    return (cfg.n_dense_layers * (attn + mlp)
            + cfg.n_expert_layers * (attn + here * expert + shared + router)
            + cfg.vocab_size * cfg.dim)


def serve_flops_per_token(cfg, context):
    """Forward only, as routed, attention in the published per-head
    form: 2 per weight a token meets, and per token of context attended
    2 x heads x (the score's 128 + 64, the value's 128)."""
    per_key = cfg.qk_nope_dim + cfg.qk_rope_dim + cfg.v_head_dim
    return (2 * routed_param_count(cfg)
            + 2 * cfg.n_layers * cfg.n_heads * per_key * context)


def weight_bytes(cfg, itemsize=2):
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    """One latent row a layer: the numbers that count, not the lanes the
    pool stores them in."""
    return cfg.n_layers * cfg.row_width * itemsize


# A router margin under this share of the largest router logit is a tie.
# Top 8 of 256 sit closer than top 2 of 8: the 8th and 9th largest of 256
# normal logits are 0.055 sigma apart on average, 2^-5.7 of the largest
# (2.8 sigma), so a fifth of the positions of a layer are "ties" at
# Mixtral's 2^-5 and half of those by chance.  2^-7 is what bf16 hidden
# states move a logit by (a few of their 2^-9 roundings).
ROUTER_TIE = 2.0 ** -7


# The token check cannot see the router's precision: on this rank's share
# a flipped 8th expert matters only when it or its rival is one of the 16
# held, so a bfloat16 router reads 99.6% of tokens near where float32
# reads 99.9-100% (my chip runs, PR 33).  The probe looks at the router
# itself, through the program's expert layer at the two row counts the
# serving programs give it: a decode step's and a chunk's.
ROUTER_PROBE_ROWS = 4096
ROUTER_PROBE_LIMIT = 4


def router_probe(cfg, params, seed, step_rows, chunk_rows):
    """The program's expert layer (the first of the stack) on seeded
    hidden states, against the reference's float32 router on the same
    numbers: how many rows the two send to the held experts differently
    (the sum over held experts of the difference of their row counts).
    The rows go through twice, as the serving programs hand them over:
    ``step_rows`` at a time (a decode step; few rows, where every held
    expert evaluates every row) and ``chunk_rows`` at a time (a chunk:
    the grouped product), and the larger difference counts.  A float32
    router parts from the reference only where a margin is under
    float32's own rounding; a bfloat16 one at every margin under 2^-8 of
    a logit, a tenth of the rows.

    This is a pass of the check's own over the functions the programs
    are built from, not the compiled programs that were timed: those
    count rows over every slot, idle ones too, and not by request."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import pangu_ultra_moe as program

    first, held = cfg.experts_held
    lp = jax.tree.map(lambda a: a[0], params["blocks"])
    h = jax.random.normal(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                          (ROUTER_PROBE_ROWS, cfg.dim),
                          lp["gate"].dtype)

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

    kw = dict(n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
              qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
              top_k=cfg.top_k, first=cfg.experts_held[0],
              scale=cfg.routed_scaling_factor,
              normalize=cfg.norm_topk_prob, rope_theta=cfg.rope_theta,
              eps=cfg.norm_eps)
    latents = jax.jit(lambda p, t: reference.latents(p, t, **kw))
    logits = jax.jit(lambda p, t, cache, start, swap: reference.logits(
        p, t, cache, start, swap.shape[1], swap, **kw))

    def forward(params, tokens, start, count):
        cache = latents(params, tokens)
        none = jnp.zeros((cfg.n_expert_layers, count), bool)
        plain, margins = logits(params, tokens, cache, start, none)
        ties = margins < ROUTER_TIE
        return plain, [
            (logits(params, tokens, cache, start,
                    none.at[layer].set(ties[layer]))[0], ties[layer])
            for layer in range(cfg.n_expert_layers)]

    return forward
