"""Ling-3.0-flash-VL's language model: ``deepspeed_tpu.models.ling_flash``
under the keys of inclusionAI/Ling-3.0-flash-VL's ``config.json``, as
ONE RANK's share of an expert-parallel deployment.

``RANKS`` chips share each layer: the mixers (KDA and latent attention),
the shared expert and the router (over all the experts there are, its
group limit and all) are replicated, and a configuration's
``num_experts`` counts the experts held HERE, rank 0's: the published
count is ``RANKS`` times it.  With as many groups as ranks the held
experts are one whole group, group 0.  The arithmetic below is of the
share: held experts only.

Beside what ``families/qwen3_next.py`` holds: two alternatives a layer
for the token check (the expert on a tie and the GROUP on a tie: a
token whose 4th and 5th groups score alike may keep either, and where
one of them is group 0 that is a quarter of its routed weight here or
not), and the state probe reads the first and the last KDA layer.
"""

import jax

from benchmark.reference import ling_flash as reference

RANKS = 8


def program_config(model, **overrides):
    from deepspeed_tpu.models.ling_flash import LingFlashConfig

    held = model["num_experts"]
    assert model["head_dim"] == model["v_head_dim"] \
        == model["qk_nope_head_dim"], "a KDA head is head_dim x head_dim"
    return LingFlashConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_dense_layers=model["first_k_dense_replace"],
        layer_group_size=model["layer_group_size"],
        n_heads=model["num_attention_heads"],
        kda_head_dim=model["head_dim"],
        conv_kernel=model["short_conv_kernel_size"],
        kda_lower_bound=float(model["kda_lower_bound"]),
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        ffn_dim=model["intermediate_size"],
        moe_ffn_dim=model["moe_intermediate_size"],
        shared_ffn_dim=model["moe_shared_expert_intermediate_size"],
        n_routed_experts=held * RANKS, experts_held=(0, held),
        top_k=model["num_experts_per_tok"], n_group=model["n_group"],
        topk_group=model["topk_group"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"], **overrides)


def toy(model):
    return dict(model, vocab_size=512, hidden_size=128, num_hidden_layers=6,
                first_k_dense_replace=1, layer_group_size=3,
                num_attention_heads=4, num_key_value_heads=4, head_dim=32,
                kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                rotary_dim=16, v_head_dim=32, intermediate_size=256,
                moe_intermediate_size=64,
                moe_shared_expert_intermediate_size=64, num_experts=16,
                max_position_embeddings=1024)


def init_params(cfg, key, dtype):
    """The program's own initialiser; ``key`` is an argument of the jit
    that calls this, never a constant in it."""
    from deepspeed_tpu.models import ling_flash

    return ling_flash.init_params(key, cfg, dtype)


def _counts(cfg):
    """Parameters of (a KDA mixer, a latent-attention mixer, a dense
    layer's MLP, one expert, the shared expert, the router with its
    bias), a layer; norms inside."""
    from benchmark.roofline import kda

    d, H = cfg.dim, cfg.n_heads
    mixer = (kda.projection_params(cfg) + cfg.conv_kernel * cfg.conv_channels
             + H + H * cfg.kda_head_dim + cfg.kda_head_dim)
    mla = (d * H * (cfg.qk_nope_dim + cfg.qk_rope_dim)
           + d * cfg.row_width
           + cfg.kv_lora_rank * H * (cfg.qk_nope_dim + cfg.v_head_dim)
           + d * H + H * cfg.v_head_dim * d + cfg.kv_lora_rank)
    return (mixer, mla, 3 * d * cfg.ffn_dim, 3 * d * cfg.moe_ffn_dim,
            3 * d * cfg.shared_ffn_dim,
            d * cfg.n_routed_experts + cfg.n_routed_experts)


def param_count(cfg):
    """What this rank holds."""
    mixer, mla, mlp, expert, shared, router = _counts(cfg)
    every = cfg.experts_held[1] * expert + shared + router
    return (cfg.n_kda_layers * mixer + cfg.n_mla_layers * mla
            + cfg.n_dense_layers * mlp + cfg.n_expert_layers * every
            + cfg.n_layers * 2 * cfg.dim
            + 2 * cfg.vocab_size * cfg.dim + cfg.dim)


def routed_param_count(cfg):
    """What one token multiplies with on this rank: of its top-k experts
    the held share (top_k x held / all, on average, whatever the group
    limit does to which tokens they come from), the shared expert, the
    router, the mixers' projections and the output head."""
    mixer, mla, mlp, expert, shared, router = _counts(cfg)
    here = cfg.top_k * cfg.experts_held[1] / cfg.n_routed_experts
    return (cfg.n_kda_layers * mixer + cfg.n_mla_layers * mla
            + cfg.n_dense_layers * mlp
            + cfg.n_expert_layers * (here * expert + shared + router)
            + cfg.vocab_size * cfg.dim)


def serve_flops_per_token(cfg, context):
    """Forward only, as routed: 2 per weight a token meets; per token of
    context attended, in the latent layers alone, 2 x heads x (the
    score's 128 + 64, the value's 128); in a KDA layer the recurrence's
    three products over a head's state, whatever the context
    (``roofline/kda.py``)."""
    from benchmark.roofline import kda

    per_key = cfg.qk_nope_dim + cfg.qk_rope_dim + cfg.v_head_dim
    return (2 * routed_param_count(cfg)
            + cfg.n_kda_layers * kda.rule_flops(cfg, 1)
            + 2 * cfg.n_mla_layers * cfg.n_heads * per_key * context)


def weight_bytes(cfg, itemsize=2):
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    """One latent row a latent layer (the numbers that count, not the
    lanes the pool stores them in): the KDA layers leave nothing a
    token."""
    return cfg.n_mla_layers * cfg.row_width * itemsize


def state_bytes_per_slot(cfg, itemsize=2):
    """What a slot keeps in the KDA layers, whatever its length: the
    float32 state and the convolution's rows."""
    from benchmark.roofline import kda

    return cfg.n_kda_layers * kda.state_bytes(cfg, itemsize)


# A router margin under this share of the largest router logit is a tie
# (``reference.route``'s margins are in the logit's units): what bf16
# hidden states move a logit by, as in families/pangu_ultra_moe.py.
ROUTER_TIE = 2.0 ** -7

ROUTER_PROBE_ROWS = 4096
ROUTER_PROBE_LIMIT = 4

# the state probe, as families/qwen3_next.py's: whole chunks of the
# cell's, a last chunk with a 64th of its rows real, decode steps of the
# other slot between the chunks, then steps of its own
STATE_PROBE_CHUNKS = 3
STATE_PROBE_STEPS = 32
# |S - S_ref|_F / |S_ref|_F a head, the heads' mean and the largest, of
# the first KDA layer (its input is the embedding: what parts it from
# the reference is the layer's own arithmetic) and of the last (which
# sees every layer before it, their routers' flips too).  What reads
# over them, and why these limits, is in the configuration's check_why
STATE_PROBE_LIMITS = {"first": (0.0058, 0.0061), "last": (0.26, 0.30)}


def _ref_kw(cfg):
    return dict(group=cfg.layer_group_size, heads=cfg.n_heads,
                lower=cfg.kda_lower_bound, nope=cfg.qk_nope_dim,
                rope_theta=cfg.rope_theta, top_k=cfg.top_k,
                groups=(cfg.n_group, cfg.topk_group),
                first=cfg.experts_held[0],
                scale=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob, eps=cfg.norm_eps)


def state_probe(cfg, params, seed, chunk_rows):
    """A slot's KDA states after a long prompt, against the reference's.

    As ``families/qwen3_next.py::state_probe``, whose account of the
    schedule holds here word for word: the serving programs themselves
    (``serving.serving_programs`` over ``forward_paged``, jitted over a
    private cache of two slots) run ``STATE_PROBE_CHUNKS`` whole chunks
    of ``chunk_rows`` tokens into slot 0 and a last chunk of which a
    64th is real; between two chunks a decode step over both slots with
    slot 0 masked as the engine masks a slot between chunks; then
    ``STATE_PROBE_STEPS`` decode steps with slot 0 live.

    What is compared is slot 0's state in the first and in the last KDA
    layer with the reference's token-by-token float32 recurrence over
    the same tokens through the whole model (``reference.state_after``),
    a head: ``|S - S_ref|_F / |S_ref|_F``, the heads' mean and the
    largest of them, each layer under its own two limits.

    A pass of the check's own over the programs' functions at the
    cell's widths, not the compiled programs that were timed."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import kernels, serving
    from deepspeed_tpu.inference.paged_forward import forward_paged
    from deepspeed_tpu.models.family import decoder_family

    page, steps = 16, STATE_PROBE_STEPS
    n = STATE_PROBE_CHUNKS * chunk_rows + max(1, chunk_rows // 64)
    pages = -(-(n + steps) // page)
    rng = np.random.default_rng((seed ^ 0x5A5A) & 0x7FFFFFFF)
    seq = rng.integers(0, cfg.vocab_size, n + steps)
    dtype = params["embed"].dtype
    row = decoder_family(cfg).recurrent.state_row(cfg)
    trash = 2 * pages
    tables = np.arange(2 * pages, dtype=np.int32).reshape(2, pages)
    cache = kernels.PagedKVCache(
        k=jnp.zeros((cfg.n_mla_layers, 1, trash + 1, page, cfg.head_dim),
                    dtype), v=None,
        table=jnp.asarray(tables), seq_lens=jnp.zeros((2,), jnp.int32),
        page_size=page,
        expert_rows=jnp.zeros((cfg.experts_held[1],), jnp.int32),
        conv=jnp.zeros((row.layers, 2) + row.conv, dtype),
        state=jnp.zeros((row.layers, 2) + row.state, kernels.STATE_DTYPE))
    forward = lambda continuation: lambda p, t, c: forward_paged(
        p, t, cfg, c, continuation=continuation, tp=False)
    sample = lambda logits, keys, temps: jnp.argmax(logits, -1).astype(
        jnp.int32)
    _, chunk, _, _, decode = serving.serving_programs(
        forward(False), forward(False), forward(True), sample, 1, 2,
        expert_rows=True, state=True)
    chunk, decode = jax.jit(chunk), jax.jit(decode)
    key, zero = jax.random.PRNGKey(0), jnp.zeros((), jnp.int32)

    def step(cache, lens, toks):
        """One decode program over both slots; a slot of length 0 gets
        the trash page for a table, as the engine uploads it."""
        table = np.where(np.asarray(lens)[:, None] > 0, tables, trash)
        _, out = decode(params, jnp.asarray(toks, jnp.int32)[:, None],
                        cache._replace(
                            table=jnp.asarray(table),
                            seq_lens=jnp.asarray(lens, jnp.int32)),
                        key, zero, jnp.zeros((2,), jnp.float32))
        return out

    for done in range(0, n, chunk_rows):
        take = min(chunk_rows, n - done)
        toks = np.zeros((1, chunk_rows), np.int32)
        toks[0, :take] = seq[done:done + take]
        view = cache._replace(
            table=jnp.asarray(tables[0:1]), slot=jnp.zeros((1,), jnp.int32),
            seq_lens=jnp.full((1,), done, jnp.int32))
        _, view = chunk(params, jnp.asarray(toks), view,
                        jnp.full((1,), take - 1, jnp.int32))
        cache = cache._replace(k=view.k, conv=view.conv, state=view.state,
                               expert_rows=view.expert_rows)
        if done + take < n:                 # slot 0 is between chunks
            cache = step(cache, [0, 5 + done // chunk_rows], [7, 7])
    for j in range(steps):
        cache = step(cache, [n + j, 0], [seq[n + j], 7])
    kw = _ref_kw(cfg)
    want = jax.jit(lambda p, t: reference.state_after(
        p, t, t.shape[0], **kw)[1])(params, jnp.asarray(seq))
    norm = lambda a: jnp.sqrt((a * a).sum((-2, -1)))
    out = {"tokens": n + steps, "chunk": chunk_rows,
           "state_dtype": str(jnp.dtype(kernels.STATE_DTYPE))}
    for name, layer in (("first", 0), ("last", row.layers - 1)):
        got = cache.state[layer, 0].astype(jnp.float32)
        error = norm(got - want[layer]) / norm(want[layer])
        mean, worst = STATE_PROBE_LIMITS[name]
        out[name] = {"error_mean": float(error.mean()),
                     "error_worst_head": float(error.max()),
                     "limit": mean, "limit_worst_head": worst}
    out["failed"] = not all(                                    # or NaN
        r["error_mean"] <= r["limit"]
        and r["error_worst_head"] <= r["limit_worst_head"]
        for r in (out["first"], out["last"]))
    return out


def router_probe(cfg, params, seed, step_rows, chunk_rows):
    """The probes of the check's own that ``runners/serve_backlog_long``
    runs beside the token check, under the one name it knows.

    The router's, as ``families/pangu_ultra_moe.py::router_probe``: the
    program's expert layer (the first of the KDA stack) on seeded hidden
    states against the reference's float32 group-limited router,
    ``step_rows`` and ``chunk_rows`` at a time: how many rows the two
    send to the held experts differently.  A router without the group
    limit sends this rank rows from every token, not from the half that
    kept group 0.  And :func:`state_probe`, whose result rides along
    under ``state``; where it fails, ``differ`` is raised over the
    router's limit too, so that the runner, which reads ``differ`` alone,
    fails the run."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import ling_flash as program

    first, held = cfg.experts_held
    lp = jax.tree.map(lambda a: a[0], params["kda_blocks"])
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
        _, idx, _ = reference.route(
            h.astype(jnp.float32), lp["gate"], lp["gate_bias"], cfg.top_k,
            (cfg.n_group, cfg.topk_group), cfg.routed_scaling_factor,
            cfg.norm_topk_prob)
    here = idx[..., None] == first + jnp.arange(held)       # [N, k, Eh]
    differ, routed_here = 0, 0
    for rows in (step_rows, chunk_rows):
        got, n = counted(rows)
        want = here[:n].sum((0, 1))
        differ = max(differ, int(jnp.abs(got - want).sum()))
        routed_here = max(routed_here, int(want.sum()))
    state = state_probe(cfg, params, seed, chunk_rows)
    return {"rows": ROUTER_PROBE_ROWS, "by": [step_rows, chunk_rows],
            "routed_here": routed_here, "router_differ": differ,
            "differ": max(differ, ROUTER_PROBE_LIMIT + 1)
            if state["failed"] else differ,
            "limit": ROUTER_PROBE_LIMIT, "state": state}


def reference_logits(cfg):
    """(params, tokens[T], start, count) -> (logits[count, V] of the
    positions from start, alternatives).  An alternative is (logits,
    where[count]): the logits with, at every position of one expert
    layer whose router is on a tie there, the k-th expert swapped for
    the (k+1)-th, or the last kept group for the next, and the positions
    that may claim it: those ties."""
    import jax.numpy as jnp

    kw = _ref_kw(cfg)
    carry = jax.jit(lambda p, t, start: reference.carry(p, t, start, **kw))
    logits = jax.jit(lambda p, t, held, start, swap: reference.logits(
        p, t, held, start, swap.shape[1], swap, **kw))

    def forward(params, tokens, start, count):
        held = carry(params, tokens, start)
        none = jnp.zeros((cfg.n_layers, count), jnp.int32)
        plain, margins = logits(params, tokens, held, start, none)
        ties = margins < ROUTER_TIE                     # [L, 2, count]
        return plain, [
            (logits(params, tokens, held, start, none.at[layer].set(
                jnp.where(ties[layer, kind], kind + 1, 0)))[0],
             ties[layer, kind])
            for layer in range(cfg.n_dense_layers, cfg.n_layers)
            for kind in (0, 1)]

    return forward
