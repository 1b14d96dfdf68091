"""Qwen3-Next: ``deepspeed_tpu.models.qwen3_next`` under the keys of
Qwen/Qwen3-Next-80B-A3B-Instruct's ``config.json``, as ONE RANK's share
of an expert-parallel deployment.

``RANKS`` chips share each layer: the mixers (Gated DeltaNet and gated
attention), the shared expert and the router (over all the experts
there are) are replicated, and a configuration's ``num_experts`` counts
the experts held HERE, rank 0's: the published count is ``RANKS`` times
it.  The arithmetic below is of the share: held experts only.

What this file adds to what a family's file holds (``families/
pangu_ultra_moe.py``): ``state_bytes_per_slot`` (a slot's recurrent
state, which a decode step reads and writes whatever the sequence's
length: ``readers/gdn.py``, ``readers/decode_step_roofline_state.py``)
and, inside ``router_probe``, a probe of a slot's state
(:func:`state_probe`): the token check cannot see a state kept in fewer
bits than float32, which moves few tokens.
"""

import jax

from benchmark.reference import qwen3_next as reference

RANKS = 8


def program_config(model, **overrides):
    from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig

    held = model["num_experts"]
    return Qwen3NextConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        full_attention_interval=model["full_attention_interval"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        partial_rotary_factor=model["partial_rotary_factor"],
        lin_k_heads=model["linear_num_key_heads"],
        lin_v_heads=model["linear_num_value_heads"],
        lin_k_dim=model["linear_key_head_dim"],
        lin_v_dim=model["linear_value_head_dim"],
        conv_kernel=model["linear_conv_kernel_dim"],
        moe_ffn_dim=model["moe_intermediate_size"],
        shared_ffn_dim=model["shared_expert_intermediate_size"],
        n_routed_experts=held * RANKS, experts_held=(0, held),
        top_k=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"], **overrides)


def toy(model):
    return dict(model, vocab_size=512, hidden_size=128, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                linear_num_key_heads=2, linear_num_value_heads=4,
                linear_key_head_dim=32, linear_value_head_dim=32,
                moe_intermediate_size=64, shared_expert_intermediate_size=64,
                num_experts=2, num_experts_per_tok=4,
                max_position_embeddings=1024)


def init_params(cfg, key, dtype):
    """The program's own initialiser; ``key`` is an argument of the jit
    that calls this, never a constant in it."""
    from deepspeed_tpu.models import qwen3_next

    return qwen3_next.init_params(key, cfg, dtype)


def _counts(cfg):
    """Parameters of (a DeltaNet mixer, an attention mixer, one expert,
    the shared expert with its gate, the router), a layer."""
    d = cfg.dim
    Kd, Vd = cfg.lin_k_heads * cfg.lin_k_dim, cfg.lin_v_heads * cfg.lin_v_dim
    gdn = (d * (2 * Kd + 2 * Vd) + d * 2 * cfg.lin_v_heads + Vd * d
           + cfg.conv_kernel * cfg.conv_channels + 2 * cfg.lin_v_heads
           + cfg.lin_v_dim)
    attn = (2 * d * cfg.n_heads * cfg.head_dim
            + 2 * d * cfg.n_kv_heads * cfg.head_dim
            + cfg.n_heads * cfg.head_dim * d + 2 * cfg.head_dim)
    return (gdn, attn, 3 * d * cfg.moe_ffn_dim,
            3 * d * cfg.shared_ffn_dim + d, d * cfg.n_routed_experts)


def param_count(cfg):
    """What this rank holds."""
    gdn, attn, expert, shared, router = _counts(cfg)
    every = cfg.experts_held[1] * expert + shared + router + 2 * cfg.dim
    return (cfg.n_lin_layers * gdn + cfg.n_full_layers * attn
            + cfg.n_layers * every + 2 * cfg.vocab_size * cfg.dim + cfg.dim)


def routed_param_count(cfg):
    """What one token multiplies with on this rank: of its top-k experts
    the held share (top_k x held / all, on average), the shared expert,
    the router, the mixers' projections and the output head."""
    gdn, attn, expert, shared, router = _counts(cfg)
    here = cfg.top_k * cfg.experts_held[1] / cfg.n_routed_experts
    return (cfg.n_lin_layers * gdn + cfg.n_full_layers * attn
            + cfg.n_layers * (here * expert + shared + router)
            + cfg.vocab_size * cfg.dim)


def serve_flops_per_token(cfg, context):
    """Forward only, as routed: 2 per weight a token meets; per token of
    context attended, in the attention layers alone, 2 x heads x (score
    + value); in a linear layer the recurrence's three products over a
    head's state, whatever the context (``roofline/gdn.py``)."""
    from benchmark.roofline import gdn

    return (2 * routed_param_count(cfg)
            + cfg.n_lin_layers * gdn.rule_flops(cfg, 1)
            + 2 * cfg.n_full_layers * cfg.n_heads * 2 * cfg.head_dim
            * context)


def weight_bytes(cfg, itemsize=2):
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of the attention layers alone: the linear layers leave
    nothing a token."""
    return cfg.n_full_layers * cfg.n_kv_heads * cfg.head_dim * 2 * itemsize


def state_bytes_per_slot(cfg, itemsize=2):
    """What a slot keeps in the linear layers, whatever its length: the
    float32 state and the convolution's rows."""
    return cfg.n_lin_layers * (
        cfg.lin_v_heads * cfg.lin_k_dim * cfg.lin_v_dim * 4
        + (cfg.conv_kernel - 1) * cfg.conv_channels * itemsize)


# A router margin under this share of the largest router logit is a tie.
# Top 10 of 512 sit closer still than top 8 of 256: the 10th and 11th
# largest of 512 normal logits are 0.04 sigma apart on average, 2^-6.2 of
# the largest (3.05 sigma).  2^-7 is what bf16 hidden states move a logit
# by (a few of their 2^-9 roundings), as in families/pangu_ultra_moe.py.
ROUTER_TIE = 2.0 ** -7

ROUTER_PROBE_ROWS = 4096
ROUTER_PROBE_LIMIT = 4

# the state probe: whole chunks of the cell's, then a last chunk with
# a 64th of its rows real, then steps.  A state kept in fewer bits is
# rounded once a chunk but once a token by the steps, so the steps are
# what shows it: 8 of them read a bfloat16 state 1.3 times a float32
# one, 32 of them 1.6 times (v5e, PR 35)
STATE_PROBE_CHUNKS = 3
STATE_PROBE_STEPS = 32
# |S - S_ref|_F / |S_ref|_F a value head: a limit for the heads' mean
# and one for the largest head, which moves more from seed to seed (what
# reads over them, and why these limits, is in the configuration's
# check_why)
STATE_PROBE_LIMIT = 0.0044
STATE_PROBE_LIMIT_WORST_HEAD = 0.0068


def _ref_kw(cfg):
    return dict(interval=cfg.full_attention_interval, head_dim=cfg.head_dim,
                rotary=cfg.rotary_dim, rope_theta=cfg.rope_theta,
                k_heads=cfg.lin_k_heads, v_heads=cfg.lin_v_heads,
                k_dim=cfg.lin_k_dim, v_dim=cfg.lin_v_dim, top_k=cfg.top_k,
                first=cfg.experts_held[0], normalize=cfg.norm_topk_prob,
                eps=cfg.norm_eps)


def state_probe(cfg, params, seed, chunk_rows):
    """A slot's state after a long prompt, against the reference's.

    The serving programs themselves (``serving.serving_programs`` over
    ``forward_paged``, as ``serving_engine`` builds them, jitted here
    over a private cache of two slots) run a seeded prompt as the engine
    runs one: ``STATE_PROBE_CHUNKS`` whole chunks of ``chunk_rows``
    tokens into slot 0 and a last chunk of which a 64th is real (a
    prompt that ends just past a chunk's edge: the rows past its last
    real token must move nothing); between two chunks a decode step over
    both slots, slot 1 live and slot 0 as the engine uploads a slot that
    is between chunks (length 0, the trash page for a table), which must
    leave slot 0's state as it was; then ``STATE_PROBE_STEPS`` decode
    steps with slot 0 live.  The last such step between chunks is a few
    tokens from the end, where every head still remembers it: after a
    whole chunk more, only the slowest would.  The state is kept in the
    dtype the engine keeps it in (``kernels.STATE_DTYPE``).

    What is compared is the first linear layer's state of slot 0 (its
    input is the embedding, so the reference needs that one layer: its
    token-by-token recurrence in float32 over the same tokens), a value
    head: ``|S - S_ref|_F / |S_ref|_F``, the heads' mean and the largest
    of them, each under its limit.  A float32 state parts from the
    reference by what bf16 projections feed it, every head alike; a
    state kept in bf16 rounds
    every token's update besides, and the roundings add up over the
    tokens a head remembers; a decode step or a padded row that moves
    the state adds tokens the prompt never had, which the fast heads
    show most.

    A pass of the check's own over the programs' functions at the
    cell's widths, not the compiled programs that were timed."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import kernels, serving
    from deepspeed_tpu.inference.paged_forward import forward_paged
    from deepspeed_tpu.models import qwen3_next as program

    page, steps = 16, STATE_PROBE_STEPS
    n = STATE_PROBE_CHUNKS * chunk_rows + max(1, chunk_rows // 64)
    pages = -(-(n + steps) // page)
    rng = np.random.default_rng((seed ^ 0x5A5A) & 0x7FFFFFFF)
    seq = rng.integers(0, cfg.vocab_size, n + steps)
    dtype = params["embed"].dtype
    row = program._state_row(cfg)
    trash = 2 * pages
    shape = (cfg.n_full_layers, cfg.n_kv_heads, trash + 1, page,
             cfg.head_dim)
    tables = np.arange(2 * pages, dtype=np.int32).reshape(2, pages)
    cache = kernels.PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
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
        cache = cache._replace(k=view.k, v=view.v, conv=view.conv,
                               state=view.state,
                               expert_rows=view.expert_rows)
        if done + take < n:                 # slot 0 is between chunks
            cache = step(cache, [0, 5 + done // chunk_rows], [7, 7])
    for j in range(steps):
        cache = step(cache, [n + j, 0], [seq[n + j], 7])
    lp = jax.tree.map(lambda a: a[0], params["gdn_blocks"])
    gdn = {k: v for k, v in _ref_kw(cfg).items()
           if k in ("k_heads", "v_heads", "k_dim", "v_dim", "eps")}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, lp: reference._delta_net(
            x, lp, (jnp.zeros(row.conv), jnp.zeros(row.state)),
            x.shape[0], **gdn)[1][1])(
                params["embed"][jnp.asarray(seq)].astype(jnp.float32), lp)
    got = cache.state[0, 0].astype(jnp.float32)
    norm = lambda a: jnp.sqrt((a * a).sum((-2, -1)))
    error = norm(got - want) / norm(want)
    return {"tokens": n + steps, "chunk": chunk_rows,
            "state_dtype": str(jnp.dtype(kernels.STATE_DTYPE)),
            "error_worst_head": float(error.max()),
            "error_mean": float(error.mean()),
            "limit": STATE_PROBE_LIMIT,
            "limit_worst_head": STATE_PROBE_LIMIT_WORST_HEAD}


def router_probe(cfg, params, seed, step_rows, chunk_rows):
    """The probes of the check's own that ``runners/serve_backlog_long``
    runs beside the token check, under the one name it knows.

    The router's, as ``families/pangu_ultra_moe.py::router_probe``: the
    program's expert layer (the first linear layer's) on seeded hidden
    states against the reference's float32 softmax router, ``step_rows``
    and ``chunk_rows`` at a time: how many rows the two send to the held
    experts differently.  And :func:`state_probe`, whose result rides
    along under ``state``; where it is over its limit, ``differ`` is
    raised over the router's limit too, so that the runner, which reads
    ``differ`` alone, fails the run: the run's ``token_check.
    router_probe.state`` says which probe it was."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import qwen3_next as program

    first, held = cfg.experts_held
    lp = jax.tree.map(lambda a: a[0], params["gdn_blocks"])
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
                                    cfg.top_k, cfg.norm_topk_prob)
    here = idx[..., None] == first + jnp.arange(held)       # [N, k, Eh]
    differ, routed_here = 0, 0
    for rows in (step_rows, chunk_rows):
        got, n = counted(rows)
        want = here[:n].sum((0, 1))
        differ = max(differ, int(jnp.abs(got - want).sum()))
        routed_here = max(routed_here, int(want.sum()))
    state = state_probe(cfg, params, seed, chunk_rows)
    out = {"rows": ROUTER_PROBE_ROWS, "by": [step_rows, chunk_rows],
           "routed_here": routed_here, "differ": differ,
           "router_differ": differ, "limit": ROUTER_PROBE_LIMIT,
           "state": state}
    if not (state["error_mean"] <= state["limit"]              # or NaN
            and state["error_worst_head"] <= state["limit_worst_head"]):
        out["differ"] = max(differ, ROUTER_PROBE_LIMIT + 1)
        out["state"]["failed"] = True
    return out


def reference_logits(cfg):
    """(params, tokens[T], start, count) -> (logits[count, V] of the
    positions from start, alternatives).  An alternative is (logits,
    where[count]): the logits with the k-th expert swapped for the
    (k+1)-th at every position of one layer whose router is on a tie
    there, and the positions that may claim it: those ties."""
    import jax.numpy as jnp

    kw = _ref_kw(cfg)
    carry = jax.jit(lambda p, t, start: reference.carry(p, t, start, **kw))
    logits = jax.jit(lambda p, t, held, start, swap: reference.logits(
        p, t, held, start, swap.shape[1], swap, **kw))

    def forward(params, tokens, start, count):
        held = carry(params, tokens, start)
        none = jnp.zeros((cfg.n_layers, count), bool)
        plain, margins = logits(params, tokens, held, start, none)
        ties = margins < ROUTER_TIE
        return plain, [
            (logits(params, tokens, held, start,
                    none.at[layer].set(ties[layer]))[0], ties[layer])
            for layer in range(cfg.n_layers)]

    return forward
