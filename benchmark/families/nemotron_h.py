"""Nemotron-H: ``deepspeed_tpu.models.nemotron_h`` under the keys of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's ``config.json``, as ONE
RANK's share of an expert-parallel deployment at the model's whole depth.

``RANKS`` chips share each layer: the Mamba-2 mixers, attention, the
router (over all the experts there are) and the shared expert are
replicated, and a configuration's ``n_routed_experts`` counts the experts
held HERE, rank 0's: the published count is ``RANKS`` times it.  The
arithmetic below is of the share, at the published widths (the program
stores an expert's 1,856 columns in 1,920: zeros are not parameters).

What this file adds to what a sparse share's file holds (``families/
laguna.py``): the Mamba-2 layers' ``state_bytes_per_slot`` and a probe
of a slot's state beside the router's (:func:`state_probe`, as
``families/granite_hybrid.py`` and for its reason: the token check
cannot be counted on to see a state kept in fewer bits than float32).
"""

import jax

from benchmark.families.granite_hybrid import (STATE_PROBE_CHUNKS,
                                               STATE_PROBE_STEPS,
                                               state_failed)
from benchmark.reference import nemotron_h as reference
from benchmark.roofline import ssm

RANKS = 8

# keys of the source whose value says which layer this program builds;
# any other value is another model
_STATED = {"attention_bias": False, "mlp_bias": False, "use_bias": False,
           "mamba_proj_bias": False, "use_conv_bias": True,
           "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
           "tie_word_embeddings": False, "n_shared_experts": 1,
           "n_group": 1, "topk_group": 1, "residual_in_fp32": False,
           "sliding_window": None}


def program_config(model, **overrides):
    try:
        from deepspeed_tpu.models.nemotron_h import NemotronHConfig
    except ImportError:
        # a program from before PR 48: the cell cannot run on it
        raise SystemExit("this program has no family nemotron_h "
                         "(deepspeed_tpu/models/nemotron_h.py): the cell "
                         "needs it")

    for key, value in _STATED.items():
        if model[key] != value:
            raise SystemExit(f"nemotron_h builds {key} = {value!r}, and "
                             f"the configuration says {model[key]!r}")
    if model["norm_eps"] != model["layer_norm_epsilon"] \
            or model["intermediate_size"] != model["moe_intermediate_size"]:
        raise SystemExit("nemotron_h builds one epsilon and one expert "
                         "width")
    held = model["n_routed_experts"]
    return NemotronHConfig.from_pattern(
        model["hybrid_override_pattern"][:model["num_hidden_layers"]],
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        ssm_heads=model["mamba_num_heads"],
        ssm_head_dim=model["mamba_head_dim"],
        ssm_state=model["ssm_state_size"], ssm_groups=model["n_groups"],
        conv_kernel=model["conv_kernel"],
        moe_ffn_dim=model["moe_intermediate_size"],
        shared_ffn_dim=model["moe_shared_expert_intermediate_size"],
        n_routed_experts=held * RANKS, experts_held=(0, held),
        top_k=model["num_experts_per_tok"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"],
        max_seq_len=model["max_position_embeddings"],
        norm_eps=model["layer_norm_epsilon"],
        ssm_block=model["chunk_size"], **overrides)


def toy(model):
    """The published shape at a size the CPU walks in seconds
    (--rehearse): two periods in four sections with a part-period tail,
    two groups, an expert width that is not whole tiles."""
    pattern = "MEM*EMEM*EMEMEM*EMEME"
    return dict(
        model, vocab_size=512, hidden_size=128,
        hybrid_override_pattern=pattern, num_hidden_layers=len(pattern),
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        mamba_num_heads=8, mamba_head_dim=32, ssm_state_size=32,
        n_groups=2, intermediate_size=72, moe_intermediate_size=72,
        moe_shared_expert_intermediate_size=144, n_routed_experts=2,
        num_experts_per_tok=4, chunk_size=16,
        max_position_embeddings=1024)


def init_params(cfg, key, dtype):
    """The program's own initialiser; ``key`` is an argument of the jit
    that calls this, never a constant in it."""
    from deepspeed_tpu.models import nemotron_h

    return nemotron_h.init_params(key, cfg, dtype)


def _counts(cfg):
    """Parameters of (a Mamba-2 layer, an attention layer, one expert,
    the shared expert, the router with its selection bias), each layer's
    one norm with it."""
    d = cfg.dim
    attn = 2 * d * cfg.n_heads * cfg.head_dim \
        + 2 * d * cfg.n_kv_heads * cfg.head_dim + d
    return (ssm.mixer_params(cfg) + d, attn, 2 * d * cfg.moe_ffn_dim,
            2 * d * cfg.shared_ffn_dim,
            (d + 1) * cfg.n_routed_experts)


def param_count(cfg):
    """What this rank holds, at the published widths."""
    mixer, attn, expert, shared, router = _counts(cfg)
    return (cfg.n_ssm_layers * mixer + cfg.n_attn_layers * attn
            + cfg.n_expert_layers * (cfg.experts_held[1] * expert + shared
                                     + router + cfg.dim)
            + 2 * cfg.vocab_size * cfg.dim + cfg.dim)


def routed_param_count(cfg):
    """What one token multiplies with on this rank: of its top-k experts
    the held share (top_k x held / all, on average), the shared expert,
    the router, the mixers' and attention's projections and the head."""
    mixer, attn, expert, shared, router = _counts(cfg)
    here = cfg.top_k * cfg.experts_held[1] / cfg.n_routed_experts
    return (cfg.n_ssm_layers * mixer + cfg.n_attn_layers * attn
            + cfg.n_expert_layers * (here * expert + shared + router)
            + cfg.vocab_size * cfg.dim)


def serve_flops_per_token(cfg, context):
    """Forward only, as routed: 2 per weight a token meets; per token of
    context attended, in the attention layers alone, 2 x heads x (score +
    value); in a Mamba-2 layer the recurrence over a head's state,
    whatever the context (``roofline/ssm.py``)."""
    return (2 * routed_param_count(cfg)
            + cfg.n_ssm_layers * ssm.rule_flops(cfg, 1)
            + 2 * cfg.n_attn_layers * cfg.n_heads * 2 * cfg.head_dim
            * context)


def weight_bytes(cfg, itemsize=2):
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of the attention layers alone: the other 46 layers leave
    nothing a token."""
    return cfg.n_attn_layers * cfg.n_kv_heads * cfg.head_dim * 2 * itemsize


def state_bytes_per_slot(cfg, itemsize=2):
    """What a slot keeps in the Mamba-2 layers, whatever its length: the
    float32 state and the convolution's rows."""
    return cfg.n_ssm_layers * ssm.state_bytes(cfg, itemsize)


def _ref_kw(cfg):
    return dict(pattern=cfg.pattern, head_dim=cfg.head_dim,
                heads=cfg.ssm_heads, groups=cfg.ssm_groups,
                state=cfg.ssm_state, top_k=cfg.top_k,
                first=cfg.experts_held[0], scale=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob, ffn=cfg.moe_ffn_dim,
                eps=cfg.norm_eps)


# The state probe is ``families/granite_hybrid.py``'s (its chunks, its
# steps, its reading of the limits) over this family's programs.
# |S - S_ref|_F / |S_ref|_F a head, a limit for the heads' mean and one
# for the largest head, a layer.  The first Mamba-2 layer's input is the
# embedding: what parts it from the reference is what bf16 projections
# feed it and what the state is kept in, and its limits lie between a
# float32 state's readings and a bfloat16 state's.  The last one's input
# has come through 50 layers, 22 of them routed: the program's router
# and the reference's part on near-ties and the reading is theirs (a
# mean of 0.07 to 0.22 whatever the state is kept in, one head as far
# as 0.83); the limit of its mean lies between that and 1, which is
# what a state never written, or lost, reads in every head (layer
# indices that do not run on from section to section); its largest head
# has no limit a good run keeps under (2 is past what any state reads).
# The readings are in the configuration's check_why.
STATE_PROBE_LIMITS = {"first": (0.0047, 0.0075), "last": (0.6, 2.0)}


def state_probe(cfg, params, seed, chunk_rows):
    """A slot's state after a prompt as the engine runs one, against the
    reference's: ``families/granite_hybrid.py::state_probe`` over this
    family's programs (an expert layer's counts ride in the cache).

    ``STATE_PROBE_CHUNKS`` whole chunks of ``chunk_rows`` tokens into
    slot 0 and a last chunk of which a sixteenth is real; between two
    chunks a decode step over both slots, slot 1 live and slot 0 as the
    engine uploads a slot that is between chunks, which must leave slot
    0's state as it was; then ``STATE_PROBE_STEPS`` decode steps with
    slot 0 live.  Slot 0's state in the first and in the last Mamba-2
    layer is compared with the reference's token-by-token float32
    recurrence over the same tokens, a head.

    A pass of the check's own over the programs' functions at the
    cell's widths, not the compiled programs that were timed."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import kernels, serving
    from deepspeed_tpu.inference.paged_forward import forward_paged
    from deepspeed_tpu.models import nemotron_h as program

    page, steps = 16, STATE_PROBE_STEPS
    n = STATE_PROBE_CHUNKS * chunk_rows + max(1, chunk_rows // 16)
    pages = -(-(n + steps) // page)
    rng = np.random.default_rng((seed ^ 0x5A5A) & 0x7FFFFFFF)
    seq = rng.integers(0, cfg.vocab_size, n + steps)
    dtype = params["embed"].dtype
    row = program.FAMILY.recurrent.state_row(cfg)
    trash = 2 * pages
    shape = (cfg.n_attn_layers, cfg.n_kv_heads, trash + 1, page,
             cfg.head_dim)
    tables = np.arange(2 * pages, dtype=np.int32).reshape(2, pages)
    cache = kernels.PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        table=jnp.asarray(tables), seq_lens=jnp.zeros((2,), jnp.int32),
        page_size=page,
        expert_rows=jnp.zeros((cfg.experts_held[1] + 1,), jnp.int32),
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
    _, want = reference.state_after(params, jnp.asarray(seq), n + steps,
                                    **_ref_kw(cfg))
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
    return out


# A router logit moves by 2^-7 of the largest logit under bf16 hidden
# states (``families/pangu_ultra_moe.py``); the reference's margin is a
# gap of SCORES over a quarter of that logit (a sigmoid's slope is at
# most a quarter), so the same number says the same thing.
ROUTER_TIE = 2.0 ** -7
# The token check cannot see the router's precision on a share (a
# flipped 6th expert matters only when it or its rival is one of the 16
# held); the probe looks at the router itself.
ROUTER_PROBE_ROWS = 4096
ROUTER_PROBE_LIMIT = 4


def router_probe(cfg, params, seed, step_rows, chunk_rows):
    """The probes of the check's own that ``runners/serve_backlog_long``
    runs beside the token check, under the one name it knows.

    The router's, as ``families/laguna.py::router_probe``: the program's
    expert layer (the first one's) on seeded hidden states against the
    reference's float32 router with its selection bias, ``step_rows`` and
    ``chunk_rows`` at a time: how many rows the two send to the held
    experts differently.  And :func:`state_probe`, whose result rides
    along under ``state``; where it is over a limit, ``differ`` is raised
    over the router's limit too, so that the runner, which reads
    ``differ`` alone, fails the run: ``state.failed`` says which probe it
    was."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import nemotron_h as program

    first, held = cfg.experts_held
    lp = jax.tree.map(lambda a: a[0], params["moe_blocks"])
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
            cfg.routed_scaling_factor, cfg.norm_topk_prob)
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
    if state_failed(state):
        out["differ"] = max(differ, ROUTER_PROBE_LIMIT + 1)
        out["state"]["failed"] = True
    return out


def reference_logits(cfg):
    """(params, tokens[T], start, count) -> (logits[count, V] of the
    positions from start, alternatives).  An alternative is (logits,
    where[count]): the logits with the k-th expert swapped for the
    (k+1)-th at every position of one expert layer whose router is on a
    tie there, and the positions that may claim it: those ties.  A layer
    with no tie in the stretch has no alternative."""
    import jax.numpy as jnp

    kw = _ref_kw(cfg)

    def forward(params, tokens, start, count):
        held = reference.carry(params, tokens, start, **kw)
        none = jnp.zeros((cfg.n_expert_layers, count), bool)
        plain, margins = reference.logits(params, tokens, held, start,
                                          count, none, **kw)
        ties = margins < ROUTER_TIE
        return plain, [
            (reference.logits(params, tokens, held, start, count,
                              none.at[layer].set(ties[layer]), **kw)[0],
             ties[layer])
            for layer in range(cfg.n_expert_layers)
            if bool(ties[layer].any())]

    return forward
