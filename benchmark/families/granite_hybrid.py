"""Granite-4.0-H: ``deepspeed_tpu.models.granite_hybrid`` under the keys
of ibm-granite/granite-4.0-h-micro's ``config.json``, whole: every layer,
the whole vocabulary, one chip.

What this file adds to what a dense family's file holds (``families/
gpt2.py``): ``state_bytes_per_slot`` (a slot's Mamba-2 state, which a
decode step reads and writes whatever the sequence's length:
``readers/ssm.py``, ``readers/decode_step_roofline_state.py``) and a
probe of a slot's state (:func:`state_probe`), handed to the runner
under the one name it knows, ``router_probe``: the token check cannot be
counted on to see a state kept in fewer bits than float32.
"""

import jax

from benchmark.reference import granite_hybrid as reference
from benchmark.roofline import ssm

# keys of the source whose value says which layer this program builds;
# any other value is another model
_STATED = {"mamba_n_groups": 1, "mamba_conv_bias": True,
           "mamba_proj_bias": False, "attention_bias": False,
           "num_local_experts": 0, "position_embedding_type": "nope",
           "tie_word_embeddings": True, "hidden_act": "silu",
           "normalization_function": "rmsnorm"}


def program_config(model, **overrides):
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig

    for key, value in _STATED.items():
        if model[key] != value:
            raise SystemExit(f"granite_hybrid builds {key} = {value!r}, "
                             f"and the configuration says {model[key]!r}")
    d, H = model["hidden_size"], model["mamba_n_heads"]
    if model["mamba_expand"] * d != H * model["mamba_d_head"]:
        raise SystemExit("mamba_expand x hidden_size is not mamba_n_heads "
                         "x mamba_d_head")
    return GraniteHybridConfig.from_layer_types(
        model["layer_types"][:model["num_hidden_layers"]],
        vocab_size=model["vocab_size"], dim=d,
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=d // model["num_attention_heads"],
        ffn_dim=model["shared_intermediate_size"], ssm_heads=H,
        ssm_head_dim=model["mamba_d_head"], ssm_state=model["mamba_d_state"],
        conv_kernel=model["mamba_d_conv"],
        embedding_multiplier=float(model["embedding_multiplier"]),
        residual_multiplier=model["residual_multiplier"],
        attention_multiplier=model["attention_multiplier"],
        logits_scaling=float(model["logits_scaling"]),
        max_seq_len=model["max_position_embeddings"],
        norm_eps=model["rms_norm_eps"],
        ssm_block=model["mamba_chunk_size"], **overrides)


def toy(model):
    """One period of the published ten kinds at a size the CPU walks in
    seconds (--rehearse)."""
    return dict(model, vocab_size=512, hidden_size=128, num_hidden_layers=10,
                num_attention_heads=4, num_key_value_heads=2,
                shared_intermediate_size=256, mamba_n_heads=8,
                mamba_d_head=32, mamba_d_state=32, mamba_chunk_size=16,
                max_position_embeddings=1024)


def init_params(cfg, key, dtype):
    """The program's own initialiser; ``key`` is an argument of the jit
    that calls this, never a constant in it."""
    from deepspeed_tpu.models import granite_hybrid

    return granite_hybrid.init_params(key, cfg, dtype)


def _counts(cfg):
    """Parameters of (a Mamba-2 mixer, an attention mixer, the MLP), a
    layer, the two layer norms aside."""
    d = cfg.dim
    attn = 2 * d * cfg.n_heads * cfg.head_dim \
        + 2 * d * cfg.n_kv_heads * cfg.head_dim
    return ssm.mixer_params(cfg), attn, 3 * d * cfg.ffn_dim


def param_count(cfg):
    """The embedding once: it is the head too."""
    mixer, attn, mlp = _counts(cfg)
    return (cfg.n_ssm_layers * mixer + cfg.n_attn_layers * attn
            + cfg.n_layers * (mlp + 2 * cfg.dim)
            + cfg.vocab_size * cfg.dim + cfg.dim)


def serve_flops_per_token(cfg, context):
    """Forward only: 2 per weight a token meets (the head's product
    among them: the embedding's rows, which the lookup does not
    multiply); per token of context attended, in the attention layers
    alone, 2 x heads x (score + value); in a Mamba-2 layer the
    recurrence over a head's state, whatever the context
    (``roofline/ssm.py``)."""
    return (2 * param_count(cfg)
            + cfg.n_ssm_layers * ssm.rule_flops(cfg, 1)
            + 2 * cfg.n_attn_layers * cfg.n_heads * 2 * cfg.head_dim
            * context)


def weight_bytes(cfg, itemsize=2):
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of the attention layers alone: the Mamba-2 layers leave
    nothing a token."""
    return cfg.n_attn_layers * cfg.n_kv_heads * cfg.head_dim * 2 * itemsize


def state_bytes_per_slot(cfg, itemsize=2):
    """What a slot keeps in the Mamba-2 layers, whatever its length: the
    float32 state and the convolution's rows."""
    return cfg.n_ssm_layers * ssm.state_bytes(cfg, itemsize)


def _ref_kw(cfg):
    return dict(period=cfg.period, head_dim=cfg.head_dim,
                heads=cfg.ssm_heads, state=cfg.ssm_state,
                emb_mult=cfg.embedding_multiplier,
                res_mult=cfg.residual_multiplier,
                attn_scale=cfg.attention_multiplier, eps=cfg.norm_eps)


# the state probe: whole chunks of the cell's, then a last chunk with a
# sixteenth of its rows real, then steps.  A state kept in fewer bits is
# rounded once a chunk but once a token by the steps, so the steps are
# what shows it
STATE_PROBE_CHUNKS = 3
STATE_PROBE_STEPS = 32
# |S - S_ref|_F / |S_ref|_F a head, for the first Mamba-2 layer (whose
# input is the embedding: what parts it from the reference is what bf16
# projections feed it and what the state is kept in) and for the last
# (whose input has come through every layer in bf16): a limit for the
# heads' mean and one for the largest head, a layer (what reads over
# them, and why these limits, is in the configuration's check_why)
STATE_PROBE_LIMITS = {"first": (0.0057, 0.013), "last": (0.1, 0.22)}


def state_probe(cfg, params, seed, chunk_rows):
    """A slot's state after a prompt as the engine runs one, against the
    reference's.

    The serving programs themselves (``serving.serving_programs`` over
    ``forward_paged``, as ``serving_engine`` builds them, jitted here
    over a private cache of two slots) run a seeded prompt:
    ``STATE_PROBE_CHUNKS`` whole chunks of ``chunk_rows`` tokens into
    slot 0 and a last chunk of which a sixteenth is real (the rows past
    its last real token must move nothing); between two chunks a decode
    step over both slots, slot 1 live and slot 0 as the engine uploads a
    slot that is between chunks (length 0, the trash page for a table),
    which must leave slot 0's state as it was; then
    ``STATE_PROBE_STEPS`` decode steps with slot 0 live.  The state is
    kept in the dtype the engine keeps it in (``kernels.STATE_DTYPE``).

    What is compared is slot 0's state in the first and in the last
    Mamba-2 layer with the reference's token-by-token float32 recurrence
    over the same tokens (its whole forward: the last layer's input has
    passed every layer before it), a head: ``|S - S_ref|_F / |S_ref|_F``,
    the heads' mean and the largest of them, each under its limit.

    A pass of the check's own over the programs' functions at the
    cell's widths, not the compiled programs that were timed."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import kernels, serving
    from deepspeed_tpu.inference.paged_forward import forward_paged
    from deepspeed_tpu.models import granite_hybrid as program

    page, steps = 16, STATE_PROBE_STEPS
    n = STATE_PROBE_CHUNKS * chunk_rows + max(1, chunk_rows // 16)
    pages = -(-(n + steps) // page)
    rng = np.random.default_rng((seed ^ 0x5A5A) & 0x7FFFFFFF)
    seq = rng.integers(0, cfg.vocab_size, n + steps)
    dtype = params["embed"].dtype
    row = program.FAMILY.recurrent.state_row(cfg)
    trash = 2 * pages
    shape = (cfg.n_attn_layers, cfg.n_kv_heads, trash + 1, page,
             cfg.kv_width)
    tables = np.arange(2 * pages, dtype=np.int32).reshape(2, pages)
    cache = kernels.PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        table=jnp.asarray(tables), seq_lens=jnp.zeros((2,), jnp.int32),
        page_size=page,
        conv=jnp.zeros((row.layers, 2) + row.conv, dtype),
        state=jnp.zeros((row.layers, 2) + row.state, kernels.STATE_DTYPE))
    forward = lambda continuation: lambda p, t, c: forward_paged(
        p, t, cfg, c, continuation=continuation, tp=False)
    sample = lambda logits, keys, temps: jnp.argmax(logits, -1).astype(
        jnp.int32)
    _, chunk, _, _, decode = serving.serving_programs(
        forward(False), forward(False), forward(True), sample, 1, 2,
        state=True)
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
                               state=view.state)
        if done + take < n:                 # slot 0 is between chunks
            cache = step(cache, [0, 5 + done // chunk_rows], [7, 7])
    for j in range(steps):
        cache = step(cache, [n + j, 0], [seq[n + j], 7])
    want = jax.jit(lambda p, t: reference.hidden(p, t, **_ref_kw(cfg))[1])(
        params, jnp.asarray(seq))
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


def state_failed(state):
    """Whether a reading of :func:`state_probe` is over a limit (or not
    a number)."""
    return not all(
        state[layer]["error_mean"] <= state[layer]["limit"]
        and state[layer]["error_worst_head"]
        <= state[layer]["limit_worst_head"] for layer in ("first", "last"))


def router_probe(cfg, params, seed, step_rows, chunk_rows):
    """The probe of the check's own that ``runners/serve_backlog_long``
    runs beside the token check, under the one name it knows.  This
    family routes nothing (``routed_here`` 0): what rides here is
    :func:`state_probe`, under ``state``; where it is over a limit,
    ``differ`` is raised over ``limit`` so that the runner, which reads
    ``differ`` alone, fails the run, and ``state.failed`` says why."""
    state = state_probe(cfg, params, seed, chunk_rows)
    failed = state_failed(state)
    if failed:
        state["failed"] = True
    return {"rows": 0, "by": [step_rows, chunk_rows], "routed_here": 0,
            "differ": int(failed), "limit": 0, "state": state}


def reference_logits(cfg):
    """jitted (params, tokens[T], start, count) -> (logits[count, V] of
    the positions from start, alternatives).  A dense model is
    continuous: there is no alternative."""
    kw = dict(_ref_kw(cfg), logits_scale=cfg.logits_scaling)
    return jax.jit(lambda p, t, start, count: (
        reference.logits(p, t, start, count, **kw), []), static_argnums=3)
