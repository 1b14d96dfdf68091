"""Phi-4-mini-flash: ``deepspeed_tpu.models.phi4_flash`` under the keys of
microsoft/Phi-4-mini-flash-reasoning's ``config.json``, whole: every
layer, the whole tied vocabulary, one chip.

What this file adds to what a state-space family's file holds
(``families/granite_hybrid.py``): the arithmetic of a model whose prompt
rows pay half of it (``self_flops_per_token`` / ``tail_flops_per_token``:
layers behind the self-decoder run on a row's last token alone), of one
pool layer that eight layers read (``pool_reads``), and of two per-slot
kinds (``state_bytes_per_slot``: the Mamba-1 states and the window
layers' rings, apart in ``slot_bytes``); and a probe of a slot's Mamba-1
state (:func:`state_probe`), handed to the runner under the one name it
knows, ``router_probe``.
"""

import math

import jax

from benchmark.reference import phi4_flash as reference
from benchmark.roofline import mamba1

# keys of the source whose value says which layer this program builds;
# any other value is another model
_STATED = {"model_type": "phi4flash", "mb_per_layer": 2,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "hidden_act": "silu"}
# the Mamba-1 family's constants, which the source's keys do not hold
# (the configuration's ``assumed.mamba``)
D_STATE, D_CONV, EXPAND, DT_RANK_PER = 16, 4, 2, 16


def program_config(model, **overrides):
    try:
        from deepspeed_tpu.models.phi4_flash import Phi4FlashConfig
    except ImportError:
        # a program from before PR 55: the cell cannot run on it
        raise SystemExit("this program has no family phi4_flash "
                         "(deepspeed_tpu/models/phi4_flash.py): the cell "
                         "needs it")

    for key, value in _STATED.items():
        if model[key] != value:
            raise SystemExit(f"phi4_flash builds {key} = {value!r}, and the "
                             f"configuration says {model[key]!r}")
    d = model["hidden_size"]
    return Phi4FlashConfig(
        vocab_size=model["vocab_size"], dim=d,
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"],
        mb_per_layer=model["mb_per_layer"],
        sliding_window=model["sliding_window"], d_inner=EXPAND * d,
        d_state=D_STATE, d_conv=D_CONV, dt_rank=math.ceil(d / DT_RANK_PER),
        norm_eps=model["layer_norm_eps"],
        max_seq_len=model["max_position_embeddings"], **overrides)


def toy(model):
    """Every kind in the published order at a size the CPU walks in
    seconds (--rehearse): three (Mamba-1, window) periods, (Mamba-1,
    full), two (GMU, cross); a window the rehearsal's contexts pass."""
    return dict(model, vocab_size=512, hidden_size=128, num_hidden_layers=12,
                num_attention_heads=8, num_key_value_heads=4,
                intermediate_size=256, sliding_window=16,
                max_position_embeddings=1024)


def init_params(cfg, key, dtype):
    """The program's own initialiser; ``key`` is an argument of the jit
    that calls this, never a constant in it."""
    from deepspeed_tpu.models import phi4_flash

    return phi4_flash.init_params(key, cfg, dtype)


# ---------------------------------------------------------- the counts
def _counts(cfg):
    """Parameters of (a Mamba-1 mixer, an attention mixer with keys of
    its own, a cross-attention mixer, a GMU, the SwiGLU), a layer, the two
    layer norms aside."""
    d, Dh = cfg.dim, cfg.head_dim
    q, kv = cfg.n_heads * Dh, cfg.n_kv_heads * Dh
    # W_o and its bias, the four lambda vectors, the norm's gain
    out = q * d + d + 4 * Dh + 2 * Dh
    return (mamba1.mixer_params(cfg), d * (q + 2 * kv) + q + 2 * kv + out,
            d * q + q + out, 2 * d * cfg.d_inner, 3 * d * cfg.ffn_dim)


def _self_params(cfg):
    """Layers of the self-decoder (which every token passes)."""
    mamba, attn, _, _, mlp = _counts(cfg)
    return (cfg.n_mamba_layers * mamba + (cfg.n_sliding_layers + 1) * attn
            + cfg.n_self_layers * (mlp + 4 * cfg.dim))


def _tail_params(cfg):
    """Layers behind it, and the final norm."""
    _, _, cross, gmu, mlp = _counts(cfg)
    return cfg.n_cross_layers * (cross + gmu + 2 * (mlp + 4 * cfg.dim)) \
        + 2 * cfg.dim


def param_count(cfg):
    """The embedding once: it is the head too."""
    return _self_params(cfg) + _tail_params(cfg) + cfg.vocab_size * cfg.dim


def _pair_flops(cfg):
    """One (query, visible key) pair, every head, as published: a head's
    score over ``head`` numbers and its probabilities over ``[v1 | v2]``,
    ``2 head`` (the zero lanes the program pads queries with are not
    counted)."""
    return cfg.n_heads * (2 * cfg.head_dim + 4 * cfg.head_dim)


def self_flops_per_token(cfg, context):
    """What every token pays: 2 per weight of the self-decoder, the
    Mamba-1 layers' convolution and recurrence, the window layers' keys
    (the window's at most) and the full layer's."""
    return (2 * _self_params(cfg)
            + cfg.n_mamba_layers * mamba1.rule_flops(cfg, 1)
            + _pair_flops(cfg) * (
                cfg.n_sliding_layers * min(context, cfg.sliding_window)
                + context))


def tail_flops_per_token(cfg, context):
    """What a generated token, and a prompt's LAST row, pays besides: 2
    per weight of the cross-decoder and of the head (the embedding's
    rows), and the cross layers' reads of the full layer's keys."""
    return (2 * (_tail_params(cfg) + cfg.vocab_size * cfg.dim)
            + cfg.n_cross_layers * _pair_flops(cfg) * context)


def serve_flops_per_token(cfg, context):
    """For ``readers/serve_mfu.py``, which charges every token of a
    request one number and cannot tell a prompt's row from a generated
    one: what EVERY token pays, the self-decoder.  A lower count of what
    the requests needed (a generated token pays the cross-decoder and the
    head too); ``readers/phi4_flash.py`` counts each row what it paid
    (``v55.serve_mfu_rows.sat``)."""
    return self_flops_per_token(cfg, context)


def weight_bytes(cfg, itemsize=2):
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of the ONE full layer: what a token leaves in the pool."""
    return cfg.n_kv_heads * cfg.head_dim * 2 * itemsize


def pool_reads(cfg):
    """How many layers read the pool's one layer in a decode step: its
    writer and the cross layers."""
    return 1 + cfg.n_cross_layers


def slot_bytes(cfg, itemsize=2):
    """(the Mamba-1 layers' state and rows, the window layers' rings): a
    slot's, whatever its length."""
    ring = cfg.sliding_window * 2 * cfg.n_kv_heads * cfg.head_dim * itemsize
    return (cfg.n_mamba_layers * mamba1.state_bytes(cfg, itemsize),
            cfg.n_sliding_layers * ring)


def state_bytes_per_slot(cfg, itemsize=2):
    return sum(slot_bytes(cfg, itemsize))


def _ref_kw(cfg):
    from deepspeed_tpu.models import phi4_flash

    return dict(kinds=phi4_flash.layer_kinds(cfg), head_dim=cfg.head_dim,
                window=cfg.sliding_window, d_state=cfg.d_state,
                dt_rank=cfg.dt_rank, eps=cfg.norm_eps)


# the state probe: whole chunks of the cell's, then a last chunk with a
# sixteenth of its rows real, then steps.  A state kept in fewer bits is
# rounded once a chunk but once a token by the steps, so the steps are
# what shows it
STATE_PROBE_CHUNKS = 3
STATE_PROBE_STEPS = 32
# |S - S_ref|_F / |S_ref|_F a group of 128 channels, for the first
# Mamba-1 layer (whose input is the embedding) and for the last (layer
# 16, whose scan is the memory: its input has come through sixteen layers
# in bf16): a limit for the groups' mean and one for the largest group, a
# layer (what reads over them, and why these limits, is in the
# configuration's check_why)
STATE_PROBE_LIMITS = {"first": (0.005, 0.0075), "last": (0.06, 0.08)}
# the short probe: a first chunk of this many real tokens into the other
# slot, then as many decode steps; at a context of a few rows one row
# that a cross layer does not see (the one the full layer wrote in the
# same program) is a tenth of what it reads, where at the traffic's
# thousands the token check cannot see it
SHORT_PROBE_TOKENS = 4
# |logits - logits_ref|_2 / |logits_ref|_2 over the vocabulary, the
# largest of the probe's positions
SHORT_PROBE_LIMIT = 0.08


def state_probe(cfg, params, seed, chunk_rows):
    """A slot's Mamba-1 state after a prompt as the engine runs one,
    against the reference's: as ``families/granite_hybrid.py::
    state_probe`` (the serving programs themselves over a private cache
    of two slots: ``STATE_PROBE_CHUNKS`` whole chunks into slot 0, a last
    chunk a sixteenth real, between two chunks a decode step of slot 1
    with slot 0 uploaded as a slot between chunks, then
    ``STATE_PROBE_STEPS`` decode steps of slot 0), the cache with the
    window layers' rings beside the states.  What is compared is slot 0's
    state in the first and in the last Mamba-1 layer with the reference's
    token-by-token float32 recurrence over the same tokens, a group of
    128 channels: ``|S - S_ref|_F / |S_ref|_F``, the groups' mean and the
    largest, each under its limit.

    A pass of the check's own over the programs' functions at the
    cell's widths, not the compiled programs that were timed."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import kernels, serving
    from deepspeed_tpu.inference.paged_forward import forward_paged
    from deepspeed_tpu.models import phi4_flash as program

    page, steps = 16, STATE_PROBE_STEPS
    n = STATE_PROBE_CHUNKS * chunk_rows + max(1, chunk_rows // 16)
    pages = -(-(n + steps) // page)
    rng = np.random.default_rng((seed ^ 0x5A5A) & 0x7FFFFFFF)
    seq = rng.integers(0, cfg.vocab_size, n + steps)
    dtype = params["embed"].dtype
    fam = program.FAMILY
    row, kv = fam.recurrent.state_row(cfg), fam.cache_row(cfg)
    trash = 2 * pages
    shape = (fam.pool_layers(cfg), kv.n_kv, trash + 1, page, kv.pool_width)
    tables = np.arange(2 * pages, dtype=np.int32).reshape(2, pages)
    cache = kernels.PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        table=jnp.asarray(tables), seq_lens=jnp.zeros((2,), jnp.int32),
        page_size=page,
        conv=jnp.zeros((row.layers, 2) + row.conv, dtype),
        state=jnp.zeros((row.layers, 2) + row.state, kernels.STATE_DTYPE),
        ring=jnp.zeros((row.ring.layers, 2) + row.ring.conv, dtype))
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
                               state=view.state, ring=view.ring)
        if done + take < n:                 # slot 0 is between chunks
            cache = step(cache, [0, 5 + done // chunk_rows], [7, 7])
    for j in range(steps):
        cache = step(cache, [n + j, 0], [seq[n + j], 7])
    short = _short_probe(cfg, params, seq, cache, tables, chunk_rows, chunk,
                         forward)
    want = jax.jit(lambda p, t: reference.hidden(p, t, **_ref_kw(cfg))[1])(
        params, jnp.asarray(seq))
    # the reference's [channels, states] as the program keeps them
    want = want.reshape(want.shape[0], cfg.state_heads, -1,
                        cfg.d_state).transpose(0, 1, 3, 2)
    norm = lambda a: jnp.sqrt((a * a).sum((-2, -1)))
    out = {"tokens": n + steps, "chunk": chunk_rows,
           "state_dtype": str(jnp.dtype(kernels.STATE_DTYPE)),
           "short": short}
    for name, layer in (("first", 0), ("last", row.layers - 1)):
        got = cache.state[layer, 0].astype(jnp.float32)
        error = norm(got - want[layer]) / norm(want[layer])
        mean, worst = STATE_PROBE_LIMITS[name]
        out[name] = {"error_mean": float(error.mean()),
                     "error_worst_head": float(error.max()),
                     "limit": mean, "limit_worst_head": worst}
    return out


def _short_probe(cfg, params, seq, cache, tables, chunk_rows, chunk,
                 forward):
    """The logits of a few rows at a context of a few rows, against the
    reference's: ``SHORT_PROBE_TOKENS`` of ``seq`` as a first chunk into
    slot 1 (the chunk program's one row: the layers behind the cut on the
    last real token), then as many decode steps over both slots (the
    paged forward itself, for its logits; slot 0 idle).  ``{"error":
    the largest relative distance of a row's logits, "errors": each
    row's, "limit"}``."""
    import jax.numpy as jnp
    import numpy as np

    m = SHORT_PROBE_TOKENS
    toks = np.zeros((1, chunk_rows), np.int32)
    toks[0, :m] = seq[:m]
    row, view = chunk(params, jnp.asarray(toks), cache._replace(
        table=jnp.asarray(tables[1:2]), slot=jnp.ones((1,), jnp.int32),
        seq_lens=jnp.zeros((1,), jnp.int32)),
        jnp.full((1,), m - 1, jnp.int32))
    cache = cache._replace(k=view.k, v=view.v, conv=view.conv,
                           state=view.state, ring=view.ring)
    got = [row]
    step = jax.jit(forward(False))
    trash = cache.k.shape[2] - 1
    for j in range(m):
        table = np.array(tables)
        table[0] = trash
        logits, cache = step(
            params, jnp.asarray([[7], [seq[m + j]]], jnp.int32),
            cache._replace(
                table=jnp.asarray(table), slot=None,
                seq_lens=jnp.asarray([0, m + j], jnp.int32),
                real=jnp.asarray([0, 1], jnp.int32)))
        got.append(logits[1, 0])
    want = jax.jit(lambda p, t: reference.logits(
        p, t, m - 1, m + 1, **_ref_kw(cfg)))(params, jnp.asarray(seq[:2 * m]))
    errors = [float(jnp.linalg.norm(g.astype(jnp.float32) - w)
                    / jnp.linalg.norm(w)) for g, w in zip(got, want)]
    return {"error": max(errors), "errors": [round(e, 5) for e in errors],
            "limit": SHORT_PROBE_LIMIT}


def state_failed(state):
    """Whether a reading of :func:`state_probe` is over a limit (or not
    a number)."""
    return not (all(
        state[layer]["error_mean"] <= state[layer]["limit"]
        and state[layer]["error_worst_head"]
        <= state[layer]["limit_worst_head"] for layer in ("first", "last"))
        and state["short"]["error"] <= state["short"]["limit"])


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
    kw = _ref_kw(cfg)
    return jax.jit(lambda p, t, start, count: (
        reference.logits(p, t, start, count, **kw), []), static_argnums=3)
