"""Brumby: ``deepspeed_tpu.models.brumby`` under the keys of
manifestai/Brumby-14B-Base's ``config.json`` (Qwen3's keys: the row
carries none of the mixer's, and what the program takes beside them is
the configuration's ``assumed``), the first ``num_hidden_layers`` layers
with the embedding and the whole head: one pipeline stage's share.

What this file adds to what a recurrent family's file holds (``families/
qwen3_next.py``): a model with NO page pool (``kv_bytes_per_token`` 0:
``readers/decode_step_roofline_state.py`` then counts weights and the
slots' state alone), the arithmetic of a state stated at the exact size
of ``phi`` whatever layout the program pads it to (``roofline/
retention.py``), and a check of a slot's state AND its normaliser in the
first and the last layer, READ OUT OF THE TIMED ENGINE'S OWN CACHE when
the window closes (:func:`watch_window`, :func:`held_state`), against the
state the reference's own ``k``, ``v`` and ``G`` define over that slot's
tokens (:func:`state_check`), handed to the runner under the one name it
knows, ``router_probe``.
"""

import jax

from benchmark.reference import brumby as reference
from benchmark.roofline import retention

# keys of the source whose value says which layer this program builds;
# any other value is another model
_STATED = {"model_type": "brumby", "attention_bias": False,
           "hidden_act": "silu", "rope_scaling": None,
           "sliding_window": None, "use_sliding_window": False,
           "tie_word_embeddings": False}


def program_config(model, **overrides):
    try:
        from deepspeed_tpu.models.brumby import BrumbyConfig
    except ImportError:
        # a program from before PR 59: the cell cannot run on it
        raise SystemExit("this program has no family brumby "
                         "(deepspeed_tpu/models/brumby.py): the cell "
                         "needs it")

    for key, value in _STATED.items():
        if model[key] != value:
            raise SystemExit(f"brumby builds {key} = {value!r}, and the "
                             f"configuration says {model[key]!r}")
    # the harness asks for this as it builds the engine it will time: the
    # close of that engine's window is where its state is read
    watch_window()
    # blocks of the chunked rule: the program's choice (any block gives the
    # recurrence's numbers); the toy's chunk of 32 is four of its blocks
    overrides.setdefault("ret_block", 128 if model["head_dim"] >= 128 else 8)
    return BrumbyConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], ffn_dim=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"], **overrides)


def toy(model):
    """Five query heads a state head, as published, at a size the CPU
    walks in seconds (--rehearse); blocks of 8 so that a chunk of 32 is
    four of them."""
    return dict(model, vocab_size=512, hidden_size=128, num_hidden_layers=3,
                num_attention_heads=10, num_key_value_heads=2, head_dim=16,
                intermediate_size=256, max_position_embeddings=1024)


def init_params(cfg, key, dtype):
    """The program's own initialiser; ``key`` is an argument of the jit
    that calls this, never a constant in it."""
    from deepspeed_tpu.models import brumby

    return brumby.init_params(key, cfg, dtype)


# ---------------------------------------------------------- the counts
def layer_params(cfg):
    """W_q, W_k + W_v, W_o, the gate with its bias, the SwiGLU, the four
    gains: 330.3 M at the published widths."""
    d, Dh = cfg.dim, cfg.head_dim
    Hd, Kd = cfg.n_heads * Dh, cfg.n_kv_heads * Dh
    return (d * Hd + 2 * d * Kd + Hd * d + (d + 1) * cfg.n_kv_heads
            + 3 * d * cfg.ffn_dim + 2 * d + 2 * Dh)


def param_count(cfg):
    return (cfg.n_layers * layer_params(cfg) + 2 * cfg.vocab_size * cfg.dim
            + cfg.dim)


def serve_flops_per_token(cfg, context):
    """Forward only: 2 per weight a token meets (the embedding is a
    lookup) and the recurrence's own products over a head's state,
    whatever the context (``roofline/retention.py``)."""
    return (2 * (cfg.n_layers * layer_params(cfg)
                 + cfg.vocab_size * cfg.dim)
            + cfg.n_layers * retention.rule_flops(cfg, 1))


def weight_bytes(cfg, itemsize=2):
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    """No layer leaves anything a token: there is no pool."""
    return 0


def state_bytes_per_slot(cfg, itemsize=2):
    """What a slot keeps, whatever its length: S and z of every K/V head
    of every layer, float32, at the exact size of phi."""
    return cfg.n_layers * retention.state_bytes(cfg)


def _ref_kw(cfg):
    return dict(head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                eps=cfg.norm_eps, ret_eps=cfg.ret_eps)


# ------------------------------------------- the state the engine holds
# What the timed engine's own cache held of one slot when the window
# closed (:func:`held_state`, left here by :func:`watch_window`'s hook),
# for :func:`router_probe`, which runs when the engine is gone.
_HELD = {}
# |S - S_ref|_F / |S_ref|_F a K/V head, and the same for the normaliser
# z, in the first layer (whose input is the embedding) and in the last
# (whose input has come through every layer in bf16): {part: (the heads'
# mean, the largest head)} a layer (what reads over them, and why these
# limits, is in the configuration's check_why: each lies between the
# largest good reading and the smallest of a state kept in bfloat16, both
# read out of the timed engine through benchmark/run.py)
STATE_LIMITS = {
    "first": {"S": (0.0075, 0.0080), "z": (0.0045, 0.0065)},
    "last": {"S": (0.027, 0.036), "z": (0.013, 0.024)}}
# float32 weights (a rehearsal): the program and the reference part by
# rounding alone (1e-6 read), and anything that moves a state stands out
STATE_LIMITS_F32 = {
    layer: {part: (1e-4, 2e-4) for part in ("S", "z")}
    for layer in ("first", "last")}
# tokens a block of the reference's sums over a slot's past
MOMENT_BLOCK = 512


def watch_window():
    """From here to the close of the next window the harness drives
    (``harness/serve.py::drive`` with a tracer, as ``runners/
    serve_backlog_long.py`` tells the window from the warm-up), have that
    close leave in ``_HELD`` what :func:`held_state` reads of the engine
    that was timed.  The harness hands a family no engine and its runner
    calls ``router_probe`` when the engine is deleted, so the family
    stands in the runner's way as the runner stands in the harness's:
    ``serve.drive`` is looked up when it is called.  What the close does
    is take a reference and a slot's tokens: the profiler of a traced run
    is still running there, and nothing is started on the device."""
    from benchmark.harness import serve

    inner = serve.drive
    if getattr(inner, "reads_state", False):
        return

    def drive(engine, *args, tracer=None, **kw):
        t = inner(engine, *args, tracer=tracer, **kw)
        if tracer is not None:
            if serve.drive is drive:
                serve.drive = inner
            _HELD.clear()
            _HELD.update(held_state(engine))
        return t

    drive.reads_state = True
    serve.drive = drive


def held_state(engine):
    """The engine's own ``cache.state`` ([layers, slots, K/V heads,
    rotations x (Dh + 8), Dh], the buffer its programs update in place
    under every live slot: the array as the window's last program left
    it, which outlives the engine for as long as it is held here) and one
    slot of it with the tokens that slot's state has taken: the live slot
    with the most decode steps behind
    it (its prompt came in chunks between the other slots' steps, its
    last chunk was padded, and every step since ran beside slots that
    came and went).  ``_Slot.seq_len`` is what the cache has taken of the
    slot's prompt and answer, a step in flight included (its token is the
    last one the host read); a slot still in its prompt has taken
    ``seq_len`` of it.  {} for an engine that keeps no such state."""
    import numpy as np

    cache = getattr(engine, "cache", None)
    state = getattr(cache, "state", None)
    if state is None or cache.k is not None:      # another family's engine
        return {}
    live = [(len(s.generated), s.seq_len, b)
            for b, s in enumerate(engine.slots)
            if s is not None and s.seq_len > 0]
    if not live:
        return {}
    _, taken, b = max(live)
    s = engine.slots[b]
    tokens = np.asarray(list(s.req.tokens) + list(s.generated), np.int32)
    return {"slot": b, "request": s.req.req_id, "taken": taken,
            "prompt": len(s.req.tokens), "tokens": tokens,
            "slots_live": len(live), "state_dtype": str(state.dtype),
            "state": state}


def _moments(k, v, G):
    """The definition's sums over a slot's past, a K/V head: k, v [T, KV,
    Dh], G [T, KV] (the reference's own, float32) -> M [KV, Dh + 1, Dh,
    Dh]: ``M[r, i, j] = sum_s exp(G_T - G_s) v_s[r] k_s[i] k_s[j]`` and, at
    ``r = Dh``, the same with 1 for ``v_s[r]`` (the normaliser's).  In
    blocks of ``MOMENT_BLOCK`` tokens; no ``phi``."""
    import jax.numpy as jnp

    T, KV, Dh = k.shape
    pad = -T % MOMENT_BLOCK
    decay = jnp.exp(G[-1][None] - G)          # [T, KV]
    v1 = jnp.concatenate([v, jnp.ones((T, KV, 1), v.dtype)], -1)
    blocks = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                               ).reshape((-1, MOMENT_BLOCK) + a.shape[1:])

    def add(M, b):
        kb, vb, db = b
        return M + jnp.einsum("skr,ski,skj->krij", vb * db[..., None], kb,
                              kb, precision=jax.lax.Precision.HIGHEST), None

    M, _ = jax.lax.scan(add, jnp.zeros((KV, Dh + 1, Dh, Dh), jnp.float32),
                        (blocks(k), blocks(v1), blocks(decay)))
    return M


def _laid_out(M):
    """M [KV, Dh + 1, Dh, Dh] as the configuration's ``assumed.layout``
    keeps a head's state: [KV, Dh/2 + 1, Dh + 1, Dh], rotation d's row r
    and lane i hold ``w_d M[r, i, (i - d) mod Dh]``, ``w_0 = w_(Dh/2) = 1``
    and ``2^(1/2)`` between (a pair of lanes stands once for both its
    orders).  The benchmark's own reading of that layout: nothing of the
    program's is imported."""
    import numpy as np

    Dh = M.shape[-1]
    d, i = np.arange(Dh // 2 + 1)[:, None], np.arange(Dh)[None]
    w = np.full((Dh // 2 + 1,), np.sqrt(2.0), np.float32)
    w[0] = w[-1] = 1.0
    return (M[:, :, i, (i - d) % Dh] * w[:, None]).transpose(0, 2, 1, 3)


def state_check(cfg, params, held):
    """What the engine held of a slot (:func:`held_state`) against the
    state the definition gives that slot's own tokens.

    The reference's forward (``reference.hidden(keep=True)``: the
    definition, float32, every query against every earlier key) runs
    over the tokens the slot's state had taken and hands out its own k,
    v and G of the first and the last layer; their decayed sums
    (:func:`_moments`) in the stated layout (:func:`_laid_out`) are
    ``S_ref`` (rows of values) and ``z_ref`` (the normaliser's row).
    ``|S - S_ref|_F / |S_ref|_F`` a K/V head, the heads' mean and the
    largest, each under its limit, for S and for z."""
    import jax.numpy as jnp
    import numpy as np

    taken, tokens = held["taken"], held["tokens"]
    # the slot's first and last layer come off the device, and the slots'
    # 5.4 GiB go before the reference needs the room
    state = held.pop("state")
    got = {"first": np.asarray(state[0, held["slot"]]),
           "last": np.asarray(state[-1, held["slot"]])}
    del state
    out = {k: held[k] for k in ("slot", "request", "taken", "prompt",
                                "slots_live", "state_dtype")}
    out["decode_steps"] = max(0, taken - held["prompt"])
    if taken > len(tokens):
        return dict(out, failed=True, why=f"the cache has taken {taken} "
                    f"tokens and the host holds {len(tokens)}")
    upto = -(-taken // REFERENCE_ROWS) * REFERENCE_ROWS
    seq = np.zeros(upto, np.int32)
    seq[:taken] = tokens[:taken]
    ends = lambda a: a[jnp.asarray([0, cfg.n_layers - 1]), :taken]

    def defined(p, t):
        _, (k, v, G) = reference.hidden(p, t, keep=True, **_ref_kw(cfg))
        return jnp.stack([_moments(*kvg) for kvg in zip(
            ends(k), ends(v), ends(G))])

    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(defined)(params, jnp.asarray(seq)))
    Dh = cfg.head_dim
    norm = lambda a: np.sqrt((a * a).sum(tuple(range(1, a.ndim))))
    limits = STATE_LIMITS_F32 \
        if params["embed"].dtype == jnp.float32 else STATE_LIMITS
    for at, name in enumerate(("first", "last")):
        ref = _laid_out(want[at])
        tiles = got[name].astype(np.float32).reshape(
            ref.shape[:2] + (-1, Dh))[:, :, :Dh + 1]
        out[name] = {}
        for part, rows in (("S", slice(0, Dh)), ("z", slice(Dh, Dh + 1))):
            error = norm(tiles[:, :, rows] - ref[:, :, rows]) \
                / norm(ref[:, :, rows])
            mean, worst = limits[name][part]
            out[name][part] = {"error_mean": float(error.mean()),
                               "error_worst_head": float(error.max()),
                               "limit": mean, "limit_worst_head": worst}
    return out


def state_failed(state):
    """Whether a reading of :func:`state_check` is over a limit (or not
    a number), or there was nothing to read."""
    return "first" not in state or not all(
        state[layer][part]["error_mean"] <= state[layer][part]["limit"]
        and state[layer][part]["error_worst_head"]
        <= state[layer][part]["limit_worst_head"]
        for layer in ("first", "last") for part in ("S", "z"))


def router_probe(cfg, params, seed, step_rows, chunk_rows):
    """The check of the family's own that ``runners/serve_backlog_long``
    runs beside the token check, under the one name it knows.  This
    family routes nothing (``routed_here`` 0): what rides here is
    :func:`state_check` of what the window's close left in ``_HELD``,
    under ``state``; where it is over a limit, or nothing was read,
    ``differ`` is raised over ``limit`` so that the runner, which reads
    ``differ`` alone, fails the run, and ``state.failed`` says why."""
    held = dict(_HELD)
    _HELD.clear()
    state = state_check(cfg, params, held) if held else {
        "failed": True, "why": "the window's close read no slot's state"}
    failed = state_failed(state)
    if failed:
        state["failed"] = True
    return {"rows": 0, "by": [step_rows, chunk_rows], "routed_here": 0,
            "differ": int(failed), "limit": 0, "state": state}


# the reference runs the definition over the tokens up to the window it is
# asked for, rounded up to this many (a prompt's padding behind the window
# feeds nothing, and the definition's cost grows with the square)
REFERENCE_ROWS = 2048


def reference_logits(cfg):
    """(params, tokens[T], start, count) -> (logits[count, V] of the
    positions from start, alternatives).  A dense model is continuous:
    there is no alternative."""
    kw = _ref_kw(cfg)
    logits = jax.jit(lambda p, t, start, count: reference.logits(
        p, t, start, count, **kw), static_argnums=3)

    def forward(params, tokens, start, count):
        upto = min(len(tokens), -(-(start + count) // REFERENCE_ROWS)
                   * REFERENCE_ROWS)
        with jax.default_matmul_precision("highest"):
            return logits(params, tokens[:upto], start, count), []

    return forward
