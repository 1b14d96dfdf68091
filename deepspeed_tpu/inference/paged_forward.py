"""The forwards over a K/V cache, each written once over a decoder
family's hooks (:mod:`deepspeed_tpu.models.family`).

:func:`forward_paged` is what the serving engine's programs run (ref: the
reference's inference kernels' workspace contract, modernised to
vLLM-style page tables; it serves GPT-2, llama and MoE models through one
inference engine).  :func:`paged_layered_fns` is the same forward one
layer a program, for weight-streamed (ZeRO-Inference) serving;
:func:`forward_with_cache` the contiguous-cache forward of the offline
generators, the drafter and the hybrid engine.
"""

from __future__ import annotations

import functools
import itertools
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kernels import (
    latent_attention_step, paged_attention_step, paged_layer_loop,
    paged_period_loop, paged_reader, state_chunk, state_chunker, state_rows,
    state_step, state_stepper, write_state_rows)
from deepspeed_tpu.inference.quantized import dequantize_params
from deepspeed_tpu.models.family import (CarriedRows, CarriedState,
                                         SlotState, decoder_family,
                                         sections_of)
# (the per-slot seam's three hand-overs: rows, a layer's state, a chunk's)
from deepspeed_tpu.parallel.moe import extra_pair_passes


def _interpret(interpret: Optional[bool] = None) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _paged_block(fam, out, cfg, x, lp, ctx, layer, kp, vp, kps, vps, rows,
                 table, start, *, continuation: bool, prefill: bool,
                 reader, tp: bool):
    """One layer over the pool ``kp``/``vp`` ``[L, KV, P, ps, Dh]`` (a
    latent family: ``kp`` ``[L, 1, P, ps, C + Dr]`` alone), ``out`` the layer
    kind's second half, ``reader(head_dim=)`` ``paged_reader``'s word."""
    B, T = x.shape[:2]
    q, k, v = fam.qkv(cfg, x, lp, *ctx)
    phase = dict(continuation=continuation, prefill=prefill, reader=reader(
        head_dim=fam.cache_row(cfg).head_width or q.shape[-1])[0],
        flash_force_reference=tp)
    if fam.latent is not None:
        attn, kp = latent_attention_step(
            q, k, *fam.latent(cfg, lp), kp, layer, table, start, **phase)
    else:
        attn, kp, vp, kps, vps = paged_attention_step(
            q, k, v, kp, vp, layer, table, start, kps=kps, vps=vps, **phase)
    x = out(cfg, x, attn.reshape(B, T, -1), lp)
    if isinstance(x, tuple):            # the layer's FFN counts its rows
        x, routed = x
        rows = _count_routed(fam, cfg, rows, routed, B * T)
    return x, kp, vp, kps, vps, rows


def _count_routed(fam, cfg, rows, routed, N: int):
    """A cache's running count ``rows`` with an expert layer's
    ``routed`` [n] (the rows of its ``N`` that went to each held expert)
    added.  A count one longer than ``routed`` has the further passes of
    the held experts' pair buffer behind the experts (an engine of a
    family that holds a share of what its ``router`` scores)."""
    if rows is None:
        return None
    if rows.shape == routed.shape:
        return rows + routed
    scored, top_k = fam.router(cfg)
    return rows + jnp.append(
        routed, extra_pair_passes(routed, N, top_k, scored))


def _paged_read_block(rd, cfg, x, lp, ctx, kp, vp, table, lens, *, reader):
    """One layer of a ``family.PoolReader``: one token a row, a query of
    its own over pool layer ``rd.reads`` as far as ``lens``; it writes
    nothing."""
    q = rd.q(cfg, x, lp, *ctx)
    with jax.named_scope("kv_attend"), jax.named_scope(rd.scope):
        attn = paged_attention_step(
            q, None, None, kp, vp, rd.reads, table, lens, continuation=False,
            prefill=False, reader=reader(head_dim=q.shape[-1])[0],
            flash_force_reference=False)[0]
    return rd.out(cfg, x, attn.reshape(x.shape[0], 1, -1), lp)


def _forward_periods(fam, params, x, cfg, cache, block, lead, ctx, read, *,
                     whole: bool, tp: bool, interpret: bool):
    """The layers of a family some of whose layers keep a bounded state
    a slot (``fam.recurrent``): first its leading stack, if it has one
    (``lead``: its block, over the pool's first layers; or
    ``Recurrent.lead``: per-slot layers with a second half of their own,
    over the state buffers' first layers), then its sections in order
    (``family.sections_of``: a period and a count each, the three
    kinds' layer indices running on over the same pool and the same
    state buffers), a section's periods in a loop, a
    period's kinds in the order stated: a pool layer is ``block``
    (:func:`_paged_block`); an FFN alone (``Recurrent.ffn``) touches
    neither cache; a per-slot layer reads its rows' state beside the
    pool, mixes, and writes it back, but in a decode step over every
    slot on one device (``kernels.state_stepper``), where ``mix`` steps
    its layer of the carried state where it lies (``CarriedState``) and
    only the convolution's rows go out and back, or not even they
    (``Recurrent.rows_in_place``); a prompt chunk's rows it is handed
    with the chunk's kernel where the build runs one (``SlotState``).
    ``cache.real``: how many tokens of each row may move a state; a row
    that starts at position 0 starts from zero state, whatever its slot
    held (a first chunk's rows are zeroed before ``mix`` or the kernel
    sees them).  A kind a period names by a string is a further per-slot
    kind (``Recurrent.also``: the same layer over ``cache.ring``) or a
    pool layer that writes nothing (``Recurrent.readers``: ``read``);
    where a kind hands a value on (``Recurrent.hands_on``) it rides with
    the activations, ``(x, memory)``; before the last ``Recurrent.tail``
    sections a program of T > 1 tokens cuts both to each row's last real
    token."""
    rec = fam.recurrent
    B, T = x.shape[:2]
    start, slot = cache.seq_lens, cache.slot
    real = (jnp.full((B,), T, jnp.int32) if cache.real is None
            else cache.real)
    n_lead = 0
    if fam.lead is not None:
        n_lead = jax.tree.leaves(params[fam.lead[0]])[0].shape[0]
        x, cache = paged_layer_loop(lead, x, params[fam.lead[0]], cache,
                                    count=n_lead)
    # (a family with no pool layer at all: the cache holds no pool)
    n_pool = 0 if cache.k is None else cache.k.shape[0] - n_lead
    every_slot = T == 1 and slot is None
    stepped = state_stepper(decode=every_slot, tp=tp)[0] == "pallas"
    step = functools.partial(state_step, interpret=interpret)
    chunk = functools.partial(state_chunk, interpret=interpret)
    # the per-slot kinds by their name in a period, and the pool's readers
    also = {r.key: r for r in rec.also}
    per_slot = {True: rec, "lead": rec, **also}
    readers = {r.key: r for r in rec.readers}
    memory = rec.hands_on is not None

    def split(stack):
        held = {k: stack[k] for k in fam.whole_stacks
                if k in stack} if whole else {}
        return held, {k: v for k, v in stack.items() if k not in held}

    # a kind's stack stays whole, its layers taken out by index (a period's
    # slice copied 150 MB a projection, v5e, PR 35), but one section's pool
    stacks = {True: split(params[rec.key]),
              False: split(params["blocks"] if n_pool else {}),
              None: split(params[rec.ffn[0]]) if rec.ffn else None,
              **{key: split(params[key]) for key in (*also, *readers)}}
    s_lead = 0                  # per-slot layers of a leading stack, sliced
    if rec.lead is not None:    # a layer (a leading FFN holds no experts)
        stacks["lead"] = {}, params[rec.lead[0]]
        s_lead = jax.tree.leaves(stacks["lead"][1])[0].shape[0]

    def layer_of(kind, layer):
        held, stack = stacks[kind]
        lp = {k: jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
              for k, v in stack.items()}
        return dict(lp, **held, layer=layer) if held else lp

    def counted(x, rows):
        """``(x, rows)``, the layer's FFN counting its experts' rows or not."""
        if isinstance(x, tuple):
            return x[0], _count_routed(fam, cfg, rows, x[1],
                                       x[0].shape[0] * x[0].shape[1])
        return x, rows

    def recurrent_layer(kind, out_half, first, carry, layer):
        """Per-slot layer ``layer`` of the state buffers, a layer of the
        stack ``kind`` ("lead", or True: the family's own) whose layer 0
        keeps the buffers' layer ``first``; ``conv`` and ``state`` the
        buffers of the layer's kind (a ring kind's: the rings and None)."""
        x, rows, conv, state = carry
        x, mem = x if memory else (x, None)
        rk = per_slot[kind]
        in_place = state is not None and stepped
        rows_in_place = rk.rows_in_place and every_slot
        # a prompt chunk's state on the chip, where the build runs it there
        on_chip = T > 1 and state is not None and state_chunker(
            (rk, cfg), tp=tp, interpret=interpret)[0] == "pallas"
        lp = layer_of(kind, layer - first if first else layer)
        # out of the carried buffers and back: all a decode step leaves
        # (a kind that keeps a state alone has no rows: ``conv`` is None)
        rows_in_place = rows_in_place or conv is None
        rows_out = () if rows_in_place else (conv,)
        state_out = () if in_place or state is None else (state,)
        out = rows_out + state_out
        held = state_rows(out, layer, slot)
        if T > 1:
            fresh = start == 0
            held = tuple(jnp.where(
                fresh.reshape((B,) + (1,) * (a.ndim - 1)), 0, a)
                for a in held)
        held = (None if conv is None else
                CarriedRows(conv, layer) if rows_in_place else held[0],
                CarriedState(state, layer, step) if in_place
                else SlotState(held[-1], chunk) if on_chip
                else held[-1] if state_out else None)
        y, held = rk.mix(cfg, x, lp, held, real, start, ctx)
        if rk.hands_on is not None:
            y, mem = y
        with jax.named_scope("kv_write"), jax.named_scope(rk.write_scope):
            out = write_state_rows(
                out, layer, slot,
                (() if rows_in_place else held[:1])
                + (held[1:] if state_out else ()))
        if conv is not None:
            conv = held[0].buffer if rows_in_place else out[0]
        if state is not None:
            state = held[1].buffer if in_place else out[-1]
        x, rows = counted(out_half(cfg, x, y, lp), rows)
        return ((x, mem) if memory else x, rows, conv, state), None

    # one function a kind: a scan's body is traced once a function, and the
    # masks it closes over stay one constant of the program
    own_layer = {kind: functools.partial(
        recurrent_layer, kind, rk.out, s_lead if kind is True else 0)
        for kind, rk in per_slot.items() if kind != "lead"}

    def period(kinds, done, scanned, x, att, p, kp, vp, rows, conv, state,
               ring):
        """Period ``p`` of a section whose period is ``kinds``, ``done``
        layers of each kind before it; ``att``: the period's pool
        layers' params where the section ``scanned`` them."""
        n = {kind: kinds.count(kind) for kind in done}
        i = dict.fromkeys(done, 0)
        # consecutive layers of one kind: (kind, how many)
        for kind, run in ((k, len(list(g)))
                          for k, g in itertools.groupby(kinds)):
            first, i[kind] = i[kind], i[kind] + run     # in the period
            if kind in also:
                # the family's second per-slot kind: the same layer over
                # the rings, a loop of its own as the first kind's
                (x, rows, ring, _), _ = jax.lax.scan(
                    own_layer[kind], (x, rows, ring, None),
                    p * n[kind] + (done[kind] + first)
                    + jnp.arange(run, dtype=jnp.int32))
                continue
            if kind is True:
                # a loop of their own: each iteration reads its layer's
                # state and updates the carried buffer once.  Unrolled,
                # layer i + 1 read the buffer layer i had just updated
                # inside one loop body, and under the memory pressure of
                # a full chip the compiler rematerialised layer i's
                # in-place update from the buffer it had already
                # overwritten: the state moved twice a step (v5e, PR 35)
                (x, rows, conv, state), _ = jax.lax.scan(
                    own_layer[True], (x, rows, conv, state),
                    p * n[kind] + (done[kind] + first + s_lead)
                    + jnp.arange(run, dtype=jnp.int32))
                continue
            x, mem = x if memory else (x, None)
            for j in range(first, first + run):
                layer = p * n[kind] + (done[kind] + j)  # in the kind's stack
                if kind is None:
                    lp = layer_of(None, layer)
                    x, rows = counted(rec.ffn[1](
                        cfg, x, dict(lp, memory=mem) if memory else lp), rows)
                    continue
                if kind in readers:
                    x = read(readers[kind], x, layer_of(kind, layer), kp, vp,
                             start + real)
                    continue
                if scanned:
                    lp = {k: v[j] for k, v in att.items()}
                    if stacks[False][0]:
                        lp = dict(lp, **stacks[False][0], layer=layer)
                else:
                    lp = layer_of(False, layer)
                x, kp, vp, _, _, rows = block(
                    x, lp, n_lead + layer if n_lead else layer, kp, vp,
                    None, None, rows)
            x = (x, mem) if memory else x
        return x, kp, vp, rows, conv, state, ring

    if s_lead:
        (x, rows, conv, state), _ = jax.lax.scan(
            functools.partial(recurrent_layer, "lead", rec.lead[1], 0),
            (x, cache.expert_rows, cache.conv, cache.state),
            jnp.arange(s_lead, dtype=jnp.int32))
        cache = cache._replace(expert_rows=rows, conv=conv, state=state)
    if memory:
        x = x, jnp.zeros((B, T, rec.hands_on(cfg)), x.dtype)
    done = dict.fromkeys(stacks, 0)
    sections = sections_of(rec, cfg, n_pool)
    for at, (kinds, count) in enumerate(sections):
        if rec.tail and T > 1 and at == len(sections) - rec.tail:
            # what is behind here a row's last real token alone pays
            x = jax.tree.map(lambda a: jax.vmap(
                lambda row, i: jax.lax.dynamic_slice_in_dim(row, i, 1))(
                    a, jnp.maximum(real - 1, 0)), x)
        n_att = kinds.count(False)
        scanned = bool(n_att) and n_att * count == n_pool
        att = {k: v.reshape((count, n_att) + v.shape[1:])
               for k, v in stacks[False][1].items()} if scanned else {}
        x, cache = paged_period_loop(
            functools.partial(period, kinds, dict(done), scanned), x, att,
            cache, count)
        done = {kind: n + count * kinds.count(kind)
                for kind, n in done.items()}
    return x[0] if memory else x, cache._replace(
        seq_lens=start + jnp.where(real > 0, T, 0), real=None)


def forward_paged(params, tokens, cfg, cache, *,
                  continuation: bool = False, tp: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  resident: bool = True):
    """Forward over a paged KV cache.  tokens: [B, T] → (logits, cache).

    The pool's leading dimension is the layers that attend over pages:
    the model's depth for most families, the attention layers alone for
    one with recurrent layers (``DecoderFamily.recurrent``), whose
    per-slot state the cache carries beside the pool with ``real``, the
    rows' real token counts (:class:`~deepspeed_tpu.inference.kernels.
    PagedKVCache`).  Such a cache comes back with ``real`` consumed and
    the lengths of rows that had no real token left as they were.  A
    family that states a ``Recurrent.tail`` gives, from a program of T > 1
    tokens, the logits of each row's last real token alone, ``[B, 1, V]``.

    ``tp``: True = params/cache are sharded over the mesh, so every
    pallas path (paged kernels AND the prefill flash kernel) must yield
    to the GSPMD-partitionable XLA formulations.  Serving closures pass
    this EXPLICITLY at build time — correctness must not hang off the
    mutable ambient mesh, which is only consulted when ``tp`` is None
    (direct callers).  ``resident``: False = ``params`` are values made
    inside the program (dequantised int8 leaves), not arrays it was
    handed.  A family's ``whole_stacks`` go to its ``out`` unsliced only
    where they are resident on one device: a kernel reads a layer of
    such a stack in place, and could neither be partitioned over a mesh
    nor be handed a stack that is dequantised whole.

    Prefill (T > 1, empty cache): dense causal attention over the prompt,
    K/V bulk-written into pages.  Decode (T == 1): paged attention over
    the live pages.  ``continuation=True`` (T > 1, non-empty cache):
    chunked prefill — the chunk's K/V scatter in at each row's frontier
    and attention runs over history + chunk (the FastGen split-fuse read
    path).

    Multi-position decode contract: the continuation path returns
    logits at EVERY position, not just the last — the serving engine's
    speculative verify depends on it to score a K+1-token draft window
    in one sweep (custom ``chunk_prefill_fn`` replacements must honor
    this; see MIGRATION.md).  Under learned positions, draft positions
    past the table CLAMP into its last row — harmless, because an
    acceptance at such a position would exceed the request's token
    budget and the host discards it (the engine bounds real positions
    by ``max_seq``, and the family's ``check`` bounds ``max_seq``).

    Which reader a layer's attention runs is ``paged_reader``'s answer
    from the phase, the layout and the shapes (on one device over float
    pages the Mosaic readers: decode's, and a chunk's at whole 128-row
    blocks and 128-lane heads; else XLA's gather): a rule of the build,
    not an argument.  A cache carrying ``k_scale`` planes is
    int8-resident (``kv_tier.quantized_resident``): writes quantize per
    token row on device and attention gathers the codes and dequantizes
    them (:func:`~deepspeed_tpu.inference.kernels.dequantize_pages`).
    """
    fam = decoder_family(cfg)
    T = tokens.shape[1]
    interpret = _interpret(interpret)
    if tp is None:
        from deepspeed_tpu.topology import current_mesh

        tp = fam.sharded(current_mesh())
    start = cache.seq_lens
    prefill = T > 1 and not continuation
    if prefill:
        try:
            if int(jnp.max(start)) != 0:
                raise ValueError(
                    "forward_paged prefill (T>1) requires an empty "
                    "cache; pass continuation=True for chunked prefill")
        except (jax.errors.TracerArrayConversionError,
                jax.errors.ConcretizationTypeError):
            pass  # traced: caller's responsibility
    x, ctx = fam.embed(params, tokens, start, cfg)
    reader = functools.partial(             # a layer's, by its head's width
        paged_reader, decode=T == 1, tp=tp, interpret=interpret,
        quant=cache.k_scale is not None, tokens=T)

    def block(out, whole=None, first=0):
        def run(x, lp, layer, kp, vp, kps, vps, rows):
            if whole:
                lp = dict(lp, **whole, layer=layer - first)
            return _paged_block(
                fam, out, cfg, x, lp, ctx, layer, kp, vp, kps, vps, rows,
                cache.table, start, continuation=continuation,
                prefill=prefill, reader=reader, tp=tp)

        return run

    if fam.recurrent is not None:
        def read(rd, x, lp, kp, vp, lens):
            return _paged_read_block(
                rd, cfg, x, lp, ctx, kp, vp, cache.table, lens,
                reader=functools.partial(reader, decode=True))

        x, cache = _forward_periods(
            fam, params, x, cfg, cache, block(fam.out),
            fam.lead and block(fam.lead[1]), ctx, read,
            whole=resident and not tp, tp=tp, interpret=interpret)
        return fam.head(params, x, cfg), cache
    n_lead = 0
    if fam.lead is not None:
        # a leading stack of another layer kind, then the family's own,
        # one loop each over the same pool
        key, lead_out = fam.lead
        n_lead = jax.tree.leaves(params[key])[0].shape[0]
        x, cache = paged_layer_loop(block(lead_out), x, params[key], cache,
                                    count=n_lead)
    blocks = params["blocks"]
    whole = ({k: blocks[k] for k in fam.whole_stacks}
             if resident and not tp else {})
    x, cache = paged_layer_loop(
        block(fam.out, whole, n_lead), x,
        {k: v for k, v in blocks.items() if k not in whole}, cache,
        first=n_lead, count=cache.k.shape[0] - n_lead)
    return fam.head(params, x, cfg), cache._replace(seq_lens=start + T)


def paged_layered_fns(cfg, *, tp: bool = False):
    """Per-layer factoring of :func:`forward_paged` for weight-streamed
    (ZeRO-Inference) serving — the serving twin of a family's
    ``layered_model``: stem (embedding + what the positions give) and
    head (final norm + LM head) stay HBM-resident, each transformer layer
    is its OWN jittable program so the streaming engine can upload layer
    l+1's weights while layer l computes.  Returns
    ``(stem_fn, block_fn, head_fn)``:

        stem_fn(stem, tokens, start)            -> (x, ctx)
        block_fn(lp, x, ctx, kp, vp, table, start,
                 *, continuation, prefill)      -> (x, kp, vp)
        head_fn(head, x)                        -> logits [B, T, V] f32

    ``kp``/``vp`` are ONE layer's pages [KV, P, ps, Dh]: the block is
    :func:`forward_paged`'s own, run on a pool of one layer, so streamed
    serving is token-identical to the resident engine.  Every param tree
    may carry int8 :class:`~deepspeed_tpu.inference.quantized.
    QuantizedTensor` leaves — the dequant is traced into each per-layer
    program, exactly as the whole-model quantized forward fuses it."""
    fam = decoder_family(cfg)

    def stem_fn(sp, tokens, start):
        return fam.embed(dequantize_params(sp), tokens, start, cfg)

    def block_fn(lp, x, ctx, kp, vp, table, start, *,
                 continuation: bool, prefill: bool):
        reader = functools.partial(
            paged_reader, decode=x.shape[1] == 1, tp=tp,
            interpret=_interpret(), quant=False, tokens=x.shape[1])
        x, kp, vp, _, _, _ = _paged_block(
            fam, fam.out, cfg, x, dequantize_params(lp), ctx, 0, kp[None],
            vp[None], None, None, None, table, start,
            continuation=continuation, prefill=prefill, reader=reader, tp=tp)
        return x, kp[0], vp[0]

    def head_fn(hp, x):
        return fam.head(dequantize_params(hp), x, cfg)

    return stem_fn, block_fn, head_fn


def cached_attention(q, k_cache, v_cache, new_k, new_v, start_pos,
                     scale: Optional[float] = None):
    """Attention of q against cache[:start_pos+T] (ref: the reference's
    decode-attention kernel contract: softmax(q @ K^T) @ V with the causal
    frontier at start_pos + local position).

    q: [B, T, H, Dh]; caches [B, maxT, KV, Dh]; new_k/v: [B, T, KV, Dh].
    Returns (out [B, T, H, Dh], k_cache, v_cache) with new_k/v written at
    ``start_pos``.
    """
    B, T, H, Dh = q.shape
    maxT, KV = k_cache.shape[1], k_cache.shape[2]
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, new_k.astype(k_cache.dtype), (0, start_pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, new_v.astype(v_cache.dtype), (0, start_pos, 0, 0))
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k_cache, rep, axis=2)
        v = jnp.repeat(v_cache, rep, axis=2)
    else:
        k, v = k_cache, v_cache
    scale = scale if scale is not None else Dh ** -0.5
    scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    kpos = jnp.arange(maxT)
    qpos = start_pos + jnp.arange(T)
    mask = kpos[None, :] <= qpos[:, None]          # [T, maxT]
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs,
                     v.astype(jnp.float32)).astype(q.dtype)
    return out, k_cache, v_cache


def forward_with_cache(params, tokens, cfg, cache):
    """Incremental forward for generation over a contiguous
    :class:`~deepspeed_tpu.inference.generation.KVCache`: attends to
    cache[:len] + tokens, writes new K/V at position ``cache.length``
    (ref: the reference's inference transformer kernels' KV-cache
    contract).  tokens: [B, T] → (logits [B, T, V] f32, updated cache)."""
    fam = decoder_family(cfg)
    fam.refuse(contiguous_cache=True)
    B, T = tokens.shape
    start = cache.length
    x, ctx = fam.embed(params, tokens, start, cfg)

    def block(x, layer):
        lp, kc, vc = layer
        q, k, v = fam.qkv(cfg, x, lp, *ctx)
        with jax.named_scope("kv_attend"):
            attn, kc, vc = cached_attention(q, kc, vc, k, v, start)
        x = fam.out(cfg, x, attn.reshape(B, T, cfg.n_heads * cfg.head_dim),
                    lp)
        if fam.expert_rows(cfg)[0]:
            x, _ = x
        return x, (kc, vc)

    x, (new_k, new_v) = jax.lax.scan(block, x,
                                     (params["blocks"], cache.k, cache.v))
    return (fam.head(params, x, cfg),
            cache._replace(k=new_k, v=new_v, length=start + T))
