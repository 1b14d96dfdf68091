"""What a decoder family is, stated once.

A family's file (``gpt2.py``, ``llama.py``, ``mixtral.py``,
``pangu_ultra_moe.py``, ``qwen3_next.py``, ``granite_hybrid.py``,
``laguna.py``, ``nemotron_h.py``, ``ling_flash.py``, ``phi4_flash.py``,
``brumby.py``) ends
with its
``FAMILY = DecoderFamily(...)``: the pieces of one transformer layer and
the facts a serving build needs.  Everything that serves, streams, drafts
or generates (the ``inference`` package) reads the record through
:func:`decoder_family`; nothing here or in a family's file imports from
``inference``.  A new family is its own file plus one name in
``_FAMILY_MODULES``.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax.numpy as jnp


def _no_check(cfg, mesh, max_seq) -> None:
    return None


class CacheRow(NamedTuple):
    """What one token leaves in the page pool, a layer: ``n_kv`` rows of
    ``key_width`` numbers read as keys and, where ``values_in_keys``,
    their first ``value_width`` as values too (one pool, no V pool: a
    latent row ``[c | k_rope]``), else a second pool of ``value_width``
    wide value rows.  ``head_width``: a head's own numbers where the row
    pads them with zeros to whole tiles (0: all of ``key_width``)."""

    n_kv: int
    key_width: int
    value_width: int
    values_in_keys: bool = False
    head_width: int = 0

    @property
    def pool_width(self) -> int:
        """Numbers a row takes in the pool: a shared row lies in whole
        128-lane tiles, zeros behind (declared 576 wide the compiler re-lays
        the pool in a copy and Mosaic refuses the page slice: ``kernels``)."""
        if not self.values_in_keys:
            return self.key_width
        return -(-self.key_width // 128) * 128


class StateRow(NamedTuple):
    """What one SLOT keeps, a layer that keeps a bounded state, whatever
    the length of its sequence: ``conv`` (rows x channels in the cache's
    dtype: the last inputs of a short causal convolution, or a ring of
    the last ``rows`` tokens' ``[K | V]``; None where the layer keeps a
    state alone: ``PagedKVCache.conv`` is then None, and ``mix`` is handed
    None for its rows and hands None back) and ``state`` (the
    recurrence's matrix a head, float32; None where the layer keeps
    rows alone), for each of ``layers`` such layers.  It is indexed by
    slot, not by page: ``PagedKVCache.conv`` is ``[layers, B, *conv]``
    and ``PagedKVCache.state`` ``[layers, B, *state]`` or None.  (A layer
    that is an FFN alone, ``Recurrent.ffn``, keeps nothing.)"""

    layers: int
    conv: Tuple[int, ...]
    state: Optional[Tuple[int, ...]]
    # what a slot keeps a layer of a family's SECOND per-slot kind
    # (``Recurrent.also``): see :class:`SlotRows`
    ring = None


class SlotRows(StateRow):
    """A :class:`StateRow` (it compares as one) of a family whose slots
    keep two kinds of rows: the first kind's, and as ``ring`` the
    ``StateRow`` of the second (``Recurrent.also``), whose rows live in
    ``PagedKVCache.ring`` ``[layers, B, *conv]``."""

    def __new__(cls, first: StateRow, ring: StateRow):
        self = super().__new__(cls, *first)
        self.ring = ring
        return self


class PoolReader(NamedTuple):
    """Layers that attend over pages ANOTHER layer wrote and write none:
    ``key`` their stack (and their kind's name in a period), ``q(cfg, x,
    lp, *ctx) -> q [B, 1, H, Dh]``, ``out`` their second half (as
    ``DecoderFamily.out``), ``reads`` the pool layer they read, ``scope``
    their word inside ``kv_attend``.  Such a layer runs on one token a
    row (a decode step, or behind ``Recurrent.tail``'s cut) over the
    row's length WITH the rows this program wrote."""

    key: str
    q: Callable[..., Any]
    out: Callable[..., Any]
    reads: int
    scope: str


@dataclasses.dataclass(frozen=True)
class Recurrent:
    """The layers of a family that keep a bounded state a slot beside
    the page pool, in periods with layers that attend over pages (an
    author's fields, as ``DecoderFamily``'s).  Six users: a delta rule
    gated a head (``qwen3_next``) or a key channel (``ling_flash``, whose
    pool layers are latent), a state-space mixer (``granite_hybrid``,
    ``nemotron_h``): a matrix a head that every token moves, a sliding
    window (``laguna``): a ring of the last tokens' K and V, and both at
    once (``phi4_flash``).  What the sixth changed: a period's kinds are
    no longer three (a string names a further kind by its stack's key: a
    second per-slot kind, ``also``, or pool layers that write nothing,
    ``readers``), a per-slot layer may hand a value on to the layers
    behind it (``hands_on``), and the last sections may run on a row's
    last real token alone (``tail``).  What a seventh changed
    (``brumby``: power retention, a state of 4.3 MiB a head with its
    normaliser in it, read by five query heads, in EVERY layer): a period
    may have no pool layer at all (the cache then holds no pool, ``blocks``
    is not a key of the params, ``DecoderFamily.qkv`` / ``out`` name
    nothing, and a section may be empty: a tail that is the head alone); a
    kind may keep a state and no rows (``StateRow.conv`` None); a step's
    vectors and its ``o`` may be n rows a state head of what the rule
    expands itself (:func:`step_state`: a key of 128 numbers for a row of
    8,320, a whole tile of 8 query rows); and a rule may be stated on the
    state where it lies (``in_place``, of :func:`step_state` and
    :func:`chunk_state`): a reference it reads and writes a piece at a
    time, because a head of megabytes is no value of a kernel's, with an
    ``o`` read from the operands (a step's: rows as the rows that read
    the state; a chunk's: as wide as the operand whose head holds the
    heads that read one state).  The six families' statements keep their
    meaning.

    ``period(cfg)``: one entry a layer of a period: True where the layer
    mixes tokens over its per-slot state, False where it attends over
    the page pool (``qkv`` / ``out``), None where it is an FFN alone
    (``ffn``), in the model's order; the model is whole periods (behind
    ``DecoderFamily.lead``, if any, whose layers attend over the pool,
    or ``lead``).  ``sections(cfg)``, for a model that
    is not: ``((period, count), ...)``, run in order over one pool and
    one state buffer, each kind's layer indices running on (None: one
    section, :func:`sections_of`).  ``key``: the params' stack of the
    per-slot layers; ``blocks`` holds the pool's layers alone.  ``ffn``:
    ``(key, hook)`` of the layers that are an FFN alone, their stack and
    ``hook(cfg, x, lp) -> x`` or ``(x, rows)``, residual included: such
    a layer touches neither the pool nor the per-slot state.  ``mix(cfg,
    x, lp, state, valid, start, ctx) -> (y, state)``: ``x`` [B, T, d],
    ``state`` the rows' ``(conv [B, *conv], state [B, *state])``,
    ``valid`` [B] how many of each row's T tokens are real (the rest
    padding, or the whole row a slot idle or between two chunks of its
    prompt): the state moves on real tokens only; ``start`` [B] where
    each row's first token stands and ``ctx`` what ``embed`` made of the
    positions (a mixer without positions ignores both).  ``out(cfg, x,
    y, lp)``: the residual and, where the layer has one, the FFN half
    (``(x, rows)`` where that counts its experts' rows, else ``x``).
    ``state_row``: what a slot keeps.  ``write_scope``: the scope word,
    inside ``kv_write``, of the write-back of a layer's rows.
    ``chunk_reader(cfg, tokens, interpret) -> (reader, reason)``.
    In a decode step over every slot on one device ``mix`` is handed as
    ``state[1]`` not the rows' state but a :class:`CarriedState`: the
    whole buffer of every such layer and which layer this is.  A family
    steps either through :func:`step_state` with its one-token rule and
    hands back what that returned; it never reads one as an array.
    ``rows_in_place``: there ``state[0]`` is a :class:`CarriedRows` too:
    the mixer writes its layer of the buffer where it lies and hands it
    back (right for 3 rows of a convolution).  ``block``: the family's
    rule of a chunk's block of tokens, a few heads side by side, over
    the operands it names itself (:func:`chunk_state`).  ``lead``:
    ``(key, out)`` of a leading stack of per-slot layers with another
    second half (leading dense layers that fall on the period's per-slot
    kind): ``mix`` runs on that stack's own mixer weights over the state
    buffers' first layers, ``out(cfg, x, y, lp)`` in ``out``'s place, and
    ``key``'s own layers' state stands behind theirs.

    ``also``: further per-slot kinds, a ``Recurrent`` each (its ``key``,
    ``mix``, ``out``, ``state_row``, ``write_scope``, ``rows_in_place``;
    its ``period`` is not read), named in a period by its ``key``; one is
    built: its rows live in ``PagedKVCache.ring`` and ``state_row``
    states them as :class:`SlotRows`.  ``readers``: :class:`PoolReader`s,
    named in a period by their ``key``.  ``hands_on(cfg) -> width``: this
    kind's ``mix`` returns ``((y, m), state)``, ``m`` [B, T, width] the
    value its layer hands on; the newest is ``lp["memory"]`` of every
    ``ffn`` hook behind it.  ``tail``: how many of the last sections a
    row's last real token alone pays: a program of T > 1 tokens cuts ``x``
    and the memory to ``[B, 1]`` at ``cache.real - 1`` before them, and
    its logits are that row's, ``[B, 1, V]`` (exact where the sections
    behind the cut keep nothing a token: their outputs at other positions
    feed nothing)."""

    key: str
    period: Callable[[Any], tuple]
    mix: Callable[..., Tuple[Any, Any]]
    out: Callable[..., Any]
    state_row: Callable[[Any], StateRow]
    write_scope: str
    rows_in_place: bool = False
    chunk_reader: Optional[Callable[..., Tuple[str, str]]] = None
    sections: Optional[Callable[[Any], tuple]] = None
    ffn: Optional[Tuple[str, Callable[..., Any]]] = None
    block: Optional[Callable[..., Tuple[Any, Any]]] = None
    lead: Optional[Tuple[str, Callable[..., Any]]] = None
    also: Tuple["Recurrent", ...] = ()
    readers: Tuple[PoolReader, ...] = ()
    hands_on: Optional[Callable[[Any], int]] = None
    tail: int = 0


class CarriedRows(NamedTuple):
    """A layer's per-slot rows where they live: ``buffer`` [layers,
    slots, rows, channels] (the serving programs' carry) and ``layer``
    (a traced index)."""

    buffer: Any
    layer: Any


class CarriedState(NamedTuple):
    """A recurrent layer's state where it lives: ``buffer`` [layers,
    slots, *state] (every recurrent layer's, the serving programs' carry),
    ``layer`` (a traced index) and ``step(rule, buffer, layer, vectors)
    -> (o, buffer)``, which applies a one-token rule to that layer of
    the buffer in place (:func:`deepspeed_tpu.inference.kernels.
    state_step`)."""

    buffer: Any
    layer: Any
    step: Callable[..., Tuple[Any, Any]]


def step_state(rule, S, *vectors, in_place=None):
    """One token of a recurrence: ``rule(S, *vectors) -> (o, S)``, the
    family's statement of it over the last two dimensions of ``S`` [...,
    R, C], each vector [..., 1, C], [..., R, 1] or [..., 1, 1] (a scalar
    where the rule is applied to one head's [R, C]) or a tile [1, H, R,
    C] that the slots share (a layer's own: a decay a (row, column) pair)
    and ``o`` [..., 1, C] or [..., R, 1].  ``S`` is the rows' state [B,
    H, R, C], and the rule is applied to it as it stands, or a
    :class:`CarriedState`, whose layer is stepped where it lies; the
    second result is of the kind ``S`` was.  A vector of any other shape
    [..., n, w] is n rows of what the rule expands itself, and ``o`` may
    be such rows.  ``in_place(S_ref, *vectors) -> o``: the rule on a
    head's [R, C] as a reference in the kernel's memory, read and written
    a piece at a time, for a head of megabytes (``kernels.state_step``:
    ``o`` is then rows as the first vector that is rows)."""
    if isinstance(S, CarriedState):
        o, buffer = S.step(rule, S.buffer, S.layer, vectors,
                           **({} if in_place is None
                              else {"in_place": in_place}))
        return o, S._replace(buffer=buffer)
    return rule(S.astype(jnp.float32), *vectors)


class SlotState(NamedTuple):
    """The rows' state of a recurrent layer at a prompt chunk, where the
    build runs the chunk on the chip: ``rows`` [B, H, R, C] and
    ``chunk(rule, rows, mats, cols, lanes, block=) -> (o, rows)``, which
    carries every head's state through the chunk's blocks under the
    family's block rule (:func:`deepspeed_tpu.inference.kernels.
    state_chunk`)."""

    rows: Any
    chunk: Callable[..., Tuple[Any, Any]]


def chunk_state(rule, S, mats, cols, lanes, block: int, **how):
    """A prompt chunk of a recurrence on a :class:`SlotState`: ``rule(S
    [h, R, C], *tiles, col, lane) -> (o [h, block, C], S)`` is the
    family's statement of one block of tokens on h heads side by side,
    ``mats`` its operands [B, T, heads, width] (a head of fewer serves as
    many of ``S``'s as it takes), ``cols`` and ``lanes`` [B, T, H, n] what
    it needs a token and head, handed to it down a block (``col`` [h,
    block, n]) and across (``lane`` [h, n, block]).  Returns (o [B, T, H,
    C], the rows' state as an array).  ``how``: ``in_place`` (the rule
    takes the step's states as a reference [h, R, C], reads and writes
    them a piece at a time and returns ``o`` alone: a head of megabytes;
    ``o`` is then as wide a state head as the widest head of ``mats``:
    the queries of several heads that read one state, side by side)."""
    return S.chunk(rule, S.rows, mats, cols, lanes, block=block, **how)


def _per_head_rows(cfg) -> CacheRow:
    return CacheRow(cfg.n_kv_heads, cfg.head_dim, cfg.head_dim)


def sections_of(rec: Recurrent, cfg, pool_layers: Optional[int] = None):
    """``((period, count), ...)`` of a family's per-slot seam: what its
    ``sections`` states, else its one ``period`` as often as
    ``pool_layers`` (the pool's layers behind the lead) hold its pool
    layers."""
    if rec.sections is not None:
        return tuple(rec.sections(cfg))
    kinds = tuple(rec.period(cfg))
    return ((kinds, pool_layers // kinds.count(False)),)


@dataclasses.dataclass(frozen=True)
class DecoderFamily:
    """The hooks of one decoder family (an author's fields, not options).

    ``embed(params, tokens, start, cfg) -> (x, ctx)``: token (and
    learned position) embeddings, plus what every layer needs of the
    positions (``(cos, sin)`` under RoPE, ``()`` otherwise); ``start``
    is where ``tokens[:, 0]`` stands, see :func:`positions_from`.
    ``qkv(cfg, x, lp, *ctx) -> (q, k, v)``: ``[B, T, H, Dh]`` and
    ``[B, T, KV, Dh]``.  ``out(cfg, x, attn, lp) -> x``: the attention
    output projection and the FFN half, each with its residual;
    ``attn`` is ``[B, T, H * Dh]``.  ``head(params, x, cfg) -> logits``
    ``[B, T, V]`` f32.  Each hook names its own ``jax.named_scope``s.
    """

    config_type: type
    embed: Callable[..., Tuple[Any, tuple]]
    qkv: Callable[..., Tuple[Any, Any, Any]]
    out: Callable[..., Any]
    head: Callable[..., Any]
    # cfg -> PartitionSpec tree of the params, over the mesh's named axes
    param_specs: Callable[[Any], Any]
    # path fragments of the leaves weight-only quantization leaves exact
    quant_skip_paths: Tuple[str, ...]
    # the mesh axes whose size > 1 makes a serving build a sharded one
    shard_axes: Tuple[str, ...] = ("model",)
    # raises for a (cfg, mesh, max_seq) the family cannot serve
    check: Callable[[Any, Any, int], None] = _no_check
    # cfg -> rows of a learned position table; None: positions are free
    max_positions: Callable[[Any], Optional[int]] = lambda cfg: None
    # cfg -> (stem keys, head keys) of the params that stay on the device
    # under weight streaming, the stacked ``blocks`` streaming between
    # them; None: the family has no streamed split
    streamed_split: Optional[Callable[[Any], Tuple[tuple, tuple]]] = None
    # cfg -> what a token's cache row is; the default is per-head K and V
    cache_row: Callable[[Any], CacheRow] = _per_head_rows
    # a leading stack of another layer kind: (key of its stacked params,
    # its ``out`` hook).  The paged loop runs it before ``blocks``, its
    # layers indexing the same pool from layer 0
    lead: Optional[Tuple[str, Callable[..., Any]]] = None
    # latent attention: ``(cfg, lp) -> (w_uk [C, H, Dn], w_uv [C, H, Dv],
    # softmax scale)``.  ``qkv`` then returns ``(q [B, T, H, Dn + Dr],
    # row [B, T, 1, C + Dr], None)`` and the paged forward attends in the
    # absorbed form at T == 1 and in the per-head form at T > 1
    # (:func:`~deepspeed_tpu.inference.kernels.latent_attention_step`)
    latent: Optional[Callable[..., Tuple[Any, Any, float]]] = None
    # cfg -> (how many held experts' routed rows a program counts, how
    # many (row, expert) pairs one row is routed to in all: top-k x the
    # expert layers); where the first is not 0, ``out`` returns
    # ``(x, rows [n] int32)``
    expert_rows: Callable[[Any], Tuple[int, int]] = lambda cfg: (0, 0)
    # cfg -> (how many experts the router scores in all, how many of
    # them a row is routed to).  Where that is more experts than
    # ``expert_rows`` says are held (a rank's share of an expert-parallel
    # deployment), the programs count one thing more behind the held
    # experts' rows: the further passes the held experts' pair buffer
    # needed (:func:`~deepspeed_tpu.parallel.moe.extra_pair_passes`)
    router: Callable[[Any], Tuple[int, int]] = lambda cfg: (0, 0)
    # leaves of ``blocks`` the paged loop does not slice a layer out of:
    # ``out`` gets them whole, [L, ...], with ``lp["layer"]`` the layer's
    # index in them (a kernel that takes the stack and an index reads a
    # layer in place; a slice handed to it would be a copy)
    whole_stacks: Tuple[str, ...] = ()
    # layers in periods of two kinds, some over a bounded per-slot
    # state beside the page pool: see ``Recurrent``.  The pool then has
    # the other layers only (and the ``lead``'s)
    recurrent: Optional[Recurrent] = None
    # (mechanism, why) the family cannot serve with yet: see ``refuse``
    refuses: Tuple[Tuple[str, str], ...] = ()

    @property
    def name(self) -> str:
        return self.config_type.__name__

    def pool_layers(self, cfg) -> int:
        """How many of ``cfg``'s layers attend over pages: the pool's
        leading dimension.  Every layer; with a per-slot seam, those
        that neither keep a state a slot nor are an FFN alone."""
        rec = self.recurrent
        if rec is None:
            return cfg.n_layers
        if rec.sections is None:
            return cfg.n_layers - rec.state_row(cfg).layers
        return sum(kinds.count(False) * count
                   for kinds, count in rec.sections(cfg))

    def ffn_alone_layers(self, cfg) -> int:
        """How many of ``cfg``'s layers are an FFN alone
        (``Recurrent.ffn``): neither pages nor a state."""
        rec = self.recurrent
        if rec.sections is None:
            return cfg.n_layers - self.pool_layers(cfg) \
                - rec.state_row(cfg).layers
        return sum(kinds.count(None) * count
                   for kinds, count in rec.sections(cfg))

    def sharded(self, mesh) -> bool:
        return mesh is not None and any(
            mesh.size(ax) > 1 for ax in self.shard_axes)

    def refuse(self, **asked) -> None:
        """Raise, naming the mechanism, for the first one ``asked``
        (``mechanism=truthy``) that the family ``refuses``: a build goes
        no further, and never falls silently to a path that is wrong."""
        for mechanism, why in self.refuses:
            if asked.get(mechanism):
                raise NotImplementedError(
                    f"{self.name} cannot serve with {mechanism}: {why}")


def positions_from(start, T: int):
    """Absolute positions of ``T`` tokens from ``start``: ``[T]`` from a
    scalar (one contiguous cache), ``[B, T]`` from per-row ``[B]`` offsets
    (ragged frontiers under continuous batching place each row at ITS
    length, not row 0's)."""
    if jnp.ndim(start) == 0:
        return start + jnp.arange(T, dtype=jnp.int32)
    return start[:, None] + jnp.arange(T, dtype=jnp.int32)[None]


# the registry: one module name a family
_FAMILY_MODULES = ("gpt2", "llama", "mixtral", "pangu_ultra_moe",
                   "qwen3_next", "granite_hybrid", "laguna", "nemotron_h",
                   "ling_flash", "phi4_flash", "brumby")


def decoder_families() -> Tuple[DecoderFamily, ...]:
    return tuple(
        importlib.import_module(f"deepspeed_tpu.models.{m}").FAMILY
        for m in _FAMILY_MODULES)


def decoder_family(cfg) -> DecoderFamily:
    """The record of ``cfg``'s family, by ``type(cfg)``."""
    fams = decoder_families()
    for fam in fams:
        if isinstance(cfg, fam.config_type):
            return fam
    raise TypeError(
        f"{type(cfg).__name__} is not a decoder family's config; "
        f"supported: {', '.join(f.name for f in fams)}")
