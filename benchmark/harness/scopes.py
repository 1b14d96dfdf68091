"""The names the program gives what runs, read from a traced run.

``harness/trace.py`` keeps a device operation's ``<op>:<shape>`` and the
benchmark's own ``bench/`` spans.  This module reads the same
``.xplane.pb`` again, after the window, and keeps what the program
itself says: (a) the host spans whose names start ``dstpu/``
(``telemetry.Span``: ``dstpu/serving_step`` and its children,
``dstpu/train_step``), with their keyword stats, beside the ``bench/``
spans; (b) for every device operation the path of ``jax.named_scope``
names it was traced under, from which comes its *scope*: the innermost
word of the path that belongs to the program's vocabulary; (c) the
Mosaic kernel's own name (``name=`` on the ``pallas_call``).

Where the scope comes from (looked up on a v5e trace, PR 24).  The
profiler names a device operation by its HLO line, which carries no
``metadata={op_name=...}``, and the event's own stats are three times.
The path is a stat of the event's *metadata* (the ``event_metadata`` map
of the device plane): ``tf_op``, for example
``jit(dstpu_decode)/while/body/closed_call/mlp/moe_ffn/vmap()/dot_general:``.
``jax.profiler.ProfileData`` does not hand out that map, so
``metadata_stats`` reads just it from the file's bytes (protobuf's wire
format; nothing but the standard library), and the events, lines and
times come from ``ProfileData`` as in ``harness/trace.py``.  The
profiler gives a fusion its root's path.  Compiler-inserted copies have
no ``tf_op``: an unscoped operation whose result has the shape of the
K/V pool, or of one layer of it, counts as ``kv_copy``; everything else
unscoped is reported under ``unscoped``.

The rest is arithmetic on lists, so that the tests feed it lists
written by hand as well as a recorded file.
"""

import dataclasses
import functools
import re
from typing import Dict, List, Optional, Tuple

from benchmark.harness import trace as T

VOCABULARY = ("embed", "attn_qkv", "kv_write", "kv_attend", "flash",
              "attn_out", "mlp", "moe_router", "moe_ffn", "final_norm",
              "lm_head", "sample", "loss", "grad_clip", "optimizer")
KV_COPY, UNSCOPED = "kv_copy", "unscoped"
PROGRAM_PREFIX, BENCH_PREFIX = "dstpu/", "bench/"
STEP_SPAN = "dstpu/serving_step"

PATH_STAT = "tf_op"
WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
KERNEL = re.compile(r"%(dstpu_[a-z0-9_]*[a-z0-9])(?:\.\d+)? = ")
SHAPE = re.compile(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]")
OPCODE = re.compile(r"[\s)][a-z][a-z\-]*\(")     # where the operands begin
BACKWARD = "transpose("


@dataclasses.dataclass
class Op:
    """One device operation, as the scopes see it."""

    name: str                    # <op>:<shape>, as harness/trace.py cuts it
    start: float
    dur: float
    path: str = ""               # the named-scope path, "" if none
    results: Tuple = ()          # ((dtype, dims), ...) the HLO line gives
    operands: Tuple = ()         # its results and its operands
    kernel: Optional[str] = None  # dstpu_* of a Mosaic kernel


@dataclasses.dataclass
class Span:
    name: str
    start: float
    dur: float
    stats: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Scoped:
    ops: Dict[int, List[Op]]               # chip -> device operations
    programs: Dict[int, List[T.Event]]     # chip -> whole programs
    spans: List[Span]                      # dstpu/ and bench/ host spans
    _rows: Dict = dataclasses.field(default_factory=dict, repr=False,
                                    compare=False)   # by_scope, by pool


# ------------------------------------------------------------- reading
def parse_shapes(text):
    return tuple((m.group(1), tuple(int(x) for x in m.group(2).split(",")
                                    if x))
                 for m in SHAPE.finditer(text))


def op_from_event(text, start, dur, path=""):
    """``text`` is the profiler's name of the event: the HLO line,
    ``%name = <results> <opcode>(<operands>), ...``."""
    kernel = KERNEL.match(text) if "tpu_custom_call" in text else None
    opcode = OPCODE.search(text)
    cut = opcode.end() if opcode else len(text)
    end, depth = cut, 1
    while end < len(text) and depth:           # to the matching ")"
        depth += {"(": 1, ")": -1}.get(text[end], 0)
        end += 1
    return Op(T.op_name(text), start, dur, path or "",
              parse_shapes(text[:cut]), parse_shapes(text[cut:end]),
              kernel.group(1) if kernel else None)


# protobuf's wire format, as far as XSpace needs it: XSpace.planes = 1;
# XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps: key =
# 1, value = 2); XEventMetadata.name = 2, .stats = 5; XStatMetadata.name
# = 2; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7
def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield number, value


def _entry(buf):
    """The value message of a map entry."""
    return next((v for n, v in _fields(buf) if n == 2), b"")


def metadata_stats(serialized, stat=PATH_STAT):
    """plane name -> {event name: value of ``stat``}, for the events
    whose metadata carries that stat as a string."""
    out = {}
    for number, plane in _fields(memoryview(serialized)):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, v in _fields(plane):
            if n == 2:
                name = bytes(v).decode()
            elif n == 4:
                events.append(_entry(v))
            elif n == 5:
                key = next((x for k, x in _fields(v) if k == 1), 0)
                stat_names[key] = next(
                    (bytes(x).decode() for k, x in _fields(_entry(v))
                     if k == 2), "")
        found = {}
        for meta in events:
            ev_name, value = "", None
            for n, v in _fields(meta):
                if n == 2:
                    ev_name = bytes(v).decode()
                elif n == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == stat:
                        value = bytes(st[5]).decode() if 5 in st \
                            else stat_names.get(st.get(7))
            if value:
                found.setdefault(ev_name, value)
        if found:
            out[name] = found
    return out


@functools.lru_cache(maxsize=2)
def load(path):
    """The trace at ``path`` with the program's names kept."""
    with open(path, "rb") as f:
        return from_serialized(f.read())


def from_text_proto(text):
    from jax.profiler import ProfileData

    return from_serialized(ProfileData.text_proto_to_serialized_xspace(text))


def from_serialized(serialized):
    from jax.profiler import ProfileData

    paths = metadata_stats(serialized)
    out = Scoped({}, {}, [])
    for plane in ProfileData.from_serialized_xspace(serialized).planes:
        chip = T.DEVICE_PLANE.match(plane.name)
        path = paths.get(plane.name, {})
        for line in plane.lines:
            if chip and line.name == T.OPS_LINE:
                out.ops.setdefault(int(chip.group(1)), []).extend(
                    op_from_event(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9, path.get(e.name))
                    for e in line.events)
            elif chip and line.name == T.MODULES_LINE:
                out.programs.setdefault(int(chip.group(1)), []).extend(
                    (T.PROGRAM.sub("", e.name), e.start_ns * 1e-9,
                     e.duration_ns * 1e-9) for e in line.events)
            elif not chip:
                out.spans.extend(
                    Span(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         {k: str(v) for k, v in e.stats})
                    for e in line.events
                    if e.name.startswith((PROGRAM_PREFIX, BENCH_PREFIX)))
    return out


def of_run(run):
    """The scoped trace of a traced run, or None: read again from the
    file the profiler wrote, after the window."""
    if run.traced is None:
        return None
    try:
        return load(T.newest_xplane(run.trace_dir))
    except FileNotFoundError:
        return None


# -------------------------------------------------------------- scopes
def scope_of(path):
    """(scope, backward): the innermost word of ``path`` that the
    vocabulary knows, or None; backward when the operation was traced
    under a transpose (``transpose(jvp(...))``: the backward pass;
    ``jvp(...)`` alone is the forward pass of a differentiated
    function)."""
    scope = None
    for word in WORD.findall(path):
        if word in VOCABULARY:
            scope = word
    return scope, BACKWARD in path


def pool_shapes(window):
    """The dims of the K/V pool and of one layer of it, from a serving
    run's window; () for a run that has no pool."""
    cfg = window.get("program_config")
    if cfg is None or "pool_pages" not in window:
        return ()
    kv = getattr(cfg, "n_kv_heads", None) or cfg.n_heads
    layer = (kv, window["pool_pages"] + 1, window["page_size"],
             cfg.head_dim)
    return (_squeezed((cfg.n_layers,) + layer), layer)


def label(op, pool=()):
    """The scope an operation's time is counted under."""
    scope, _ = scope_of(op.path)
    if scope is not None:
        return scope
    if pool and op.results and _squeezed(op.results[0][1]) in pool:
        return KV_COPY
    return UNSCOPED


def _squeezed(dims):
    """A slice of one layer keeps a leading 1: [1, KV, P, ps, hd]."""
    dims = tuple(dims)
    while len(dims) > 1 and dims[0] == 1:
        dims = dims[1:]
    return dims


def named(scoped):
    """Whether any operation carries a scope of the vocabulary: a
    program from before the names has none, and reads as nothing."""
    return any(scope_of(o.path)[0] for ops in scoped.ops.values()
               for o in ops)


def programs_named(scoped, word):
    return any(word in n for ev in scoped.programs.values()
               for n, _, _ in ev)


def kernel_seconds(scoped):
    """Mosaic kernel name -> calls on all chips and seconds (the chips'
    mean)."""
    out = {}
    for ops in scoped.ops.values():
        for o in ops:
            if o.kernel:
                row = out.setdefault(o.kernel, {"calls": 0, "seconds": 0.0})
                row["calls"] += 1
                row["seconds"] += o.dur / len(scoped.ops)
    return out


def self_seconds(ops):
    """[(op, self seconds)]: children's time taken out of parents'."""
    st = T.self_times([(i, o.start, o.dur) for i, o in enumerate(ops)])
    return [(ops[i], t) for i, _, _, t in st]


def by_scope(scoped, pool=()):
    """scope -> {"self_s", "ops", "collective_s", "backward_s"}; the
    chips' mean of seconds, operations counted on all chips.  Kept on
    the trace: several readers ask for the same reduction."""
    if pool in scoped._rows:
        return scoped._rows[pool]
    out = scoped._rows[pool] = {}
    chips = max(1, len(scoped.ops))
    for ops in scoped.ops.values():
        for op, t in self_seconds(ops):
            row = out.setdefault(label(op, pool), {
                "self_s": 0.0, "ops": 0, "collective_s": 0.0,
                "backward_s": 0.0})
            row["self_s"] += t / chips
            row["ops"] += 1
            if T.COLLECTIVE.search(op.name):
                row["collective_s"] += t / chips
            if scope_of(op.path)[1]:
                row["backward_s"] += t / chips
    return out


def window_of(scoped):
    return T.window(T.Trace(
        {c: [(o.name, o.start, o.dur) for o in ops]
         for c, ops in scoped.ops.items()}, scoped.programs, []))


def share_of_window(scoped, scopes, pool=()):
    """Self time under ``scopes`` over the traced window; chips' mean."""
    w = window_of(scoped)
    if w is None or w[1] <= w[0] or not scoped.ops:
        return None
    rows = by_scope(scoped, pool)
    return sum(rows[s]["self_s"] for s in scopes if s in rows) / (w[1] - w[0])


def share_of_busy(scoped, scopes, pool=()):
    rows = by_scope(scoped, pool)
    busy = sum(r["self_s"] for r in rows.values())
    if not busy:
        return None
    return sum(rows[s]["self_s"] for s in scopes if s in rows) / busy


def unscoped_ops(scoped, pool=(), n=10):
    """The unscoped operations with most self time: what the names
    miss, by ``<op>:<shape>``."""
    total = {}
    for ops in scoped.ops.values():
        for op, t in self_seconds(ops):
            if label(op, pool) == UNSCOPED:
                total[op.name] = total.get(op.name, 0.0) + t / len(scoped.ops)
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


# ------------------------------------------------------------ programs
def program_share(scoped, words):
    """Time in the runs of the programs whose name holds one of
    ``words`` over the traced window; the chips' mean."""
    w = window_of(scoped)
    if w is None or w[1] <= w[0] or not scoped.programs:
        return None
    per_chip = [sum(d for n, _, d in ev if any(x in n for x in words))
                for ev in scoped.programs.values()]
    return sum(per_chip) / len(per_chip) / (w[1] - w[0])


def collectives_in_a_run(scoped, word, chip=None):
    """Collective operations inside one run of the program whose name
    holds ``word``, on one chip: the median over its whole runs.  An
    asynchronous one (``-start`` ... ``-done``) counts once."""
    if not scoped.programs:
        return None
    chip = min(scoped.programs) if chip is None else chip
    runs = [(s, s + d) for n, s, d in scoped.programs[chip] if word in n]
    coll = sorted(o.start for o in scoped.ops.get(chip, [])
                  if T.COLLECTIVE.search(o.name) and "-done" not in o.name)
    counts = [sum(1 for t in coll if s <= t < e) for s, e in runs]
    return _median(counts)


# --------------------------------------------------------------- spans
def children(scoped, parent=STEP_SPAN):
    """[(parent span, [its dstpu/ children])] for every ``parent`` span:
    a child starts and ends inside it.  The spans of one thread nest,
    and the engine steps on one thread."""
    mine = sorted((s for s in scoped.spans
                   if s.name.startswith(PROGRAM_PREFIX)),
                  key=lambda s: (s.start, -s.dur))
    out = []
    for s in mine:
        if s.name == parent:
            out.append((s, []))
        elif out and s.start >= out[-1][0].start and \
                s.start + s.dur <= out[-1][0].start + out[-1][0].dur + 1e-9:
            out[-1][1].append(s)
    return out


def step_phases(scoped, names, parent=STEP_SPAN, beside=()):
    """Per ``parent`` span, the seconds its children named in ``names``
    take, plus those of the ``beside`` spans that follow it before the
    next parent (``serving_tick`` runs after ``serving_step``)."""
    steps = children(scoped, parent)
    out = [sum(c.dur for c in kids if c.name in names) for _, kids in steps]
    if beside:
        later = sorted((s for s in scoped.spans if s.name in beside),
                       key=lambda s: s.start)
        starts = [p.start for p, _ in steps] + [float("inf")]
        i = 0
        for s in later:
            while i + 1 < len(starts) and starts[i + 1] <= s.start:
                i += 1
            if i < len(out) and s.start >= starts[i]:
                out[i] += s.dur
    return out


def coverage(scoped, parent=STEP_SPAN):
    """Share of the parents' time their children cover: the children
    tile the parent when this is near 1."""
    steps = children(scoped, parent)
    whole = sum(p.dur for p, _ in steps)
    return sum(c.dur for _, kids in steps for c in kids) / whole \
        if whole else None


def programs_per_step(scoped, parent=STEP_SPAN, chip=None):
    """Program runs on the Modules line that start inside one ``parent``
    span, the median over spans."""
    if not scoped.programs:
        return None
    chip = min(scoped.programs) if chip is None else chip
    starts = sorted(s for _, s, _ in scoped.programs[chip])
    spans = [s for s in scoped.spans if s.name == parent]
    return _median([sum(1 for t in starts if s.start <= t < s.start + s.dur)
                    for s in spans])


def innermost(spans):
    """[(start, end, name)]: for every instant some span covers, the
    innermost one.  The spans of one thread nest (``bench/step`` holds
    ``dstpu/serving_step`` holds its phases)."""
    out, stack = [], []                    # stack of (end, name)

    def emit(t0, t1):
        if stack and t1 > t0:
            out.append((t0, t1, stack[-1][1]))

    at = None
    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        while stack and stack[-1][0] <= s.start:
            emit(at, stack[-1][0])
            at = stack.pop()[0]
        emit(at, s.start)
        at = s.start
        stack.append((s.start + s.dur, s.name))
    while stack:
        emit(at, stack[-1][0])
        at = stack.pop()[0]
    return out


def idle_by_span(scoped, chip=None):
    """Idle seconds of one chip by what the host was doing: every idle
    instant goes to the *innermost* ``bench/`` or ``dstpu/`` span that
    covers it (a gap that straddles two phases is split between them),
    or to ``trace.OUTSIDE``.  Sorted, most first."""
    plain = T.Trace({c: [(o.name, o.start, o.dur) for o in ops]
                     for c, ops in scoped.ops.items()}, scoped.programs, [])
    busy = T.busy(plain)
    w = T.window(plain)
    if not busy or w is None:
        return []
    gaps = T.minus([w], busy[min(busy) if chip is None else chip])
    total = {}
    covered = 0.0
    for t0, t1, name in innermost(scoped.spans):
        idle = T.length(T.minus([(t0, t1)], T.minus([(t0, t1)], gaps)))
        if idle > 1e-12:                   # not a rounding residue
            total[name] = total.get(name, 0.0) + idle
            covered += idle
    rest = T.length(gaps) - covered
    if rest > 1e-12:
        total[T.OUTSIDE] = rest
    return sorted(total.items(), key=lambda kv: -kv[1])


def _median(values):
    values = sorted(values)
    if not values:
        return None
    mid = len(values) // 2
    return values[mid] if len(values) % 2 else \
        0.5 * (values[mid - 1] + values[mid])


# ------------------------------------------- a piece kept beside tests
def cut_text_proto(serialized, start, end, min_dur=0.0, name_chars=600):
    """The part of a recorded trace between two instants (seconds), as
    an XSpace text proto that ``from_text_proto`` reads back: device
    operations of at least ``min_dur`` with their ``tf_op``, whole
    programs, and the ``dstpu/`` and ``bench/`` spans with their stats.
    An HLO line is cut to ``name_chars`` unless it is a Mosaic kernel's
    (its marker comes last).  How a small piece of a chip trace is kept
    beside the tests; the expected values are read from the piece."""
    from jax.profiler import ProfileData

    paths = metadata_stats(serialized)
    quote = lambda s: '"' + s.replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n") + '"'
    keep = lambda e: e.start_ns * 1e-9 >= start and \
        (e.start_ns + e.duration_ns) * 1e-9 <= end
    out = []
    planes = ProfileData.from_serialized_xspace(serialized).planes
    for pid, plane in enumerate(planes, 1):
        chip = T.DEVICE_PLANE.match(plane.name)
        names, stat_ids, body = {}, {PATH_STAT: 1}, []
        for lid, line in enumerate(plane.lines, 1):
            if chip and line.name not in (T.OPS_LINE, T.MODULES_LINE):
                continue
            events = []
            for e in line.events:
                if not keep(e) or (chip and line.name == T.OPS_LINE
                                   and e.duration_ns * 1e-9 < min_dur):
                    continue
                if not chip and not e.name.startswith(
                        (PROGRAM_PREFIX, BENCH_PREFIX)):
                    continue
                text = e.name if "tpu_custom_call" in e.name \
                    else e.name[:name_chars]
                mid = names.setdefault(
                    text, (len(names) + 1,
                           paths.get(plane.name, {}).get(e.name)))[0]
                stats = "" if chip else "".join(
                    f" stats {{ metadata_id: "
                    f"{stat_ids.setdefault(k, len(stat_ids) + 1)} "
                    f"str_value: {quote(str(v))} }}" for k, v in e.stats)
                events.append(
                    f"    events {{ metadata_id: {mid} offset_ps: "
                    f"{round(e.start_ns * 1e3)} duration_ps: "
                    f"{round(e.duration_ns * 1e3)}{stats} }}")
            if events:
                body += [f"  lines {{ id: {lid} name: {quote(line.name)}"] \
                    + events + ["  }"]
        if not body:
            continue
        meta = [f"  event_metadata {{ key: {i} value {{ id: {i} name: "
                f"{quote(n)}"
                + (f" stats {{ metadata_id: 1 str_value: {quote(p)} }}"
                   if p else "") + " } }" for n, (i, p) in names.items()]
        meta += [f"  stat_metadata {{ key: {i} value {{ id: {i} name: "
                 f"{quote(k)} }} }}" for k, i in stat_ids.items()]
        out += [f"planes {{ id: {pid} name: {quote(plane.name)}"] + body \
            + meta + ["}"]
    return "\n".join(out) + "\n"
