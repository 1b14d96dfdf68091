"""From the profiler's ``.xplane.pb`` to numbers.

``load`` reads the file with nothing but JAX and keeps three things:
per chip the device operations (name, start, duration in seconds), per
chip the runs of whole programs, and the host's spans whose names start
``bench/``.  Everything else here is arithmetic on those lists, so the
tests feed it lists written by hand as well as a recorded file.

Device operations nest (a ``while`` holds its body), so time is given to
the innermost operation: an operation's *self* time is its duration less
what its children cover.  Busy time is the union of all intervals.
"""

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]          # name, start s, duration s

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench/"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")
OUTSIDE = "outside_the_benchmark_s_spans"
HLO = re.compile(r"^%(?P<op>\S+) = \(?(?P<shape>[a-z0-9]+\[[0-9,]*\])?")
CALLS = re.compile(r"calls=%(\S*(?:" + COLLECTIVE.pattern + r")[^\s,)]*)")
PROGRAM = re.compile(r"\(\d+\)$")


def op_name(text):
    """The profiler names a device operation by its whole HLO line,
    operands and all.  Keep what identifies it: ``<op>:<result shape>``,
    then ``_<callee>`` where a fusion calls a collective, then
    ``_pallas`` where it is a Mosaic kernel (a ``tpu_custom_call``)."""
    m = HLO.match(text)
    if not m:
        return text
    name = m["op"] + (":" + m["shape"] if m["shape"] else "")
    callee = CALLS.search(text)
    if callee:
        name += "_" + callee.group(1)
    return name + "_pallas" if "tpu_custom_call" in text else name


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]           # chip -> device operations
    programs: Dict[int, List[Event]]      # chip -> runs of whole programs
    spans: List[Event]                    # the benchmark's host spans


def newest_xplane(logdir):
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(files, key=os.path.getmtime)


def load(path=None, *, serialized=None):
    from jax.profiler import ProfileData

    data = (ProfileData.from_serialized_xspace(serialized)
            if serialized is not None else ProfileData.from_file(path))
    trace = Trace({}, {}, [])
    for plane in data.planes:
        chip = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if chip and line.name == OPS_LINE:
                trace.ops.setdefault(int(chip.group(1)), []).extend(
                    (op_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events)
            elif chip and line.name == MODULES_LINE:
                trace.programs.setdefault(int(chip.group(1)), []).extend(
                    (PROGRAM.sub("", e.name), e.start_ns * 1e-9,
                     e.duration_ns * 1e-9) for e in line.events)
            elif not chip:
                trace.spans.extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return trace


# ------------------------------------------------------------ intervals
def union(intervals):
    """Disjoint sorted (start, end) covering the same instants."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals):
    return sum(e - s for s, e in intervals)


def minus(a, b):
    """The instants of the disjoint sorted ``a`` that ``b`` leaves."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < e:
            out.append((at, e))
    return out


def self_times(events):
    """[(name, start, end, self seconds)] with children's time taken out
    of their parents'."""
    out, stack = [], []                   # stack of [name, start, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][2] <= start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(end, stack[-1][2]) - start
        stack.append([name, start, end, dur])
    out.extend(tuple(s) for s in reversed(stack))
    return out


def window(trace):
    """(start, end) of the traced window: what any device line spans."""
    ev = [e for chip in trace.ops.values() for e in chip]
    ev += [e for chip in trace.programs.values() for e in chip]
    if not ev:
        return None
    return min(s for _, s, _ in ev), max(s + d for _, s, d in ev)


def busy(trace):
    """chip -> disjoint intervals in which an operation ran."""
    return {chip: union((s, s + d) for _, s, d in ev)
            for chip, ev in trace.ops.items()}


def mean_over_chips(per_chip):
    vals = list(per_chip.values())
    return sum(vals) / len(vals) if vals else None


def busy_seconds(trace):
    return mean_over_chips({c: length(b) for c, b in busy(trace).items()})


def idle_share(trace):
    w = window(trace)
    if w is None or w[1] <= w[0]:
        return None
    return 1.0 - busy_seconds(trace) / (w[1] - w[0])


def share_of_busy(trace, match):
    """Self time of the operations whose name ``match`` accepts, over
    busy time; the chips' mean."""
    busy_s = {c: length(b) for c, b in busy(trace).items()}

    def one(chip):
        st = self_times(trace.ops[chip])
        return sum(t for n, _, _, t in st if match(n)) / busy_s[chip]

    return mean_over_chips({c: one(c) for c in trace.ops if trace.ops[c]})


def exposed_collective_share(trace):
    """Time in collective operations during which nothing else runs on
    that chip, over the traced window; the chips' mean."""
    w = window(trace)
    if w is None or w[1] <= w[0]:
        return None

    def one(chip):
        st = self_times(trace.ops[chip])
        coll = union((s, e) for n, s, e, t in st
                     if COLLECTIVE.search(n) and t > 0)
        rest = union((s, e) for n, s, e, t in st
                     if not COLLECTIVE.search(n) and t > 0
                     and not _is_parent(n, s, e, t))
        return length(minus(coll, rest)) / (w[1] - w[0])

    return mean_over_chips({c: one(c) for c in trace.ops if trace.ops[c]})


def _is_parent(name, start, end, self_t):
    # an operation that only holds others (its own time is a sliver of
    # its span) does not count as "something else running"
    return self_t < 0.5 * (end - start)


def program_seconds(trace):
    """name -> sorted durations of that program's runs on the chip that
    ran it most."""
    out = {}
    for ev in trace.programs.values():
        for name, _, dur in ev:
            out.setdefault(name, []).append(dur)
        break
    return {n: sorted(d) for n, d in out.items()}


def top_ops(trace, n=10):
    """The n operations with most self time, summed over runs, averaged
    over chips."""
    total = {}
    for chip in trace.ops:
        for name, _, _, t in self_times(trace.ops[chip]):
            total[name] = total.get(name, 0.0) + t / len(trace.ops)
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(trace, n=10, chip=None):
    """Idle time of one chip by what the host was doing: each gap goes
    to the benchmark's span that covers most of it, or to OUTSIDE."""
    b = busy(trace)
    if not b:
        return []
    chip = min(b) if chip is None else chip
    w = window(trace)
    gaps = minus([w], b[chip])
    spans = sorted((s, s + d, name) for name, s, d in trace.spans)
    total = {}
    for gs, ge in gaps:
        best, cover = OUTSIDE, 0.0
        for ss, se, name in spans:
            if ss >= ge:
                break
            c = min(ge, se) - max(gs, ss)
            # the innermost span wins a tie: it started later
            if c > 0 and c >= cover:
                best, cover = name, c
        total[best] = total.get(best, 0.0) + (ge - gs)
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def describe(path, limit=12):
    """Planes, lines and a few events of a file: look at a trace by hand
    before trusting a number read from it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            ev = list(line.events)
            out.append(f"  line {line.name!r}: {len(ev)} events")
            for e in ev[:limit]:
                stats = {k: (str(v)[:60]) for k, v in list(e.stats)[:6]}
                out.append(f"    {e.name[:90]!r} start_ns={e.start_ns:.0f} "
                           f"dur_ns={e.duration_ns:.0f} {stats}")
    return "\n".join(out)


def to_text_proto(trace, start=None, end=None):
    """The trace, or the part of it between two instants, as an XSpace
    text proto that ``load(serialized=from_text_proto(...))`` reads back:
    how a small piece of a recorded trace is kept beside the tests."""
    keep = lambda s, d: (start is None or s >= start) and \
        (end is None or s + d <= end)
    planes = []
    for chip in sorted(set(trace.ops) | set(trace.programs)):
        planes.append((f"/device:TPU:{chip}", [
            (OPS_LINE, trace.ops.get(chip, [])),
            (MODULES_LINE, trace.programs.get(chip, []))]))
    planes.append(("/host:CPU", [("benchmark", trace.spans)]))
    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        names, body = {}, []
        for lid, (lname, events) in enumerate(lines, 1):
            body.append(f'  lines {{ id: {lid} name: "{lname}"')
            for name, s, d in events:
                if keep(s, d):
                    mid = names.setdefault(name, len(names) + 1)
                    body.append(f"    events {{ metadata_id: {mid} offset_ps: "
                                f"{round(s * 1e12)} duration_ps: "
                                f"{round(d * 1e12)} }}")
            body.append("  }")
        meta = [f'  event_metadata {{ key: {i} value {{ id: {i} name: '
                f'"{n}" }} }}' for n, i in names.items()]
        out += [f'planes {{ id: {pid} name: "{pname}"'] + body + meta + ["}"]
    return "\n".join(out) + "\n"


def from_text_proto(text):
    from jax.profiler import ProfileData

    return load(serialized=ProfileData.text_proto_to_serialized_xspace(text))
