"""Surface-parity gates (pass 4) + the Chrome-trace evidence check.

Four surfaces that historically drift apart are pinned to each other:

- **config ↔ CONFIG.md**: every serving/control-plane config block's
  dataclass fields must appear in its CONFIG.md section, and every
  key a section's table documents must exist as a field.  A knob that
  exists but is undocumented is unusable; a documented knob that does
  not exist is a lie.
- **metrics ↔ docs**: every metric name cited in README.md, CONFIG.md
  or ``tools/dstpu_top.py`` must match a name actually registered via
  the ``MetricsRegistry`` (f-string registrations like
  ``slo_{name}_attainment`` become patterns; doc placeholders —
  ``slo_<tier>_…``, ``{ttft,itl,deadline}`` alternation, ``kv_tier_*``
  families — expand accordingly).  Trace-event names emitted through
  ``tracer.event("…")`` count as citable too (docs reference both).
- **faults ↔ CONFIG.md**: the rule-validation tables in ``faults.py``
  (``SUBSYSTEMS`` / ``MODES`` / ``_KEYED_SUBSYSTEMS``) against the
  fault-rule rows of CONFIG.md — a ``match=`` documented for a
  subsystem whose opportunities carry no key would validate fine and
  silently never fire.
- **trace pairing**: the committed ``TRACE_SAMPLE.chrome.json`` (the
  cheap runtime-evidence half of this pass: it is re-stamped by the
  slow lane's trace selftest) must hold balanced async begin/end
  pairs per ``(cat, id, name)`` with monotonic, non-negative
  timestamps — an unpaired span is how an export bug reads as a hung
  request in every downstream viewer.
"""

from __future__ import annotations

import ast
import json
import re
from typing import Dict, List, Optional, Tuple

from .core import Finding, SourceFile

PASS = "parity"

# config block class -> CONFIG.md section name (## `section`)
CONFIG_BLOCKS = {
    "ZeroInferenceConfig": "zero_inference",
    "PrefixCacheConfig": "prefix_cache",
    "KVTierConfig": "kv_tier",
    "CommConfig": "comm",
    "SpeculativeConfig": "speculative",
    "SLOConfig": "slo",
    "FaultsConfig": "faults",
    "FleetConfig": "fleet",
    "FabricConfig": "fabric",
    "AutoscaleConfig": "autoscale",
    "TelemetryConfig": "telemetry",
    "TracingConfig": "tracing",
    "HistoryConfig": "history",
    "IncidentsConfig": "incidents",
    "DevprofConfig": "devprof",
    "MeshConfig": "mesh",
    "ObsWireConfig": "obs_wire",
    "TransportConfig": "transport",
    "ProcFleetConfig": "proc_fleet",
}

# metric families the citation scan is anchored to: a doc token is only
# judged when it starts with one of these (anything else — function
# names, config keys, bench-JSON paths — is not a metric citation)
METRIC_FAMILIES = (
    "serving_", "prefix_cache_", "spec_", "kv_tier_", "slo_",
    "fleet_", "autoscale_", "zi_", "pstream_", "aio_",
    "tier_reader_", "comm_", "infinity_", "history_", "incident_",
    "devprof_", "obswire_", "transport_",
)
# bench-evidence JSON namespaces and row labels that share a family
# prefix but are not registry metrics (cited next to the metrics in
# the same docs)
_NON_METRIC_TOKENS = frozenset((
    "spec_ab", "prefix_ab", "kv_tier_ab", "tp_ab", "slo_overhead",
    "zi_spec_off", "zi_spec_on",
))

_WILD = "[a-zA-Z0-9_]+"


# ------------------------------------------------------------ config ↔ doc
def _md_sections(md_text: str) -> Dict[str, str]:
    """``section-name -> body`` for every ``## `name` …`` heading."""
    out: Dict[str, str] = {}
    cur: Optional[str] = None
    buf: List[str] = []
    for line in md_text.splitlines():
        m = re.match(r"^##\s+.*?`([a-z_]+)`", line)
        if line.startswith("## "):
            if cur is not None:
                out[cur] = "\n".join(buf)
            cur, buf = (m.group(1) if m else None), []
        elif cur is not None:
            buf.append(line)
    if cur is not None:
        out[cur] = "\n".join(buf)
    return out


def _dataclass_fields(config_sf: SourceFile,
                      class_name: str) -> Optional[List[str]]:
    for node in config_sf.tree.body:
        if isinstance(node, ast.ClassDef) and node.name == class_name:
            fields = []
            for sub in node.body:
                if isinstance(sub, ast.AnnAssign) and \
                        isinstance(sub.target, ast.Name) and \
                        not sub.target.id.startswith("_"):
                    fields.append(sub.target.id)
            return fields
    return None


def _table_keys(section: str) -> List[str]:
    """First-cell backticked keys of the section's markdown table."""
    keys: List[str] = []
    for line in section.splitlines():
        m = re.match(r"^\|\s*(`[^|]*`)\s*\|", line)
        if m:
            keys.extend(re.findall(r"`([a-z_][a-z0-9_]*)`",
                                   m.group(1)))
    return keys


def check_config_doc(config_sf: SourceFile, config_md: str,
                     md_rel: str = "CONFIG.md",
                     blocks: Dict[str, str] = None) -> List[Finding]:
    blocks = blocks if blocks is not None else CONFIG_BLOCKS
    findings: List[Finding] = []
    sections = _md_sections(config_md)
    for cls, sec_name in blocks.items():
        fields = _dataclass_fields(config_sf, cls)
        if fields is None:
            findings.append(Finding(
                PASS, "config-doc-drift", config_sf.rel, 0,
                f"config block class {cls} (mapped to CONFIG.md "
                f"section `{sec_name}`) no longer exists"))
            continue
        section = sections.get(sec_name)
        if section is None:
            findings.append(Finding(
                PASS, "config-doc-drift", md_rel, 0,
                f"CONFIG.md has no `## \\`{sec_name}\\`` section for "
                f"config class {cls}"))
            continue
        for f in fields:
            if f == "enabled":
                continue          # block-presence opt-in, doc'd in prose
            if not re.search(r"`[^`\n]*\b%s\b[^`\n]*`" % re.escape(f),
                             section):
                findings.append(Finding(
                    PASS, "config-doc-drift", md_rel, 0,
                    f"{cls}.{f} is not documented in the CONFIG.md "
                    f"`{sec_name}` section (no backticked mention)"))
        valid = set(fields) | {"enabled"}
        for key in _table_keys(section):
            if key not in valid:
                findings.append(Finding(
                    PASS, "config-doc-drift", md_rel, 0,
                    f"CONFIG.md `{sec_name}` table documents key "
                    f"`{key}` which is not a {cls} field"))
    return findings


# ----------------------------------------------------------- metrics ↔ doc
def registered_metrics(files: List[SourceFile]
                       ) -> Tuple[set, List[str], set]:
    """Scan the package ASTs for registry registrations.  Returns
    ``(literal_names, pattern_regexes, event_names)``: first args of
    ``.counter/.gauge/.histogram`` calls (f-strings become wildcard
    patterns), ``.span(name)`` as ``name_seconds``, and first args of
    ``.event("…")`` emits (trace-event names are citable in docs)."""
    literals: set = set()
    patterns: List[str] = []
    events: set = set()

    def record(arg: ast.AST, suffix: str = "") -> None:
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            literals.add(arg.value + suffix)
        elif isinstance(arg, ast.JoinedStr):
            parts = []
            literal_chars = 0
            for v in arg.values:
                if isinstance(v, ast.Constant):
                    parts.append(re.escape(str(v.value)))
                    literal_chars += len(
                        str(v.value).replace("_", ""))
                else:
                    parts.append(_WILD)
            # a pattern that is nearly all placeholder (e.g. the comm
            # fan-in's {prefix}_{op}_{cname}) matches ANY segmented
            # name and would hide every rename — too generic to count
            if literal_chars + len(suffix.replace("_", "")) >= 4:
                patterns.append("".join(parts) + re.escape(suffix))

    for sf in files:
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute) and node.args):
                continue
            attr = node.func.attr
            if attr in ("counter", "gauge", "histogram"):
                record(node.args[0])
            elif attr == "span":
                record(node.args[0], suffix="_seconds")
            elif attr in ("event", "_event"):
                # `_event`: the autoscaler's ledger+tracer wrapper —
                # its literal kinds are trace events too (the docs
                # cite them; `event` alone would miss every emit that
                # goes through the wrapper)
                a = node.args[0]
                if isinstance(a, ast.Constant) and \
                        isinstance(a.value, str):
                    events.add(a.value)
    return literals, patterns, events


def _doc_tokens(text: str) -> List[str]:
    """Backtick-quoted inline code spans of a markdown document."""
    return re.findall(r"`([^`\n]+)`", text)


def _source_strings(sf: SourceFile) -> List[str]:
    return [n.value for n in ast.walk(sf.tree)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)]


def _expand_alternation(token: str) -> List[str]:
    """``a_{x,y}_b`` -> [``a_x_b``, ``a_y_b``] (one level)."""
    m = re.search(r"\{([^{}]+,[^{}]+)\}", token)
    if not m:
        return [token]
    out = []
    for alt in m.group(1).split(","):
        out.extend(_expand_alternation(
            token[:m.start()] + alt.strip() + token[m.end():]))
    return out


def _token_regex(token: str) -> Optional[str]:
    """Doc token -> anchored regex (``<ph>`` and ``*`` wildcard), or
    None when the token is not a well-formed metric citation."""
    token = re.sub(r"<[a-z_]+>", "\x00", token)
    token = token.replace("*", "\x00")
    if not re.fullmatch(r"[a-z0-9_\x00]+", token):
        return None
    return re.escape(token).replace("\x00", _WILD)


def check_metric_citations(files: List[SourceFile],
                           docs: Dict[str, str],
                           source_docs: List[SourceFile] = ()
                           ) -> List[Finding]:
    """Every metric-shaped citation in ``docs`` (markdown text keyed by
    repo-relative name) and in the string literals of ``source_docs``
    (e.g. dstpu_top) must resolve against the registered names."""
    literals, patterns, events = registered_metrics(files)
    # every registered pattern, instantiated with a probe segment, so a
    # doc-side wildcard can be matched against pattern-registered names
    instantiated = {p.replace(_WILD, "zz9") for p in patterns}
    pattern_res = [re.compile(p + "$") for p in patterns]
    names = literals | events

    def resolves(token: str) -> bool:
        for t in _expand_alternation(token):
            rx = _token_regex(t)
            if rx is None:
                return True          # not a metric citation shape
            r = re.compile(rx + "$")
            if any(r.match(n) for n in names):
                continue
            if any(r.match(inst) for inst in instantiated):
                continue
            if any(p.match(t) for p in pattern_res):
                continue
            return False
        return True

    def candidates(tokens, where: str, findings: List[Finding]):
        for tok in tokens:
            tok = tok.strip()
            base = tok.split(".")[0]     # `FILE.json` paths etc.
            if "." in tok or " " in tok or "=" in tok or ":" in tok:
                continue
            if not any(base.startswith(f) for f in METRIC_FAMILIES):
                continue
            if base in _NON_METRIC_TOKENS:
                continue
            # metric names are >= 3 segments (family + subject +
            # suffix); 2-segment tokens sharing a family prefix are
            # API/config citations (`serving_engine`, `aio_read`) —
            # out of scope unless they carry an explicit wildcard or
            # placeholder marking them as a metric family
            if tok.count("_") < 2 and not ("*" in tok or "<" in tok
                                           or "{" in tok):
                continue
            if not resolves(tok):
                findings.append(Finding(
                    PASS, "metric-doc-drift", where, 0,
                    f"`{tok}` is cited but no registered metric or "
                    f"trace event matches it — rename the citation "
                    f"or register the metric"))

    findings: List[Finding] = []
    for rel, text in docs.items():
        candidates(_doc_tokens(text), rel, findings)
    for sf in source_docs:
        toks = [s for s in _source_strings(sf)
                if re.fullmatch(r"[a-z][a-z0-9_]+", s)]
        candidates(toks, sf.rel, findings)
    # dedupe (the same family token is often cited repeatedly)
    seen = set()
    out = []
    for f in findings:
        k = (f.path, f.message)
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out


# ------------------------------------------------------------ faults ↔ doc
def _module_tuple(sf: SourceFile, name: str) -> Optional[Tuple[str, ...]]:
    for node in sf.tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    try:
                        v = ast.literal_eval(node.value)
                    except ValueError:
                        return None
                    return tuple(v)
    return None


def check_faults_doc(faults_sf: SourceFile, config_md: str,
                     md_rel: str = "CONFIG.md") -> List[Finding]:
    findings: List[Finding] = []
    subsystems = _module_tuple(faults_sf, "SUBSYSTEMS")
    modes = _module_tuple(faults_sf, "MODES")
    keyed = _module_tuple(faults_sf, "_KEYED_SUBSYSTEMS")
    if not (subsystems and modes and keyed):
        findings.append(Finding(
            PASS, "fault-table-drift", faults_sf.rel, 0,
            "faults.py no longer defines SUBSYSTEMS / MODES / "
            "_KEYED_SUBSYSTEMS as literal tuples — the validation "
            "table the docs mirror is gone"))
        return findings
    bad_keyed = set(keyed) - set(subsystems)
    if bad_keyed:
        findings.append(Finding(
            PASS, "fault-table-drift", faults_sf.rel, 0,
            f"_KEYED_SUBSYSTEMS names unknown subsystems "
            f"{sorted(bad_keyed)}"))
    section = _md_sections(config_md).get("faults")
    if section is None:
        findings.append(Finding(
            PASS, "fault-table-drift", md_rel, 0,
            "CONFIG.md has no `## `faults`` section"))
        return findings
    for sub in subsystems:
        if not re.search(r"`[^`\n]*\b%s\b[^`\n]*`" % re.escape(sub),
                         section):
            findings.append(Finding(
                PASS, "fault-table-drift", md_rel, 0,
                f"fault subsystem `{sub}` (faults.SUBSYSTEMS) is not "
                f"documented in the CONFIG.md faults section"))
    for mode in modes:
        if not re.search(r"`[^`\n]*\b%s\b[^`\n]*`" % re.escape(mode),
                         section):
            findings.append(Finding(
                PASS, "fault-table-drift", md_rel, 0,
                f"fault mode `{mode}` (faults.MODES) is not "
                f"documented in the CONFIG.md faults section"))
    # the `match` row must cite exactly the keyed subsystems: a match
    # documented for an unkeyed subsystem validates then never fires
    match_rows = [ln for ln in section.splitlines()
                  if re.match(r"^\|.*`match`", ln)]
    if not match_rows:
        findings.append(Finding(
            PASS, "fault-table-drift", md_rel, 0,
            "CONFIG.md faults table has no `match` row"))
    else:
        row = " ".join(match_rows)
        cited = {s for s in subsystems
                 if re.search(r"`%s`" % re.escape(s), row)}
        if cited != set(keyed):
            findings.append(Finding(
                PASS, "fault-table-drift", md_rel, 0,
                f"CONFIG.md `match` row cites {sorted(cited)} but "
                f"faults._KEYED_SUBSYSTEMS is {sorted(keyed)} — "
                f"match= only applies to keyed subsystems"))
    docstring = ast.get_docstring(faults_sf.tree) or ""
    for sub in subsystems:
        if sub not in docstring:
            findings.append(Finding(
                PASS, "fault-table-drift", faults_sf.rel, 0,
                f"fault subsystem `{sub}` missing from the faults.py "
                f"module-docstring hook-point table"))
    return findings


# --------------------------------------------------------- trace pairing
def check_trace_pairing(doc: dict, rel: str) -> List[Finding]:
    """Validate the committed Chrome trace export: balanced async
    b/e per (cat, id, name), non-negative monotonic timestamps."""
    findings: List[Finding] = []
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return [Finding(PASS, "trace-bad-format", rel, 0,
                        "no traceEvents list")]
    open_spans: Dict[Tuple, int] = {}
    last_ts = 0.0
    for i, e in enumerate(events):
        ph = e.get("ph")
        if ph == "M":
            continue
        ts = e.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            findings.append(Finding(
                PASS, "trace-bad-ts", rel, 0,
                f"event {i} ({e.get('name')!r}) has invalid ts "
                f"{ts!r}"))
            continue
        if ts + 1e-9 < last_ts:
            findings.append(Finding(
                PASS, "trace-nonmonotonic", rel, 0,
                f"event {i} ({e.get('name')!r}) ts {ts} < previous "
                f"{last_ts} — the exporter must emit in time order"))
        last_ts = max(last_ts, ts)
        if ph in ("b", "e"):
            key = (e.get("cat"), e.get("id"), e.get("name"))
            open_spans[key] = open_spans.get(key, 0) + \
                (1 if ph == "b" else -1)
            if open_spans[key] < 0:
                findings.append(Finding(
                    PASS, "trace-unpaired", rel, 0,
                    f"async end without begin for {key}"))
                open_spans[key] = 0
    for key, n in sorted(open_spans.items(), key=repr):
        if n > 0:
            findings.append(Finding(
                PASS, "trace-unpaired", rel, 0,
                f"{n} unclosed async span(s) for {key} — reads as a "
                f"forever-hung request in trace viewers"))
    return findings


# ------------------------------------------------------------------ driver
def run(files: List[SourceFile], *, config_sf: SourceFile,
        faults_sf: SourceFile, config_md: str, readme_md: str,
        dstpu_top_sf: Optional[SourceFile] = None,
        trace_doc: Optional[dict] = None,
        trace_rel: str = "TRACE_SAMPLE.chrome.json") -> List[Finding]:
    findings: List[Finding] = []
    findings += check_config_doc(config_sf, config_md)
    findings += check_faults_doc(faults_sf, config_md)
    docs = {"CONFIG.md": config_md, "README.md": readme_md}
    findings += check_metric_citations(
        files, docs,
        source_docs=[dstpu_top_sf] if dstpu_top_sf is not None else [])
    if trace_doc is not None:
        findings += check_trace_pairing(trace_doc, trace_rel)
    return findings
