#!/usr/bin/env python
"""dstpu_top: the serving "htop" — poll an engine's ``/statusz`` and
render slots, queue, KV/prefix-cache occupancy, speculation acceptance
and per-tier SLO burn live in the terminal.

The engine side is the introspection server the telemetry HTTP sink
grew in PR 6: point any engine at a port (``telemetry.http_port`` in
the config block) and this tool at the same port.

    python tools/dstpu_top.py --url http://127.0.0.1:8080
    python tools/dstpu_top.py --url ... --interval 1
    python tools/dstpu_top.py --url ... --once        # one frame, exit
    python tools/dstpu_top.py --once --json           # raw snapshot

``--connect URL[,URL...]`` goes through the obs_wire scrape plane
instead of plain fetches: each URL gets a RemoteReplica poller
(timeout/retry/backoff, FRESH→STALE→LOST staleness), frames render
from the LAST-KNOWN snapshot, and every remote carries a staleness
badge — a SIGKILLed replica keeps rendering, flagged ``[LOST]``,
instead of killing the frame.

    python tools/dstpu_top.py --connect http://127.0.0.1:8080
    python tools/dstpu_top.py --connect http://h1:8080,http://h2:8080

Uses curses when stdout is a tty (clean redraws, q to quit); falls
back to plain ANSI-clear refresh otherwise (``--plain`` forces it —
pipeable).  ``--once`` renders a single frame and exits, which is
also what the tests drive.  Only ``--connect`` imports deepspeed_tpu;
the ``--url`` path stays pure stdlib.
"""

import argparse
import json
import sys
import time
import urllib.error
import urllib.request


def fetch(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _bar(frac: float, width: int = 20) -> str:
    frac = min(max(frac, 0.0), 1.0)
    n = int(round(frac * width))
    return "[" + "#" * n + "." * (width - n) + f"] {100 * frac:5.1f}%"


_SPARK_GLYPHS = " .:-=+*#%@"


def _spark(values, width: int = 32) -> str:
    """ASCII sparkline over the trailing ``width`` points (min-max
    scaled; flat series render mid-glyph so 'no variation' doesn't
    read as 'no data')."""
    vals = [float(v) for v in values if v is not None][-width:]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    span = hi - lo
    if span <= 0:
        return _SPARK_GLYPHS[len(_SPARK_GLYPHS) // 2] * len(vals)
    return "".join(
        _SPARK_GLYPHS[min(int((v - lo) / span * (len(_SPARK_GLYPHS) - 1)
                              + 0.5), len(_SPARK_GLYPHS) - 1)]
        for v in vals)


# key series rendered as sparklines when /historyz is available —
# label -> history series name (fine ring)
_ENGINE_SPARKS = (
    ("queue", "serving_queue_depth"),
    ("kv util", "serving_kv_page_utilization"),
    ("ttft p95", "serving_ttft_seconds:p95"),
    ("decode/s", "serving_decode_steps:rate"),
)
_FLEET_SPARKS = (
    ("queue", "fleet_queue_depth"),
    ("slots", "fleet_active_slots"),
    ("routable", "fleet_routable_replicas"),
    ("done/s", "fleet_completed_requests:rate"),
)


def _series_points(historyz: dict, name: str):
    """Fine-ring values of one series from a /historyz document."""
    h = (historyz or {}).get("history", {})
    rec = h.get("series", {}).get(name)
    if not rec or not rec.get("rings"):
        return []
    return [v for _t, v in rec["rings"][0].get("points", [])]


def render_history(historyz: dict, sparks, now_monotonic=None) -> list:
    """Sparkline block + incident ticker from a /historyz document.
    Empty list when the document is absent/disabled — callers append
    unconditionally."""
    if not historyz:
        return []
    L = []
    h = historyz.get("history", {})
    if h.get("enabled"):
        for label, name in sparks:
            pts = _series_points(historyz, name)
            if not pts:
                continue
            L.append(f"hist  {label:<9}[{_spark(pts)}]"
                     f"  now {pts[-1]:.3g}")
    inc = historyz.get("incidents", {})
    if inc.get("enabled"):
        recent = inc.get("recent", [])
        line = (f"incid bundles {inc.get('bundles', 0)}"
                f"  suppressed {inc.get('suppressed', 0)}")
        if recent:
            now = (now_monotonic
                   if now_monotonic is not None
                   else (h.get("t_monotonic") or 0.0))
            ticker = "  ".join(
                f"[{b.get('incident', '?')}"
                + (f" {max(now - b.get('t0_monotonic', now), 0.0):.0f}s"
                   if now else "")
                + "]"
                for b in recent[-4:])
            line += "  " + ticker
        L.append(line)
    return L


def render_fleet(status: dict, health: dict | None = None,
                 historyz: dict | None = None) -> list:
    """One frame for a FleetRouter /statusz snapshot: fleet totals +
    one row per replica (state, queue, shed rate, affinity hit rate)
    + the cross-replica SLO rollup + history sparklines and the
    incident ticker when /historyz is served."""
    L = []
    fl = status.get("fleet", {})
    states = " ".join(f"{k}={v}" for k, v in
                      sorted(fl.get("states", {}).items()))
    hdr = (f"FleetRouter  up {status.get('uptime_s', 0):.0f}s"
           f"  replicas {states}")
    if health is not None:
        hdr += ("  READY" if health.get("ready") else "  NOT-READY")
        if health.get("degraded"):
            hdr += "  DEGRADED"
    L.append(hdr)
    L.append("-" * 78)
    aff = fl.get("affinity", {})
    L.append(f"fleet submitted {fl.get('submitted', 0)}"
             f"  completed {fl.get('completed', 0)}"
             f"  failed {fl.get('failed', 0)}"
             f"  shed {fl.get('shed', 0)}"
             f"  resubmits {fl.get('resubmits', 0)}"
             f"  failovers {fl.get('failovers', 0)}"
             f"  drains {fl.get('drains', 0)}")
    L.append(f"route affinity {aff.get('affinity_routed', 0)}"
             f"/{aff.get('affinity_routed', 0) + aff.get('least_loaded_routed', 0)}"
             f"  hit-rate {aff.get('hit_rate', 0.0):.3f}"
             f"  queue {fl.get('queue_depth', 0)}"
             f"  in-flight {fl.get('in_flight', 0)}"
             f"  orphaned {fl.get('orphaned', 0)}")
    fab = fl.get("fabric")
    if fab:
        line = (f"fab   exp {fab.get('exports', 0)}"
                f"  fetch {fab.get('fetches', 0)}"
                f"  moved {fab.get('bytes_moved', 0) / 2**20:.1f}MB"
                f"  mig {fab.get('migrations', 0)}"
                f"  fb {fab.get('migration_fallbacks', 0)}"
                f"  handoff {fab.get('handoffs', 0)}")
        roles = fl.get("roles") or {}
        if roles:
            line += "  | " + "  ".join(
                f"{ro} q={r.get('queue_depth', 0)}"
                f" ({r.get('routable', 0)}/{r.get('replicas', 0)})"
                for ro, r in sorted(roles.items()))
        L.append(line)
    el = status.get("elastic", {})
    if el.get("enabled"):
        ro = el.get("rollout") or {}
        line = (f"elast target {el.get('target_replicas', '?')} "
                f"[{el.get('min_replicas', '?')}"
                f"..{el.get('max_replicas', '?')}]"
                f"  up {el.get('scale_ups', 0)}"
                f"  down {el.get('scale_downs', 0)}"
                f"  cold-starts {el.get('cold_starts_in_flight', 0)}")
        if el.get("cooldown_remaining_s"):
            line += f"  cooldown {el['cooldown_remaining_s']:.1f}s"
        if ro.get("active"):
            line += (f"  ROLLOUT {ro.get('version')} "
                     f"{ro.get('updated', 0)}/{ro.get('total', 0)} "
                     f"({ro.get('state', '?')})")
        elif ro.get("rolled_back"):
            line += f"  ROLLED-BACK {ro.get('version')}"
        L.append(line)
    fm = fl.get("mesh", {})
    if fm.get("tp", 1) > 1 or fm.get("sharded_replicas"):
        L.append(f"mesh  tp={fm.get('tp', 1)}"
                 f"  sharded {fm.get('sharded_replicas', 0)}"
                 f"/{len(fl.get('replicas', []))} replicas")
    L.extend(render_history(historyz, _FLEET_SPARKS))
    L.append("-" * 78)
    L.append(f"{'replica':<9}{'state':<13}{'role':<9}{'ver':<6}"
             f"{'mesh':<7}{'queue':>6}"
             f"{'slots':>6}{'shed%':>7}{'failed':>7}{'aff':>5}"
             f"{'digest':>7}  reasons")
    for r in fl.get("replicas", []):
        reasons = ",".join(r.get("reasons", []))[:24]
        if r.get("stalled_for_s"):
            reasons = (reasons + f" stall {r['stalled_for_s']:.1f}s"
                       ).strip()
        if r.get("scrape_state"):
            # out-of-process replica: staleness badge leads the
            # reasons column so a LOST child is unmissable
            badge = r["scrape_state"]
            if r.get("scrape_age_s") is not None:
                badge += f" {r['scrape_age_s']:.0f}s"
            reasons = (f"[{badge}] " + reasons).strip()
        rm = r.get("mesh", {})
        mesh_col = ("x".join(f"{a}{s}" for a, s in
                             sorted(rm.get("axes", {}).items()))
                    or "1dev") if rm else "-"
        L.append(f"{r['replica']:<9}{r['state']:<13}"
                 f"{str(r.get('role') or '-')[:8]:<9}"
                 f"{str(r.get('version', '-'))[:5]:<6}"
                 f"{mesh_col[:6]:<7}"
                 f"{r.get('queue_depth', 0):>6}"
                 f"{r.get('active_slots', 0):>6}"
                 f"{100 * r.get('shed_rate', 0.0):>6.1f}%"
                 f"{r.get('failed', 0):>7}"
                 f"{r.get('affinity_hits', 0):>5}"
                 f"{r.get('digest_pages', 0):>7}"
                 f"  {reasons}")
    slo = status.get("slo", {})
    if slo.get("enabled"):
        L.append("-" * 78)
        L.append(f"{'tier (fleet)':<14}{'attain':>8}{'target':>8}"
                 f"{'goodput t/s':>13}  {'max burn':<22}{'alert':>6}")
        for name, t in sorted(slo.get("tiers", {}).items()):
            burns = " ".join(f"{w}={b:.1f}"
                             for w, b in sorted(t["burn_rates"].items()))
            L.append(f"{name:<14}{t['attainment']:>8.3f}"
                     f"{t['target']:>8.3f}"
                     f"{t['goodput_tokens_per_s']:>13.1f}  "
                     f"{burns:<22}"
                     f"{'FIRE' if t.get('alert_active') else '-':>6}")
    return L


def render(status: dict, health: dict | None = None,
           historyz: dict | None = None) -> list:
    """One frame of text lines from a /statusz snapshot (plus the
    optional /historyz document for sparklines + incident ticker)."""
    if status.get("engine") == "FleetRouter" or "fleet" in status:
        return render_fleet(status, health, historyz)
    L = []
    hdr = (f"{status.get('engine', '?')}  up {status.get('uptime_s', 0):.0f}s"
           f"  step age {status.get('last_step_age_s')}s")
    if health is not None:
        hdr += ("  READY" if health.get("ready") else "  NOT-READY")
        if health.get("degraded"):
            hdr += "  DEGRADED"
        wd = health.get("watchdog")
        if wd:
            hdr += (f"  wd {'FIRED' if wd['fired'] else 'ok'} "
                    f"({wd['last_heartbeat_age_s']:.0f}s/"
                    f"{wd['timeout_s']:.0f}s)")
    L.append(hdr)
    L.append("-" * 78)

    kv = status.get("kv", {})
    usable = max(kv.get("pages_usable", 1), 1)
    L.append(f"kv    live {_bar(kv.get('pages_live', 0) / usable)}"
             f"  free {kv.get('pages_free', 0)}"
             f"  warm {kv.get('pages_warm', 0)}"
             f"  frag {kv.get('fragmentation', 0.0):.2f}")
    pc = status.get("prefix_cache", {})
    if pc.get("enabled"):
        L.append(f"cache warm {pc.get('warm_pool_pages', 0)} pages"
                 f"  hit-rate {pc.get('token_hit_rate', 0.0):.3f}"
                 f"  published {pc.get('published_lifetime', 0)}"
                 f"  evicted {pc.get('evicted_lifetime', 0)}")
    kt = status.get("kv_tier", {})
    if kt.get("enabled"):
        L.append(f"tier  host {kt.get('host_pages', 0)}p/"
                 f"{kt.get('host_bytes', 0) / 1e6:.0f}MB"
                 f"  nvme {kt.get('nvme_pages', 0)}p/"
                 f"{kt.get('nvme_bytes', 0) / 1e6:.0f}MB"
                 f"  demoted {kt.get('demoted_lifetime', 0)}"
                 f"  promoted {kt.get('promoted_lifetime', 0)}"
                 f"  stall {kt.get('promote_stall_s', 0.0):.2f}s"
                 f"{'  int8' if kt.get('quantize_cold') else ''}")
    sp = status.get("speculative", {})
    if sp.get("enabled"):
        mal = sp.get("mean_accept_len")
        L.append(f"spec  sweeps {sp.get('verify_sweeps', 0)}"
                 f"  mean accept "
                 f"{mal if mal is not None else '-'}")
    em = status.get("mesh", {})
    if em.get("sharded"):
        axes = " ".join(f"{a}={s}" for a, s in
                        sorted(em.get("axes", {}).items()))
        L.append(f"mesh  {em.get('devices', 1)} devices  {axes}"
                 f"  (tp={em.get('tp', 1)} ep={em.get('ep', 1)})")
    rb = status.get("robustness", {})
    rkt = rb.get("kv_tier", {})
    if rb and (rb.get("degraded") or rb.get("shed_requests")
               or rb.get("failed_requests")
               or rkt.get("fallback_events")):
        reasons = " ".join(sorted(f"{k}={v}" for k, v in
                                  rb.get("shed_by_reason", {}).items()))
        L.append(f"rbst  shed {rb.get('shed_requests', 0)}"
                 f"/{100 * rb.get('shed_rate', 0.0):.0f}%"
                 f"{' (' + reasons + ')' if reasons else ''}"
                 f"  failed {rb.get('failed_requests', 0)}"
                 f"  tier-fallback {rkt.get('fallback_events', 0)}"
                 f"  cksum {rkt.get('checksum_failures', 0)}"
                 f"{'  TIER-DISABLED' if rkt.get('disabled') else ''}"
                 + ("  DEGRADED: " + ",".join(rb.get("reasons", []))
                    if rb.get("degraded") else ""))
    zi = status.get("zero_inference")
    if zi:
        L.append(f"zi    streamed {zi['plan'].get('n_streamed', 0)}/"
                 f"{zi['plan'].get('n_layers', 0)} layers"
                 f"  stalls {zi.get('stream_stalls', 0)}"
                 f" ({zi.get('stream_stall_s', 0.0):.2f}s)"
                 f"  {zi.get('bytes_uploaded', 0) / 1e6:.0f} MB up")
    cm = status.get("comm")
    if cm:
        L.append(f"comm  int8 wire {cm.get('bytes_on_wire_int8', 0) / 1e6:.1f}"
                 f" MB (f32 {cm.get('bytes_on_wire_f32', 0) / 1e6:.1f} MB,"
                 f" x{cm.get('compression_ratio', 0.0):.2f})"
                 f"  leaves {cm.get('leaves_quantized', 0)}q"
                 f"/{cm.get('leaves_exact', 0)}x"
                 f"  relerr {cm.get('max_rel_err', 0.0):.1e}"
                 f"<{cm.get('serving_rtol', 0.0):g}")
    dp = status.get("devprof", {})
    if dp.get("enabled"):
        steady = dp.get("compiles_steady", 0)
        bd = status.get("build", {})
        L.append(f"dev   compiles {dp.get('compiles_warmup', 0)}w"
                 f"/{steady}s{'  RECOMPILING' if steady else ''}"
                 f"  build {bd.get('programs', 0)} programs"
                 f" ({bd.get('cache_misses', 0)} compiled):"
                 f" trace {bd.get('trace_s', 0.0):.2f}s"
                 f" lower {bd.get('lower_s', 0.0):.2f}s"
                 f" load {bd.get('cache_load_s', 0.0):.2f}s"
                 f" compile {bd.get('compile_s', 0.0):.2f}s")
    L.extend(render_history(historyz, _ENGINE_SPARKS))

    slo = status.get("slo", {})
    if slo.get("enabled"):
        L.append("-" * 78)
        L.append(f"{'tier':<14}{'attain':>8}{'target':>8}"
                 f"{'goodput t/s':>13}  {'burn':<24}{'alert':>6}")
        for name, t in sorted(slo.get("tiers", {}).items()):
            burns = " ".join(f"{w}={b:.1f}"
                             for w, b in sorted(t["burn_rates"].items()))
            L.append(f"{name:<14}{t['attainment']:>8.3f}"
                     f"{t['target']:>8.3f}"
                     f"{t['goodput_tokens_per_s']:>13.1f}  "
                     f"{burns:<24}"
                     f"{'FIRE' if t.get('alert_active') else '-':>6}")

    L.append("-" * 78)
    q = status.get("queue", {})
    L.append(f"slots {status.get('active_slots', 0)}/"
             f"{status.get('max_batch', 0)} active"
             f"   queue {q.get('depth', 0)}"
             f"   finished-pending {status.get('finished_pending_drain', 0)}")
    L.append(f"{'slot':<5}{'state':<9}{'req':<12}{'tier':<12}"
             f"{'prog':<12}{'seq':>5}{'pages':>6}{'age s':>8}")
    for s in status.get("slots", []):
        if s.get("state") == "idle":
            L.append(f"{s['slot']:<5}idle")
            continue
        if s["state"] == "prefill":
            prog = f"{s.get('prefill_done', 0)}/{s['prompt_tokens']}"
        else:
            prog = f"{s['generated']}/{s['max_new_tokens']}"
        L.append(f"{s['slot']:<5}{s['state']:<9}"
                 f"{str(s['req'])[:11]:<12}"
                 f"{str(s.get('tier') or '-')[:11]:<12}"
                 f"{prog:<12}{s['seq_len']:>5}{s['pages']:>6}"
                 f"{s['age_s']:>8.1f}")
    for r in q.get("head", [])[:8]:
        L.append(f"  ..  queued   {str(r['req'])[:11]:<12}"
                 f"{str(r.get('tier') or '-')[:11]:<12}"
                 f"{r['prompt_tokens']:>4} toks"
                 f"{r['age_s']:>9.1f}")
    return L


def connect_remotes(urls, cfg=None):
    """Build one RemoteReplica scrape client per URL (the --connect
    path).  Imported lazily: --url stays stdlib-only."""
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from deepspeed_tpu.obs_wire import RemoteReplica

    remotes = []
    for i, u in enumerate(urls):
        u = u.strip().rstrip("/")
        if not u:
            continue
        remotes.append(RemoteReplica(u, f"remote{i}", cfg=cfg))
    return remotes


def remote_badge(rem) -> str:
    """One-line scrape-plane header for a remote: staleness badge +
    scrape accounting."""
    age = rem.age_s()
    badge = rem.state + (f" {age:.1f}s" if age is not None else "")
    line = (f"== {rem.id} [{badge}]  {rem.url}"
            f"  scrapes {rem.scrapes}  errors {rem.scrape_errors}")
    if rem.last_error:
        line += f"  last: {str(rem.last_error)[:40]}"
    return line


def connect_frame(remotes) -> list:
    """One frame over the scrape plane: poll every remote (failures
    land in the staleness machine, never raise), then render each
    remote's last-known statusz/healthz/historyz under its badge."""
    lines = []
    n_lost = sum(1 for r in remotes if r.state == "LOST")
    lines.append(f"obs_wire  remotes {len(remotes)}  lost {n_lost}")
    for rem in remotes:
        try:
            rem.poll()
        except Exception as e:     # WireSchemaError: pin LOST, render on
            rem.force_lost(f"{e}")
        lines.append("")
        lines.append(remote_badge(rem))
        if rem.last_statusz is None:
            lines.append("  (no snapshot yet)")
            continue
        lines.extend(render(rem.last_statusz, rem.last_healthz,
                            rem.last_historyz))
    return lines


def one_frame(base: str):
    status = fetch(base + "/statusz")
    try:
        health = fetch(base + "/healthz")
    except urllib.error.HTTPError as e:       # 503 = not ready, still JSON
        health = json.loads(e.read().decode())
    try:
        # served only when the history/incidents blocks are on —
        # a 404 just means no sparkline/ticker rows this frame
        historyz = fetch(base + "/historyz")
    except Exception:
        historyz = None
    return status, health, historyz


def _frame_lines(base: str) -> list:
    try:
        status, health, historyz = one_frame(base)
        return render(status, health, historyz)
    except Exception as e:
        return [f"dstpu_top: {base} unreachable: {e}"]


def loop_plain(base: str, interval: float, once: bool,
               frame_fn=None) -> int:
    frame_fn = frame_fn or (lambda: _frame_lines(base))
    while True:
        lines = frame_fn()
        if not once:
            sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
        print("\n".join(lines), flush=True)
        if once:
            return 0
        time.sleep(interval)


def loop_curses(base: str, interval: float, frame_fn=None) -> int:
    import curses

    frame_fn = frame_fn or (lambda: _frame_lines(base))

    def run(scr):
        curses.curs_set(0)
        scr.nodelay(True)
        while True:
            lines = frame_fn()
            scr.erase()
            maxy, maxx = scr.getmaxyx()
            for y, line in enumerate(lines[:maxy - 1]):
                scr.addnstr(y, 0, line, maxx - 1)
            scr.addnstr(maxy - 1, 0,
                        f"q quit   refresh {interval:.1f}s", maxx - 1,
                        curses.A_REVERSE)
            scr.refresh()
            t0 = time.monotonic()
            while time.monotonic() - t0 < interval:
                if scr.getch() in (ord("q"), ord("Q")):
                    return
                time.sleep(0.05)

    curses.wrapper(run)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", default="http://127.0.0.1:8080",
                    help="engine introspection base URL "
                         "(telemetry.http_port)")
    ap.add_argument("--connect", default=None, metavar="URL[,URL...]",
                    help="scrape-plane mode: one RemoteReplica poller "
                         "per URL, staleness/LOST badges, last-known "
                         "frames survive a dead replica")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--once", action="store_true",
                    help="render one frame and exit")
    ap.add_argument("--plain", action="store_true",
                    help="plain refresh instead of curses")
    ap.add_argument("--json", action="store_true",
                    help="with --once: print the raw /statusz JSON")
    args = ap.parse_args()
    base = args.url.rstrip("/")
    if args.connect:
        remotes = connect_remotes(args.connect.split(","))
        if not remotes:
            print("dstpu_top: --connect got no URLs", file=sys.stderr)
            return 2
        if args.json:
            for rem in remotes:
                try:
                    rem.poll()
                except Exception as e:
                    rem.force_lost(f"{e}")
            print(json.dumps(
                {rem.id: {"url": rem.url, "scrape_state": rem.state,
                          "statusz": rem.last_statusz}
                 for rem in remotes}, indent=1, sort_keys=True))
            return 0
        frame_fn = lambda: connect_frame(remotes)   # noqa: E731
        if args.once or args.plain or not sys.stdout.isatty():
            return loop_plain(base, args.interval, args.once,
                              frame_fn=frame_fn)
        return loop_curses(base, args.interval, frame_fn=frame_fn)
    if args.json:
        print(json.dumps(fetch(base + "/statusz"), indent=1,
                         sort_keys=True))
        return 0
    if args.once or args.plain or not sys.stdout.isatty():
        return loop_plain(base, args.interval, args.once)
    return loop_curses(base, args.interval)


if __name__ == "__main__":
    sys.exit(main())
