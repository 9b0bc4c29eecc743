"""Offline summarizer for -tracefile / dumptrace span dumps.

Usage:
    python tools/trace_view.py <trace.json>
    python tools/trace_view.py --xplane <file.xplane.pb | planes.json>

Reads a Chrome-trace/perfetto JSON dump produced by util/telemetry
(``-tracefile`` at shutdown, or the ``dumptrace`` RPC mid-flight) and
prints:

  - a per-stage time table (count, total, mean, p50, p99 per span name);
  - the MEASURED pipeline overlap fraction, per block and aggregate: for
    every height with both a ``block.scan`` and a ``block.settle`` span,
    the in-flight window is scan-end -> settle-end (the signature batch
    is on the device for that whole stretch) and the blocked time is the
    settle span's duration — overlap = the fraction of the in-flight
    window the host spent doing useful work instead of waiting;
  - the top-10 slowest settles (the blocks worth profiling first);
  - a "reorg report" when the dump carries speculation-tree events
    (block.reorg / block.unwind / block.branch_drop instants, ISSUE 9):
    reorg depths, settle-failure unwinds, and losing-branch lifetimes;
  - a "signature serving" section when the dump carries SigService spans
    (serving.flush / serving.settle, ISSUE 7): flush-reason breakdown
    with lane counts, the flush->settle span-chain timing, and the list
    of deadline-miss instants (flushes that fired later than 2x the
    configured deadline).

Percentiles are nearest-rank over the raw span durations (exact, no
interpolation): sorted[ceil(q*n) - 1]. All times are milliseconds.

The report is plain deterministic text (golden-tested by
tests/unit/test_trace_view.py); pipe it wherever, or load the same JSON
at ui.perfetto.dev for the interactive view.

``--xplane`` reads a profiler trace instead (a ``--keep-trace`` run of
chipbench/run.py, or a ``startprofile`` / ``stopprofile`` dump): under
-telemetry=counters and above every span is also a ``bcp.<name>`` host
event on the device trace's own clock, and the report puts the device's
idle time under them. Each idle gap of the first device inside the window
(the harness's ``chipbench.window`` annotation, else the extent of the
``bcp.*`` events) is cut where spans start and end, and each piece goes
to the innermost span open at that instant (of several threads' spans, the
one that started last). It also checks that every ``bcp.import*`` event
lies inside ``chipbench.import``, and prints the device's idle time between
the first ``bcp.import.enqueue`` and the end of the last
``bcp.import.settle_wait``, whole and in gaps of a millisecond or more: the
chip idle while the import had work for it (the ``queue_empty_s`` the import
logs is a lower bound of this, from the host's side). The trace is loaded
with chipbench/xplane.load; a ``.json`` file holds the same planes as plain
lists (the form chipbench/tests keeps).
"""

from __future__ import annotations

import functools
import json
import math
import sys
from collections import defaultdict


def load(path: str) -> list[dict]:
    """Events from a dump: accepts both the wrapped {"traceEvents": []}
    object form and a bare event array."""
    with open(path) as f:
        obj = json.load(f)
    events = obj["traceEvents"] if isinstance(obj, dict) else obj
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a Chrome-trace dump")
    return events


def percentile(durs: list[float], q: float) -> float:
    """Nearest-rank percentile over raw values (exact)."""
    if not durs:
        return 0.0
    s = sorted(durs)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def stage_table(events: list[dict]) -> list[tuple]:
    """[(name, count, total_ms, mean_ms, p50_ms, p99_ms)], total desc."""
    by_name: dict[str, list[float]] = defaultdict(list)
    for ev in events:
        if ev.get("ph") == "X":
            by_name[ev["name"]].append(float(ev.get("dur", 0.0)) / 1e3)
    rows = []
    for name, durs in by_name.items():
        total = sum(durs)
        rows.append((name, len(durs), total, total / len(durs),
                     percentile(durs, 0.5), percentile(durs, 0.99)))
    rows.sort(key=lambda r: (-r[2], r[0]))
    return rows


def block_overlap(events: list[dict]) -> list[dict]:
    """Per-block measured overlap: for each block with one block.scan
    and one block.settle span, in-flight = settle end - scan end and
    blocked = the settle span's duration. Returns
    [{height, scan_ms, settle_ms, inflight_ms, overlap}] height-ordered.

    Pairing keys on the span's ``hash`` arg when present (the pipelined
    engine stamps both spans with it) and falls back to height — pairing
    by height alone would marry an UNWOUND block's scan to the competing
    block's settle at the same height and overstate the in-flight
    window. Blocks missing either span (unwound blocks never settle) are
    skipped; a re-scan of the same block keeps the latest pair."""
    scans: dict[object, dict] = {}
    settles: dict[object, dict] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args", {})
        height = args.get("height")
        if height is None:
            continue
        key = args.get("hash", f"h{int(height)}")
        if ev["name"] == "block.scan":
            scans[key] = ev
        elif ev["name"] == "block.settle":
            settles[key] = ev
    out = []
    for key in sorted(
            set(scans) & set(settles),
            key=lambda k: int(scans[k]["args"]["height"])):
        scan, settle = scans[key], settles[key]
        height = int(scan["args"]["height"])
        scan_end = float(scan["ts"]) + float(scan.get("dur", 0.0))
        settle_end = float(settle["ts"]) + float(settle.get("dur", 0.0))
        inflight = (settle_end - scan_end) / 1e3
        blocked = float(settle.get("dur", 0.0)) / 1e3
        if inflight <= 0.0:
            continue
        out.append({
            "height": height,
            "scan_ms": float(scan.get("dur", 0.0)) / 1e3,
            "settle_ms": blocked,
            "inflight_ms": inflight,
            "overlap": max(0.0, min(1.0, 1.0 - blocked / inflight)),
        })
    return out


def serving_section(events: list[dict]) -> list[str]:
    """The SigService report lines (empty when the dump has no serving
    spans — keeps pre-serving dumps' reports byte-stable).

    The enqueue -> flush -> settle chain is read off the span structure:
    every serving.flush span is parented on its oldest lane's enqueue
    context and nests one serving.settle span, so flush duration minus
    settle duration is the host-side dispatch overhead."""
    flushes = [ev for ev in events
               if ev.get("ph") == "X" and ev.get("name") == "serving.flush"]
    settles = [ev for ev in events
               if ev.get("ph") == "X" and ev.get("name") == "serving.settle"]
    misses = [ev for ev in events
              if ev.get("ph") == "i"
              and ev.get("name") == "serving.deadline_miss"]
    if not (flushes or settles or misses):
        return []
    lines = ["", "signature serving (SigService)"]
    by_reason: dict[str, list[dict]] = defaultdict(list)
    for ev in flushes:
        by_reason[str(ev.get("args", {}).get("reason", "?"))].append(ev)
    lines.append(
        f"{'flush reason':<14}{'count':>7}{'lanes':>9}{'mean_ms':>10}"
        f"{'p99_ms':>10}")
    for reason in sorted(by_reason, key=lambda r: -len(by_reason[r])):
        evs = by_reason[reason]
        durs = [float(ev.get("dur", 0.0)) / 1e3 for ev in evs]
        lanes = sum(int(ev.get("args", {}).get("lanes", 0)) for ev in evs)
        lines.append(
            f"{reason:<14}{len(evs):>7}{lanes:>9}"
            f"{sum(durs) / len(durs):>10.2f}{percentile(durs, 0.99):>10.2f}")
    if flushes and settles:
        fd = [float(ev.get("dur", 0.0)) / 1e3 for ev in flushes]
        sd = [float(ev.get("dur", 0.0)) / 1e3 for ev in settles]
        lines += [
            "",
            "flush -> settle chain: "
            f"{len(flushes)} flush / {len(settles)} settle spans, "
            f"settle p50 {percentile(sd, 0.5):.2f} ms "
            f"p99 {percentile(sd, 0.99):.2f} ms, "
            f"dispatch overhead mean "
            f"{max(0.0, sum(fd) / len(fd) - sum(sd) / len(sd)):.2f} ms",
        ]
    if misses:
        lines += ["", f"deadline misses: {len(misses)}"]
        for ev in misses:
            a = ev.get("args", {})
            lines.append(
                f"  age {a.get('age_ms')} ms vs deadline "
                f"{a.get('deadline_ms')} ms ({a.get('lanes')} lane(s))")
    return lines


def reorg_section(events: list[dict]) -> list[str]:
    """The speculation-tree reorg report (empty when the dump carries no
    reorg/branch events — keeps pre-tree dumps' reports byte-stable).

    Reads three instant families the chainstate emits (ISSUE 9):
    ``block.reorg`` (settled blocks disconnected toward a new tip, with
    depth), ``block.unwind`` (a branch dropped by a settle FAILURE, with
    the failing block and how many speculative blocks went with it), and
    ``block.branch_drop`` (a losing branch dropped un-externalized when
    its competitor settled, with its lifetime)."""
    reorgs = [ev for ev in events
              if ev.get("ph") == "i" and ev.get("name") == "block.reorg"]
    unwinds = [ev for ev in events
               if ev.get("ph") == "i" and ev.get("name") == "block.unwind"]
    drops = [ev for ev in events
             if ev.get("ph") == "i"
             and ev.get("name") == "block.branch_drop"]
    if not (reorgs or drops):
        return []
    lines = ["", "reorg report (speculation tree)"]
    if reorgs:
        depths = [int(ev.get("args", {}).get("depth", 0)) for ev in reorgs]
        lines.append(
            f"reorgs: {len(reorgs)}  depth max {max(depths)} "
            f"mean {sum(depths) / len(depths):.2f}")
        for ev in reorgs:
            a = ev.get("args", {})
            lines.append(
                f"  depth {a.get('depth')} -> {a.get('to_hash')} "
                f"height {a.get('to_height')}")
    unwound = sum(int(ev.get("args", {}).get("dropped", 0))
                  for ev in unwinds)
    if unwinds:
        lines.append(
            f"settle-failure unwinds: {len(unwinds)} "
            f"({unwound} speculative block(s) dropped)")
    if drops:
        lives = [float(ev.get("args", {}).get("lifetime_ms", 0.0))
                 for ev in drops]
        blocks = sum(int(ev.get("args", {}).get("blocks", 0))
                     for ev in drops)
        lines.append(
            f"losing branches dropped: {len(drops)} ({blocks} block(s)), "
            f"lifetime mean {sum(lives) / len(lives):.1f} ms "
            f"max {max(lives):.1f} ms")
        for ev in drops:
            a = ev.get("args", {})
            lines.append(
                f"  branch {a.get('branch')} from height {a.get('height')}"
                f": {a.get('blocks')} block(s), {a.get('reason')}, "
                f"lived {float(a.get('lifetime_ms', 0.0)):.1f} ms")
    return lines


def summarize(events: list[dict]) -> str:
    """The full text report over one dump."""
    spans = [ev for ev in events if ev.get("ph") == "X"]
    lines = [
        f"trace summary: {len(events)} events, {len(spans)} spans",
        "",
        "per-stage time",
        f"{'stage':<28}{'count':>7}{'total_ms':>12}{'mean_ms':>10}"
        f"{'p50_ms':>10}{'p99_ms':>10}",
    ]
    for name, count, total, mean, p50, p99 in stage_table(events):
        lines.append(
            f"{name:<28}{count:>7}{total:>12.1f}{mean:>10.2f}"
            f"{p50:>10.2f}{p99:>10.2f}")

    blocks = block_overlap(events)
    lines += ["", "pipeline overlap (block.scan end -> block.settle end)"]
    if not blocks:
        lines.append("no block.scan/block.settle pairs in this trace")
    else:
        inflight = sum(b["inflight_ms"] for b in blocks)
        blocked = sum(b["settle_ms"] for b in blocks)
        agg = max(0.0, min(1.0, 1.0 - blocked / inflight)) if inflight \
            else 0.0
        lines.append(f"blocks measured: {len(blocks)}")
        lines.append(
            f"aggregate overlap fraction: {agg:.4f}  "
            f"(in-flight {inflight:.1f} ms, blocked {blocked:.1f} ms)")
        lines += ["", "top 10 slowest settles",
                  f"{'height':>8}{'settle_ms':>12}{'overlap':>10}"]
        slowest = sorted(blocks, key=lambda b: (-b["settle_ms"],
                                                b["height"]))[:10]
        for b in slowest:
            lines.append(f"{b['height']:>8}{b['settle_ms']:>12.2f}"
                         f"{b['overlap']:>10.4f}")

    lines += serving_section(events)
    lines += reorg_section(events)

    unwinds = [ev for ev in events
               if ev.get("ph") == "i" and ev.get("name") == "block.unwind"]
    if unwinds:
        lines += ["", f"unwinds: {len(unwinds)}"]
        for ev in unwinds:
            a = ev.get("args", {})
            lines.append(
                f"  height {a.get('height')}: dropped {a.get('dropped')} "
                f"block(s) ({a.get('reason')})")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# --xplane: the device's idle time under the program's own spans
# ---------------------------------------------------------------------------

SPAN_PREFIX = "bcp."
HARNESS_PREFIX = "chipbench."


@functools.lru_cache(maxsize=None)
def _xplane():
    """chipbench/xplane.py (it imports JAX only to read a .pb)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "xplane.py")
    spec = importlib.util.spec_from_file_location("chipbench_xplane", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_planes(path: str) -> list:
    """The planes of chipbench/xplane.py's ``load``: device planes whole,
    of the host planes the ``bcp.*`` and ``chipbench.*`` events."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    return _xplane().load(path, keep_host=(SPAN_PREFIX, HARNESS_PREFIX))


def _host_events(planes: list, prefix: str) -> list:
    """[(name, start_ns, end_ns)] of the host planes' events under
    ``prefix``, by start."""
    out = []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            out += [(name, start, start + dur)
                    for name, start, dur in line["events"]
                    if name.startswith(prefix)]
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


def _device_busy(planes: list) -> list:
    """Merged busy intervals of the first device plane ('XLA Ops', else
    'XLA Modules'), by the harness's own union."""
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {line["name"]: line for line in plane["lines"]}
        line = lines.get("XLA Ops") or lines.get("XLA Modules")
        if line is None:
            continue
        return _xplane().union(
            [(s, s + d) for _, s, d in line["events"] if d > 0])
    raise ValueError("the trace has no device plane with operations")


def idle_by_span(gaps: list, spans: list) -> dict:
    """{span name or 'unannotated': idle ns}: each gap cut at the spans'
    edges, each piece to the span open there that started last."""
    cuts = sorted({t for gap in gaps for t in gap}
                  | {t for _, a, b in spans for t in (a, b)})
    starts = sorted(spans, key=lambda ev: ev[1])
    out: dict = {}
    active: list = []
    nxt = gap_i = 0
    for left, right in zip(cuts, cuts[1:]):
        while nxt < len(starts) and starts[nxt][1] <= left:
            active.append(starts[nxt])
            nxt += 1
        active = [ev for ev in active if ev[2] > left]
        while gap_i < len(gaps) and gaps[gap_i][1] <= left:
            gap_i += 1
        if gap_i == len(gaps):
            break
        if not (gaps[gap_i][0] <= left and right <= gaps[gap_i][1]):
            continue
        name = (max(active, key=lambda ev: (ev[1], -ev[2]))[0]
                if active else "unannotated")
        out[name] = out.get(name, 0) + right - left
    return out


LONG_GAP_NS = 1_000_000


def dispatch_stretch(spans: list):
    """(start of the first ``bcp.import.enqueue``, end of the last
    ``bcp.import.settle_wait``), or None: the stretch in which the import
    has work for the device."""
    starts = [a for n, a, _ in spans if n == SPAN_PREFIX + "import.enqueue"]
    ends = [b for n, _, b in spans
            if n == SPAN_PREFIX + "import.settle_wait"]
    if not starts or not ends or max(ends) <= min(starts):
        return None
    return min(starts), max(ends)


def xplane_report(planes: list) -> str:
    spans = _host_events(planes, SPAN_PREFIX)
    harness = {name: (a, b) for name, a, b in
               _host_events(planes, HARNESS_PREFIX)}
    busy = _device_busy(planes)
    if HARNESS_PREFIX + "window" in harness:
        window = harness[HARNESS_PREFIX + "window"]
    elif spans:
        window = (min(a for _, a, _ in spans), max(b for _, _, b in spans))
    else:
        window = (busy[0][0], busy[-1][1])
    edges = [window[0]] + [min(max(t, window[0]), window[1])
                           for iv in busy for t in iv] + [window[1]]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    idle = sum(b - a for a, b in gaps)
    length = window[1] - window[0]
    lines = [
        f"xplane summary: window {length / 1e6:.3f} ms, device idle "
        f"{idle / 1e6:.3f} ms ({100.0 * idle / length:.2f}%) in "
        f"{len(gaps)} gaps, {len(spans)} bcp.* host events",
        "",
        "device idle time by the innermost span open",
        f"{'span':<34}{'idle_ms':>12}{'of idle':>9}",
    ]
    table = idle_by_span(gaps, spans)
    for name, ns in sorted(table.items(), key=lambda kv: (-kv[1], kv[0])):
        lines.append(f"{name:<34}{ns / 1e6:>12.3f}"
                     f"{100.0 * ns / idle if idle else 0.0:>8.2f}%")

    inside = harness.get(HARNESS_PREFIX + "import")
    imports = [ev for ev in spans
               if ev[0].startswith(SPAN_PREFIX + "import")]
    if inside is not None:
        in_gaps = [(max(a, inside[0]), min(b, inside[1])) for a, b in gaps
                   if b > inside[0] and a < inside[1]]
        in_idle = sum(b - a for a, b in in_gaps)

        def named(events):
            table = idle_by_span(in_gaps, events)
            ns = sum(v for name, v in table.items() if name != "unannotated")
            return 100.0 * ns / in_idle if in_idle else 0.0

        outside = sum(1 for _, a, b in imports
                      if a < inside[0] or b > inside[1])
        lines += [
            "",
            f"inside {HARNESS_PREFIX}import: idle {in_idle / 1e6:.3f} ms, "
            f"{named(imports):.2f}% of it under a {SPAN_PREFIX}import* span, "
            f"{named(spans):.2f}% under any {SPAN_PREFIX}* span",
            f"{SPAN_PREFIX}import* events outside {HARNESS_PREFIX}import: "
            f"{outside} of {len(imports)}",
        ]
    stretch = dispatch_stretch(spans)
    if stretch is not None:
        inside_gaps = [(max(a, stretch[0]), min(b, stretch[1]))
                       for a, b in gaps if b > stretch[0] and a < stretch[1]]
        total = sum(b - a for a, b in inside_gaps)
        long_ns = sum(b - a for a, b in inside_gaps
                      if b - a >= LONG_GAP_NS)
        lines += ["", f"between the first enqueue and the last settle "
                      f"({(stretch[1] - stretch[0]) / 1e6:.3f} ms): device "
                      f"idle {total / 1e6:.3f} ms, {long_ns / 1e6:.3f} ms of "
                      f"it in gaps of 1 ms or more"]
    return "\n".join(lines) + "\n"


def main(argv: list[str]) -> int:
    if argv[1:2] == ["--xplane"]:
        if len(argv) != 3:
            print(f"usage: {argv[0]} --xplane <file>", file=sys.stderr)
            return 2
        sys.stdout.write(xplane_report(load_planes(argv[2])))
        return 0
    if len(argv) != 2:
        print(__doc__.strip().splitlines()[0], file=sys.stderr)
        print(f"usage: {argv[0]} <trace.json>", file=sys.stderr)
        return 2
    sys.stdout.write(summarize(load(argv[1])))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
