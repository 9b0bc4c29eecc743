"""From a profiler trace to numbers: device busy and idle time, time by XLA
module, the operations that took most time, the longest idle gaps.

``load`` reads an ``.xplane.pb`` (jax.profiler.ProfileData, nothing but JAX)
into plain lists; ``reduce`` works on those lists alone, so the reduction is
checked on a small recorded trace kept as JSON (chipbench/tests).

A trace is ``[{"name": plane, "lines": [{"name": line, "events":
[[name, start_ns, duration_ns], ...]}]}]``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "chipbench."
# host events kept from a trace: the harness's annotations and the program's
# own spans (util/telemetry writes each as a ``bcp.<span>`` TraceAnnotation)
HOST_PREFIXES = (ANNOTATION_PREFIX, "bcp.")
_MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str, keep_host=ANNOTATION_PREFIX) -> list:
    """Device planes whole; of the host planes only the events whose name
    starts with ``keep_host``, a prefix or a tuple of them (by default the
    harness's own TraceAnnotations)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name.startswith(keep_host)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: list) -> list:
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def _line(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def op_name(event_name: str) -> str:
    """An XLA op event carries its whole HLO line: keep what is left of the
    '=' ('%while.2'), at most 80 characters."""
    return event_name.split(" = ", 1)[0][:80]


def module_name(event_name: str) -> str:
    """'jit__glv_dev_program(123456)' -> 'jit__glv_dev_program'."""
    return _MODULE_ID.sub("", event_name).strip()


def idle_by_span(gaps: list, spans: list) -> dict:
    """{span name or 'unannotated': idle ns}: each gap (start, end) cut at
    the edges of the spans (name, start, end), each piece to the span open
    there that started last (of two that started together, the shorter).
    One pass over the sorted edges: a trace has tens of thousands of gaps."""
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


def reduce(planes: list, window_ns: tuple = None, top: int = 10) -> dict:
    """``window_ns`` = (start, end) on the trace's clock; by default the
    span of the harness's outermost annotation, else of the device events.

    Returns busy_s (union of the device-op intervals, averaged over the
    device planes), window_s, idle_share, modules {name: {"seconds",
    "count"}} (summed over devices), device_ops and idle_gaps (lists of
    [name, seconds], at most ``top``). An idle gap is cut at the edges of
    the host events the trace was loaded with, and each piece goes to the
    event open there that started last: the innermost of the harness's
    annotations and the program's spans."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    spans = [ev for p in planes if p not in devices
             for line in p["lines"] for ev in line["events"]
             if ev[0].startswith(HOST_PREFIXES)]
    annotations = [ev for ev in spans if ev[0].startswith(ANNOTATION_PREFIX)]
    if window_ns is None and annotations:
        outer = max(annotations, key=lambda ev: ev[2])
        window_ns = (outer[1], outer[1] + outer[2])

    busy_total = 0
    modules: dict = {}
    op_time: dict = {}
    first_busy = None
    busy_ends = []
    for plane in devices:
        line = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        if line is None:
            raise ValueError(f"{plane['name']} has neither an "
                             f"'{OPS_LINE}' nor an '{MODULES_LINE}' line")
        ivals = [(s, s + d) for _, s, d in line["events"] if d > 0]
        if window_ns is not None:
            ivals = [(max(s, window_ns[0]), min(e, window_ns[1]))
                     for s, e in ivals
                     if e > window_ns[0] and s < window_ns[1]]
        merged = union(ivals)
        if first_busy is None:
            first_busy = merged
        busy_total += sum(e - s for s, e in merged)
        if merged:
            busy_ends.append((merged[0][0], merged[-1][1]))
        for name, _, dur in line["events"]:
            name = op_name(name)
            op_time[name] = op_time.get(name, 0) + dur
        mods = _line(plane, MODULES_LINE)
        for name, _, dur in (mods["events"] if mods else ()):
            m = modules.setdefault(module_name(name),
                                   {"seconds": 0.0, "count": 0})
            m["seconds"] += dur / 1e9
            m["count"] += 1
    if window_ns is None:
        if not busy_ends:
            raise ValueError("no device operation in the trace")
        window_ns = (min(s for s, _ in busy_ends),
                     max(e for _, e in busy_ends))
    window_s = (window_ns[1] - window_ns[0]) / 1e9
    busy_s = busy_total / len(devices) / 1e9

    # idle gaps of the first device
    edges = [window_ns[0]] + [t for iv in first_busy for t in iv] \
        + [window_ns[1]]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    gap_time = idle_by_span(gaps, [(name, start, start + dur)
                                   for name, start, dur in spans])

    def ranked(table: dict) -> list:
        return [[n, v / 1e9] for n, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "devices": len(devices), "modules": modules,
        "device_ops": ranked(op_time), "idle_gaps": ranked(gap_time),
    }
