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
_MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str, keep_host: str = ANNOTATION_PREFIX) -> list:
    """Device planes whole; of the host planes only the events whose name
    starts with ``keep_host`` (the harness's own TraceAnnotations)."""
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


def reduce(planes: list, window_ns: tuple = None, top: int = 10) -> dict:
    """``window_ns`` = (start, end) on the trace's clock; by default the
    span of the harness's outermost annotation, else of the device events.

    Returns busy_s (union of the device-op intervals, averaged over the
    device planes), window_s, idle_share, modules {name: {"seconds",
    "count"}} (summed over devices), device_ops and idle_gaps (lists of
    [name, seconds], at most ``top``)."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    annotations = [ev for p in planes if p not in devices
                   for line in p["lines"] for ev in line["events"]
                   if ev[0].startswith(ANNOTATION_PREFIX)]
    if window_ns is None and annotations:
        outer = max(annotations, key=lambda ev: ev[2])
        window_ns = (outer[1], outer[1] + outer[2])

    busy_total = 0
    modules: dict = {}
    op_time: dict = {}
    first_busy = None
    spans = []
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
            spans.append((merged[0][0], merged[-1][1]))
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
        if not spans:
            raise ValueError("no device operation in the trace")
        window_ns = (min(s for s, _ in spans), max(e for _, e in spans))
    window_s = (window_ns[1] - window_ns[0]) / 1e9
    busy_s = busy_total / len(devices) / 1e9

    # idle gaps of the first device, each named for the harness annotation
    # (the innermost, i.e. shortest) that covers most of it
    edges = [window_ns[0]] + [t for iv in first_busy for t in iv] \
        + [window_ns[1]]
    gap_time: dict = {}
    for start, end in zip(edges[0::2], edges[1::2]):
        if end <= start:
            continue
        best, best_cover = "unannotated", 0
        for name, a_start, a_dur in sorted(annotations,
                                           key=lambda ev: -ev[2]):
            cover = min(end, a_start + a_dur) - max(start, a_start)
            if cover > 0 and cover >= best_cover * 0.999:
                best, best_cover = name, cover
        gap_time[best] = gap_time.get(best, 0) + (end - start)

    def ranked(table: dict) -> list:
        return [[n, v / 1e9] for n, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "devices": len(devices), "modules": modules,
        "device_ops": ranked(op_time), "idle_gaps": ranked(gap_time),
    }
