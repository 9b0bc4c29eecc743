#!/usr/bin/env python3
"""chipbench/run.py: one process, one cell, once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets up, warms up, measures, checks, prints, exits. The process IS bcpd: a
driver (chipbench/drivers/<driver>.py) builds an argv from the cell's
configuration file and runs the lines of cli/bcpd.main in the process that
holds the chip. Everything that belongs to one cell is found by name from
BENCHMARK.json: the configuration (chipbench/configs), the traffic mix
(chipbench/traffic), the driver, the per-layer metrics
(chipbench/layer_metrics). Nothing here branches on a cell's name.

The last line of standard output is the result object; earlier lines are
one JSON object each (phases, counters, every number compared beside its
limit). The numbers compared are also the last lines of standard error and
the result object's last key, ``compared``. There is no CPU mode that
prints a result: without a TPU, or with fewer chips than the cell asks for,
the exit code is not 0 and no result is printed. ``--rehearse`` runs the
control flow at tiny sizes on whatever JAX finds and prints ``platform`` and
no metric. ``--fault <name>`` makes the driver break its input or its limit
so that the check has to fail.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

import checks  # noqa: E402
import xplane  # noqa: E402


class BenchError(Exception):
    """The run cannot give a result; exit code 2, nothing on the last
    line."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"missing file: {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench: str = HERE):
    path = os.path.join(bench, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"missing file: {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything BENCHMARK.json and the data files say about one cell."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    bench = os.path.join(root, manifest["paths"][0])
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    entry = next((c for c in manifest["configs"]
                  if c["name"] == cell["config"]), None)
    if entry is None:
        raise BenchError(f"workload {workload!r} names configuration "
                         f"{cell['config']!r}, which BENCHMARK.json lacks")

    def listed(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in manifest["end_to_end"] if listed(m)]
    moved = {m["name"] for m in end_to_end}
    return {
        "cell": cell, "bench": bench,
        "config": load_json(os.path.join(root, entry["file"])),
        "traffic": load_json(os.path.join(
            bench, "traffic", cell["traffic"] + ".json")),
        "end_to_end": end_to_end,
        "per_layer": [m for m in manifest["per_layer"]
                      if listed(m) and m["moves"] in moved],
    }


def load_tables(device_kind: str, bench: str = HERE) -> dict:
    peaks = load_json(os.path.join(bench, "peaks.json"))
    if device_kind not in peaks["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"chipbench/peaks.json: add it with its source")
    return {"peaks": peaks["devices"][device_kind],
            "opcounts": load_json(os.path.join(bench, "opcounts.json"))}


def build_native() -> float:
    """`make -C native` from the committed sources (make's own dependency
    tracking keeps a fresh library)."""
    t0 = time.monotonic()
    proc = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise BenchError(f"native build failed (rc={proc.returncode}):\n"
                         f"{proc.stdout[-1500:]}\n{proc.stderr[-2500:]}")
    return time.monotonic() - t0


def claim_device(chips: int, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if rehearse:
        return found
    if found["platform"] != "tpu" or found["count"] < chips:
        raise BenchError(f"the cell needs {chips} TPU chip(s); JAX found "
                         f"{found}")
    return found


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class Ctx:
    """What run.py and a driver share for the length of one run."""

    def __init__(self, args, loaded: dict):
        self.workload = args.workload
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.fault = args.fault
        self.keep_trace = args.keep_trace
        self.config = loaded["config"]
        self.traffic = loaded["traffic"]
        self.cell = loaded["cell"]
        self.chips = self.cell["chips"]
        self.cache_root = os.path.join(loaded["bench"], ".cache")
        self.workdir = tempfile.mkdtemp(prefix="chipbench-")
        self.emit = emit
        self.device = None
        self.state: dict = {}      # the driver's own

    def chain_cache(self, kind: str, sigs: int) -> str:
        """chipbench/.cache/<config>-<traffic>-<seed>/<kind>-<sigs>[-fault]:
        a repeated seed does not sign again."""
        leaf = f"{kind}-{sigs}" + (f"-{self.fault}" if self.fault else "")
        return os.path.join(
            self.cache_root, f"{self.cell['config']}-"
            f"{self.cell['traffic']}-{self.seed}", leaf)

    def annotate(self, name: str):
        """A host span in the profiler's own trace (no-op without one)."""
        import jax

        return jax.profiler.TraceAnnotation("chipbench." + name)


def lost_module_events(result: dict, modules: dict) -> dict:
    """{module: events in the trace} of the programs the driver says every
    dispatch runs (``dispatch_modules``) whose count of module events is not
    the window's ``dispatches``: the profiler's device plane came back
    without them, and every device time read from such a trace is wrong.
    Empty where the trace kept them all, or the driver states no count."""
    stated = result.get("dispatches")
    if stated is None:
        return {}
    counts = {name: modules.get(name, {}).get("count", 0)
              for name in result.get("dispatch_modules", ())}
    return {name: n for name, n in counts.items() if n != stated}


def _traced_once(ctx: Ctx, driver) -> tuple:
    import jax

    trace_dir = os.path.join(ctx.cache_root, "trace-" + ctx.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with ctx.annotate("window"):
            result = driver.window(ctx)
    finally:
        jax.profiler.stop_trace()
    path = xplane.find_xplane(trace_dir)
    if ctx.keep_trace:
        os.makedirs(ctx.keep_trace, exist_ok=True)
        shutil.copy(path, ctx.keep_trace)
    t0 = time.monotonic()
    trace = xplane.reduce(xplane.load(path, keep_host=xplane.HOST_PREFIXES))
    lost = lost_module_events(result, trace["modules"])
    emit({"phase": "trace", "xplane_bytes": os.path.getsize(path),
          "reduce_s": time.monotonic() - t0, "modules": trace["modules"],
          "dispatches": result.get("dispatches"), "lost": lost})
    shutil.rmtree(trace_dir, ignore_errors=True)
    return result, trace, lost


def traced_window(ctx: Ctx, driver) -> tuple:
    """The window under jax.profiler, in a run of its own; returns the
    driver's result and the trace's reduction (chipbench/xplane.py). Where
    the trace has another count of module events than the window made
    dispatches, the window is traced once more; twice is a failed run."""
    result, trace, lost = _traced_once(ctx, driver)
    if lost:
        result, trace, lost = _traced_once(ctx, driver)
    if lost:
        raise BenchError(
            f"the profiler's trace lost device events twice: the window "
            f"made {result['dispatches']} dispatches and the trace has "
            f"{lost} module events; no device time can be read from it")
    if not trace["busy_s"] > 0:
        raise BenchError("no operation ran on the device in the traced "
                         "window")
    return result, trace


def run(args, root: str = ROOT) -> int:
    loaded = load_cell(args.workload, root)
    ctx = Ctx(args, loaded)
    driver = load_module("drivers", ctx.config["driver"], loaded["bench"])
    try:
        return _run(loaded, ctx, driver)
    finally:
        try:
            driver.close(ctx)
        finally:
            shutil.rmtree(ctx.workdir, ignore_errors=True)


def _run(loaded: dict, ctx: Ctx, driver) -> int:
    emit({"phase": "start", "workload": ctx.workload, "seed": ctx.seed,
          "seconds": ctx.seconds, "trace": int(ctx.trace),
          "rehearse": ctx.rehearse, "fault": ctx.fault or None,
          "driver": ctx.config["driver"]})
    native_s = build_native()
    driver.setup(ctx)              # what needs no JAX in this process
    ctx.device = claim_device(ctx.chips, ctx.rehearse)
    tables = None if ctx.rehearse else load_tables(ctx.device["kind"],
                                                   loaded["bench"])
    driver.warm(ctx)               # trace, lower, compile or hit the cache
    setup_s = time.monotonic() - T_START
    emit({"phase": "setup", "setup_s": setup_s, "native_build_s": native_s,
          "device": ctx.device, **ctx.state.get("setup_report", {})})

    trace = None
    if ctx.trace and not ctx.rehearse:
        result, trace = traced_window(ctx, driver)
    else:
        result = driver.window(ctx)
    emit({"phase": "window", "window_s": result["window_s"],
          "attempted": result["attempted"], "failed": result["failed"],
          **result.get("report", {})})

    # a run of several windows holds each to its own snapshots
    windows = result.get("windows") or [result]
    for one in windows:
        compiled = checks.programs_compiled(one["before"], one["after"])
        if compiled and not ctx.fault:  # a fault may leave the warmed path
            raise BenchError(f"programs compiled or retraced inside the "
                             f"window: {compiled}")

    t0 = time.monotonic()
    numbers = driver.check(ctx, result)
    if not ctx.rehearse:
        from bitcoincashplus_tpu.util import devicewatch

        bad = [dict(item, window=i + 1)
               for i, one in enumerate(windows)
               for item in checks.no_fallback(
                   one["before"], one["after"], sigs=one.get("sigs", 0),
                   dispatches=one.get("dispatches"),
                   cache_dir=devicewatch.compile_cache_dir())]
        for item in bad:
            emit({"phase": "fallback", **item})
        numbers.append(checks.compared("fallback_checks_failed",
                                       len(bad), 0))
    for number in numbers:
        emit({"phase": "compared", **number})
    correct = all(n["ok"] for n in numbers)
    emit({"phase": "check", "correct": correct,
          "check_s": time.monotonic() - t0})

    if ctx.rehearse:
        emit({"rehearsal": True, "platform": ctx.device["platform"],
              "correct": correct, "attempted": result["attempted"],
              "failed": result["failed"]})
        return 0 if correct or ctx.fault else 1

    values = dict(result["values"], setup_s=setup_s)
    obs = {"before": result["before"], "after": result["after"],
           "setup": ctx.state["setup"],
           "trace": trace, "result": result, "traffic": ctx.traffic,
           "config": ctx.config, **tables}
    if ctx.trace:
        wanted = loaded["per_layer"]
        for metric in wanted:
            reader = load_module("layer_metrics", metric["name"],
                                 loaded["bench"])
            value = reader.read(obs)
            if value is not None:
                values[metric["name"]] = value
    else:
        wanted = loaded["end_to_end"]
        missing = [m["name"] for m in wanted if m["name"] not in values]
        if missing:
            raise BenchError(f"the driver reported no {missing}")
    metrics = {}
    for metric in wanted:
        if metric["name"] not in values:
            continue  # a reader that found nothing to read
        value = float(values[metric["name"]])
        if metric["unit"] == "%" and not 0.0 <= value <= 100.0:
            raise BenchError(
                f"{metric['name']} = {value} %: a share outside 0..100 is "
                f"a fault in the count or the divisor, not a result")
        metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    device = dict(ctx.device, memory_peak_bytes=memory_peak_bytes())
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    # every number compared beside its limit: the last lines of standard
    # error, and the last key of the result's line
    line["compared"] = {n["name"]: {"value": n["value"], "limit": n["limit"]}
                        for n in numbers}
    for n in numbers:
        print(f"compared {n['name']} = {n['value']} (limit {n['limit']})"
              + ("" if n["ok"] else " NOT WITHIN IT"), file=sys.stderr)
    sys.stderr.flush()
    emit(line)
    return 0


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; no metric")
    ap.add_argument("--fault", default="",
                    help="a fault of the harness's own making, by name; "
                         "the run has to come out not correct")
    ap.add_argument("--keep-trace", default="",
                    help="copy the .xplane.pb of a --trace 1 run here")
    args = ap.parse_args(argv)
    try:
        return run(args, root)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2


if __name__ == "__main__":
    sys.exit(main())
