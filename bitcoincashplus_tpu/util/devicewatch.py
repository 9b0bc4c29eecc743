"""Device-lane observability — the half of the system PR 6 couldn't see.

The unified telemetry layer (util/telemetry) made the HOST side
measurable; every number below the dispatch boundary was still dark:
nothing verified the bounded-recompile bucket invariant at runtime
(ops/ecdsa_batch pads batches to a small compiled-shape set precisely so
XLA retraces stay bounded), nothing accounted for host<->device bytes,
and the "mining loses ~15x to host dispatch" claim had no per-phase
decomposition behind it. This module is the device-lane
monitor registered around every jit entrypoint:

- **Compile/retrace sentinel** (``program()``/``ProgramWatch.dispatch``):
  each watched program counts dispatches per abstract-shape signature; a
  ``jax.monitoring`` listener attributes XLA trace/lower/compile seconds
  to the dispatch that paid them (``bcp_xla_compile_seconds{program}``).
  A program that grows more distinct signatures than its DECLARED shape
  budget fires ``bcp_xla_retrace_unexpected_total{program}``, a trace
  instant, and a log warning — the bucket design's bounded-recompile
  invariant, checked at runtime instead of assumed.

- **Transfer & memory accounting** (``note_transfer``, the
  ``devicewatch_memory`` collector): ``bcp_device_transfer_bytes_total
  {site,direction}`` totals on host->device staging and result fetch,
  transfer-time histograms where a site can actually isolate the wait
  (result fetch; explicit device_put in the bench), and a scrape-time
  collector projecting ``device.memory_stats()`` into HBM gauges —
  graceful no-op on CPU backends, whose ``memory_stats()`` is None.

- **Profiling** (``start_profile``/``stop_profile``): an on-demand
  ``jax.profiler`` wrapper (TensorBoard-compatible dump into the
  datadir) surfaced as the ``startprofile``/``stopprofile`` RPC pair.
  The host's legs of a dispatch (pack, enqueue, the wait for the result)
  are spans (util/telemetry: ecdsa.pack, ecdsa.enqueue, ecdsa.settle,
  miner.enqueue, miner.poll_wait, dispatch.*), which a session records as
  ``bcp.<name>`` host events on the device trace's own clock;
  ``tools/trace_view.py --xplane`` puts the device's idle gaps under them.

- **Stall watchdog** (``Watchdog``/``WATCHDOG``): a no-progress sentinel
  for threads that must keep draining work (the SigService flush loop,
  the pipeline settle horizon). Subsystems register a pending-work probe
  and ``beat()`` on every unit of progress; pending work with no beat
  for the quiet period fires ``bcp_watchdog_stalled{subsystem}``, a log
  warning, and a trace instant. OBSERVE-ONLY by design: the watchdog
  never kills or restarts anything — the degradation machinery
  (breakers, caller-side CPU re-verify) already owns recovery, and a
  false-positive kill would be worse than a loud gauge.

No jax import at module level: validation/ and the crash-test workers
import this (via ops/dispatch) without touching the backend; every jax
access is lazy and guarded on ``"jax" in sys.modules`` so a metrics
scrape can never be the thing that initializes the device backend.

Env knobs:
    BCP_DEVICEWATCH_COST   cost_analysis capture at first compile:
                           "auto" (default: only when the measured
                           compile was cheap, < 0.5 s — the capture
                           re-lowers, and must never double a minutes-
                           long CPU kernel compile), "always", "never"
    BCP_WATCHDOG_QUIET     default stall quiet period, seconds (10)
    BCP_WATCHDOG_INTERVAL  global watchdog ticker cadence, seconds (1)
"""

from __future__ import annotations

import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Callable, Optional

from . import telemetry as tm
from .log import log_printf

# -- telemetry families (util/telemetry). Registered at import so the
# whole namespace is visible on /metrics from the first scrape, samples
# or not — the acceptance surface for "is device accounting wired".
_COMPILE_H = tm.histogram(
    "bcp_xla_compile_seconds",
    "XLA trace+lower+compile seconds attributed to a watched program's "
    "dispatch (one observation per compiling dispatch)",
    labels=("program",),
    buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
             30.0, 60.0, 120.0, 300.0))
_COMPILES_C = tm.counter(
    "bcp_xla_compiles_total",
    "Dispatches of a watched program that paid an XLA trace/compile",
    labels=("program",))
_RETRACE_C = tm.counter(
    "bcp_xla_retrace_unexpected_total",
    "New abstract-shape signatures beyond a program's declared shape "
    "budget — the bounded-recompile invariant, violated",
    labels=("program",))
_XFER_B = tm.counter(
    "bcp_device_transfer_bytes_total",
    "Bytes crossing the host<->device boundary per site and direction "
    "(h2d = staging, d2h = result fetch)",
    labels=("site", "direction"))
_XFER_H = tm.histogram(
    "bcp_device_transfer_seconds",
    "Transfer wait where a site can isolate it (result fetch; explicit "
    "device_put staging in the bench) — h2d bytes are always counted, "
    "h2d TIME only where it is not hidden inside an async dispatch",
    labels=("site", "direction"),
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0))
_WD_STALLED_G = tm.gauge(
    "bcp_watchdog_stalled",
    "1 while a subsystem has pending work but made no progress for its "
    "quiet period, else 0 (observe-only — no kill action)",
    labels=("subsystem",))
_WD_EPISODES_C = tm.counter(
    "bcp_watchdog_stall_episodes_total",
    "Stall episodes detected per subsystem",
    labels=("subsystem",))
_WD_IDLE_G = tm.gauge(
    "bcp_watchdog_idle_seconds",
    "Seconds since the subsystem's last progress beat (the last-progress "
    "gauge; meaningful while pending work exists)",
    labels=("subsystem",))


# ---------------------------------------------------------------------------
# Compile/retrace sentinel
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_PROGRAMS: dict[str, "ProgramWatch"] = {}
_TLS = threading.local()
_LISTENER_INSTALLED = False
# compile seconds observed by the jax.monitoring listener while no
# watched dispatch was active on that thread (other jits in the process)
_UNATTRIBUTED = {"compile_s": 0.0, "events": 0}
# persistent XLA compilation cache state (compile_cache_dir ->
# enable_compile_cache): the GLV verify programs are minutes of cold
# compile per bucket that every restart and child process would re-pay;
# the cache makes it a once-per-toolchain cost. Event tallies come from the
# jax.monitoring event listener (cache_hits etc.), surfaced in
# gettpuinfo.device.
_COMPILE_CACHE = {"dir": None, "enabled": False, "events": {}}
_CACHE_EVENT_PREFIX = "/jax/compilation_cache/"


def _ctx_stack() -> list:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


def _on_compile_event(event: str, duration: float, **_kw) -> None:
    """jax.monitoring duration listener: attribute XLA compile-pipeline
    seconds (/jax/core/compile/*: jaxpr trace, MLIR lowering, backend
    compile) to the watched dispatch active on this thread, if any. jit
    compiles synchronously on the calling thread, so thread-local
    attribution is exact."""
    if not event.startswith("/jax/core/compile/"):
        return
    stack = getattr(_TLS, "stack", None)
    if stack:
        stack[-1]["compile_s"] += duration
        stack[-1]["events"] += 1
    else:
        with _LOCK:
            _UNATTRIBUTED["compile_s"] += duration
            _UNATTRIBUTED["events"] += 1


def _on_cache_event(event: str, **_kw) -> None:
    """jax.monitoring event listener: tally compilation-cache events
    (/jax/compilation_cache/cache_hits and friends) so gettpuinfo.device
    can prove the persistent cache is actually being hit."""
    if not event.startswith(_CACHE_EVENT_PREFIX):
        return
    key = event[len(_CACHE_EVENT_PREFIX):]
    with _LOCK:
        _COMPILE_CACHE["events"][key] = \
            _COMPILE_CACHE["events"].get(key, 0) + 1


def _ensure_listener() -> bool:
    """Install the jax.monitoring listeners once, lazily, and only when
    jax is already imported (a watch must never be the thing that
    initializes the backend). Returns whether the listener is live."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return True
    if "jax" not in sys.modules:
        return False
    with _LOCK:
        if _LISTENER_INSTALLED:
            return True
        try:
            from jax import monitoring as _jm

            _jm.register_event_duration_secs_listener(_on_compile_event)
            _jm.register_event_listener(_on_cache_event)
            _LISTENER_INSTALLED = True
        except Exception:  # pragma: no cover - jax without monitoring
            return False
    return True


CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir(flag: str = "") -> str:
    """Where the persistent XLA compilation cache lives — the one resolver:
    JAX_COMPILATION_CACHE_DIR if set (the cache is placed from outside),
    else ``flag`` (-compilecache=<dir>), else <checkout>/.jax_cache. The
    path is part of the cache key, so it never depends on a temp dir, a
    pid or a clock."""
    return os.path.abspath(
        os.environ.get(CACHE_ENV) or flag
        or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compile_cache(flag: str = "") -> dict:
    """Turn on jax's persistent XLA compilation cache (default ON) at
    compile_cache_dir(flag). When the environment names the directory jax
    reads it itself and this makes no jax_compilation_cache_dir update;
    otherwise the resolved directory is set in jax and exported, so child
    processes inherit the same cache. Installs the monitoring listener so
    cache hits surface in gettpuinfo.device."""
    import jax

    path = compile_cache_dir(flag)
    os.makedirs(path, exist_ok=True)
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
        os.environ[CACHE_ENV] = path
    # the kernels this repo cares about are all multi-second compiles;
    # 2 s keeps trivial jits out of the cache directory
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
    _ensure_listener()
    with _LOCK:
        _COMPILE_CACHE["dir"] = path
        _COMPILE_CACHE["enabled"] = True
    return compile_cache_snapshot()


def compile_cache_snapshot() -> dict:
    """Compilation-cache state for gettpuinfo.device: directory, enabled
    flag, and the monitoring event tallies (cache_hits is the number of
    compiles this process skipped by reading the cache)."""
    with _LOCK:
        events = dict(_COMPILE_CACHE["events"])
        return {
            "dir": _COMPILE_CACHE["dir"],
            "enabled": _COMPILE_CACHE["enabled"],
            "cache_hits": events.get("cache_hits", 0),
            "events": events,
        }


def _cost_capture_mode() -> str:
    return os.environ.get("BCP_DEVICEWATCH_COST", "auto")


class ProgramWatch:
    """Per-program compile/shape accounting around a jit entrypoint.

    ``dispatch(sig)`` wraps ONE call of the program: ``sig`` is the
    abstract-shape signature the caller derives from its bucketing (for
    the ECDSA kernels that is the padded bucket size — the compiled
    shape IS the bucket). A signature never seen before counts a
    (re)trace; compile seconds come from the jax.monitoring listener
    (falling back to the wrapped call's wall time when the listener is
    unavailable). ``shape_budget`` declares how many distinct signatures
    the program's bucket design allows — one more is an invariant
    violation, not a tuning knob, and fires the sentinel."""

    def __init__(self, name: str, shape_budget: Optional[int] = None):
        self.name = name
        self.shape_budget = shape_budget
        self.signatures: dict[tuple, int] = {}
        self.dispatches = 0
        self.compiles = 0
        self.compile_seconds = 0.0
        self.retraces_unexpected = 0
        self.warnings = 0
        self.last_warning = ""
        self.cost: dict[str, dict] = {}  # sig -> first-compile cost analysis

    @contextmanager
    def dispatch(self, *sig_parts, jitfn=None, args=None, kwargs=None):
        """Wrap one program call. Bookkeeping runs even when the wrapped
        call raises (a failed compile still consumed a shape attempt and
        compile time); cost capture runs only on success."""
        listener = _ensure_listener()
        sig = tuple(sig_parts)
        rec = {"compile_s": 0.0, "events": 0}
        _ctx_stack().append(rec)
        t0 = time.perf_counter()
        failed = False
        try:
            yield self
        except BaseException:
            failed = True
            raise
        finally:
            dt = time.perf_counter() - t0
            stack = _ctx_stack()
            if stack and stack[-1] is rec:
                stack.pop()
            self._after_dispatch(sig, rec, dt, listener, failed,
                                 jitfn, args, kwargs)

    def _after_dispatch(self, sig, rec, dt, listener, failed,
                        jitfn, args, kwargs) -> None:
        with _LOCK:
            new = sig not in self.signatures
            self.signatures[sig] = self.signatures.get(sig, 0) + 1
            self.dispatches += 1
            compiled = rec["compile_s"] > 0.0 or (new and not listener)
            compile_s = rec["compile_s"] if rec["compile_s"] > 0.0 else dt
            if compiled:
                self.compiles += 1
                self.compile_seconds += compile_s
            over_budget = (new and self.shape_budget is not None
                           and len(self.signatures) > self.shape_budget)
            if over_budget:
                self.retraces_unexpected += 1
                self.warnings += 1
                self.last_warning = (
                    f"program {self.name!r}: unexpected retrace — shape "
                    f"signature {sig!r} is distinct shape "
                    f"#{len(self.signatures)} against a declared budget "
                    f"of {self.shape_budget} (bounded-recompile invariant "
                    f"violated; compile {compile_s:.3f}s)")
        if compiled:
            _COMPILES_C.labels(program=self.name).inc()
            _COMPILE_H.labels(program=self.name).observe(compile_s)
        if over_budget:
            _RETRACE_C.labels(program=self.name).inc()
            tm.instant("devicewatch.retrace_unexpected",
                       program=self.name, sig=str(sig),
                       shapes=len(self.signatures),
                       budget=self.shape_budget)
            log_printf("WARNING: %s", self.last_warning)
        if (new and not failed and jitfn is not None
                and args is not None):
            self._capture_cost(sig, compile_s, jitfn, args, kwargs or {})

    def _capture_cost(self, sig, compile_s, jitfn, args, kwargs) -> None:
        """First-compile cost analysis (FLOPs / bytes accessed) via the
        AOT lower+compile path. That path does NOT share the dispatch
        cache, so a second compile is paid — gated to cheap compiles
        ("auto": < 0.5 s measured, where the persistent compilation
        cache or plain speed makes the re-lower negligible) unless
        BCP_DEVICEWATCH_COST=always forces it. The listener is suspended
        for the capture so its compile doesn't count as a dispatch."""
        mode = _cost_capture_mode()
        if mode in ("0", "off", "never"):
            return
        if mode not in ("1", "always") and compile_s >= 0.5:
            return
        _ctx_stack().append({"compile_s": 0.0, "events": 0})  # sink
        try:
            ca = jitfn.lower(*args, **kwargs).compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            entry = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            }
            tr = ca.get("transcendentals")
            if tr:
                entry["transcendentals"] = float(tr)
            with _LOCK:
                self.cost[str(sig)] = entry
        except Exception:  # noqa: BLE001 — cost capture is best-effort
            pass
        finally:
            stack = _ctx_stack()
            if stack:
                stack.pop()

    def snapshot(self) -> dict:
        with _LOCK:
            return {
                "dispatches": self.dispatches,
                "compiles": self.compiles,
                "compile_seconds": round(self.compile_seconds, 4),
                "shapes": len(self.signatures),
                "shape_budget": self.shape_budget,
                "retraces_unexpected": self.retraces_unexpected,
                "signatures": {str(k): v
                               for k, v in sorted(self.signatures.items())},
                "cost": {k: dict(v) for k, v in self.cost.items()},
                "last_warning": self.last_warning,
            }


def program(name: str, shape_budget: Optional[int] = None) -> ProgramWatch:
    """Get-or-register the watch for one jit program. A later caller
    passing a budget upgrades a budget-less registration (modules
    register lazily, in whatever import order the process took)."""
    with _LOCK:
        pw = _PROGRAMS.get(name)
        if pw is None:
            pw = _PROGRAMS[name] = ProgramWatch(name, shape_budget)
        elif shape_budget is not None and pw.shape_budget is None:
            pw.shape_budget = shape_budget
        return pw


# ---------------------------------------------------------------------------
# Transfer accounting
# ---------------------------------------------------------------------------

_TRANSFERS: dict[tuple, int] = {}  # (site, direction) -> bytes, ungated


def note_transfer(site: str, direction: str, nbytes: int,
                  seconds: Optional[float] = None) -> None:
    """Account one host<->device crossing: bytes always, wait time only
    when the caller measured a real blocking transfer (direction is
    "h2d" or "d2h")."""
    n = int(nbytes)
    with _LOCK:
        _TRANSFERS[(site, direction)] = \
            _TRANSFERS.get((site, direction), 0) + n
    _XFER_B.labels(site=site, direction=direction).inc(n)
    if seconds is not None:
        _XFER_H.labels(site=site, direction=direction).observe(seconds)


def transfer_snapshot() -> dict:
    with _LOCK:
        out: dict[str, dict] = {}
        for (site, direction), n in sorted(_TRANSFERS.items()):
            out.setdefault(site, {})[direction] = n
        return out


# ---------------------------------------------------------------------------
# Device-memory collector (HBM gauges; graceful no-op on CPU backends)
# ---------------------------------------------------------------------------


def _devices():
    """The live device list WITHOUT triggering backend init: if jax has
    not been imported by real work yet, a metrics scrape must not be the
    thing that initializes the accelerator backend."""
    if "jax" not in sys.modules:
        return []
    try:
        import jax

        return list(jax.devices())
    except Exception:  # noqa: BLE001 — scrape must survive a dead backend
        return []


def _collect_device_memory():
    """Registry collector: per-device memory_stats() projected into HBM
    gauges. CPU backends return None from memory_stats() — the families
    are still emitted (empty / supported=0) so the namespace is stable
    across backends."""
    mem = {"name": "bcp_device_memory_bytes", "type": "gauge",
           "help": "device.memory_stats() projection (bytes_in_use, "
                   "peak_bytes_in_use, bytes_limit, ... per device)",
           "samples": []}
    sup = {"name": "bcp_device_memory_supported", "type": "gauge",
           "help": "1 when the device exposes memory_stats() "
                   "(accelerators), 0 otherwise (CPU backends)",
           "samples": []}
    devices = _devices()
    for i, d in enumerate(devices):
        label = f"{getattr(d, 'platform', 'unknown')}:{i}"
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — per-device probe
            stats = None
        sup["samples"].append(({"device": label}, 1 if stats else 0))
        for k, v in (stats or {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                mem["samples"].append(
                    ({"device": label, "stat": k}, float(v)))
    count = {"name": "bcp_device_count", "type": "gauge",
             "help": "Devices visible to the process (0 until jax is "
                     "imported by real work)",
             "samples": [({}, float(len(devices)))]}
    return [mem, sup, count]


def _collect_programs():
    """Registry collector: per-program distinct-shape counts (the compile
    counters themselves are native families)."""
    with _LOCK:
        shapes = {name: len(pw.signatures) for name, pw in _PROGRAMS.items()}
    if not shapes:
        return []
    return [{
        "name": "bcp_xla_program_shapes", "type": "gauge",
        "help": "Distinct abstract-shape signatures seen per watched "
                "program (compare against the declared budget)",
        "samples": [({"program": n}, v) for n, v in sorted(shapes.items())],
    }]


tm.register_collector("devicewatch_memory", _collect_device_memory)
tm.register_collector("devicewatch_programs", _collect_programs)


# ---------------------------------------------------------------------------
# On-demand jax.profiler wrapper (startprofile / stopprofile RPCs)
# ---------------------------------------------------------------------------

_PROFILE = {"active": False, "path": None, "t0": 0.0, "dumps": 0}


def start_profile(logdir: str) -> dict:
    """Start a jax.profiler trace into ``logdir`` (TensorBoard-compatible
    dump: plugins/profile/<ts>/*.xplane.pb + trace.json.gz). Raises
    RuntimeError when a profile is already running (the profiler is
    process-global)."""
    import jax

    with _LOCK:
        if _PROFILE["active"]:
            raise RuntimeError(
                f"profiler already active (dir: {_PROFILE['path']})")
        _PROFILE["active"] = True
        _PROFILE["path"] = logdir
        _PROFILE["t0"] = time.monotonic()
    try:
        os.makedirs(logdir, exist_ok=True)
        jax.profiler.start_trace(logdir)
    except BaseException:
        with _LOCK:
            _PROFILE["active"] = False
            _PROFILE["path"] = None
        raise
    return {"path": logdir, "active": True}


def stop_profile() -> dict:
    """Stop the running jax.profiler trace; returns {path, seconds}.
    Raises RuntimeError when no profile is running."""
    import jax

    with _LOCK:
        if not _PROFILE["active"]:
            raise RuntimeError("profiler not active (startprofile first)")
        path = _PROFILE["path"]
        seconds = time.monotonic() - _PROFILE["t0"]
    try:
        jax.profiler.stop_trace()
    finally:
        with _LOCK:
            _PROFILE["active"] = False
            _PROFILE["path"] = None
            _PROFILE["dumps"] += 1
    return {"path": path, "seconds": round(seconds, 3)}


def profile_snapshot() -> dict:
    with _LOCK:
        return {"active": _PROFILE["active"], "path": _PROFILE["path"],
                "dumps": _PROFILE["dumps"]}


# ---------------------------------------------------------------------------
# Stall watchdog (observe-only)
# ---------------------------------------------------------------------------

def _default_quiet() -> float:
    try:
        return float(os.environ.get("BCP_WATCHDOG_QUIET", "10"))
    except ValueError:
        return 10.0


class Watchdog:
    """No-progress sentinel. Subsystems register a ``pending_fn`` (how
    many units of work are parked right now — must be lock-free/cheap)
    and ``beat()`` on every unit of progress. ``check()`` marks a
    subsystem stalled when it has pending work and the last beat is
    older than its quiet period; the episode fires the counter, a log
    warning, and a trace instant ONCE per stall, and clears on the next
    beat (or when the pending work drains). Observe-only: no kill, no
    restart — the breaker/fallback machinery owns recovery.

    ``clock`` is injectable (fake-clock unit tests); the process-global
    ``WATCHDOG`` additionally runs a lazy 1 Hz daemon ticker so stalls
    surface even when nobody scrapes /metrics."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 auto_ticker: bool = False):
        self._clock = clock
        self._auto_ticker = auto_ticker
        self._lock = threading.Lock()
        self._entries: dict[str, dict] = {}
        # cumulative per-subsystem beats, surviving re-registration (a
        # bench that closes its node must still be able to prove the
        # watchdog was exercised)
        self._beat_totals: dict[str, int] = {}
        self._ticker: Optional[threading.Thread] = None
        self._ticker_stop = threading.Event()

    def register(self, subsystem: str, pending_fn: Callable[[], int],
                 quiet_s: Optional[float] = None) -> None:
        """(Re-)register a subsystem; a fresh owner supersedes a closed
        one's closure by name (the PR 6 collector pattern). quiet_s <= 0
        disables detection for the subsystem (gauges still export)."""
        q = _default_quiet() if quiet_s is None else float(quiet_s)
        with self._lock:
            self._entries[subsystem] = {
                "pending_fn": pending_fn, "quiet_s": q,
                "last_beat": self._clock(), "stalled": False,
                "episodes": 0, "beats": 0,
            }
        _WD_STALLED_G.labels(subsystem=subsystem).set(0)
        if self._auto_ticker:
            self._ensure_ticker()

    def unregister(self, subsystem: str) -> None:
        with self._lock:
            self._entries.pop(subsystem, None)

    def beat(self, subsystem: str) -> None:
        """Record one unit of progress. Unregistered names are a cheap
        no-op (a bare ChainstateManager in a unit test must not have to
        care whether a node wired the watchdog)."""
        with self._lock:
            self._beat_totals[subsystem] = \
                self._beat_totals.get(subsystem, 0) + 1
            ent = self._entries.get(subsystem)
            if ent is None:
                return
            ent["last_beat"] = self._clock()
            ent["beats"] += 1
            was_stalled, ent["stalled"] = ent["stalled"], False
        if was_stalled:
            _WD_STALLED_G.labels(subsystem=subsystem).set(0)
            log_printf("watchdog: %s recovered (progress beat)", subsystem)
            tm.instant("watchdog.recovered", subsystem=subsystem)

    def check(self, now: Optional[float] = None) -> list[str]:
        """Evaluate every subsystem; returns the currently-stalled names.
        Called by the ticker, the scrape-time collector, and tests."""
        now = self._clock() if now is None else now
        with self._lock:
            entries = list(self._entries.items())
        stalled_names = []
        for name, ent in entries:
            try:
                pending = int(ent["pending_fn"]())
            except Exception:  # noqa: BLE001 — a dead probe isn't a stall
                pending = 0
            idle = max(0.0, now - ent["last_beat"])
            _WD_IDLE_G.labels(subsystem=name).set(round(idle, 3))
            is_stalled = (pending > 0 and ent["quiet_s"] > 0
                          and idle >= ent["quiet_s"])
            fire = clear = False
            with self._lock:
                live = self._entries.get(name)
                if live is not ent:
                    continue  # re-registered mid-check
                if is_stalled and not ent["stalled"]:
                    ent["stalled"] = True
                    ent["episodes"] += 1
                    fire = True
                elif not is_stalled and ent["stalled"]:
                    ent["stalled"] = False
                    clear = True
            if fire:
                _WD_STALLED_G.labels(subsystem=name).set(1)
                _WD_EPISODES_C.labels(subsystem=name).inc()
                log_printf(
                    "WARNING: watchdog: %s stalled — %d pending unit(s), "
                    "no progress for %.1fs (quiet period %.1fs); "
                    "observe-only, no action taken",
                    name, pending, idle, ent["quiet_s"])
                tm.instant("watchdog.stalled", subsystem=name,
                           pending=pending, idle_s=round(idle, 3),
                           quiet_s=ent["quiet_s"])
            elif clear:
                _WD_STALLED_G.labels(subsystem=name).set(0)
                log_printf("watchdog: %s recovered (pending drained)", name)
            if is_stalled:
                stalled_names.append(name)
        return stalled_names

    def snapshot(self) -> dict:
        now = self._clock()
        with self._lock:
            return {
                name: {
                    "stalled": ent["stalled"],
                    "episodes": ent["episodes"],
                    "beats": ent["beats"],
                    "quiet_s": ent["quiet_s"],
                    "idle_s": round(max(0.0, now - ent["last_beat"]), 3),
                }
                for name, ent in self._entries.items()
            }

    def beat_totals(self) -> dict:
        """Cumulative beats per subsystem across registrations (survives
        a node close/unregister — bench/test evidence the watchdog ran)."""
        with self._lock:
            return dict(self._beat_totals)

    # -- ticker ---------------------------------------------------------

    def _ensure_ticker(self) -> None:
        with self._lock:
            if self._ticker is not None and self._ticker.is_alive():
                return
            self._ticker_stop.clear()
            self._ticker = threading.Thread(
                target=self._tick_loop, name="devicewatch-watchdog",
                daemon=True)
            self._ticker.start()

    def _tick_loop(self) -> None:
        try:
            interval = float(os.environ.get("BCP_WATCHDOG_INTERVAL", "1"))
        except ValueError:
            interval = 1.0
        interval = max(0.05, interval)
        while not self._ticker_stop.wait(interval):
            try:
                self.check()
            except Exception:  # noqa: BLE001 — the ticker must survive
                pass

    def stop_ticker(self) -> None:
        self._ticker_stop.set()
        with self._lock:
            t, self._ticker = self._ticker, None
        if t is not None:
            t.join(timeout=5)


WATCHDOG = Watchdog(auto_ticker=True)


def _collect_watchdog():
    """Scrape-time evaluation: a /metrics pull re-checks every subsystem
    (the gauges/counters are native families, set inside check())."""
    WATCHDOG.check()
    return []


tm.register_collector("devicewatch_watchdog", _collect_watchdog)


# ---------------------------------------------------------------------------
# gettpuinfo's "device" section
# ---------------------------------------------------------------------------


def snapshot() -> dict:
    """The device-lane monitor's full state: per-program compile/shape
    accounting (+ first-compile cost analysis), transfer totals, the
    profiler state, unattributed compile time, and the watchdog."""
    with _LOCK:
        programs = {name: pw for name, pw in sorted(_PROGRAMS.items())}
        unattr = dict(_UNATTRIBUTED)
    return {
        "programs": {name: pw.snapshot() for name, pw in programs.items()},
        "transfer_bytes": transfer_snapshot(),
        "unattributed_compiles": {
            "compile_s": round(unattr["compile_s"], 4),
            "events": unattr["events"],
        },
        "compilation_cache": compile_cache_snapshot(),
        "profiler": profile_snapshot(),
        "watchdog": WATCHDOG.snapshot(),
    }


def reset() -> None:
    """Test isolation: drop program watches, transfer tallies, and
    watchdog registrations (the global families live in the telemetry
    registry and are zeroed by telemetry.reset())."""
    with _LOCK:
        _PROGRAMS.clear()
        _TRANSFERS.clear()
        _UNATTRIBUTED["compile_s"] = 0.0
        _UNATTRIBUTED["events"] = 0
        _COMPILE_CACHE["events"].clear()
    with WATCHDOG._lock:
        WATCHDOG._entries.clear()
        WATCHDOG._beat_totals.clear()
