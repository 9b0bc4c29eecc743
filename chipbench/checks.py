"""What decides ``correct``: numbers compared with limits of their own.

``no_fallback`` is chip_smoke.no_fallback_checks, reading counter deltas
over the window instead of totals; the comparisons with the plain reference
(chipbench/reference.py) live in each driver's ``check``.
"""

from __future__ import annotations


def compared(name: str, value, limit, ok: bool = None, note: str = "") -> dict:
    """One number beside its limit. ``ok`` defaults to value <= limit."""
    if ok is None:
        ok = value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok),
            **({"note": note} if note else {})}


def worst_of(windows: list) -> list:
    """The numbers of a run's windows (one list a window, the same names
    in the same order) as one list: each name once, with the reading of
    the first window that is not within the limit, else the largest, so
    that one failing window makes the run's number fail."""
    if len(windows) == 1:
        return windows[0]
    out = []
    for readings in zip(*windows, strict=True):
        if len({n["name"] for n in readings}) != 1:
            raise ValueError(f"windows compared different numbers: "
                             f"{[n['name'] for n in readings]}")
        bad = [i for i, n in enumerate(readings) if not n["ok"]]
        at = bad[0] if bad else max(range(len(readings)),
                                    key=lambda i: readings[i]["value"])
        where = (f"window {at + 1} of {len(readings)}; not within it in "
                 f"{[i + 1 for i in bad]}" if bad
                 else f"worst of {len(readings)} windows")
        note = "; ".join(filter(None, (readings[at].get("note"), where)))
        out.append({**readings[at], "note": note})
    return out


def programs_compiled(before: dict, after: dict) -> dict:
    """{program: compiles inside the window}, the non-zero ones."""
    b, a = before["device"]["programs"], after["device"]["programs"]
    delta = {n: a[n]["compiles"] - b.get(n, {}).get("compiles", 0)
             for n in a}
    return {n: d for n, d in delta.items() if d}


def no_fallback(before: dict, after: dict, *, sigs: int = 0,
                lanes: int = 8190, dispatches: int = None,
                cache_dir: str = None) -> list:
    """Every way the window could have run somewhere else than on the TPU,
    from two gettpuinfo snapshots; returns the names of the checks that
    failed, each with what was read.

    ``dispatches`` is the number of device dispatches the window has to
    make, where the driver states one: it derives it from its reference's
    replay of the block files and the configuration's flags (a flush every
    n blocks drains the aggregate's tail into smaller buckets), never from
    the program's counters. Without one the window is whole ``lanes``-wide
    slices. Either way the count is held exactly."""
    if dispatches is not None and (type(dispatches) is not int
                                   or dispatches < 0):
        raise TypeError(f"a stated dispatch count is a whole number, not "
                        f"{dispatches!r}")
    bad: list = []

    def check(name: str, ok: bool, read) -> None:
        if not ok:
            bad.append({"check": name, "read": read})

    dev, batch, ecdsa = after["device"], after["batch"], after["ecdsa"]
    b_batch, b_dd = before["batch"], before["ecdsa"]["dev_decompose"]
    dd = ecdsa["dev_decompose"]
    check("device.platform == tpu", dev["platform"] == "tpu",
          dev["platform"])
    verified = batch["sigs_verified"] - b_batch["sigs_verified"]
    check("batch.sigs_verified moved by the window's signatures",
          verified == sigs, verified)
    for key in ("cpu_fallback_sigs", "fault_fallback_sigs", "kat_failures",
                "pallas_fallbacks"):
        check(f"batch.{key} did not move", batch[key] == b_batch[key],
              batch[key] - b_batch[key])
    if sigs:
        check("ecdsa.kernel == glv", ecdsa["kernel"] == "glv",
              ecdsa["kernel"])
    check("ecdsa.glv_broken false", ecdsa["glv_broken"] is False,
          ecdsa["glv_broken"])
    check("ecdsa.glv_fallbacks did not move",
          ecdsa["glv_fallbacks"] == before["ecdsa"]["glv_fallbacks"],
          ecdsa["glv_fallbacks"])
    check("dev_decompose.broken false", dd["broken"] is False, dd["broken"])
    check("dev_decompose.fallbacks did not move",
          dd["fallbacks"] == b_dd["fallbacks"], dd["fallbacks"])
    moved = dd["dispatches"] - b_dd["dispatches"]
    if dispatches is None:
        check("dev_decompose.dispatches moved by the full buckets",
              moved == -(-sigs // lanes), moved)
    else:
        check("dev_decompose.dispatches moved by the count the driver "
              "states", moved == dispatches,
              {"moved": moved, "stated": dispatches})
    for name, br in after["breakers"].items():
        was = before["breakers"].get(name, {})
        check(f"breaker {name} closed, no fallbacks",
              br["state"] == "closed"
              and br["fallback_calls"] == was.get("fallback_calls", 0)
              and br["fallback_items"] == was.get("fallback_items", 0),
              {k: br[k] for k in ("state", "fallback_calls",
                                  "fallback_items")})
    for name, pw in dev["programs"].items():
        check(f"program {name} within its shape budget",
              pw["retraces_unexpected"] == 0
              and (pw["shape_budget"] is None
                   or pw["shapes"] <= pw["shape_budget"]),
              {k: pw[k] for k in ("shapes", "shape_budget",
                                  "retraces_unexpected")})
    cc = dev["compilation_cache"]
    check("compilation_cache enabled at the expected dir",
          cc["enabled"] is True
          and (cache_dir is None or cc["dir"] == cache_dir), cc)
    return bad
