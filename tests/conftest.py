"""Test configuration.

Tests run on the CPU backend with an 8-device virtual mesh so multi-chip
sharding logic (parallel/) is exercised without TPU hardware — the same
mechanism the driver uses for dryrun_multichip (see __graft_entry__.py).
Behaviour on the real chip is covered by chip_smoke.py, and what the
chip's compiler accepts by tests/unit/test_chip_compile.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compilation cache: the fused-GLV verify programs cost minutes
# of cold XLA compile on the CPU backend, and the functional tests spawn
# real node processes that would otherwise each pay it again. One
# resolver places it (util/devicewatch.compile_cache_dir:
# JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) and exports the
# directory, so every spawned bcpd inherits it with no extra flags; it
# also installs the jax.monitoring listener, so in-process cache hits are
# observable just like the spawned nodes'. Tests assert the inheritance
# end to end via gettpuinfo.device.compilation_cache.
from bitcoincashplus_tpu.util import devicewatch as _dw  # noqa: E402  (env first)

_dw.enable_compile_cache()

import faulthandler  # noqa: E402

import pytest  # noqa: E402

# Wall limit of every test, in seconds: three times what the longest
# took from an empty compile cache on the 8-core sandbox (PR 25). A thread
# inside jax.block_until_ready never runs a Python signal handler, so the
# limit is the process's own: past it faulthandler dumps every thread's
# stack and exits, xdist reports the test failed ("node down") and starts
# a new worker, and the rest of the run goes on.
# ``@pytest.mark.wall_limit(seconds, reason=...)`` sets another limit for
# one named test; nothing switches the limit off.
TEST_WALL_LIMIT_S = 600


_STDERR_FD = pytest.StashKey[int]()


def pytest_configure(config):
    # the real stderr, before capture redirects fd 2 for each test
    config.stash[_STDERR_FD] = os.dup(2)


def pytest_unconfigure(config):
    os.close(config.stash[_STDERR_FD])


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    # around setup, call and teardown: the fixtures that mine a chain
    # (module-scoped ones too) are inside the limit
    marker = item.get_closest_marker("wall_limit")
    limit = marker.args[0] if marker else TEST_WALL_LIMIT_S
    faulthandler.dump_traceback_later(
        limit, exit=True, file=item.config.stash[_STDERR_FD])
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


def pytest_collection_modifyitems(config, items):
    """Fast signal first: run the unit suite before the functional suite
    (which spawns real bcpd processes at several minutes per file), and
    the adversarial chaos campaigns after the rest of the functional
    suite — under a bounded CI budget the newest, heaviest campaigns are
    the first thing a timeout cuts, never the established coverage.
    The ``pipeline`` suite (pipelined-IBD differentials/unwind, tier-1,
    JAX_PLATFORMS=cpu) runs after the plain unit suite and before the
    functional/adversarial groups; the ``glv`` and ``msm`` kernel suites
    are plain-unit (group 0) on purpose — fast, ordered with the unit
    run (the msm suite pins every MSM dispatch to the bucket-64 shape,
    the only rung whose XLA compile is unit-test-priced). The
    ``telemetry`` suite runs after ``pipeline`` (its registry-zeroing
    fixture must not interleave with suites asserting on live counters)
    and the ``serving`` suite (SigService flush policy / serviced-accept
    differentials) after ``telemetry``, both before the functional
    groups. Stable sort: order within each group is unchanged."""

    def group(item) -> int:
        # the ``devprof`` suite (device-lane observability — the same
        # registry-zeroing isolation pattern as telemetry) runs after
        # ``telemetry`` and before ``serving``; the ``mining`` suite
        # (resident loop + hoist differentials — ISSUE 10) runs after
        # ``devprof`` (it asserts on devicewatch program state) and
        # before ``serving``; the ``forkstorm`` multi-node campaigns run
        # DEAD LAST, after even the adversarial chaos suites — they are
        # the newest, heaviest coverage and the first thing a CI timeout
        # should cut
        if "functional" not in str(item.fspath):
            # the ``lint`` suite (bcplint static analysis + lockwatch
            # sentinel — ISSUE 15) runs FIRST: pure-AST, no jax import,
            # and an invariant violation is the cheapest, highest-signal
            # failure the run can produce
            if item.get_closest_marker("lint"):
                return -1
            # the ``mempoolstorm`` differential suite (ISSUE 20) is the
            # newest non-functional coverage: after ``serving``, still
            # before every functional group (fractional key — the
            # functional ladder starts at 6)
            if item.get_closest_marker("mempoolstorm"):
                return 5.5
            if item.get_closest_marker("serving"):
                return 5
            if item.get_closest_marker("mining"):
                return 4
            if item.get_closest_marker("devprof"):
                return 3
            if item.get_closest_marker("telemetry"):
                return 2
            return 1 if item.get_closest_marker("pipeline") else 0
        # the ``snapshot`` onboarding test runs after the plain
        # functional group, then adversarial, then forkstorm, then the
        # ``fleet`` multi-node serving campaigns dead last (ISSUE 16 —
        # the newest, heaviest topologies are the first thing a CI
        # timeout cuts)
        if item.get_closest_marker("fleet"):
            return 10
        if item.get_closest_marker("forkstorm"):
            return 9
        if item.get_closest_marker("adversarial"):
            return 8
        return 7 if item.get_closest_marker("snapshot") else 6

    items.sort(key=group)


@pytest.fixture
def fault_harness(monkeypatch):
    """Arm the BCP_FAULT_* harness for one test and restore a clean
    injector + breaker registry afterwards (the fault state is process-
    global by design — it must never leak across tests).

    The `faults` marker (registered in pyproject.toml) tags the
    supervised-dispatch fault suite; it is tier-1 fast — injection fires
    BEFORE any heavy kernel compile, and device stubs stand in for the
    ECDSA kernel — so it runs by default. Smoke subset alone:
    ``JAX_PLATFORMS=cpu pytest -m faults -q``.

    Usage: ``inj = fault_harness("fail-always", ops="ecdsa", n=3)``."""
    from bitcoincashplus_tpu.ops import dispatch
    from bitcoincashplus_tpu.util import faults

    def arm(mode: str, ops: str = "all", **env):
        monkeypatch.setenv("BCP_FAULT_MODE", mode)
        monkeypatch.setenv("BCP_FAULT_OPS", ops)
        for key, val in env.items():
            monkeypatch.setenv("BCP_FAULT_" + key.upper(), str(val))
        faults.INJECTOR.reload()
        return faults.INJECTOR

    yield arm
    # monkeypatch's own env restore runs AFTER this generator resumes, so
    # scrub the fault vars by hand before rebuilding the global state
    for key in [k for k in os.environ if k.startswith("BCP_FAULT")]:
        os.environ.pop(key, None)
    faults.INJECTOR.reload()
    dispatch.reset()


class StubVerifyKernels:
    """What ``stub_verify_kernels`` hands a test: ``calls`` lists every
    kernel call as (rung, arrays) in order, rung "glv", "w4" or "schnorr"
    (the Schnorr bucket's program, six arrays); ``fail``
    maps a rung to the exception its next calls raise; ``verdicts``, when
    set, replaces the oracle: arrays -> (bucket,) bool."""

    def __init__(self):
        self.calls: list = []
        self.fail: dict = {}
        self.verdicts = None

    def rungs(self) -> list:
        return [rung for rung, _ in self.calls]


@pytest.fixture
def stub_verify_kernels(monkeypatch):
    """Stand-ins for the two device verify programs, at the two calls the
    one dispatch function (ops/ecdsa_batch._dispatch_packed_device) makes:
    the real ones cost minutes of compile on the CPU test backend, and the
    supervision around them (KAT lanes, retries, breaker, settle-time
    detection, CPU re-verify) is identical. Verdicts come from the packed
    arrays alone, with Python ints: R = u1·G + u2·Q, R.x against r and,
    where wrap_ok, r + n; so the KAT lanes get honest answers."""
    import numpy as np

    import bitcoincashplus_tpu.ops.secp256k1 as dev
    from bitcoincashplus_tpu.crypto import secp256k1 as oracle

    # a stubbed first dispatch "compiles" in no time, and devicewatch
    # would then lower and compile the real program for its cost analysis
    monkeypatch.setenv("BCP_DEVICEWATCH_COST", "off")
    stub = StubVerifyKernels()

    def oracle_verdicts(arrays):
        u1m, u2m, qxb, qyb, q_inf, r0b, rnb, wrap8 = arrays
        ok = np.zeros(len(q_inf), bool)
        for i in np.nonzero(np.asarray(q_inf) == 0)[0]:
            u1, u2, qx, qy, r0, rn = (
                int.from_bytes(m[i].tobytes(), "big")
                for m in (u1m, u2m, qxb, qyb, r0b, rnb))
            pt = oracle.point_add(oracle.point_mul(u1, oracle.G),
                                  oracle.point_mul(u2, (qx, qy)))
            ok[i] = pt is not None and (
                pt[0] == r0 or bool(wrap8[i]) and pt[0] == rn)
        return ok

    def kernel(rung):
        def call(*arrays, interpret=False):
            stub.calls.append((rung, arrays))
            if rung in stub.fail:
                raise stub.fail[rung]
            ok = (stub.verdicts or oracle_verdicts)(arrays)
            return np.asarray(ok, bool), np.zeros(len(ok), bool)
        return call

    def schnorr_verdicts(arrays):
        u1m, u2m, qxb, qyb, q_inf, r0b = arrays
        ok = np.zeros(len(q_inf), bool)
        for i in np.nonzero(np.asarray(q_inf) == 0)[0]:
            u1, u2, qx, qy, r0 = (
                int.from_bytes(m[i].tobytes(), "big")
                for m in (u1m, u2m, qxb, qyb, r0b))
            pt = oracle.point_add(oracle.point_mul(u1, oracle.G),
                                  oracle.point_mul(u2, (qx, qy)))
            ok[i] = (pt is not None and pt[0] == r0
                     and oracle.jacobi(pt[1]) == 1)
        return ok

    def schnorr_kernel(*arrays):
        stub.calls.append(("schnorr", arrays))
        if "schnorr" in stub.fail:
            raise stub.fail["schnorr"]
        ok = (stub.verdicts or schnorr_verdicts)(arrays)
        return np.asarray(ok, bool), np.zeros(len(ok), bool)

    monkeypatch.setattr(dev, "schnorr_verify_batch_glv_dev", schnorr_kernel)
    monkeypatch.setattr(dev, "ecdsa_verify_batch_glv_dev", kernel("glv"))
    monkeypatch.setattr(dev, "ecdsa_verify_batch_pallas_w4_bytes",
                        kernel("w4"))
    return stub
