"""The per-test wall limit of tests/conftest.py trips, and is seen.

A subprocess pytest run of a one-line test that sleeps past a 2 s limit
(tests/fixtures/wall_limit_sleeper.py): the process exits non-zero with
every thread's stack in its output, and under xdist the worker goes down
("node down", which the driver's log counts), the test is reported failed
and the run itself ends.
"""

import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
SLEEPER = "tests/fixtures/wall_limit_sleeper.py"


@pytest.mark.parametrize("xdist", [False, True], ids=["plain", "xdist"])
def test_wall_limit_trips_with_stack_dump(xdist):
    cmd = [sys.executable, "-m", "pytest", SLEEPER, "-p", "no:cacheprovider"]
    if xdist:
        cmd += ["-p", "xdist", "-n", "1"]
    t0 = time.monotonic()
    run = subprocess.run(cmd, cwd=REPO, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True, timeout=120)
    assert run.returncode != 0, run.stdout
    assert time.monotonic() - t0 < 55, "the 60 s sleep ran to its end"
    assert "Timeout (0:00:02)!" in run.stdout, run.stdout
    assert "in test_sleeps_past_its_limit" in run.stdout, run.stdout
    if xdist:
        assert "node down" in run.stdout, run.stdout
        assert "1 failed" in run.stdout, run.stdout
