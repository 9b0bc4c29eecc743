"""The subject of tests/unit/test_wall_limit.py: one test that outlasts
its wall limit. Not collected by name; that test runs it in a subprocess."""

import time

import pytest


@pytest.mark.wall_limit(2, reason="must trip: the limit itself is under test")
def test_sleeps_past_its_limit():
    time.sleep(60)
