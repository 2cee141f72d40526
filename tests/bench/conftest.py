"""What the tests of ``tests/bench/`` share: ``BENCHMARK.json`` as a pin
sees it."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402


@pytest.fixture(params=["committed", "one-more"])
def bench(request):
    """``BENCHMARK.json`` as committed, and again with what a later PR
    appends (one more cell, configuration and per-layer entry): a pin that
    holds by name holds on both."""
    found = bench_pins.committed()
    return found if request.param == "committed" else bench_pins.one_more(
        found)
