"""The readers of the level loop's lane counters (``lane_occupancy``,
``computed_lane_occupancy``), on counters set by hand.

    python -m pytest bench/tests -q
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


@pytest.fixture
def counters():
    from repro.obs import metrics

    metrics.reset()
    yield metrics
    metrics.reset()


def test_computed_lane_occupancy_is_live_over_computed(counters):
    read = harness.Bench(ROOT).reader("computed_lane_occupancy")
    # a program that keeps no xla.lanes_computed: left out, never 0
    counters.counter("xla.lanes_live").inc(30)
    counters.counter("xla.lanes_padded").inc(1200)
    assert read(None) is None
    counters.counter("xla.lanes_computed").inc(120)
    assert read(None) == pytest.approx(25.0)
    # the padded share reads the same counters as before
    lane = harness.Bench(ROOT).reader("lane_occupancy")
    assert lane(None) == pytest.approx(2.5)
