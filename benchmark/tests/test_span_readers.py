"""The readers of the program's device, handler and gc spans on a
hand-built Reading: each number from its stage, and None where the stage
never fired (a program without the span)."""

from __future__ import annotations

import pytest

import harness

SPAN_METRICS = ("device_dispatch_us_per_row.batch",
                "device_wait_us_per_row.batch",
                "outside_handler_us_per_row.batch",
                "gc_us_per_row.batch")


def reading(stages: dict, rows: int = 40_000, seconds: float = 10.0):
    cell = harness.Cell("a-cell", 1, {}, {"clients": 2}, [], [])
    counters = {"paths": {}, "stages": stages}
    return harness.Reading(cell, seconds, {}, {"rows_driven": rows},
                           counters, None)


def test_span_readers_on_a_known_reading():
    r = reading({"device.dispatch": (10, 0.1), "device.wait": (10, 0.3),
                 "rpc": (10, 12.0), "gc": (4, 0.02)})
    read = {name: harness.metric_reader(name)(r) for name in SPAN_METRICS}
    assert read["device_dispatch_us_per_row.batch"] == pytest.approx(2.5)
    assert read["device_wait_us_per_row.batch"] == pytest.approx(7.5)
    # 2 clients x 10 s = 20 client-seconds, 12 of them in the handler
    assert read["outside_handler_us_per_row.batch"] == pytest.approx(200.0)
    assert read["gc_us_per_row.batch"] == pytest.approx(0.5)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_reader_without_its_stage(name):
    """The stages of a program older than these spans."""
    r = reading({"device": (10, 0.4), "wire.encode": (10, 0.2)})
    assert harness.metric_reader(name)(r) is None
