"""CPU rehearsal of the prefill cell at tiny widths, in both trace
modes: every declared metric is printed as a number and the run is
correct."""
import json
import math

import pytest

from bench import harness
from bench.tests.rehearse import CPU_SLOWDOWN, declared, rehearse

CELL = "granite-prefill-camera"


@pytest.mark.parametrize("trace", [0, 1])
def test_every_metric_printed(trace):
    res = rehearse(CELL, bool(trace))
    json.dumps(res)  # the result line is JSON
    assert list(res)[-1] == "check"
    for name in declared(CELL, bool(trace)):
        v = res["metrics"][name]["value"]
        assert isinstance(v, float) and math.isfinite(v), name
    assert res["correct"], res["check"]
    assert res["check"]["prefill_gap"]["value"] is not None
    assert res["failed"] == 0
    # every offered stream's frames are attempted, refused ones included
    g = harness.load_cell(CELL).mix["streams"][0]
    due = g["count"] * 2.0 / (g["period_s"] * CPU_SLOWDOWN)
    assert abs(res["attempted"] - due) <= g["count"]
    if trace:
        assert res["device"]["busy_s"] > 0
        assert res["device"]["window_s"] >= res["device"]["busy_s"]
        assert len(res["breakdown"]["device_ops"]) <= 10
