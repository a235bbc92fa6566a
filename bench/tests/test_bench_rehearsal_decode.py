"""CPU rehearsal of the decode cell at tiny widths, in both trace modes,
and of a window in which no decode step ran."""
import math

import pytest

from bench.tests.rehearse import DECODE_SPEC, declared, rehearse

CELL = "phi4-decode-streams"


@pytest.mark.parametrize("trace", [0, 1])
def test_every_metric_printed(trace):
    res = rehearse(CELL, bool(trace), spec=DECODE_SPEC)
    for name in declared(CELL, bool(trace), DECODE_SPEC):
        v = res["metrics"][name]["value"]
        assert isinstance(v, float) and math.isfinite(v), name
    assert res["correct"], res["check"]
    assert res["check"]["decode_gap"]["value"] is not None
    assert res["check"]["arena_overflow"]["value"] == 0


def _shed_everything(stack):
    stack.gateway._over_budget = lambda *a, **k: True


# A share of a roofline or of a peak with nothing to read is left out of
# the line, never printed as 0; every other declared metric is a number.
SHARES = ("decode_roofline", "step_mfu")


@pytest.mark.parametrize("trace", [0, 1])
def test_window_without_decode_is_not_correct(trace):
    res = rehearse(CELL, bool(trace), fault=_shed_everything, spec=DECODE_SPEC)
    assert res["correct"] is False
    assert res["check"]["decode_gap"]["value"] is None
    for name in declared(CELL, bool(trace), DECODE_SPEC):
        if name in SHARES:
            assert name not in res["metrics"], name
            continue
        v = res["metrics"][name]["value"]
        assert isinstance(v, float) and math.isfinite(v), name
    if trace:
        assert res["metrics"]["shed_share"]["value"] == 100.0
        assert res["metrics"]["batch_rows"]["value"] == 0.0
