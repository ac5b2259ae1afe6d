"""The trace reduction on a small hand-made trace whose answers are known,
and on a slice of a trace recorded on the chip."""
import os

import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))

# one device; times in seconds. Window 0..10.
HAND = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit_train_step", 1.0, 2.0], ["jit_train_step", 5.0, 2.0],
                    ["jit_copy", 8.0, 1.0], ["jit_train_step", 9.5, 1.0]],
        "ops": [["fusion.1", 1.0, 1.5], ["fusion.2", 2.0, 1.0],   # overlap
                ["fusion.1", 5.0, 2.0], ["copy.3", 8.0, 1.0],
                ["fusion.1", 9.5, 1.0],                           # clipped
                ["fusion.9", 11.0, 1.0]],                         # outside
    }},
    "spans": [["window", 0.0, 10.0], ["feed", 0.0, 1.0],
              ["save", 3.0, 2.0], ["save", 7.0, 0.5],
              ["step_dispatch", 7.2, 0.1]],
}


def test_busy_union_and_window():
    red = trace.reduce(HAND)
    assert red["window_s"] == 10.0
    # busy: [1,3) [5,7) [8,9) [9.5,10) = 2 + 2 + 1 + 0.5
    assert red["busy_s"] == pytest.approx(5.5)


def test_per_program_and_per_op_time():
    red = trace.reduce(HAND)
    assert trace.programs_matching(red, "train_step") == (3, pytest.approx(
        4.5))
    assert trace.ops_matching(red, r"^fusion\.1$") == (3, pytest.approx(
        4.0))
    assert trace.program_name("jit_train_step(123)") == "jit_train_step"


def test_gaps_named_by_innermost_host_span():
    red = trace.reduce(HAND)
    # gaps: [0,1) feed; [3,5) save; [7,8) midpoint 7.5 -> none inside the
    # window but window itself -> "other"; [9,9.5) -> other
    assert red["idle_by_span"]["feed"] == pytest.approx(1.0)
    assert red["idle_by_span"]["save"] == pytest.approx(2.0)
    assert red["idle_by_span"]["other"] == pytest.approx(1.5)
    b = trace.breakdown(red)
    assert b["idle_gaps"][0] == ["save", pytest.approx(2.0)]
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(4.0)]


def test_needs_one_window_span():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "spans": []})


def test_recorded_slice_of_a_train_window():
    """80 ms around a step boundary of paper-demo on one v5e, ops under
    20 us dropped: busy and idle add up to the window, the train step is
    found by its program name, and the gap between steps is named."""
    rec = trace.load(os.path.join(HERE, "data", "train_trace_slice.json"))
    red = trace.reduce(rec)
    idle = sum(red["idle_by_span"].values())
    assert red["busy_s"] + idle == pytest.approx(red["window_s"])
    assert 0.9 < red["busy_s"] / red["window_s"] < 1.0
    runs, secs = trace.programs_matching(red, r"^jit_train_step$")
    assert runs == 2 and secs == pytest.approx(red["busy_s"], rel=0.01)
    assert set(red["idle_by_span"]) <= {"feed", "step_dispatch", "other"}
    b = trace.breakdown(red)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("%while")
