"""The numbers that compare a training cell's first steps with the
reference: the worst leaf sees one leaf gone wrong, the median leaf a
drift that touches every leaf."""
import pytest

from bench import harness

drv = harness.driver("train")

LEAVES = {"a": 1.0, "b": 2.0, "c": 0.5, "d": 4.0, "bias": 0.01}


def _side(scale=None, losses=(12.0, 11.9, 11.8)):
    scale = scale or {}
    norms = {k: v * scale.get(k, 1.0) for k, v in LEAVES.items()}
    return {"losses": list(losses), "grad": dict(norms),
            "update": dict(norms)}


def test_one_leaf_moved_twice_shows_in_the_worst_leaf_alone():
    r = drv.first_step_readings(_side({"d": 2.0}), _side())
    assert r["grad_norm_gap"] == pytest.approx(1.0)
    assert r["update_norm_gap"] == pytest.approx(1.0)
    assert r["grad_median_leaf_gap"] == 0.0
    assert r["update_median_leaf_gap"] == 0.0


def test_a_drift_in_every_leaf_shows_in_the_median_leaf():
    drift = {k: 1.01 for k in LEAVES}
    r = drv.first_step_readings(_side(drift), _side())
    assert r["grad_median_leaf_gap"] == pytest.approx(0.01)
    assert r["update_median_leaf_gap"] == pytest.approx(0.01)
    assert r["loss_rel"] == 0.0


def test_a_small_leaf_is_measured_against_the_median_leaf():
    # the bias leaf doubles, but it is a hundredth of the median leaf
    r = drv.first_step_readings(_side({"bias": 2.0}), _side())
    assert r["grad_norm_gap"] == pytest.approx(0.01)


def test_a_leaf_with_no_reference_gradient_is_left_out_of_the_change():
    ref = _side()
    ref["grad"]["bias"] = 1e-6          # under a thousandth of the median
    prog = _side({"bias": 100.0})
    prog["grad"]["bias"] = 1e-6
    r = drv.first_step_readings(prog, ref)
    assert r["update_norm_gap"] == 0.0
