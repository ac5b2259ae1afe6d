"""The harness below its chip check, with the timed path broken
underneath: each fault a cell can have makes `correct` come out false.
(No cell spans chips, so an exchange left out has no cell to break.)"""
import jax
import pytest

from bench import harness
from bench.run import execute
from bench.tests.tiny import context, workload_files

harness.load_repro()
from repro.models.model import Model          # noqa: E402
from repro.train.trainer import Trainer        # noqa: E402

TRAIN_CELLS = workload_files("train")


def _run(name, tmp_path):
    return execute(context(name, seed=5, seconds=1.0,
                           work_dir=str(tmp_path)))


def _failed(res, *names):
    by = {c["name"]: c for c in res["checks"]}
    return not res["correct"] and any(
        by[n]["value"] > by[n]["limit"] for n in names)


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_step_returning_its_state_unchanged(name, tmp_path, monkeypatch):
    build = Trainer._build_step

    def broken(self):
        build(self)
        inner = self._jitted
        # the step counter still moves, or the loop would never end
        self._jitted = jax.jit(lambda st, b: (
            {**st, "step": st["step"] + 1}, inner(st, b)[1]))
    monkeypatch.setattr(Trainer, "_build_step", broken)
    assert _failed(_run(name, tmp_path), "grad_norm_gap", "update_norm_gap")


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_half_the_batch_left_out(name, tmp_path, monkeypatch):
    loss_fn = Model.loss_fn

    def half(self, params, batch):
        rows = batch["tokens"].shape[0] // 2
        return loss_fn(self, params, {k: v[:rows] for k, v in batch.items()})
    monkeypatch.setattr(Model, "loss_fn", half)
    assert _failed(_run(name, tmp_path), "loss_rel", "grad_norm_gap")


def test_faults_cover_every_cell():
    assert {w["name"] for w in harness.benchmark()["workloads"]} <= set(
        TRAIN_CELLS) == set(workload_files())


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_control_in_the_programs_place_is_not_correct(name, tmp_path):
    """tools/control.py's readings, judged by the run's own comparison:
    the program passes, the float8 reference put in its place and half
    the batch left out do not."""
    from bench.tools import control
    out = control.train_readings(context(name, seed=5,
                                         work_dir=str(tmp_path)), True)
    assert out["program"]["correct"], out["program"]
    assert not out["control"]["correct"], out["control"]
    assert not out["half_batch"]["correct"], out["half_batch"]
