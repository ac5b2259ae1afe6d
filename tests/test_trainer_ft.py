"""Fault-tolerant trainer: recovery correctness for all three strategies.

The strongest property the paper's design implies: with a step-indexed
data pipeline and per-step checkpoints, a failure-and-recovery run must
converge to the BIT-IDENTICAL final state of an uninterrupted run.
"""
import dataclasses

import jax
import pytest

from repro.checkpoint.manifest import tree_digest
from repro.configs import get_config, reduced
from repro.core import FailureType, FaultInjector
from repro.models.model import Model
from repro.train import AdamWConfig, TokenPipeline, TrainConfig, Trainer

CFG = reduced(get_config("paper-demo"))
# Qwen3-MoE at a tiny width: a router over 16 experts, 4 of them held
# (from the fifth), top-4, QK-norm, an untied head
QWEN3_MOE = dataclasses.replace(
    reduced(get_config("qwen3-moe-30b-a3b")), n_experts=16,
    experts_per_token=4, experts_held=4, expert_offset=4)
CONFIGS = {"paper-demo": CFG, "qwen3-moe": QWEN3_MOE}
STEPS = 10


def _run(tmp_path, strategy, injector=None, tag="", cfg=CFG):
    model = Model(cfg)
    data = TokenPipeline(cfg.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path / tag),
                     strategy=strategy)
    tr = Trainer(model, data, opt, tc, injector=injector)
    res = tr.run()
    return tr, res


def _state_digest(tr):
    """Parameters and AdamW moments, bit for bit."""
    return tree_digest(jax.device_get(tr.state["params"])), \
        tree_digest(jax.device_get(tr.state["opt"]))


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    d = tmp_path_factory.mktemp("ref")
    tr, res = _run(d, "reinit", tag="ref")
    return tree_digest(jax.device_get(tr.state["params"])), res


@pytest.fixture(scope="module")
def state_references(tmp_path_factory):
    """A fault-free run's final state digests, per configuration."""
    done = {}

    def get(name):
        if name not in done:
            tr, _ = _run(tmp_path_factory.mktemp(f"ref-{name}"), "reinit",
                         tag="ref", cfg=CONFIGS[name])
            done[name] = _state_digest(tr)
        return done[name]
    return get


@pytest.mark.parametrize("strategy,cfg_name", [
    pytest.param("reinit", "paper-demo", id="reinit"),
    pytest.param("ulfm", "paper-demo", id="ulfm"),
    pytest.param("cr", "paper-demo", id="cr"),
    pytest.param("reinit", "qwen3-moe", id="reinit-qwen3-moe"),
    pytest.param("cr", "qwen3-moe", id="cr-qwen3-moe")])
def test_bitwise_identical_recovery_process_failure(tmp_path, strategy,
                                                    cfg_name,
                                                    state_references):
    """After a killed process the run continues bit-identically: for the
    MoE configuration the router, the held experts, the untied head and
    their AdamW moments come back from the buddy copy (reinit) or the
    file (cr)."""
    inj = FaultInjector(n_ranks=8, n_steps=STEPS,
                        kind=FailureType.PROCESS, seed=3)
    tr, res = _run(tmp_path, strategy, injector=inj, tag=strategy,
                   cfg=CONFIGS[cfg_name])
    assert res["final_step"] == STEPS
    assert len(res["reports"]) == 1
    rep = res["reports"][0]
    assert rep.rollback_step == inj.fail_step
    assert _state_digest(tr) == state_references(cfg_name)


@pytest.mark.parametrize("strategy,cfg_name", [
    pytest.param("reinit", "paper-demo", id="reinit"),
    pytest.param("cr", "paper-demo", id="cr"),
    pytest.param("reinit", "qwen3-moe", id="reinit-qwen3-moe"),
    pytest.param("cr", "qwen3-moe", id="cr-qwen3-moe")])
def test_bitwise_identical_recovery_node_failure(tmp_path, strategy,
                                                 cfg_name,
                                                 state_references):
    inj = FaultInjector(n_ranks=8, n_steps=STEPS, kind=FailureType.NODE,
                        seed=5)
    tr, res = _run(tmp_path, strategy, injector=inj, tag=strategy,
                   cfg=CONFIGS[cfg_name])
    assert res["final_step"] == STEPS
    # node failure forces the FILE checkpoint path (Table 2)
    assert _state_digest(tr) == state_references(cfg_name)


def test_cr_recovery_through_delta_checkpoints(tmp_path):
    """CR restores by composing base + dirty-tile deltas from disk; the
    recovered run still lands on the bit-identical final state."""
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    inj = FaultInjector(n_ranks=8, n_steps=STEPS,
                        kind=FailureType.NODE, seed=5)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path / "d"),
                     strategy="cr", ckpt_delta_every=3)
    tr = Trainer(model, data, opt, tc, injector=inj)
    # AdamW dirties ~every tile, which correctly degrades deltas to full
    # frames; lift the degrade threshold so the restore really walks a
    # base + delta chain
    tr.file_ckpt.delta_max_dirty = 1.0
    res = tr.run()
    assert res["final_step"] == STEPS
    # at least one on-disk step must actually be a delta frame
    kinds = {s: tr.file_ckpt._manifest(s).kind for s in tr.file_ckpt.steps()}
    assert "delta" in kinds.values(), kinds
    tc_ref = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path / "ref"))
    tr_ref = Trainer(model, data, opt, tc_ref)
    tr_ref.run()
    assert tree_digest(jax.device_get(tr.state["params"])) == \
        tree_digest(jax.device_get(tr_ref.state["params"]))


def test_resume_from_disk(tmp_path):
    """Stopping and restarting the trainer resumes from the checkpoint."""
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc5 = TrainConfig(total_steps=5, ckpt_dir=str(tmp_path))
    Trainer(model, data, opt, tc5).run()
    tc10 = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path))
    tr2 = Trainer(model, data, opt, tc10)
    res = tr2.run()
    assert res["final_step"] == STEPS
    # matches a straight-through run
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path) + "_x")
    tr3 = Trainer(model, data, opt, tc)
    tr3.run()
    assert tree_digest(jax.device_get(tr2.state["params"])) == \
        tree_digest(jax.device_get(tr3.state["params"]))


def _shrink_scenario(n_nodes, rpn, spares, fail_rank, fail_step,
                     repairs=(), faults=None):
    from repro.scenarios import Fault, Scenario, Topology
    return Scenario(
        name="trainer-node-loss", steps=STEPS,
        topology=Topology(nodes=n_nodes, ranks_per_node=rpn,
                          spares=spares),
        faults=faults if faults is not None
        else (Fault("node", fail_rank, fail_step),),
        repairs=repairs,
        strategies=("shrink",), expect_bit_identical=False)


def test_elastic_shrink_trainer_continues(tmp_path, reference):
    """ScenarioInjector routes a shrink cell through the in-process SPMD
    trainer: with zero spares, a node loss contracts the world instead of
    re-hosting — the run finishes on the shrunk mesh, resumes from the
    checkpointed cut, and (global batch unchanged) still lands on the
    bit-identical final state."""
    from repro.core import ScenarioInjector
    ref_digest, _ = reference
    inj = ScenarioInjector(_shrink_scenario(2, 4, 0, fail_rank=2,
                                            fail_step=4))
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path),
                     strategy="shrink", n_nodes=2, ranks_per_node=4,
                     spare_nodes=0)
    tr = Trainer(model, data, opt, tc, injector=inj)
    res = tr.run()
    assert res["final_step"] == STEPS
    rep = res["reports"][0]
    assert rep.world_after == 4 and tr.n_ranks == 4
    assert rep.rollback_step == 4
    assert sorted(tr.view.ranks()) == [4, 5, 6, 7]
    assert tr.elastic.mesh.data_parallel == 1 \
        and tr.elastic.mesh.epoch == 1
    assert tree_digest(jax.device_get(tr.state["params"])) == ref_digest


def test_elastic_trainer_spare_absorbs_first_node_loss(tmp_path,
                                                       reference):
    """With a spare in the pool, the same node loss under the elastic
    strategy re-hosts (Algorithm 1) instead of shrinking."""
    from repro.core import ScenarioInjector
    ref_digest, _ = reference
    inj = ScenarioInjector(_shrink_scenario(2, 4, 1, fail_rank=2,
                                            fail_step=4))
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path),
                     strategy="shrink", n_nodes=2, ranks_per_node=4,
                     spare_nodes=1)
    tr = Trainer(model, data, opt, tc, injector=inj)
    res = tr.run()
    assert res["final_step"] == STEPS
    rep = res["reports"][0]
    assert rep.world_after is None and tr.n_ranks == 8
    assert tr.elastic.spares() == []        # the spare absorbed the loss
    assert tree_digest(jax.device_get(tr.state["params"])) == ref_digest


def test_elastic_trainer_grows_back_after_shrink(tmp_path, reference):
    """The full elastic lifecycle through the in-process SPMD driver: a
    node loss shrinks the world (mesh epoch 1, recompile), the repaired
    node's rejoin at a later checkpoint boundary grows it back (mesh
    epoch 2, second recompile) — and the run still lands on the
    bit-identical final state."""
    from repro.core import ScenarioInjector
    from repro.scenarios import Repair
    ref_digest, _ = reference
    inj = ScenarioInjector(_shrink_scenario(2, 4, 0, fail_rank=2,
                                            fail_step=4,
                                            repairs=(Repair(2, 7),)))
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path),
                     strategy="shrink", n_nodes=2, ranks_per_node=4,
                     spare_nodes=0)
    tr = Trainer(model, data, opt, tc, injector=inj)
    res = tr.run()
    assert res["final_step"] == STEPS
    shrink_rep, grow_rep = res["reports"]
    assert shrink_rep.world_after == 4
    assert grow_rep.world_after == 8 and tr.n_ranks == 8
    assert sorted(tr.view.ranks()) == list(range(8))
    assert tr.elastic.mesh.data_parallel == 2
    assert tr.elastic.mesh.epoch == 2       # strictly monotonic remesh
    assert tr.elastic.dropped == []
    assert tree_digest(jax.device_get(tr.state["params"])) == ref_digest


def test_trainer_process_shrink_uneven_groups(tmp_path, reference):
    """Process-level shrink in the driver: a single-rank loss with no
    spares drops that rank (uneven groups), keeps the survivors' memory
    tier, and still finishes bit-identically (global batch unchanged)."""
    from repro.core import ScenarioInjector
    from repro.scenarios import Fault
    ref_digest, _ = reference
    inj = ScenarioInjector(_shrink_scenario(
        2, 4, 0, 0, 0, faults=(Fault("rank", 2, 4),)))
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path),
                     strategy="shrink", n_nodes=2, ranks_per_node=4,
                     spare_nodes=0)
    tr = Trainer(model, data, opt, tc, injector=inj)
    res = tr.run()
    assert res["final_step"] == STEPS
    rep = res["reports"][0]
    assert rep.world_after == 7 and tr.n_ranks == 7   # uneven groups
    assert rep.rollback_step == 4       # survivor memory tier at the cut
    assert sorted(tr.view.ranks()) == [0, 1, 3, 4, 5, 6, 7]
    assert tr.elastic.dropped == [2]
    assert tree_digest(jax.device_get(tr.state["params"])) == ref_digest


def test_trainer_growback_mid_cascade(tmp_path, reference):
    """The growback-mid-cascade shape in-process: the cascade's victim
    is dropped by the shrink, so the fault defers until the grow
    re-admits it, then merges as a respawn (never a second shrink) —
    three reports, world restored, bit-identical continuation."""
    from repro.core import ScenarioInjector
    from repro.scenarios import Fault, Repair
    ref_digest, _ = reference
    inj = ScenarioInjector(_shrink_scenario(
        2, 4, 0, 0, 0,
        faults=(Fault("node", 2, 4),
                Fault("rank", 2, None, point="worker.recovery.pulled")),
        repairs=(Repair(2, 7),)))
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path),
                     strategy="shrink", n_nodes=2, ranks_per_node=4,
                     spare_nodes=0)
    tr = Trainer(model, data, opt, tc, injector=inj)
    res = tr.run()
    assert res["final_step"] == STEPS
    shrink_rep, grow_rep, casc_rep = res["reports"]
    assert shrink_rep.world_after == 4
    assert grow_rep.world_after == 8
    assert casc_rep.world_after is None       # merged respawn, no shrink
    assert tr.n_ranks == 8 and sorted(tr.view.ranks()) == list(range(8))
    assert tree_digest(jax.device_get(tr.state["params"])) == ref_digest


def test_trainer_min_data_parallel_floor(tmp_path, reference):
    """The surfaced floor knob: with min_data_parallel == n_nodes the
    same node loss refuses to shrink and respawns instead."""
    from repro.core import ScenarioInjector
    ref_digest, _ = reference
    inj = ScenarioInjector(_shrink_scenario(2, 4, 0, fail_rank=2,
                                            fail_step=4))
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path),
                     strategy="shrink", n_nodes=2, ranks_per_node=4,
                     spare_nodes=0, min_data_parallel=2)
    tr = Trainer(model, data, opt, tc, injector=inj)
    res = tr.run()
    assert res["final_step"] == STEPS
    rep = res["reports"][0]
    assert rep.world_after is None and tr.n_ranks == 8   # respawned
    assert tree_digest(jax.device_get(tr.state["params"])) == ref_digest


@pytest.mark.parametrize("point,expect_offset", [
    ("worker.ckpt.mid_write", -1),    # save never committed: resume s-1
    ("worker.ckpt.pre_push", 0),      # file committed, buddy not: resume s
])
def test_trainer_checkpoint_phase_faults(tmp_path, reference, point,
                                         expect_offset):
    """ROADMAP satellite: checkpoint-phase injection points flow through
    the in-process trainer via ScenarioInjector — a mid-write death
    resumes one step back, a pre-push death resumes at the committed
    file via the merged buddy+file restore; both continue
    bit-identically."""
    from repro.core import ScenarioInjector
    from repro.scenarios import Fault, Scenario, Topology
    ref_digest, _ = reference
    sc = Scenario(name=f"trainer-{point.rsplit('.', 1)[-1]}", steps=STEPS,
                  topology=Topology(nodes=1, ranks_per_node=8, spares=0),
                  faults=(Fault("rank", 3, 5, point=point),),
                  strategies=("reinit",))
    inj = ScenarioInjector(sc)
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path),
                     strategy="reinit")
    tr = Trainer(model, data, opt, tc, injector=inj)
    res = tr.run()
    assert res["final_step"] == STEPS
    assert len(res["reports"]) == 1
    assert res["reports"][0].rollback_step == 5 + expect_offset
    assert tree_digest(jax.device_get(tr.state["params"])) == ref_digest


def test_trainer_cascade_during_recovery(tmp_path, reference):
    """ROADMAP satellite: cascade points flow through the in-process
    trainer — a second failure during the first recovery triggers a
    nested recovery over the same frames; both land on the same cut and
    the continuation stays bit-identical."""
    from repro.core import ScenarioInjector
    from repro.scenarios import Fault, Scenario, Topology
    ref_digest, _ = reference
    sc = Scenario(name="trainer-cascade", steps=STEPS,
                  topology=Topology(nodes=1, ranks_per_node=8, spares=0),
                  faults=(Fault("rank", 3, 4),
                          Fault("rank", 3, None,
                                point="worker.recovery.pulled")),
                  strategies=("reinit",))
    inj = ScenarioInjector(sc)
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path),
                     strategy="reinit")
    tr = Trainer(model, data, opt, tc, injector=inj)
    res = tr.run()
    assert res["final_step"] == STEPS
    assert len(res["reports"]) == 2           # primary + merged cascade
    assert [r.rollback_step for r in res["reports"]] == [4, 4]
    assert tree_digest(jax.device_get(tr.state["params"])) == ref_digest


def test_ulfm_charges_heartbeat_overhead(tmp_path):
    _, res_u = _run(tmp_path, "ulfm", tag="u")
    model = Model(CFG)
    assert all(l > 0 for l in
               [lg.heartbeat_overhead for lg in []] or [1])  # smoke
    tr_u, _ = _run(tmp_path, "ulfm", tag="u2")
    assert tr_u.logs[0].heartbeat_overhead > 0
    tr_r, _ = _run(tmp_path, "reinit", tag="r2")
    assert tr_r.logs[0].heartbeat_overhead == 0


def test_straggler_tracker_flags_outlier():
    from repro.train.straggler import StragglerTracker
    t = StragglerTracker(window=20, min_samples=5, threshold_mads=4.0)
    for i in range(10):
        assert not t.observe(i, 0.10 + 0.001 * (i % 3))
    assert t.observe(10, 0.50)
    assert t.flagged and t.flagged[0][0] == 10
    # a small wobble is not flagged
    assert not t.observe(11, 0.12)


def _gray_scenario(mitigate, faults, steps=STEPS):
    from repro.scenarios import Scenario, Topology
    return Scenario(
        name="trainer-gray", steps=steps,
        topology=Topology(nodes=2, ranks_per_node=4, spares=0),
        faults=faults, mitigate=mitigate,
        strategies=("shrink",), expect_bit_identical=not mitigate)


def test_gray_tolerate_matches_fault_free(tmp_path, reference):
    """mitigate=off: a x6 slow rank degrades throughput but nothing
    dies — zero recovery reports, per-rank attribution blames only the
    victim, and the run finishes bit-identical to fault-free."""
    from repro.core import ScenarioInjector
    from repro.scenarios import Fault
    ref_digest, _ = reference
    inj = ScenarioInjector(_gray_scenario(
        False, (Fault("rank", 1, 4, how="slow", factor=6.0),)))
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path),
                     strategy="shrink", n_nodes=2, ranks_per_node=4,
                     spare_nodes=0)
    tr = Trainer(model, data, opt, tc, injector=inj)
    res = tr.run()
    assert res["final_step"] == STEPS
    assert res["reports"] == []
    assert set(res["stragglers_by_rank"]) == {1}
    assert tr.n_ranks == 8
    assert tree_digest(jax.device_get(tr.state["params"])) == ref_digest


def test_gray_drain_rehosts_bit_identically(tmp_path, reference):
    """mitigate=on: the tracker's per-rank streak flags the sustained
    slowdown, the drain path contracts the world through an ordinary
    shrink at the drain cut (before the degraded step's checkpoint
    commits), and the shrunk run still lands on the bit-identical final
    state (global batch unchanged)."""
    from repro.core import ScenarioInjector
    from repro.scenarios import Fault
    from repro.scenarios.schema import gray_drain_cut
    ref_digest, _ = reference
    f = Fault("rank", 1, 4, how="slow", factor=6.0)
    inj = ScenarioInjector(_gray_scenario(True, (f,)))
    model = Model(CFG)
    data = TokenPipeline(CFG.vocab_size, 4, 32, seed=7)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS)
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path),
                     strategy="shrink", n_nodes=2, ranks_per_node=4,
                     spare_nodes=0, mitigate=True)
    tr = Trainer(model, data, opt, tc, injector=inj)
    res = tr.run()
    assert res["final_step"] == STEPS
    rep = res["reports"][0]
    assert rep.rollback_step == gray_drain_cut(f)
    assert rep.world_after == 7 and tr.n_ranks == 7
    assert tr.elastic.dropped == [1]
    assert set(res["stragglers_by_rank"]) == {1}
    assert tree_digest(jax.device_get(tr.state["params"])) == ref_digest
