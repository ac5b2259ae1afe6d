"""Fault-tolerant training driver — the paper's Fig. 2 made executable.

The driver wraps its main loop in `reinit_main` (the MPI_Reinit analogue).
A deterministic FaultInjector kills a random rank (or node) at a random
step; the configured RecoveryStrategy then *actually performs* its recovery
actions on the training state:

  CR        drop everything (state, compiled-step caches), re-"deploy" and
            reload the latest FILE checkpoint.
  Reinit++  survivors keep device state and compiled steps; the lost
            shard's state is restored from the buddy MEMORY checkpoint
            (process failure) or the file checkpoint (node failure);
            Algorithms 1/2 re-form the cluster view.
  ULFM      like Reinit++ for state, but pays revoke/shrink/agree all-rank
            agreement rounds during recovery and a heartbeat tax on every
            fault-free step.

Because the data pipeline is step-indexed and checkpoints are taken every
policy-interval, a failed-and-recovered run converges to the bit-identical
state of an uninterrupted run — the integration tests assert exactly that.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import FileCheckpointer, buddy_exchange, \
    restore_from_buddy
from repro.checkpoint.policy import CheckpointPolicy
from repro.core import (ClusterView, ElasticManager, FailureEvent,
                        FailureType, FaultInjector, MeshEpoch, RankState,
                        RecoveryReport, ROLLBACK, RollbackSignal,
                        apply_recovery, get_strategy, reinit_main,
                        root_handle_failure)
from repro.core.spans import span, step_span
from repro.models.model import Model
from repro.scenarios.schema import GRAY_DRAIN_PERSIST, GRAY_HOWS, \
    gray_delay_s
from repro.sharding.partition import constraint_scope, state_shardings
from repro.sharding.rules import ShardingRules, PRESETS

from .data import TokenPipeline
from .optimizer import AdamWConfig, adamw_init, adamw_update
from .straggler import StragglerTracker


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    total_steps: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 1
    ckpt_shards: int = 4
    # K>1: full file snapshot every K-th save, dirty-tile deltas between
    ckpt_delta_every: int = 0
    # N>0: background re-base rewrites a delta chain as a fresh base once
    # it reaches N links, bounding restore cost so delta_every can be
    # raised aggressively
    ckpt_rebase_after: int = 0
    # device dirty-tile gather for delta saves: auto/on/off
    ckpt_gather: str = "auto"
    async_file_ckpt: bool = False
    strategy: str = "reinit"
    # logical deployment (the paper's root/daemon/rank tree)
    n_nodes: int = 2
    ranks_per_node: int = 4
    spare_nodes: int = 1
    # elastic world floor, in whole node groups: shrinking recovery
    # refuses to contract below min_data_parallel * ranks_per_node ranks
    min_data_parallel: int = 1
    # gray-failure policy: off tolerates a degraded rank (the run slows,
    # nothing else changes); on drains a persistent straggler through
    # the shrink path and re-admits it at the repair's grow-back
    mitigate: bool = False
    seed: int = 0
    log_every: int = 0


@dataclasses.dataclass
class StepLog:
    step: int
    loss: float
    seconds: float
    heartbeat_overhead: float = 0.0


class Trainer:
    def __init__(self, model: Model, data: TokenPipeline,
                 opt_cfg: AdamWConfig, tc: TrainConfig, *,
                 mesh=None, rules: Optional[ShardingRules] = None,
                 injector: Optional[FaultInjector] = None):
        self.model = model
        self.data = data
        self.opt_cfg = opt_cfg
        self.tc = tc
        self.mesh = mesh
        self.rules = rules or PRESETS["single"]
        self.strategy = get_strategy(tc.strategy)
        self.injector = injector
        self.view = ClusterView.build(tc.n_nodes, tc.ranks_per_node,
                                      tc.spare_nodes)
        self.n_ranks = tc.n_nodes * tc.ranks_per_node
        # elastic strategy: the membership machine owns the spare pool,
        # the shrink/grow decisions and the dropped-rank ledger; one node
        # = one data-parallel group, the mesh epoch keys the
        # compiled-step cache across shrinks and grow-backs
        self.elastic = ElasticManager(
            self.view, MeshEpoch(epoch=0, data_parallel=tc.n_nodes,
                                 model_parallel=tc.ranks_per_node),
            min_data_parallel=tc.min_data_parallel) \
            if self.strategy.key == "shrink" else None
        self.policy = CheckpointPolicy(every_steps=tc.ckpt_every,
                                       async_file=tc.async_file_ckpt)
        self.file_ckpt = FileCheckpointer(
            tc.ckpt_dir, n_shards=tc.ckpt_shards,
            delta_every=tc.ckpt_delta_every, gather=tc.ckpt_gather,
            rebase_after=tc.ckpt_rebase_after)
        # buddy memory checkpoint: (step, state_copy, buddy_copy)
        self.mem_ckpt: Optional[tuple[int, Any, Any]] = None
        # replica strategy: the victim's warm shadow — a device copy of
        # the state mirrored after *every* step (the replication stream),
        # hosted off-node by construction, so recovery is promote-and-
        # continue with zero rollback
        self.shadow_ckpt: Optional[tuple[int, Any]] = None
        self.state: Optional[dict] = None
        self.logs: list[StepLog] = []
        # the newest step's metrics (loss parts, the MoE layers'
        # expert_rows and expert_full), left on the device: nothing reads
        # them per step
        self.last_metrics: Optional[dict] = None
        self.reports: list[RecoveryReport] = []
        self.straggler = StragglerTracker()
        # gray-failure plan from the injector's scenario (if any): the
        # (index, fault) pairs whose victims get synthesized per-rank
        # delays, and the set already cured by a drain. A gray plan
        # re-tunes the tracker: few samples suffice, and the absolute
        # floor at half the smallest injected delay keeps jitter out.
        self._gray: list = []
        self._gray_mitigated: set[int] = set()
        sc = getattr(injector, "scenario", None)
        if sc is not None:
            self._gray = [(i, f) for i, f in enumerate(sc.faults)
                          if f.how in GRAY_HOWS]
        if self._gray:
            self.straggler = StragglerTracker(
                window=32, threshold_mads=4.0, min_samples=2,
                min_flag_s=0.5 * min(gray_delay_s(f)
                                     for _, f in self._gray))
        self._build_step()

    # ----------------------------------------------------------- stepping

    def _build_step(self):
        model, opt_cfg = self.model, self.opt_cfg

        def train_step(state, batch):
            def loss_fn(params):
                return model.loss_fn(params, batch)

            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state["params"])
            new_p, new_opt, om = adamw_update(state["params"], grads,
                                              state["opt"], opt_cfg)
            new_state = {"params": new_p, "opt": new_opt,
                         "step": state["step"] + 1}
            return new_state, (loss, {**metrics, **om})

        if self.mesh is not None:
            self._train_step_fn = train_step      # sharded jit built lazily
            self._jitted = None
        else:
            self._jitted = jax.jit(train_step, donate_argnums=0)

    def _step(self, state, batch):
        if self.mesh is None:
            return self._jitted(state, batch)
        if self._jitted is None:
            st_sh = state_shardings(self.mesh, state, self.rules)
            self._jitted = jax.jit(self._train_step_fn,
                                   in_shardings=(st_sh, None),
                                   out_shardings=(st_sh, None),
                                   donate_argnums=0)
        with constraint_scope(self.mesh, self.rules):
            return self._jitted(state, batch)

    # -------------------------------------------------------------- state

    def init_state(self) -> dict:
        params = self.model.init(jax.random.PRNGKey(self.tc.seed))
        return {"params": params, "opt": adamw_init(params),
                "step": jnp.zeros((), jnp.int32)}

    def _injected_at(self, point: str, step: Optional[int] = None):
        """Scenario fault due at a named interruption point — how the
        in-process driver reaches the checkpoint-phase and cascade
        injection points the real runtime fires through
        repro.scenarios.hooks. A fault whose victim rank is currently
        out of the world is deferred, not claimed: its next incarnation
        first runs at the grow that re-admits it, whose own cascade
        pass fires it (mirrors the sim's deferred cascades)."""
        inj = self.injector
        if inj is None or not hasattr(inj, "check_point"):
            return None
        live = set(self.view.ranks())
        return inj.check_point(
            point, step=step, view=self.view,
            eligible=lambda f: f.target != "rank" or f.rank in live)

    def _save_ckpt(self, step: int):
        """Both faces of Table 2: buddy memory copy + file checkpoint.

        The file path is the fast-path engine: with async_file the save
        snapshots on device (digests included), kicks the D2H drain and
        returns — serialization and sharded IO overlap the next step.

        Mirrors the real worker's commit order (file first, then the
        buddy push) so the checkpoint-phase interruption points carry
        the same meaning: a mid-write death leaves both tiers at step-1;
        a pre-push death leaves the file one step ahead of the buddy
        copy, and the merged restore must still reach `step`."""
        failure = self._injected_at("worker.ckpt.mid_write", step)
        if failure is not None:
            # dies with the shard bytes un-renamed: nothing durable at
            # `step` anywhere — recovery resumes from step-1. Unfenced
            # checkpoint-phase deaths have no stalled kill barrier to
            # promote against, so replica falls back (shadow goes cold)
            self.shadow_ckpt = None
            self._handle_failure(failure)
            raise RollbackSignal(self.view.epoch)
        state = self.state
        # the new copies replace the memory tier: release the previous
        # ones first, so the device never holds two generations of them
        # (a fault from here on restores the file this save commits)
        self.mem_ckpt = None
        with span("save.copies"):
            if self.mesh is not None and self.mesh.shape.get("data", 1) > 1:
                buddy = buddy_exchange(state, self.mesh, self.rules)
            else:
                buddy = jax.tree.map(lambda a: a + 0, state)  # device copy
            local = jax.tree.map(lambda a: a + 0, state)
        # the file snapshot is `local` itself, which no step donates: the
        # device holds the state and its two copies, not a third
        self.file_ckpt.save(step, local, async_=self.policy.async_file)
        failure = self._injected_at("worker.ckpt.pre_push", step)
        if failure is not None:
            # ReStore's mid-replication failure: the file committed but
            # the buddy copy was never pushed — the memory tier stays at
            # step-1 and the merged restore takes the newer file. Same
            # unfenced-death fallback as mid_write for replica.
            self.shadow_ckpt = None
            self._handle_failure(failure)
            raise RollbackSignal(self.view.epoch)
        self.mem_ckpt = (step, local, buddy)

    # ----------------------------------------------------------- recovery

    def _handle_failure(self, failure: FailureEvent,
                        cascade: bool = False) -> RecoveryReport:
        rep = RecoveryReport(strategy=self.strategy.name, failure=failure)
        # cascades merge into the recovery in flight via respawn, never
        # shrink on their own (a second failure during recovery must not
        # drop a rank survivors are blocked waiting on) — same policy as
        # the sim and the real root's open-join-window classification
        if self.elastic is not None and not cascade \
                and self.elastic.decide(failure) == "shrink":
            return self._handle_failure_shrink(rep, failure)

        # --- detection (child monitor / channel break at the root)
        with span("recovery.detect") as sp:
            cmd = root_handle_failure(self.view, failure)
            states = apply_recovery(self.view, cmd)
            assert len(states) == self.n_ranks  # non-shrinking invariant
            if self.elastic is not None:
                self.elastic.nonshrink_plan(failure)     # mesh bookkeeping
        rep.detect_s = sp.seconds

        # --- zero-rollback fast path (replica): the victim's warm shadow
        # holds the state at the failure step — promotion replaces the
        # heavyweight strategy recovery, and the run resumes exactly
        # where it stopped. A node loss does NOT invalidate the shadow
        # (shadows are hosted off the primary's node by construction); a
        # cold shadow (nothing mirrored yet, or consumed by the recovery
        # in flight) falls through to the ordinary path below.
        if self.strategy.replicates and self.shadow_ckpt is not None:
            with span("recovery.mpi") as sp:
                step, shadow = self.shadow_ckpt
                self.shadow_ckpt = None   # consumed: a cascade during this
                                          # recovery has no second standby
                if failure.kind is FailureType.NODE:
                    self.mem_ckpt = None  # buddy copies died with the node
            rep.mpi_recovery_s = sp.seconds
            with span("recovery.restore") as sp:
                self.state = jax.tree.map(lambda a: a + 0, shadow)
                jax.block_until_ready(self.state)
            rep.ckpt_read_s = sp.seconds
            rep.rollback_step = step
            self.reports.append(rep)
            self._fire_cascades()
            return rep

        # --- MPI recovery: what each strategy actually does
        ckpt_kind = self.strategy.checkpoint_kind(failure.kind)
        with span("recovery.mpi") as sp:
            if self.strategy.redeploys:
                # CR: teardown — lose device state AND compiled artifacts
                self.state = None
                self.mem_ckpt = None
                self._jitted = None
                self._build_step()
                jax.clear_caches()
            else:
                if self.strategy.allrank_collectives:
                    # ULFM: revoke/shrink/agree rounds across all ranks
                    x = jnp.ones((self.n_ranks,), jnp.float32)
                    for _ in range(self.strategy.allrank_collectives):
                        x = jax.jit(lambda v: v / jnp.sum(v))(x)
                    x.block_until_ready()
                if failure.kind is FailureType.NODE:
                    # node loss invalidates buddy copies of that node's
                    # shards
                    self.mem_ckpt = None
        rep.mpi_recovery_s = sp.seconds

        # --- application recovery: reload the appropriate checkpoint.
        # The memory tier is only taken when it is at least as new as the
        # file tier — a failure between the file commit and the buddy
        # push (worker.ckpt.pre_push) leaves the file one step ahead, and
        # the merged restore must reach it (the real runtime's merged
        # buddy+file restore maps, in-process)
        with span("recovery.restore") as sp:
            use_memory = ckpt_kind == "memory" and self.mem_ckpt is not None
            if use_memory:
                self.file_ckpt.wait()
                fsteps = self.file_ckpt.steps()
                if fsteps and fsteps[-1] > self.mem_ckpt[0]:
                    use_memory = False
            if use_memory:
                step, local, buddy = self.mem_ckpt
                if self.mesh is not None \
                        and self.mesh.shape.get("data", 1) > 1:
                    restored = restore_from_buddy(buddy, self.mesh,
                                                  self.rules)
                else:
                    restored = buddy
                # survivors keep `local`; the failed shard comes from
                # `restored` (same global value — asserted in tests via
                # digest equality)
                self.state = jax.tree.map(lambda a: a + 0, restored)
                rollback_step = step
            else:
                rollback_step = self._restore_file()
            jax.block_until_ready(self.state)
        rep.ckpt_read_s = sp.seconds
        rep.rollback_step = rollback_step
        self.reports.append(rep)
        self._fire_cascades()
        return rep

    def _restore_file(self) -> int:
        """Reload the newest committed file checkpoint, or a fresh state
        when there is none; returns the step rolled back to."""
        self.file_ckpt.wait()
        step, state = self.file_ckpt.load_latest()
        if step is None:
            self.state = self.init_state()
            return 0
        self.state = jax.tree.map(jnp.asarray, state)
        return step

    def _fire_cascades(self):
        """Cascade injection points (a second failure during the recovery
        just performed): a survivor right after rollback, a restoring
        rank right after gathering its frames, a kill mid-compose. Each
        fires at most once per scenario; the nested recovery re-restores
        the same state, so continuation stays bit-identical."""
        for point in ("worker.recovery.enter", "worker.recovery.pulled",
                      "worker.recovery.compose"):
            cascade = self._injected_at(point)
            if cascade is not None:
                self._handle_failure(cascade, cascade=True)
                return

    def _handle_failure_shrink(self, rep: RecoveryReport,
                               failure: FailureEvent) -> RecoveryReport:
        """Elastic shrinking recovery in the in-process SPMD driver: the
        spare pool is exhausted, so the data axis contracts instead of
        re-hosting — by a whole node group on a node loss, or by a single
        rank on a process loss (uneven groups). Survivors keep process +
        device state; the mesh epoch bump invalidates the compiled step
        (its logical world changed), and the batch re-balances over the
        survivors — the step-indexed TokenPipeline keeps the *global*
        batch, so the run stays on the same data trajectory through the
        shrink."""
        with span("recovery.detect") as sp:
            cmd = self.elastic.shrink(failure)   # view+mesh+dropped ledger
            self.n_ranks = len(cmd.world)
        rep.detect_s = sp.seconds

        with span("recovery.mpi") as sp:
            self._build_step()       # mesh epoch bumped: re-lower the step
            if failure.kind is FailureType.NODE:
                self.mem_ckpt = None     # the lost node took its
                                         # buddy-held copies with it
        rep.mpi_recovery_s = sp.seconds

        # survivors roll back to their newest durable state: the buddy
        # memory copy when it survived (process shrink), else the file
        # checkpoint at the cut
        with span("recovery.restore") as sp:
            if self.mem_ckpt is not None:
                step, local, _ = self.mem_ckpt
                self.state = jax.tree.map(lambda a: a + 0, local)
                rollback_step = step
            else:
                rollback_step = self._restore_file()
            jax.block_until_ready(self.state)
        rep.ckpt_read_s = sp.seconds
        rep.rollback_step = rollback_step
        rep.world_after = self.n_ranks
        self.reports.append(rep)
        self._fire_cascades()
        return rep

    def _observe_gray(self, step: int, dt: float):
        """Per-rank gray-failure observation for the in-process driver.
        The SPMD emulation has one wall clock, so what the tracker sees
        is barrier LATENESS relative to the fastest member — healthy
        ranks observe 0.0, victims observe the injected deceleration
        delay. That is the same signal the real root reads off arrival
        spread, with the same tracker and thresholds, and it is immune
        to globally slow steps (the restore + recompile after a
        recovery inflates dt for everyone equally, which must never
        read as a straggler). With mitigate=on (and the
        elastic strategy, the only one that can re-host), a rank on a
        GRAY_DRAIN_PERSIST streak is drained: returns the FailureEvent
        that re-hosts it through the ordinary shrink path, and marks
        the fault cured — the drained rank's next incarnation (the
        grow-back) is healthy. Tolerate mode only records the flags."""
        if not self._gray:
            return None
        live = set(self.view.ranks())
        rpn = self.tc.ranks_per_node
        delays: dict[int, float] = {}
        for i, f in self._gray:
            # `step` is the post-increment count; the fault starts
            # degrading the iteration whose top is f.step
            if i in self._gray_mitigated or step <= f.step:
                continue
            if f.target == "node":
                node = f.rank // rpn
                victims = range(node * rpn, (node + 1) * rpn)
            else:
                victims = (f.rank,)
            for r in victims:
                delays[r] = delays.get(r, 0.0) + gray_delay_s(f)
        for r in sorted(live):
            self.straggler.observe(step, delays.get(r, 0.0), rank=r)
        if not (self.tc.mitigate and self.elastic is not None):
            return None
        flagged = self.straggler.stragglers(GRAY_DRAIN_PERSIST) & live
        if not flagged:
            return None
        self.straggler.reset_streaks()
        for i, f in self._gray:
            if i in self._gray_mitigated:
                continue
            if f.target == "node":
                node = f.rank // rpn
                group = set(range(node * rpn, (node + 1) * rpn)) & live
                if group and group <= flagged:
                    self._gray_mitigated.add(i)
                    return FailureEvent(kind=FailureType.NODE,
                                        node=f"node{node}", rank=f.rank,
                                        at_step=step)
            elif f.rank in flagged:
                self._gray_mitigated.add(i)
                return FailureEvent(kind=FailureType.PROCESS,
                                    rank=f.rank, at_step=step)
        return None

    def _handle_repair(self, repair) -> Optional[RecoveryReport]:
        """Grow-back in the in-process SPMD driver: a repaired node
        rejoins at a checkpoint boundary. The admission policy (the
        membership machine) re-admits the most recently dropped group —
        world re-expands, mesh epoch bumps, the step recompiles for the
        re-grown shape — or, with a full world, adds the node to the
        spare pool (no recovery, returns None)."""
        if self.elastic is None:
            return None              # non-elastic runs never shrank
        node = f"node{repair.rank // self.tc.ranks_per_node}"
        if node in self.view.children:
            return None              # node never left the world: no-op
        if self.elastic.admit(node) == "spare":
            self.elastic.grant_spare(node)
            return None
        rep = RecoveryReport(
            strategy=self.strategy.name,
            failure=FailureEvent(kind=FailureType.NODE, node=node,
                                 at_step=repair.step))
        with span("recovery.detect") as sp:
            cmd = self.elastic.grow(node)
            self.n_ranks = len(cmd.world)
        rep.detect_s = sp.seconds

        with span("recovery.mpi") as sp:
            self._build_step()       # mesh epoch bumped: re-lower the
                                     # step for the re-expanded world
        rep.mpi_recovery_s = sp.seconds

        # the re-admitted ranks restore from the durable checkpoint at
        # the consistent cut (Table-2 "grow" scheme: file tier)
        with span("recovery.restore") as sp:
            self.file_ckpt.wait()
            step, state = self.file_ckpt.load_latest()
            if step is not None:
                self.state = jax.tree.map(jnp.asarray, state)
                rep.rollback_step = step
            jax.block_until_ready(self.state)
        rep.ckpt_read_s = sp.seconds
        rep.world_after = self.n_ranks
        self.reports.append(rep)
        self._fire_cascades()
        return rep

    # ---------------------------------------------------------------- run

    def _resilient_body(self, rank_state: RankState) -> int:
        """The user-supplied restart-point function of MPI_Reinit."""
        tc = self.tc
        if rank_state is RankState.NEW and self.state is None:
            # fresh start — or resume from disk if a checkpoint exists
            step, state = self.file_ckpt.load_latest()
            self.state = self.init_state() if step is None \
                else jax.tree.map(jnp.asarray, state)
        assert self.state is not None
        hb = self.strategy.fault_free_overhead(self.n_ranks)

        step = int(self.state["step"])
        while step < tc.total_steps:
            # one span an iteration; its children tile it, so the device's
            # idle between steps falls in a named part of the host's work
            with step_span(step):
                step = self._iteration(step, hb)
        with span("train.drain"):
            self.file_ckpt.wait()
        return step

    def _iteration(self, step: int, hb: float) -> int:
        """One step of the loop from `step`; returns the step reached."""
        ROLLBACK.check()                          # safe-point (paper §3.2)
        failure = self.injector.check(step, self.view) \
            if self.injector else None
        if failure is not None:
            self._handle_failure(failure)
            raise RollbackSignal(self.view.epoch)
        repair = self.injector.check_repair(step) \
            if self.injector is not None \
            and hasattr(self.injector, "check_repair") else None
        if repair is not None and self._handle_repair(repair):
            raise RollbackSignal(self.view.epoch)

        with span("train.feed") as feed:
            batch = self.data.batch(step)
        with span("train.dispatch") as dispatch:
            self.state, (loss, self.last_metrics) = self._step(self.state,
                                                               batch)
        with span("train.wait") as wait:
            jax.block_until_ready(self.state["params"])
        dt = feed.seconds + dispatch.seconds + wait.seconds
        with span("train.readback"):
            step = int(self.state["step"])
            loss = float(loss)
        with span("train.bookkeeping"):
            self.straggler.observe(step, dt)
            drain = self._observe_gray(step, dt)
            if drain is not None:
                # drain BEFORE this step's checkpoint commits: the last
                # durable cut is the completed boundary — the same place
                # the real root withholds the barrier release
                self._handle_failure(drain)
                raise RollbackSignal(self.view.epoch)
            if self.strategy.replicates:
                # replication stream: mirror every step's state to the
                # rank's off-node shadow (Table 2 replica rows) — this,
                # not the checkpoint cadence, is what makes the later
                # promote zero-rollback
                self.shadow_ckpt = (step, jax.tree.map(lambda a: a + 0,
                                                       self.state))
            self.logs.append(StepLog(step=step, loss=loss, seconds=dt,
                                     heartbeat_overhead=hb))
            if self.tc.log_every and step % self.tc.log_every == 0:
                print(f"[{self.strategy.name}] step {step} "
                      f"loss {loss:.4f} ({dt*1e3:.1f} ms)")
        if self.policy.should_checkpoint(step):
            with span("train.save", step=step):
                self._save_ckpt(step)
        return step

    def run(self) -> dict:
        final_step = reinit_main(self._resilient_body)
        return {
            "final_step": final_step,
            "losses": [l.loss for l in self.logs],
            "reports": self.reports,
            "stragglers": self.straggler.flagged,
            "stragglers_by_rank": dict(self.straggler.flagged_by_rank),
        }
