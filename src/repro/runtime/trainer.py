"""Fault-tolerant training loop.

* deterministic resumability: the data order is a pure function of
  (seed, epoch, step), so restoring {params, opt, epoch, step} from the
  newest committed checkpoint reproduces the exact remaining schedule;
* write-behind checkpointing through the foreactor-backed
  CheckpointManager: the save is one speculated write graph (staged
  creates, pre-issued extent writes, commit marker published last) running
  on a background thread, so checkpoint I/O overlaps step compute and the
  trainer only blocks when a save is still in flight at the next
  checkpoint boundary (``ckpt_wait_s`` in the fit() summary measures
  exactly that residual stall — ``write_behind=False`` degrades to
  synchronous saves for comparison);
* straggler watch: a per-step wall-time EMA; steps slower than
  ``straggler_factor x`` EMA are recorded (and, on a real cluster, would
  feed the coordinator's slow-host eviction);
* crash safety: any exception triggers a synchronous emergency save of
  the last good state before re-raising; if that save fails, a
  ``CheckpointError`` is raised in its place;
* placement: the train state lives on the mesh under the
  ``launch/sharding.py`` rules (FSDP over "data"), each batch is put on
  its ``batch_specs`` shardings, and a restore lands straight on those
  shardings;
* elastic resume: ``Trainer.fit`` can be re-entered with a different mesh
  (fewer/more hosts) — checkpoints are mesh-agnostic (full arrays +
  named leaves), so the step function is simply re-lowered.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import CheckpointError, CheckpointManager, CheckpointPolicy
from repro.data.pipeline import TokenBatchLoader
from repro.launch import sharding as shd
from repro.launch.steps import make_train_step, make_train_state
from repro.models.api import Model
from repro.optim.adamw import AdamWConfig
from repro.spans import span, step_span


@dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    seed: int = 0
    straggler_factor: float = 3.0
    restore: bool = True
    #: overlap checkpoint saves with step compute (save_async); False runs
    #: every save synchronously on the training thread (the serial
    #: baseline)
    write_behind: bool = True
    #: retention policy installed on the CheckpointManager at fit() time
    #: (None keeps whatever the manager was built with); every save is
    #: followed by a GC pass collecting steps outside the policy's keep-set
    retention: Optional[CheckpointPolicy] = None
    #: delta cadence: number of delta (incremental) saves between full
    #: saves.  0 = every save full; k writes k deltas then one full, so a
    #: restore chains at most k+1 checkpoints.
    delta_every: int = 0


@dataclass
class StepEvent:
    step: int
    seconds: float
    loss: float
    straggler: bool


class Trainer:
    def __init__(self, model: Model, opt_cfg: AdamWConfig,
                 loader: TokenBatchLoader, ckpt: Optional[CheckpointManager],
                 mesh, tcfg: TrainerConfig = TrainerConfig(),
                 batch_extras: Optional[Callable[[Dict], Dict]] = None):
        self.model = model
        self.opt_cfg = opt_cfg
        self.loader = loader
        self.ckpt = ckpt
        self.mesh = mesh
        self.tcfg = tcfg
        self.batch_extras = batch_extras
        self.events: List[StepEvent] = []
        self.stragglers: List[int] = []
        self.ckpt_wait_s = 0.0  # training-thread time lost to checkpoint I/O
        self.ckpt_saves = 0
        self.step = 0                               # next step to run
        self.restored_step: Optional[int] = None   # checkpoint resumed from
        self.restore_s: Optional[float] = None
        #: ``time.perf_counter()`` when the first step of fit() completed
        self.first_step_at: Optional[float] = None
        self.emergency_step: Optional[int] = None
        self.emergency_save_s: Optional[float] = None
        # delta cadence state: primed so the very first save is a full one
        self._saves_since_full = tcfg.delta_every

    def _next_delta(self) -> bool:
        """True iff the next periodic save should be incremental: the
        cadence writes ``delta_every`` deltas between full saves (the
        emergency save is always full — the crash path should not depend
        on chain state)."""
        if self.tcfg.delta_every <= 0:
            return False
        if self._saves_since_full >= self.tcfg.delta_every:
            self._saves_since_full = 0
            return False
        self._saves_since_full += 1
        return True

    # -- placement ---------------------------------------------------------
    def _state_shardings(self, like: Any) -> Any:
        """Train-state shardings on the trainer's mesh: params by
        ``param_specs``, moments and master copy mirroring them (fully
        replicated on a one-device mesh)."""
        pspecs = shd.param_specs(like["params"], self.mesh)
        specs = {"params": pspecs,
                 "opt": shd.opt_state_specs(like["opt"], pspecs, self.mesh)}
        return shd.named(specs, self.mesh)

    def _place_batch(self, batch: Dict) -> Dict:
        return jax.device_put(
            batch, shd.named(shd.batch_specs(batch, self.mesh), self.mesh))

    # -- step construction -------------------------------------------------
    def _jit_step(self, state_sh):
        step = make_train_step(self.model, self.opt_cfg)
        metrics_sh = NamedSharding(self.mesh, PartitionSpec())
        return jax.jit(step, out_shardings=(state_sh, metrics_sh),
                       donate_argnums=(0,))

    def _init_or_restore(self):
        like = jax.eval_shape(
            lambda r: make_train_state(self.model, self.opt_cfg, r),
            jax.random.PRNGKey(self.tcfg.seed))
        state_sh = self._state_shardings(like)
        state = None
        start_epoch, start_step = 0, 0
        if self.ckpt is not None and self.tcfg.restore:
            t0 = time.perf_counter()
            with span("trainer.restore") as sp:
                out = self.ckpt.restore_latest(like=like)
                if out is not None:
                    ckpt_step, tree, extra = out
                    with span("trainer.place", step=ckpt_step):
                        # host arrays straight onto their shardings: no copy
                        # lands whole on one device first
                        state = jax.block_until_ready(
                            jax.device_put(tree, state_sh))
                    if sp.is_enabled():
                        sp.set_metadata(step=ckpt_step, bytes=sum(
                            x.nbytes for x in jax.tree.leaves(tree)))
            if out is not None:
                self.restored_step = ckpt_step
                self.restore_s = time.perf_counter() - t0
                start_epoch = int(extra.get("epoch", 0))
                start_step = int(extra.get("step", ckpt_step))
                print(f"[trainer] restored step {ckpt_step} "
                      f"-> resuming at (epoch {start_epoch}, step {start_step})")
        if state is None:
            state = jax.jit(
                lambda r: make_train_state(self.model, self.opt_cfg, r),
                out_shardings=state_sh)(jax.random.PRNGKey(self.tcfg.seed))
        return state, state_sh, start_epoch, start_step

    def _emergency_save(self, step: int, epoch: int, state: Any) -> None:
        """Synchronous save of the last good state; a failure raises (the
        caller is already unwinding, so the original error is its context)."""
        t0 = time.perf_counter()
        try:
            self.ckpt.wait_pending()
            self.ckpt.save(step, state, extra={"epoch": epoch, "step": step,
                                               "emergency": True})
        except BaseException as e:
            raise CheckpointError(
                f"emergency save at step {step} failed: {e!r}") from e
        self.emergency_step = step
        self.emergency_save_s = time.perf_counter() - t0
        print(f"[trainer] emergency checkpoint at step {step}")

    def summary(self) -> Dict[str, Any]:
        """What this trainer has done so far (also readable after a failed
        ``fit``)."""
        return {
            "losses": [ev.loss for ev in self.events],
            "final_step": self.step,
            "restored_step": self.restored_step,
            "restore_s": self.restore_s,
            "first_step_at": self.first_step_at,
            "stragglers": self.stragglers,
            "ckpt_wait_s": self.ckpt_wait_s,
            "ckpt_saves": self.ckpt_saves,
            "emergency_step": self.emergency_step,
            "emergency_save_s": self.emergency_save_s,
            "mean_step_s": float(np.mean([ev.seconds for ev in self.events[1:]]))
            if len(self.events) > 1 else None,
        }

    def _periodic_save(self, step: int, state: Any) -> None:
        extra = {"epoch": step // self.loader.steps_per_epoch, "step": step}
        t0 = time.perf_counter()
        delta = self._next_delta()
        if self.tcfg.write_behind:
            # blocks only while a previous save is still in flight; the
            # write graph runs behind compute
            self.ckpt.save_async(step, state, extra=extra, delta=delta)
        else:
            self.ckpt.save(step, state, extra=extra, delta=delta)
        self.ckpt_wait_s += time.perf_counter() - t0
        self.ckpt_saves += 1

    # -- the loop ------------------------------------------------------------
    def fit(self) -> Dict[str, Any]:
        if self.ckpt is not None and self.tcfg.retention is not None:
            self.ckpt.policy = self.tcfg.retention
        with jax.set_mesh(self.mesh):
            state, state_sh, epoch, step0 = self._init_or_restore()
            step_fn = self._jit_step(state_sh)
            spe = self.loader.steps_per_epoch
            ema = None
            global_step = self.step = step0
            saved_step = None
            try:
                while global_step < self.tcfg.steps:
                    with step_span("trainer.step", global_step):
                        e, s = divmod(global_step, spe)
                        with span("trainer.load", step=global_step):
                            batch = self.loader.load(e, s)
                        if self.batch_extras is not None:
                            batch = self.batch_extras(batch)
                        t0 = time.perf_counter()
                        with span("trainer.put", step=global_step):
                            batch = self._place_batch(batch)
                        with span("trainer.compute", step=global_step) as sp:
                            state, metrics = step_fn(state, batch)
                            jax.block_until_ready((state, metrics))
                            if sp.is_enabled() and "moe_assigned" in metrics:
                                sp.set_metadata(
                                    moe_assigned=int(metrics["moe_assigned"]),
                                    moe_kept=int(metrics["moe_kept"]))
                        dt = time.perf_counter() - t0
                        loss = float(metrics["loss"])
                        if self.first_step_at is None:
                            self.first_step_at = time.perf_counter()
                        straggler = ema is not None and dt > self.tcfg.straggler_factor * ema
                        ema = dt if ema is None else 0.9 * ema + 0.1 * dt
                        self.events.append(StepEvent(global_step, dt, loss, straggler))
                        if straggler:
                            self.stragglers.append(global_step)
                            print(f"[trainer] STRAGGLER step {global_step}: "
                                  f"{dt:.3f}s vs ema {ema:.3f}s")
                        if self.tcfg.log_every and global_step % self.tcfg.log_every == 0:
                            print(f"[trainer] step {global_step:5d} loss {loss:.4f} "
                                  f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")
                        global_step = self.step = global_step + 1
                        if self.ckpt is not None and self.tcfg.ckpt_every \
                                and global_step % self.tcfg.ckpt_every == 0:
                            with span("trainer.save", step=global_step):
                                self._periodic_save(global_step, state)
                            saved_step = global_step
            except BaseException:
                if self.ckpt is not None:
                    self._emergency_save(global_step, epoch, state)
                raise
            if self.ckpt is not None:
                t0 = time.perf_counter()
                self.ckpt.wait_pending()
                # the final state is already committed when the last step
                # ended on a periodic save, or when a resume ran no step
                if global_step not in (self.restored_step, saved_step):
                    self.ckpt.save(global_step, state,
                                   extra={"epoch": epoch, "step": global_step},
                                   delta=self._next_delta())
                    self.ckpt_saves += 1
                self.ckpt_wait_s += time.perf_counter() - t0
            return dict(self.summary(), state=state)
