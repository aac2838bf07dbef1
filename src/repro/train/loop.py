"""Trainer: the fault-tolerant outer loop.

Responsibilities (the parts XLA cannot do):
  * checkpoint/restart — periodic async sharded checkpoints; on any step
    failure, reload the last checkpoint and replay the data stream from
    the same batch index (the pipeline is index-deterministic);
  * straggler mitigation — per-step wall time tracked against a running
    median; a step slower than `straggler_threshold x median` is logged
    and counted; persistent stragglers trigger an elastic re-mesh
    request (launch/elastic.py decides);
  * bounded retry — `max_retries` consecutive failures abort the job
    rather than loop forever.  Only a failure of the running step is
    retried: the step is compiled before the retry loop, so a program
    that does not compile raises at once, and so does any failure that
    reports RESOURCE_EXHAUSTED (a step that does not fit the device
    will not fit on a retry either).

Each step is a `trainer.step` span in the profiler's trace (a step
marker), over `trainer.batch`, `trainer.dispatch`, `trainer.loss.wait`
(the one place the loop blocks on the device) and `trainer.record`
(repro.obs.spans).
"""
from __future__ import annotations

import logging
import statistics
from repro.tune.timer import now

import jax
import numpy as np

from repro.checkpoint import store
from repro.obs.spans import span
from repro.optim import adamw
from repro.train.step import build_train_step

log = logging.getLogger("repro.train")


class StragglerMonitor:
    def __init__(self, threshold: float = 3.0, window: int = 50):
        self.threshold = threshold
        self.times: list[float] = []
        self.window = window
        self.flagged = 0

    def record(self, dt: float) -> bool:
        """Returns True if this step is a straggler."""
        is_straggler = False
        if len(self.times) >= 5:
            med = statistics.median(self.times[-self.window:])
            is_straggler = dt > self.threshold * med
        self.times.append(dt)
        if is_straggler:
            self.flagged += 1
        return is_straggler

    @property
    def needs_remesh(self) -> bool:
        # persistent stragglers: >10% of recent steps flagged
        recent = min(len(self.times), self.window)
        return recent >= 20 and self.flagged > 0.1 * recent


class Trainer:
    def __init__(self, cfg, train_cfg, params, data_it, *,
                 step_fn=None, checkpoint_tree_extra=None,
                 max_retries: int = 3):
        self.cfg = cfg
        self.tc = train_cfg
        self.params = params
        self.opt_state = adamw.init(params)
        self.data_it = data_it
        self.step_fn = step_fn or jax.jit(build_train_step(cfg, train_cfg))
        self.monitor = StragglerMonitor(train_cfg.straggler_threshold)
        self.max_retries = max_retries
        self.step_idx = 0
        self.history: list[dict] = []
        self._pending_save = None
        self._executables: dict = {}
        self.compile_s = 0.0
        self.compiled_memory = None  # memory_analysis() of the last compile

    # -- checkpointing ------------------------------------------------
    def _tree(self):
        return {"params": self.params, "opt": self.opt_state}

    def save(self, blocking: bool = False):
        self._wait_save()
        out = store.save(self.tc.checkpoint_dir, self._tree(),
                         self.step_idx, blocking=blocking)
        if not blocking:
            self._pending_save = out[1]

    def _wait_save(self):
        if self._pending_save is not None:
            self._pending_save.join()
            self._pending_save = None

    def try_restore(self) -> bool:
        step = store.latest_step(self.tc.checkpoint_dir)
        if step is None:
            return False
        tree, step = store.restore(self.tc.checkpoint_dir, self._tree(),
                                   step)
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.step_idx = step
        log.info("restored checkpoint at step %d", step)
        return True

    # -- the loop -----------------------------------------------------
    def _executable(self, batch, step_idx):
        """The step compiled for this batch's shapes (a step_fn without
        an ahead-of-time `lower` is returned as it is)."""
        if not hasattr(self.step_fn, "lower"):
            return self.step_fn
        key = tuple(sorted((k, v.shape, str(v.dtype))
                           for k, v in batch.items()))
        if key not in self._executables:
            t0 = now()
            exe = self.step_fn.lower(self.params, self.opt_state, batch,
                                     step_idx).compile()
            self.compile_s += now() - t0
            self.compiled_memory = exe.memory_analysis()
            self._executables[key] = exe
        return self._executables[key]

    def run(self, num_steps: int, fail_injector=None):
        """Train for num_steps (from the current step_idx)."""
        retries = 0
        target = self.step_idx + num_steps
        while self.step_idx < target:
            with span("trainer.step", step_num=self.step_idx):
                with span("trainer.batch"):
                    batch_np = self.data_it.batch_at(self.step_idx)
                    batch = {"tokens": batch_np} \
                        if isinstance(batch_np, np.ndarray) else batch_np
                step_fn = self._executable(batch, self.step_idx)
                t0 = now()
                try:
                    if fail_injector is not None:
                        fail_injector(self.step_idx)
                    with span("trainer.dispatch"):
                        out = step_fn(self.params, self.opt_state, batch,
                                      self.step_idx)
                    params, opt_state, metrics = out
                    with span("trainer.loss.wait"):
                        loss = float(metrics["loss"])
                    if not np.isfinite(loss):
                        raise FloatingPointError(
                            f"non-finite loss {loss} at step {self.step_idx}")
                    self.params, self.opt_state = params, opt_state
                except Exception as e:  # noqa: BLE001 — node/step failure
                    if "RESOURCE_EXHAUSTED" in str(e):
                        raise
                    retries += 1
                    log.warning("step %d failed (%s); retry %d/%d from "
                                "last checkpoint", self.step_idx, e,
                                retries, self.max_retries)
                    if retries > self.max_retries:
                        raise
                    if not self.try_restore():
                        # no checkpoint yet: retry the same step fresh
                        continue
                    continue
                retries = 0
                with span("trainer.record"):
                    dt = now() - t0
                    slow = self.monitor.record(dt)
                    rec = {"step": self.step_idx, "loss": loss, "dt": dt,
                           "straggler": slow}
                    self.history.append(rec)
                    self.step_idx += 1
                    if self.tc.checkpoint_every and \
                            self.step_idx % self.tc.checkpoint_every == 0:
                        self.save(blocking=False)
        self._wait_save()
        return self.history
