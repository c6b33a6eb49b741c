"""Training supervisor: checkpoint/restart fault tolerance, preemption
handling, straggler watchdog, elastic rescale.

On a real multi-pod deployment each host runs this loop; failure detection
is jax.distributed heartbeats + the coordinator restarting the job, and the
elastic path re-slices the (host-complete) checkpoint onto the surviving
mesh.  In this container the same code paths are exercised with injected
failures (tests/test_runtime.py): the supervisor catches step exceptions,
restores the latest atomic checkpoint, rebuilds the step function, and
continues — bit-exact with an uninterrupted run.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import signal
import time
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

from repro.ckpt import checkpoint as ckpt

log = logging.getLogger("repro.supervisor")


@dataclasses.dataclass(frozen=True)
class WatchdogEvent:
    """Structured straggler-watchdog emission: consumable by the serving
    engine's pressure policy as well as the training supervisor (one code
    path for both — ISSUE 6 satellite).

    ``kind`` is ``"straggler"`` (flagged, below patience) or ``"hung"``
    (``consecutive`` flags reached patience — the caller should act:
    supervisor raises, engine preempts-with-spill).

    The event carries the step's duration only.  Where the serve engine
    spent a flagged step is in its program spans
    (``repro.runtime.spans``): ``recorded(t - dt, t)`` over the step holds
    ``engine.step`` and the phases nested in it (``engine.overlap_host``,
    ``engine.collect``, ``engine.schedule_dispatch`` on the async
    scheduler; admission, seal and dispatch spans on both)."""
    kind: str
    dt: float
    ema: float
    consecutive: int


class StragglerWatchdog:
    """Step-time watchdog shared by ``Supervisor`` and
    ``serve.ServeEngine``: a step slower than ``ratio`` × the trailing
    ``window``-step *median* is flagged; ``patience`` consecutive flags
    escalate to a ``hung`` event.  The baseline is a median, not a
    mean: jit-bucket growth (prefill buckets, per-job page-count
    buckets) legitimately drops a multi-second compile into an
    otherwise-millisecond step stream, and one such spike in a mean
    window would inflate the threshold enough to mask a genuinely hung
    step for the next ``window`` steps.  Policy (raise / preempt /
    re-mesh) stays with the caller — this class only observes and
    emits."""

    def __init__(self, ratio: float = 5.0, patience: int = 3,
                 window: int = 8, on_event=None):
        self.ratio = ratio
        self.patience = patience
        self.window = window
        self.on_event = on_event
        self.step_times: list[float] = []
        self.events = 0                      # consecutive flagged steps
        self.event_log: list[WatchdogEvent] = []

    def observe(self, dt: float) -> WatchdogEvent | None:
        ev = None
        if len(self.step_times) >= self.window:
            ema = float(np.median(self.step_times[-self.window:]))
            if dt > self.ratio * max(ema, 1e-6):
                self.events += 1
                kind = "hung" if self.events >= self.patience \
                    else "straggler"
                ev = WatchdogEvent(kind=kind, dt=dt, ema=ema,
                                   consecutive=self.events)
            else:
                self.events = 0
        self.step_times.append(dt)
        if ev is not None:
            self.event_log.append(ev)
            if self.on_event is not None:
                self.on_event(ev)
        return ev

    def reset(self) -> None:
        self.events = 0


@dataclasses.dataclass
class SupervisorConfig:
    ckpt_dir: str
    save_every: int = 100
    max_steps: int = 1000
    keep: int = 3
    compress_ckpt: bool = False
    max_restarts: int = 10
    # straggler watchdog: a step slower than ratio*EMA is flagged; after
    # ``straggler_patience`` consecutive flags the step is treated as hung
    # (on a cluster: trigger backup workers / re-mesh; here: raise).
    straggler_ratio: float = 5.0
    straggler_patience: int = 3
    async_save: bool = True


class Supervisor:
    def __init__(self, cfg: SupervisorConfig, *,
                 make_state: Callable[[], tuple[Any, dict]],
                 step_fn: Callable[[Any, dict], tuple[Any, dict]],
                 data_state: Callable[[], dict] | None = None,
                 restore_data: Callable[[dict], None] | None = None,
                 on_watchdog_event: Callable[[WatchdogEvent], None]
                 | None = None):
        """Args:
          make_state: () -> (train_state, extra) fresh initialization.
          step_fn: (train_state, step_idx) -> (train_state, metrics).
          data_state / restore_data: data-pipeline cursor hooks.
          on_watchdog_event: structured straggler/hung event sink.
        """
        self.cfg = cfg
        self.make_state = make_state
        self.step_fn = step_fn
        self.data_state = data_state or (lambda: {})
        self.restore_data = restore_data or (lambda s: None)
        self.preempted = False
        self.restarts = 0
        self.watchdog = StragglerWatchdog(ratio=cfg.straggler_ratio,
                                          patience=cfg.straggler_patience,
                                          on_event=on_watchdog_event)
        self._saver = ckpt.AsyncCheckpointer(cfg.ckpt_dir,
                                             compress=cfg.compress_ckpt,
                                             keep=cfg.keep)

    # back-compat views onto the shared watchdog (tests/callers pin these)
    @property
    def step_times(self) -> list[float]:
        return self.watchdog.step_times

    @property
    def straggler_events(self) -> int:
        return self.watchdog.events

    @straggler_events.setter
    def straggler_events(self, v: int) -> None:
        self.watchdog.events = v

    def _install_signal_handler(self):
        def handler(signum, frame):
            log.warning("preemption signal %s received", signum)
            self.preempted = True
        try:
            signal.signal(signal.SIGTERM, handler)
            signal.signal(signal.SIGUSR1, handler)
        except ValueError:
            pass                                   # non-main thread (tests)

    def _resume_or_init(self):
        latest = ckpt.latest_step(self.cfg.ckpt_dir)
        if latest is not None:
            state, extra, step = ckpt.restore(self.cfg.ckpt_dir)
            self.restore_data(extra.get("data", {}))
            log.info("restored step %d from %s", step, self.cfg.ckpt_dir)
            return state, step
        state, extra = self.make_state()
        return state, 0

    def _watchdog(self, dt: float) -> None:
        ev = self.watchdog.observe(dt)
        if ev is not None:
            log.warning("straggler step: %.3fs vs EMA %.3fs "
                        "(%d consecutive)", ev.dt, ev.ema, ev.consecutive)
            if ev.kind == "hung":
                raise TimeoutError(
                    "persistent straggler — on a cluster this triggers "
                    "backup-worker promotion / re-meshing")

    def _save(self, step: int, state: Any) -> None:
        extra = {"data": self.data_state(), "wall_time": time.time()}
        if self.cfg.async_save:
            self._saver.save(step, state, extra)
        else:
            ckpt.save(self.cfg.ckpt_dir, step, state, extra,
                      compress=self.cfg.compress_ckpt, keep=self.cfg.keep)

    def run(self) -> tuple[Any, list[dict]]:
        """Run to max_steps with restart-on-failure.  Returns (state, log)."""
        self._install_signal_handler()
        history: list[dict] = []
        state, step = self._resume_or_init()
        while step < self.cfg.max_steps and not self.preempted:
            t0 = time.time()
            try:
                state, metrics = self.step_fn(state, step)
            except (TimeoutError, RuntimeError, ValueError, FloatingPointError) as e:
                self.restarts += 1
                log.error("step %d failed (%s); restart %d/%d", step, e,
                          self.restarts, self.cfg.max_restarts)
                if self.restarts > self.cfg.max_restarts:
                    raise
                self._saver.wait()
                state, step = self._resume_or_init()
                self.straggler_events = 0
                continue
            dt = time.time() - t0
            self._watchdog(dt)
            step += 1
            metrics = dict(metrics)
            metrics.update(step=step, dt=dt)
            history.append(metrics)
            if step % self.cfg.save_every == 0 or step == self.cfg.max_steps:
                self._save(step, state)
        if self.preempted:
            self._save(step, state)
        self._saver.wait()
        return state, history
