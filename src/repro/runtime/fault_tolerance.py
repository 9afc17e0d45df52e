"""Fault-tolerance harness: checkpoint/restart, failure injection.

The supervisor wraps any step-function-driven engine (the distributed
PageRank super-step loop, or the training loop) with:

  * periodic checkpoints (sync or async),
  * simulated failures (a `FailureSchedule` raising `SimulatedFailure`
    at chosen rounds — standing in for a lost pod / preempted host),
  * restart-from-latest-checkpoint recovery. Because engine state is a pure
    pytree that includes the PRNG keys, recovery replays the *identical*
    trajectory — the recovered run is bit-exact with an uninterrupted one
    (asserted in tests).

Every round that `run_staged` or the supervisor drives is one span of
`runtime.tracing` (`round_span`): the per-round timer.

Multi-stage schedules: engines whose run is a *sequence of named phases*
with different step functions and different device buffers per phase (the
3-phase stitching engines) compose per-phase step functions with
`StageSchedule` into one supervisor-drivable step function over a
stage-tagged `StagedState`. Snapshots carry the stage tag, the stage's
device buffers, and the host-side telemetry accumulators, so a killed run
resumes mid-phase and replays the identical trajectory.

Elastic resume: a `StagedState` additionally declares, per stage, a
`checkpoint.LayoutSpec` schema describing how each device buffer is laid
out across the mesh (walk lanes / vertex shards / coupon slots /
per-shard keys / replicated — see `checkpoint/elastic.py`), plus the
shard count it was built for. `Supervisor.run(resume=True)` compares the
shard count recorded in the snapshot manifest against the live mesh and,
on mismatch, routes the restored flat dict through the schema-driven
`checkpoint.relayout_staged_flat` before `from_host` — so a run killed on
P shards resumes on P' shards (grown or shrunk), then immediately
re-snapshots on the new layout so any later crash recovers new-mesh
state.
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.checkpoint import (Checkpointer, pack_json, relayout_staged_flat,
                              unpack_json)
from repro.runtime import tracing


class SimulatedFailure(RuntimeError):
    pass


@dataclasses.dataclass
class FailureSchedule:
    """Fail at the start of each listed round (once each)."""

    fail_at_rounds: List[int]
    _fired: set = dataclasses.field(default_factory=set)

    def maybe_fail(self, round_idx: int):
        if round_idx in self.fail_at_rounds and round_idx not in self._fired:
            self._fired.add(round_idx)
            raise SimulatedFailure(f"injected failure at round {round_idx}")


@dataclasses.dataclass
class Stage:
    """One named phase of a multi-stage engine.

    `step(state) -> (state, stage_done)` runs one super-step of this phase;
    `on_done(state) -> state` is the host-side transition that rebuilds the
    device buffers for the next phase (initial placements, bitmap
    broadcasts, ...) once the phase reports done.
    """

    name: str
    step: Callable[[Any], Tuple[Any, bool]]
    on_done: Optional[Callable[[Any], Any]] = None


@dataclasses.dataclass
class StagedState:
    """Machine state threaded through a `StageSchedule`: the tag of the
    stage currently running, that stage's device buffers (a flat
    name -> array dict), and JSON-able host accumulators (round counters,
    wire volumes, per-round records). Snapshots carry all three.

    `layouts` (optional) maps stage name -> {buffer name ->
    `checkpoint.LayoutSpec`}, declaring how each stage's buffers are laid
    out across the mesh, and `shards` records the mesh size the state was
    built for; together they make snapshots mesh-size-agnostic — the
    supervisor routes a resumed snapshot onto a resized mesh through
    `checkpoint.relayout_staged_flat`. Engines that never resume
    elastically may leave both unset."""

    stage: str
    arrays: Dict[str, Any]
    host: Dict[str, Any]
    layouts: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    shards: Optional[int] = None


class StageSchedule:
    """Compose per-phase step functions into ONE supervisor-drivable step
    function over a stage-tagged `StagedState`.

    Each call runs one super-step of the current stage; when a stage
    reports done its `on_done` transition fires and the machine advances
    to the next stage in order. The composed step function returns
    done=True only when the last stage completes, so the global round
    index seen by `Supervisor` (checkpoint cadence, `FailureSchedule`
    rounds) spans all phases.
    """

    def __init__(self, stages: List[Stage]):
        if not stages:
            raise ValueError("empty stage schedule")
        names = [s.name for s in stages]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate stage names: {names}")
        self.stages = stages
        self._index = {s.name: i for i, s in enumerate(stages)}

    @property
    def first_stage(self) -> str:
        return self.stages[0].name

    def step(self, state: StagedState) -> Tuple[StagedState, bool]:
        i = self._index[state.stage]
        stage = self.stages[i]
        specs = state.layouts.get(state.stage)
        if specs is not None and set(specs) != set(state.arrays):
            # an uncovered buffer would silently vanish from elastic
            # snapshots; a spec without a buffer means the schema rotted
            missing = set(state.arrays) - set(specs)
            extra = set(specs) - set(state.arrays)
            raise ValueError(
                f"stage '{state.stage}' layout schema out of sync with its "
                f"device buffers: uncovered buffers {sorted(missing)}, "
                f"dangling specs {sorted(extra)}")
        state, stage_done = stage.step(state)
        if not stage_done:
            return state, False
        if stage.on_done is not None:
            state = stage.on_done(state)
        if i + 1 == len(self.stages):
            return state, True
        state.stage = self.stages[i + 1].name
        return state, False


def round_span(state: Any):
    """The span of one round: `round.<stage>` for a `StagedState` (named
    by the stage the round starts in), `round.step` otherwise."""
    stage = state.stage if isinstance(state, StagedState) else "step"
    return tracing.span("round." + stage)


def staged_to_host(state: StagedState) -> dict:
    """Checkpoint payload for a `StagedState`: a pure pytree of arrays —
    device buffers as-is, stage tag + host accumulators as JSON leaves."""
    return dict(arrays={k: np.asarray(v) for k, v in state.arrays.items()},
                stage=pack_json(state.stage), host=pack_json(state.host))


def staged_from_host(flat: Dict[str, np.ndarray],
                     put: Callable[[str, np.ndarray], Any],
                     like: Optional[StagedState] = None) -> StagedState:
    """Rebuild a `StagedState` from a restored flat checkpoint dict.
    `put(name, host_array) -> device array` re-establishes each buffer's
    sharding (the stage layouts are engine knowledge). `like` donates the
    layout schema and live shard count (not checkpointed — they describe
    the CURRENT mesh, which on an elastic resume differs from the one the
    snapshot was written under)."""
    arrays = {k.split("/", 1)[1]: put(k.split("/", 1)[1], v)
              for k, v in flat.items() if k.startswith("arrays/")}
    return StagedState(stage=unpack_json(flat["stage"]), arrays=arrays,
                       host=unpack_json(flat["host"]),
                       layouts=like.layouts if like is not None else {},
                       shards=like.shards if like is not None else None)


@dataclasses.dataclass
class SupervisorResult:
    state: Any
    rounds: int
    restarts: int
    checkpoints_written: int


class Supervisor:
    """Generic checkpoint-restart driver.

    step_fn(state) -> (state, done: bool)
    to_host(state) -> dict            (for checkpointing)
    from_host(dict) -> state          (for recovery)
    meta_fn() -> dict                 (manifest metadata on every save;
                                       a "shards" entry enables elastic
                                       mismatch detection on resume)
    relayout(flat, old_shards) -> flat  (re-layout a snapshot written
                                       under `old_shards` onto the live
                                       mesh; consulted only on resume
                                       when the manifest's recorded
                                       shard count differs from
                                       meta_fn()["shards"])
    """

    def __init__(self, step_fn: Callable, to_host: Callable, from_host: Callable,
                 checkpointer: Checkpointer, *, checkpoint_every: int = 10,
                 max_restarts: int = 16, async_checkpoints: bool = False,
                 failure_schedule: Optional[FailureSchedule] = None,
                 meta_fn: Optional[Callable[[], dict]] = None,
                 relayout: Optional[Callable[[dict, int], dict]] = None):
        self.step_fn = step_fn
        self.to_host = to_host
        self.from_host = from_host
        self.ckpt = checkpointer
        self.checkpoint_every = checkpoint_every
        self.max_restarts = max_restarts
        self.async_checkpoints = async_checkpoints
        self.failures = failure_schedule
        self.meta_fn = meta_fn
        self.relayout = relayout

    def _meta(self) -> dict:
        return self.meta_fn() if self.meta_fn is not None else {}

    def run(self, state: Any, *, max_rounds: int = 100_000,
            resume: bool = False) -> SupervisorResult:
        restarts = 0
        ckpts = 0
        round_idx = 0
        if resume:
            # cold start from a previous (killed) run's latest snapshot;
            # an empty dir is an error, not a silent fresh run — a typo'd
            # path must not quietly discard the resume intent
            if self.ckpt.latest_step() is None:
                raise FileNotFoundError(
                    f"resume requested but no snapshots under "
                    f"{self.ckpt.base_dir}")
            flat, manifest = self.ckpt.restore()
            round_idx = int(manifest["step"])
            old_shards = (manifest.get("metadata") or {}).get("shards")
            live_shards = self._meta().get("shards")
            if (old_shards is not None and live_shards is not None
                    and int(old_shards) != int(live_shards)):
                if self.relayout is None:
                    raise ValueError(
                        f"snapshot under {self.ckpt.base_dir} was written "
                        f"at {old_shards} shards but the live mesh has "
                        f"{live_shards} and no relayout hook is configured")
                flat = self.relayout(flat, int(old_shards))
                state = self.from_host(flat)
                # re-anchor immediately: if we crash after this point,
                # recovery must restore NEW-mesh state, not the old layout
                self.ckpt.save(round_idx, self.to_host(state),
                               metadata=self._meta(), blocking=True)
                ckpts += 1
            else:
                state = self.from_host(flat)
        else:
            # fresh run: refuse a directory that already holds snapshots —
            # recovery must never restore foreign state, and silently
            # wiping them would destroy another run's recovery points
            if self.ckpt.latest_step() is not None:
                raise FileExistsError(
                    f"{self.ckpt.base_dir} already holds snapshots; pass "
                    f"resume=True to continue that run, or clear the "
                    f"directory (Checkpointer.clear()) to start fresh")
            # round-0 checkpoint so recovery is always possible
            self.ckpt.save(0, self.to_host(state), metadata=self._meta(),
                           blocking=True)
            ckpts += 1
        while round_idx < max_rounds:
            try:
                if self.failures is not None:
                    self.failures.maybe_fail(round_idx)
                with round_span(state):
                    state, done = self.step_fn(state)
                round_idx += 1
            except SimulatedFailure:
                restarts += 1
                if restarts > self.max_restarts:
                    raise
                flat, manifest = self.ckpt.restore()
                state = self.from_host(flat)
                round_idx = int(manifest["step"])
                continue
            # always snapshot on `done` — a run finishing between periodic
            # intervals must still leave the directory reflecting its
            # final state (blocking: nothing overlaps a finished run)
            if done or round_idx % self.checkpoint_every == 0:
                self.ckpt.save(round_idx, self.to_host(state),
                               metadata=self._meta(),
                               blocking=done or not self.async_checkpoints)
                ckpts += 1
            if done:
                break
        self.ckpt.wait()
        return SupervisorResult(state=state, rounds=round_idx, restarts=restarts,
                                checkpoints_written=ckpts)


def run_staged(schedule: StageSchedule, state: StagedState,
               put: Callable[[str, np.ndarray], Any], *,
               checkpoint_dir: Optional[str] = None,
               fail_at: Optional[Sequence[int]] = None,
               checkpoint_every: int = 10, max_restarts: int = 16,
               resume: bool = False, max_rounds: int = 100_000,
               tmp_prefix: str = "staged_ckpt_") -> Tuple[StagedState, int,
                                                          int]:
    """Drive a `StageSchedule` to completion: plain loop when no fault
    tolerance is requested, otherwise under the checkpoint-restart
    `Supervisor` with stage-tagged `staged_to_host` snapshots.

    `put(name, host_array)` re-establishes per-buffer sharding on restore.
    When `state` declares `shards` + `layouts`, snapshots record the mesh
    size and `resume=True` from a snapshot written at a DIFFERENT shard
    count re-layouts it onto the live mesh (see `checkpoint/elastic.py`).
    Returns (final state, restarts, checkpoints_written)."""
    if resume and checkpoint_dir is None:
        raise ValueError("resume=True needs checkpoint_dir (there is no "
                         "snapshot to cold-start from)")
    if checkpoint_dir is None and not fail_at:
        rounds = 0
        done = False
        while not done and rounds < max_rounds:   # same bound as Supervisor
            with round_span(state):
                state, done = schedule.step(state)
            rounds += 1
        return state, 0, 0
    # fail_at without a caller dir: snapshots go to a private temp dir the
    # caller has no handle to, so remove it once the run is over
    tmp_dir = tempfile.mkdtemp(prefix=tmp_prefix) \
        if checkpoint_dir is None else None
    meta_fn = ((lambda: dict(shards=int(state.shards)))
               if state.shards is not None else None)
    relayout = None
    if state.shards is not None and state.layouts:
        live_shards, layouts = int(state.shards), state.layouts
        relayout = (lambda flat, old_shards: relayout_staged_flat(
            flat, old_shards, live_shards, layouts))
    try:
        sup = Supervisor(
            schedule.step, staged_to_host,
            lambda flat: staged_from_host(flat, put, like=state),
            Checkpointer(checkpoint_dir or tmp_dir),
            checkpoint_every=checkpoint_every, max_restarts=max_restarts,
            failure_schedule=FailureSchedule(list(fail_at)) if fail_at
            else None, meta_fn=meta_fn, relayout=relayout)
        res = sup.run(state, max_rounds=max_rounds, resume=resume)
    finally:
        if tmp_dir is not None:
            shutil.rmtree(tmp_dir, ignore_errors=True)
    return res.state, res.restarts, res.checkpoints_written
