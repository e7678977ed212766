"""Fault-tolerant mesh training: the training twin of the serving
resilience layer (PR 6), built from three coupled pieces.

1. **Checkpointing** — a :class:`~paddle_tpu.checkpoint.CheckpointManager`
   snapshots the FULL train state (params, optimizer state including the
   per-replica ZeRO-1 ``(dp, k)`` slices, loss scale, RNG key, dataloader
   cursor) asynchronously: the device->host copy rides the step thread,
   serialization + fsync + the atomic commit ride the writer thread.
2. **Watchdog + warm recovery** — every step is fenced on a recovery
   epoch and (optionally) watched by the PR 6 ``CommWatchdog``; a hung or
   dead step triggers :meth:`MeshTrainer.recover`: epoch bump FIRST (the
   stuck step wakes into the new epoch and raises
   :class:`TrainStepSuperseded` without touching restored state), a
   flight dump naming the stuck span plus the step program's collective
   census, then a WARM restart — the compiled shard_map program survives,
   only the state values reload from the last committed checkpoint.
3. **The fit() retry loop** — bounded recoveries with capped exponential
   backoff resume training; with the RNG key and data cursor restored
   exactly, the replayed losses are BIT-IDENTICAL to an uninterrupted run
   (the ``analysis/faultinject.py`` ``mesh.step`` drills in
   tests/test_mesh_spmd.py pin this).

Restore is ELASTIC: a checkpoint saved at dp=8 resumes on a dp=4 mesh —
the manager gathers the saved replica rows into the logical flat vector
and the trainer re-slices it onto the CURRENT degree (loss-parity
continuation, not bit-identity: the reduction order changes).

See docs/distributed.md (recovery section) and docs/checkpoint.md.
"""
from __future__ import annotations

import collections
import os
import threading
import time

import numpy as np

import jax

from ..analysis import faultinject as _fi
from ..checkpoint import CheckpointError, CheckpointManager
from ..framework import random as rng
from .parallelize import parallelize

__all__ = ["MeshTrainer", "TrainStepSuperseded"]


class TrainStepSuperseded(RuntimeError):
    """A recovery superseded this train step while it was stuck: the step
    woke into a NEW epoch and must not touch the restored state."""


_MON = None


def _mon():
    global _MON
    if _MON is None:
        from .. import monitor as _m

        _MON = (_m, _m.counter("paddle_tpu_train_recoveries_total"))
    return _MON


def _prod(shape):
    return int(np.prod(shape)) if tuple(shape) else 1


class MeshTrainer:
    """Drive a :class:`~paddle_tpu.mesh.MeshParallel` step with
    checkpointing, hang detection and drilled warm recovery.

    ``checkpoint`` is a :class:`CheckpointManager`, a directory path, or
    None (no persistence — recovery then has no restore target and step
    failures propagate). ``hang_timeout`` arms a ``CommWatchdog`` whose
    scanner recovers a step stuck longer than that many seconds.
    """

    def __init__(self, model, optimizer, loss_fn, batch, *, mesh=None,
                 config=None, checkpoint=None, keep=3, hang_timeout=None,
                 max_recoveries=3, backoff_s=0.05, backoff_cap_s=2.0,
                 loss_scale=None):
        self.handle = parallelize(model, optimizer, loss_fn, batch,
                                  mesh=mesh, config=config)
        if isinstance(checkpoint, CheckpointManager) or checkpoint is None:
            self.manager = checkpoint
            self._own_manager = False
        else:
            self.manager = CheckpointManager(checkpoint, keep=keep)
            self._own_manager = True
        self.max_recoveries = int(max_recoveries)
        self.backoff_s = float(backoff_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.loss_scale = loss_scale
        self.step_idx = 0
        self.losses = {}                 # step -> float; replays overwrite
        self._epoch = 0                  # bumped by every recover()
        self._recover_lock = threading.Lock()
        self.recovery_stats = collections.deque(maxlen=256)
        self.last_recovery_dump = None
        self._cursor_loader = None
        self._last_batch = None
        self._dog = None
        if hang_timeout is not None:
            from ..distributed.watchdog import CommWatchdog

            self._dog = CommWatchdog(timeout=float(hang_timeout),
                                     on_timeout=self._on_hang)
        # graftscope: the trainer (and its checkpoint manager) is a
        # /statusz section, held via WeakMethod; close() unregisters
        from ..monitor import server as _obs

        _obs.register_status_provider("trainer", self.status)

    # -- the fenced step -----------------------------------------------------
    def train_step(self, *batch):
        """One mesh train step, fenced on the recovery epoch and fire
        site of the ``mesh.step`` fault point (raise = kill drill, delay
        = hang drill). Returns the global-batch loss as a python float
        (the host force doubles as the blocking section the watchdog
        observes)."""
        return self._run_step(batch, record=False)

    def _run_step(self, batch, record):
        self._last_batch = batch
        epoch = self._epoch
        # the first call compiles, and a compile is not a hang: it can
        # outlast any timeout sized for a step, so it runs unwatched
        if self._dog is not None and self.handle._jitted._cache_size():
            with self._dog.watch(f"mesh.step[{self.step_idx}]"):
                val = self._step_body(epoch, batch)
        else:
            val = self._step_body(epoch, batch)
        # completion fence: a step finishing JUST past the hang timeout
        # races the scanner's recover(). The recover lock serializes
        # them — if this thread takes it first, the recovery's
        # non-blocking acquire loses (the "hang" resolved itself, no
        # recovery runs) and the completed step's bookkeeping lands
        # atomically; if the recovery owns it, we block until its epoch
        # bump + rewind are done and supersede cleanly.
        self._recover_lock.acquire()
        try:
            if epoch != self._epoch:
                raise TrainStepSuperseded(
                    f"step {self.step_idx} superseded by recovery "
                    f"mid-flight (epoch {epoch} -> {self._epoch})")
            if record:
                self.losses[self.step_idx] = val
                self.step_idx += 1
        finally:
            self._recover_lock.release()
        return val

    def _step_body(self, epoch, batch):
        # the drillable hazard, INSIDE the watched section (a delay here
        # is what the watchdog scanner observes as a hang) and BEFORE any
        # state is touched, so a stuck step that wakes into a new epoch
        # has nothing to undo
        _fi.fire("mesh.step")
        if epoch != self._epoch:
            raise TrainStepSuperseded(
                f"step {self.step_idx} superseded by recovery "
                f"(epoch {epoch} -> {self._epoch})")
        return float(np.asarray(
            jax.device_get(self.handle.step(*batch).value)))

    # -- checkpoint save/restore ---------------------------------------------
    def _snapshot(self):
        """Assemble the full train-state snapshot: replicated tensors in
        ``arrays``, per-replica ZeRO rows (with their true numel) in
        ``zero``, everything JSON-able in ``meta``."""
        h = self.handle
        mh = h.meta
        arrays, zero = {}, {}
        for n, v in zip(h.param_names, h._pv):
            arrays[f"param/{n}"] = v
        for n, p, ks, row, sh in zip(h.param_names, h.params, h._acc_keys,
                                     h._av, mh["acc_sharded"]):
            numel = _prod(p.shape)
            for k, v, s in zip(ks, row, sh):
                if s:
                    zero[f"acc/{n}/{k}"] = (v, numel)
                else:
                    arrays[f"acc/{n}/{k}"] = v
        if mh["use_masters"]:
            for n, p, v in zip(h.param_names, h.params, h._mv):
                if mh["shard_optimizer"]:
                    zero[f"master/{n}"] = (v, _prod(p.shape))
                else:
                    arrays[f"master/{n}"] = v
        if h._rv is not None:
            # error-feedback residuals are PART OF TRAIN STATE: dropping
            # them on restore would replay the quantization error twice
            # (once lost, once re-applied) and break bit-identical resume
            for n, v in zip(h.param_names, h._rv):
                arrays[f"resid/{n}"] = v
        arrays["rng/key"] = np.asarray(
            jax.random.key_data(rng.get_rng_state()))
        meta = {"step": self.step_idx, "dp_degree": mh["degree"],
                "shard_optimizer": bool(mh["shard_optimizer"]),
                "loss_scale": self.loss_scale,
                "data_cursor": (self._cursor_loader.state_dict()
                                if self._cursor_loader is not None
                                else None)}
        return arrays, zero, meta

    def save(self, block=False):
        """Checkpoint the CURRENT state at ``step_idx`` (host copies
        synchronously; write + commit async unless ``block``)."""
        if self.manager is None:
            raise CheckpointError(
                "MeshTrainer.save needs a CheckpointManager "
                "(checkpoint=...)")
        arrays, zero, meta = self._snapshot()
        return self.manager.save(self.step_idx, arrays, zero=zero,
                                 meta=meta, block=block)

    def restore(self, step=None):
        """Reload state from a committed checkpoint (default: the newest
        digest-valid one — a corrupted newest step falls back). Re-shards
        ZeRO rows onto THIS trainer's dp degree. Returns the restored
        step."""
        if self.manager is None:
            raise CheckpointError(
                "MeshTrainer.restore needs a CheckpointManager "
                "(checkpoint=...)")
        if step is None:
            rc = self.manager.restore_latest_valid()
        else:
            rc = self.manager.restore(step)
        self._load_restored(rc)
        return rc.step

    def _load_restored(self, rc):
        """Place restored host arrays back onto the mesh with EXACTLY the
        shardings the compiled step committed (warm restart: zero
        post-recovery recompiles), converting between full and
        per-replica layouts as the current degree/knob requires. Each
        value adopts its LIVE predecessor's sharding verbatim — a TP
        param constrained inside the auto axes keeps that layout, which
        a reconstructed replicated spec would silently drop (and force a
        layout recompile)."""
        h = self.handle
        mh = h.meta
        degree = mh["degree"]

        def place_like(a, old):
            return jax.device_put(
                np.asarray(a).astype(old.dtype, copy=False),
                old.sharding)

        def full_of(name, shape):
            if name in rc.arrays:
                return np.asarray(rc.arrays[name]).reshape(shape)
            flat = rc.zero[name]           # saved sharded, wanted full
            return flat[:_prod(shape)].reshape(shape)

        def rows_of(name, numel):
            if name in rc.zero:            # any saved dp -> THIS degree
                return rc.zero_sharded(name, degree)
            from ..checkpoint.manager import reshard_rows

            return reshard_rows(
                np.asarray(rc.arrays[name]).reshape(-1)[:numel], degree)

        pv = []
        for n, old in zip(h.param_names, h._pv):
            pv.append(place_like(full_of(f"param/{n}", tuple(old.shape)),
                                 old))
        av = []
        for n, p, ks, row, sh in zip(h.param_names, h.params, h._acc_keys,
                                     h._av, mh["acc_sharded"]):
            out_row = []
            for k, v_old, s in zip(ks, row, sh):
                name = f"acc/{n}/{k}"
                a = rows_of(name, _prod(p.shape)) if s \
                    else full_of(name, tuple(v_old.shape))
                out_row.append(place_like(a, v_old))
            av.append(out_row)
        mv = []
        if mh["use_masters"]:
            for n, p, v_old in zip(h.param_names, h.params, h._mv):
                name = f"master/{n}"
                a = rows_of(name, _prod(p.shape)) \
                    if mh["shard_optimizer"] \
                    else full_of(name, tuple(v_old.shape))
                mv.append(place_like(a, v_old))
        rv = None
        if h._rv is not None:
            rv = []
            for n, v_old in zip(h.param_names, h._rv):
                a = rc.arrays.get(f"resid/{n}")
                if a is None or tuple(np.asarray(a).shape) \
                        != tuple(v_old.shape):
                    # a checkpoint from an uncompressed run, or an
                    # ELASTIC degree change (residuals are per-replica
                    # quantization errors — meaningless across a
                    # different dp): reset to zero, convergence-safe
                    a = np.zeros(tuple(v_old.shape), np.float32)
                rv.append(place_like(a, v_old))
        h.set_state(pv, av, mv, rv)
        key_data = rc.arrays.get("rng/key")
        if key_data is not None:
            rng.set_rng_state(jax.random.wrap_key_data(
                jax.numpy.asarray(key_data)))
        cursor = rc.meta.get("data_cursor")
        if cursor is not None and self._cursor_loader is not None:
            self._cursor_loader.set_state_dict(cursor)
        restored = int(rc.meta.get("step", rc.step))
        for s in [s for s in self.losses if s >= restored]:
            del self.losses[s]             # will be replayed bit-identical
        self.step_idx = restored

    # -- crash/hang recovery (the drilled path) ------------------------------
    def recover(self, reason="", stuck=""):
        """One warm recovery pass, idempotent per incident (the dying fit
        thread and the watchdog scanner collapse to one pass via the
        non-blocking lock — the loser returns immediately): epoch bump
        FIRST, flight dump naming the stuck span plus the step program's
        collective census, then state reload from the last committed
        checkpoint. The compiled step program is NOT torn down — that is
        what makes the restart warm. Returns the restored step, or None
        when another observer already recovered."""
        if self.manager is None:
            raise CheckpointError(
                "MeshTrainer.recover needs a CheckpointManager "
                "(checkpoint=...)")
        if not self._recover_lock.acquire(blocking=False):
            return None
        try:
            t0 = time.perf_counter()
            # the epoch bump FIRST: a step stuck at its injection point
            # wakes, sees the new epoch, and raises TrainStepSuperseded
            # without touching the state this recovery owns
            self._epoch += 1
            census = self._census()
            m, _rec = _mon()
            path = None
            try:
                if m.trace._state.on \
                        or os.environ.get("PADDLE_TPU_FLIGHT_DIR"):
                    path = m.trace.flight_dump(
                        reason=f"mesh train recovery: {reason}"
                               + (f"; stuck span: {stuck}" if stuck
                                  else ""),
                        extra={"stuck": stuck, "step": self.step_idx,
                               "epoch": self._epoch,
                               "collectives": census})
            except Exception:  # noqa: BLE001 - a dump failure never
                pass           # masks the recovery it documents
            self.last_recovery_dump = path
            write_error = None
            try:
                # drain in-flight async writes first: a snapshot taken
                # moments before the crash should be the restore target,
                # not replayed; a FAILED write (the torn-write drill)
                # must not fail the recovery — the fallback below simply
                # never sees that step committed
                self.manager.wait()
            except Exception as e:  # noqa: BLE001
                write_error = f"{type(e).__name__}: {e}"
            rc = self.manager.restore_latest_valid()
            self._load_restored(rc)
            t1 = time.perf_counter()
            self.recovery_stats.append({
                "reason": reason, "stuck": stuck,
                "ms": (t1 - t0) * 1e3, "restored_step": rc.step,
                "write_error": write_error, "dump": path})
            if m._state.on:
                _rec.inc()
            if m.trace._state.on:
                m.trace.record_span(
                    "train.recover",
                    m.now_ns() - int((t1 - t0) * 1e9), m.now_ns(),
                    attrs={"reason": reason[:120], "stuck": stuck,
                           "restored_step": rc.step})
            return rc.step
        finally:
            self._recover_lock.release()

    def _census(self):
        """Best-effort collective census of the compiled step program for
        the flight dump (cached by the telemetry path; computed from the
        last batch only if cheap lowering succeeds)."""
        try:
            if self.handle._collectives is not None:
                return dict(self.handle._collectives)
            if self._last_batch is not None:
                return dict(
                    self.handle.collective_counts(*self._last_batch))
        except Exception:  # noqa: BLE001 - diagnostics only
            pass
        return {}

    def _on_hang(self, desc, dump):
        """Watchdog scanner callback: the watched step exceeded the hang
        timeout. The watchdog already wrote its flight dump; recover()'s
        dump coalesces with it (same file, both reasons). Without a
        checkpoint manager there is no restore target — the dump is the
        whole response (recover() would raise, and an exception must
        never kill the scanner thread)."""
        if self.manager is None:
            return
        self.recover(
            f"watchdog-detected hang: {desc} exceeded "
            f"{self._dog.timeout}s", stuck=desc)

    # -- the retry loop ------------------------------------------------------
    def fit(self, data, steps, *, ckpt_every=1, resume=True):
        """Train until ``step_idx`` reaches ``steps``, recovering from
        step deaths and hangs up to ``max_recoveries`` consecutive times
        with capped exponential backoff.

        ``data`` is a callable ``step -> batch tuple`` (the cursor is
        then the step index itself), a fixed batch tuple, or a resumable
        loader exposing ``__next__``/``state_dict``/``set_state_dict``
        (:class:`paddle_tpu.io.CursorLoader`) whose exact cursor rides
        every checkpoint. Returns ``{step: loss}`` — after a kill/hang
        the replayed tail is bit-identical to an uninterrupted run.
        """
        if hasattr(data, "state_dict") and hasattr(data, "__next__"):
            self._cursor_loader = data
        mgr = self.manager
        if mgr is not None:
            if resume and mgr.latest_step() is not None:
                self.restore()
            else:
                if mgr.latest_step() is not None:
                    # resume=False over a directory holding a PRIOR
                    # run's commits: purge them, or a later recovery
                    # would restore_latest_valid() into foreign state
                    mgr.clear()
                # anchor commit: recovery always has a restore target,
                # even before the first periodic checkpoint lands
                self.save(block=True)
        attempts = 0
        while self.step_idx < steps:
            batch = self._next_batch(data)
            try:
                self._run_step(batch, record=True)
            except TrainStepSuperseded:
                # the scanner-thread recovery owns the rewind; a hang
                # consumes the same bounded budget as a death (a
                # persistently hanging step must raise, not loop)
                attempts += 1
                if attempts > self.max_recoveries:
                    raise
                # wait out the in-flight recovery, then reload ONCE
                # more: a SLOW-but-alive step this recovery superseded
                # may have completed mid-restore and clobbered the
                # freshly restored state with its own donated outputs
                # (MeshParallel.step assigns after dispatch) — by the
                # time Superseded reaches here that step has returned,
                # so this restore deterministically re-lands the
                # committed state
                self._recover_lock.acquire()
                self._recover_lock.release()
                self.restore()
                continue
            except CheckpointError:
                raise
            except Exception as e:  # noqa: BLE001 - the drill contract:
                # ANY step death recovers warm and resumes, bounded
                attempts += 1
                if mgr is None or attempts > self.max_recoveries:
                    raise
                restored = self.recover(
                    f"train step died: {type(e).__name__}: {e}",
                    stuck=getattr(e, "point", "") or "mesh.step")
                if restored is None:
                    # another observer (the watchdog scanner) owns this
                    # incident's recovery: wait it out, then re-land the
                    # committed state — resuming on whatever the
                    # in-flight restore half-swapped would corrupt the
                    # replay
                    self._recover_lock.acquire()
                    self._recover_lock.release()
                    self.restore()
                time.sleep(min(self.backoff_s * (2 ** (attempts - 1)),
                               self.backoff_cap_s))
                continue
            attempts = 0
            if mgr is not None and ckpt_every \
                    and self.step_idx % int(ckpt_every) == 0:
                self.save()
        if mgr is not None:
            mgr.wait()
        return dict(self.losses)

    def _next_batch(self, data):
        if self._cursor_loader is not None:
            batch = next(self._cursor_loader)
        elif callable(data):
            batch = data(self.step_idx)
        else:
            batch = data
        return batch if isinstance(batch, tuple) else tuple(batch)

    def status(self):
        """The trainer's graftscope /statusz section: step/epoch
        cursors, recovery history and the checkpoint manager's commit
        state — host-readable only, safe from the scrape thread."""
        doc = {
            "health": "ok",
            "step": self.step_idx,
            "epoch": self._epoch,
            "dp_degree": self.handle.meta["degree"],
            "shard_optimizer": self.handle.shard_optimizer,
            "recoveries": len(self.recovery_stats),
            "max_recoveries": self.max_recoveries,
            "losses_recorded": len(self.losses),
            "watchdog_armed": self._dog is not None,
        }
        if self.recovery_stats:
            doc["last_recovery"] = dict(self.recovery_stats[-1])
        if self.manager is not None:
            doc["checkpoint"] = self.manager.status()
        return doc

    def close(self):
        """Stop the watchdog and flush outstanding checkpoint writes; a
        manager THIS trainer constructed also has its writer thread
        stopped (a caller-provided manager may be shared — only
        flushed)."""
        from ..monitor import server as _obs

        _obs.unregister_status_provider("trainer", self.status)
        if self._dog is not None:
            self._dog.stop()
        if self.manager is not None:
            self.manager.wait()        # surface any lost write
            if self._own_manager:
                self.manager.close()   # stop the writer thread too
