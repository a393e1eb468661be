"""Checkpointing: Orbax multi-host sharded save/restore with keep-N rotation,
best-eval-loss tracking, explicit resume, a trainable-only payload mode, and
a non-blocking snapshot saver.

Reference parity (C9/C10 + SURVEY.md §5.4):
- ``save_steps=500`` / ``save_total_limit=3`` rotation (``training.py:268,276``)
  -> CheckpointManagerOptions(max_to_keep, save_interval_steps handled by caller);
- best-model tracking on eval_loss (``load_best_model_at_end``,
  ``training.py:273-275``) -> best_fn over per-step metrics, and the manager
  additionally keeps the best step;
- the reference has NO explicit resume path (SURVEY.md §5.4) — here
  ``latest_step``/restore make resume-from-latest a first-class flag;
- rank-0-only torch.save is replaced by a sharded multi-host Orbax save
  (every host writes its shard — no single-host bottleneck), while the
  single-file safetensors export for the inference contract
  (``best_model/``, ``training.py:310-311``) is done separately at end of
  training via models/hf_io.py.

TPU-native additions beyond the reference:
- **Trainable-only payload** (``trainable_only=True``): the frozen 86.4% of a
  last-2-layers SFT (~5.3 GB of the flagship's 7.4 GB checkpoint) is
  byte-reconstructible from the base checkpoint / init seed, so only
  (step, trainable masters, optimizer state) is persisted, plus a per-leaf
  fingerprint of the frozen params verified at restore — a silent change of
  the base weights between save and resume is a hard error, not silent
  corruption.
- **Non-blocking snapshot save** (``snapshot_async=True``, single-process):
  ``save()`` takes an on-device copy of the payload (device-side, fast) and
  hands serialization to a background thread, so the training loop resumes
  immediately while the device->host stream drains (a full flagship
  checkpoint is 7.4 GB; what a synchronous save costs on the attached v5e:
  not measured). The on-device copy must exist BEFORE the next
  donated train step reuses the state buffers; transient HBM cost is one
  copy of the (trainable-only) payload.
"""

from __future__ import annotations

import math
import os
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from llm_fine_tune_distributed_tpu.observe.xla import importing
from llm_fine_tune_distributed_tpu.train.state import TrainState

with importing("orbax.checkpoint"):  # its logging imports google.cloud.logging: seconds, named while set-up lasts
    import orbax.checkpoint as ocp


class FingerprintMismatch(RuntimeError):
    """The re-derived frozen params do not match what a trainable-only
    checkpoint was trained against. Deliberately NOT retried/fallback-ed by
    the trainer's resume chain: the checkpoint is fine, the base weights are
    wrong — retrying other layouts would bury the real diagnosis."""


def frozen_fingerprint(frozen: Dict[str, Any]):
    """Per-leaf integrity stats of the frozen params, computed ON DEVICE
    (fetching 5.3 GB to hash bytes would cost exactly the transfer the
    trainable-only mode avoids): [sum(|x|), sum(x*x), sum(x*iota)/n, count]
    in f32 per leaf. The position-weighted third component makes the
    fingerprint order-sensitive: a permuted or transposed base checkpoint
    keeps sum(|x|) and sum(x*x) exactly but moves the iota sum, so it fails
    verification instead of silently training against shuffled weights.
    Deterministic for a fixed program, and any re-derivation drift (wrong
    base checkpoint, wrong seed, wrong quantization knobs) moves the sums.
    Non-float leaves (NF4 codes, int8 absmax) hash via their int sums."""

    @jax.jit
    def stats(tree):
        out = {}
        for k, v in tree.items():
            x = v.astype(jnp.float32).reshape(-1)
            # iota normalized to [0, 1) keeps the position sum on the same
            # scale as the magnitude sums regardless of leaf size
            iota = jnp.arange(x.size, dtype=jnp.float32) / jnp.float32(
                max(x.size, 1)
            )
            out[k] = jnp.stack(
                [
                    jnp.abs(x).sum(),
                    (x * x).sum(),
                    (x * iota).sum(),
                    jnp.float32(x.size),
                ]
            )
        return out

    return {k: np.asarray(v) for k, v in stats(frozen).items()}


def verify_fingerprint(saved: Dict[str, Any], current: Dict[str, Any]) -> None:
    """Hard error when the re-derived frozen params do not match the ones the
    checkpoint was trained against. The tolerance covers cross-platform
    reduction order (save on TPU, restore on CPU) and nothing more: compared
    in float64 with rtol scaled by sqrt(leaf count) — reduction-order error
    grows like sqrt(n) · eps, so a fixed rtol that is safe for a 1M-element
    leaf would spuriously reject a legitimate 100M+-element one."""
    saved_keys, cur_keys = set(saved), set(current)
    if saved_keys != cur_keys:
        raise FingerprintMismatch(
            "trainable-only checkpoint: frozen param STRUCTURE changed since "
            f"save (missing: {sorted(saved_keys - cur_keys)[:5]}, "
            f"extra: {sorted(cur_keys - saved_keys)[:5]}) — resume with the "
            "original base checkpoint/config"
        )
    for k in saved:
        s = np.asarray(saved[k], dtype=np.float64)
        c = np.asarray(current[k], dtype=np.float64)
        if s.shape != c.shape:
            raise FingerprintMismatch(
                f"trainable-only checkpoint: frozen leaf {k!r} carries a "
                f"{s.shape}-stat fingerprint but the current code derives "
                f"{c.shape} — the checkpoint predates the fingerprint format"
            )
        n = s[-1]
        if n != c[-1]:
            raise FingerprintMismatch(
                f"trainable-only checkpoint: frozen leaf {k!r} changed size "
                f"(saved n={n}, re-derived n={c[-1]})"
            )
        rtol = max(1e-4, 2e-7 * math.sqrt(max(n, 1.0)))
        # the position sum can sit near zero for symmetric inits, so its
        # absolute floor scales with the leaf's magnitude, not a constant
        atol = rtol * max(float(s[0]), 1e-6)
        if not np.allclose(s[:-1], c[:-1], rtol=rtol, atol=atol):
            raise FingerprintMismatch(
                f"trainable-only checkpoint: frozen leaf {k!r} does not match "
                f"the weights it was trained against (saved "
                f"[|x|,x^2,x*iota,n]={s}, re-derived={c}) — the base "
                "checkpoint or init seed changed"
            )


class CheckpointManager:
    def __init__(
        self,
        directory: str,
        max_to_keep: int = 3,
        metric_name: str = "eval_loss",
        greater_is_better: bool = False,
        trainable_only: bool = False,
    ):
        directory = os.path.abspath(directory)
        self.directory = directory
        if jax.process_index() == 0:
            os.makedirs(directory, exist_ok=True)
        self.metric_name = metric_name
        self.greater_is_better = greater_is_better
        self.trainable_only = trainable_only
        # Missing metric maps to the WORST value for the configured mode so a
        # metric-less checkpoint can never rank best.
        worst = -float("inf") if greater_is_better else float("inf")
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            best_fn=(lambda m: m.get(metric_name, worst)) if metric_name else None,
            best_mode="max" if greater_is_better else "min",
            keep_checkpoints_without_metrics=True,
            create=True,
        )
        self._mgr = ocp.CheckpointManager(directory, options=options)
        self._snapshot_thread: Optional[threading.Thread] = None
        self._snapshot_error: Optional[BaseException] = None
        # full-payload async mode: frozen params never change during a run,
        # so they are fetched to host ONCE (first save) and reused — the
        # per-save on-device snapshot then covers only step/trainable/opt,
        # bounding transient HBM to the trainable payload in both modes
        self._frozen_host: Optional[Dict[str, np.ndarray]] = None

    # ------------------------------------------------------------------ save

    def _payload(self, state: TrainState, fingerprint=None):
        """The pytree actually persisted. Trainable-only mode drops the
        frozen dict (re-derived at restore) and stores the fingerprint."""
        if not self.trainable_only:
            return state
        return {
            "step": state.step,
            "trainable": state.trainable,
            "opt_state": state.opt_state,
            "frozen_fp": fingerprint or {},
        }

    def save(
        self,
        step: int,
        state: TrainState,
        metrics: Optional[Dict[str, float]] = None,
        fingerprint=None,
        snapshot_async: bool = False,
    ):
        """Persist ``step``'s state.

        ``snapshot_async=True`` (single-process only): on-device copy + background
        serialization — the caller's next train step is NOT blocked on the
        device->host stream. Any error from the background save surfaces on
        the next save()/wait()/close().
        """
        # Join (not just error-check) FIRST: a sync save racing a still-running
        # background save would drive two concurrent ocp.CheckpointManager.save
        # calls on one manager. Also surfaces any pending background error.
        self.join_snapshot()
        if self.trainable_only and not fingerprint:
            raise ValueError(
                "trainable_only save needs the frozen-param fingerprint — a "
                "checkpoint without one can never be restored in lean mode"
            )
        if not snapshot_async or jax.process_count() > 1:
            payload = self._payload(state, fingerprint)
            if jax.process_count() == 1:
                # fetch through concurrent streams BEFORE handing to Orbax:
                # its own transfer_arrays_to_host is one serial stream
                # (utils/transfer.py). Multi-process saves stay sharded
                # device saves.
                from llm_fine_tune_distributed_tpu.utils.transfer import (
                    parallel_device_get_tree,
                )

                payload = parallel_device_get_tree(payload)
            self._mgr.save(
                step,
                args=ocp.args.Composite(state=ocp.args.StandardSave(payload)),
                metrics=metrics,
            )
            self._write_latest(step, metrics)
            return
        # (the entry join above already waited out any previous background
        # save: transient HBM is bounded to ONE extra payload copy and Orbax
        # manager access stays serialized)
        if not self.trainable_only and self._frozen_host is None:
            # one-time synchronous fetch; every later save reuses it (frozen
            # leaves are never touched by the optimizer by construction)
            self._frozen_host = {
                k: np.asarray(v) for k, v in state.frozen.items()
            }
        # On-device snapshot of the MUTATING leaves only (fresh buffers): the
        # caller's next donated train step reuses the live state buffers, so
        # the copy must be enqueued BEFORE it — jnp.copy dispatches in stream
        # order and costs device time only, not a host sync.
        snap_box = [
            jax.tree.map(
                jnp.copy,
                {
                    "step": state.step,
                    "trainable": state.trainable,
                    "opt_state": state.opt_state,
                },
            )
        ]

        def _bg_save():
            try:
                # block on the snapshot (the copy happens on-stream while
                # training continues), fetch to host through concurrent
                # streams (utils/transfer.py),
                # then FREE the device copy before the Orbax write (the
                # tree helper keeps no leaf references, so clearing
                # snap_box releases the HBM)
                from llm_fine_tune_distributed_tpu.utils.transfer import (
                    parallel_device_get_tree,
                )

                snap, snap_box[0] = snap_box[0], None
                host = parallel_device_get_tree(snap)
                del snap
                if self.trainable_only:
                    host["frozen_fp"] = fingerprint
                else:
                    host = TrainState(
                        step=host["step"],
                        trainable=host["trainable"],
                        frozen=self._frozen_host,
                        opt_state=host["opt_state"],
                    )
                self._mgr.save(
                    step,
                    args=ocp.args.Composite(state=ocp.args.StandardSave(host)),
                    metrics=metrics,
                )
                self._mgr.wait_until_finished()
                self._write_latest(step, metrics)
            except BaseException as e:  # surfaced on next save/wait/close
                self._snapshot_error = e

        self._snapshot_thread = threading.Thread(
            target=_bg_save, name=f"ckpt-snapshot-{step}", daemon=True
        )
        self._snapshot_thread.start()

    def _write_latest(self, step: int, metrics: Optional[Dict[str, float]]) -> None:
        """Torn-read-proof ``latest.json`` beside the step dirs (temp path +
        ``os.replace`` — train/publish.atomic_write_json): the step really is
        durable by the time this runs, so an external reader (a publish-dir
        watcher, a resume script, a human) gets (step, metrics, payload mode)
        without importing Orbax, and never a half-written pointer. Process 0
        only — exactly the host that owns directory rotation."""
        if jax.process_index() != 0:
            return
        from llm_fine_tune_distributed_tpu.train.publish import atomic_write_json

        try:
            atomic_write_json(
                os.path.join(self.directory, "latest.json"),
                {
                    "step": int(step),
                    "metrics": {
                        k: float(v) for k, v in (metrics or {}).items()
                    },
                    "trainable_only": self.trainable_only,
                },
            )
        except OSError:
            pass  # the pointer is advisory; the checkpoint itself is durable

    def join_snapshot(self) -> None:
        if self._snapshot_thread is not None:
            self._snapshot_thread.join()
            self._snapshot_thread = None
        self._raise_pending_snapshot_error()

    def _raise_pending_snapshot_error(self) -> None:
        if self._snapshot_error is not None:
            e, self._snapshot_error = self._snapshot_error, None
            raise RuntimeError(f"background checkpoint save failed: {e}") from e

    def wait(self) -> None:
        self.join_snapshot()
        self._mgr.wait_until_finished()

    # --------------------------------------------------------------- restore

    @property
    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    @property
    def best_step(self) -> Optional[int]:
        return self._mgr.best_step()

    def restore(
        self,
        step: int,
        abstract_state: TrainState,
        trainable_only: Optional[bool] = None,
    ) -> TrainState:
        """Restore into the given abstract state (jax.eval_shape of the real
        one, carrying shardings) so arrays land directly on the right devices.

        ``trainable_only`` overrides the manager's payload mode for THIS
        restore — the trainer uses it to fall back when resuming a
        checkpoint written in the other mode (e.g. a pre-existing full
        checkpoint resumed by a trainable-only run).

        Trainable-only restore: ``abstract_state.frozen`` must be the REAL
        (already re-derived) frozen params, not abstract — they are carried
        into the result unchanged and verified against the saved fingerprint.
        """
        # A background save may still be writing the very step being restored;
        # join so the manager never runs a restore concurrent with its save.
        self.join_snapshot()
        if trainable_only is None:
            trainable_only = self.trainable_only
        if not trainable_only:
            restored = self._mgr.restore(
                step,
                args=ocp.args.Composite(state=ocp.args.StandardRestore(abstract_state)),
            )
            return restored["state"]
        frozen = abstract_state.frozen
        if any(isinstance(v, jax.ShapeDtypeStruct) for v in frozen.values()):
            raise ValueError(
                "trainable-only restore needs the re-derived frozen params "
                "(real arrays) on abstract_state.frozen"
            )
        fp_abstract = {
            k: jax.ShapeDtypeStruct((4,), np.float32) for k in frozen
        }
        abstract = {
            "step": abstract_state.step,
            "trainable": abstract_state.trainable,
            "opt_state": abstract_state.opt_state,
            "frozen_fp": fp_abstract,
        }
        restored = self._mgr.restore(
            step, args=ocp.args.Composite(state=ocp.args.StandardRestore(abstract))
        )["state"]
        verify_fingerprint(restored["frozen_fp"], frozen_fingerprint(frozen))
        return TrainState(
            step=restored["step"],
            trainable=restored["trainable"],
            frozen=frozen,
            opt_state=restored["opt_state"],
        )

    def close(self) -> None:
        self.join_snapshot()
        self._mgr.wait_until_finished()
        self._mgr.close()
