"""Orbax checkpointing of the full train state.

Fixes the reference's resume gaps (SURVEY.md section 5): the reference saves
only {backbone, decoder, optimizer} state dicts — no step/epoch, no RNG, and
eval-interval checkpoints even omit the optimizer (synthesis_task.py:625-659)
— so resume restarts counters and reshuffles data. Here the whole TrainState
(params, batch_stats, opt_state, step, rng) round-trips, and saves are async
so the TPU never waits on the filesystem.

Hardening (the fault-tolerance PR):
  * On disk a checkpoint is always the stable 5-key plain tree
    {step, params, batch_stats, opt_state, rng} — diagnostic TrainState
    fields (the non-finite-guard counter buffer) are stripped on save and
    re-injected fresh on restore, so old workspaces stay restorable and
    future guard changes never invalidate checkpoints.
  * Each finished save gets a sidecar commit marker `<dir>.commit`
    (flushed once the async save settles). Markers are ADVISORY on read
    (pre-marker workspaces restore fine) but authoritative on write:
    `save_step` overwrites a marker-less partial directory instead of the
    old `os.path.exists` guard that refused to ever re-save that step.
  * keep-last-K retention for immutable step checkpoints (`keep`),
    lead-host only, never touching in-flight saves.
  * `restore()` without an explicit name walks a fallback chain — latest,
    then step checkpoints newest-first — logging and degrading on
    corruption instead of dying; only when every candidate fails does it
    raise (with the config-mismatch hint, since that is the common cause).
"""

from __future__ import annotations

import json
import os
import re
import shutil
import time
from typing import List, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

from mine_tpu import telemetry
from mine_tpu.train.state import TrainState

LATEST_NAME = "checkpoint_latest"
STEP_FMT = "checkpoint_%012d"
STEP_RE = re.compile(r"^checkpoint_(\d{12})$")
MARKER_SUFFIX = ".commit"

# the on-disk tree: stable across TrainState diagnostic-field changes
SAVE_KEYS = ("step", "params", "batch_stats", "opt_state", "rng")


# hard bound on waiting for an in-flight mirror upload before a save may
# overwrite its source directory (or the process exits): past this the
# uploader is killed and the incident logged — a hung remote store must
# not wedge training (this repo's watchdog lesson applies to itself)
MIRROR_REAP_TIMEOUT_S = 600.0


class CheckpointManager:
    def __init__(self, workspace: str, mirror_cmd: str = "",
                 keep: int = 0, logger=None):
        """`mirror_cmd`: optional shell command run (lead host only) after
        each finished save, with the literal token `{path}` replaced by the
        shell-quoted checkpoint directory — the generic counterpart of the
        reference's hard-wired HDFS upload (synthesis_task.py:634-638).
        E.g. `gsutil -m rsync -r {path} gs://bucket/ckpts/` or
        `hdfs dfs -put -f {path} /ckpts/`. The upload runs detached; an
        in-flight upload is reaped (bounded by MIRROR_REAP_TIMEOUT_S, then
        killed) before a save may overwrite its source directory and at
        wait(). Mirror problems log warnings, never raise.

        `keep`: retain only the newest `keep` committed step checkpoints
        (0 = keep all, the old behavior)."""
        self.workspace = os.path.abspath(workspace)
        os.makedirs(self.workspace, exist_ok=True)
        self._ckptr = ocp.StandardCheckpointer()
        self.mirror_cmd = mirror_cmd
        self._mirror_proc = None
        self.keep = int(keep)
        self._logger = logger
        # (path, step) of async saves whose commit marker is still owed;
        # flushed (wait_until_finished + marker write) at the next save,
        # restore, or wait() — never per step
        self._pending_commits: List[Tuple[str, int]] = []

    def _path(self, name: str) -> str:
        return os.path.join(self.workspace, name)

    def _warn(self, msg, *args):
        if self._logger is not None:
            self._logger.warning(msg, *args)
        else:
            import logging
            logging.getLogger(__name__).warning(msg, *args)

    # ---------------- commit markers ----------------

    @staticmethod
    def marker_path(path: str) -> str:
        return path + MARKER_SUFFIX

    def has_marker(self, path: str) -> bool:
        return os.path.exists(self.marker_path(path))

    def _remove_marker(self, path: str):
        if jax.process_index() != 0:
            return
        try:
            os.remove(self.marker_path(path))
        except FileNotFoundError:
            pass

    def _flush_commits(self):
        """Settle in-flight async saves, then certify them with markers."""
        if not self._pending_commits:
            return
        self._ckptr.wait_until_finished()
        for path, step in self._pending_commits:
            if jax.process_index() != 0 or not os.path.isdir(path):
                continue
            marker = {"name": os.path.basename(path), "step": int(step),
                      "unix_time": time.time()}
            tmp = self.marker_path(path) + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(marker, fh)
            os.replace(tmp, self.marker_path(path))
        self._pending_commits = []

    # ---------------- directory scan ----------------

    def step_checkpoints(self) -> List[Tuple[int, str]]:
        """Committed-or-not step checkpoint dirs as (step, path), newest
        first. The strict 12-digit regex skips orbax tmp dirs and markers."""
        out = []
        for entry in os.listdir(self.workspace):
            m = STEP_RE.match(entry)
            path = self._path(entry)
            if m and os.path.isdir(path):
                out.append((int(m.group(1)), path))
        return sorted(out, reverse=True)

    def _retain(self):
        """Delete committed step checkpoints beyond the newest `keep`.
        Lead host only; uncommitted (marker-less) dirs beyond the window
        are stale partial saves from a crashed run and go too. Never
        touches a path with a pending (in-flight) save."""
        if self.keep <= 0 or jax.process_index() != 0:
            return
        pending = {p for p, _ in self._pending_commits}
        for _, path in self.step_checkpoints()[self.keep:]:
            if path in pending:
                continue
            shutil.rmtree(path, ignore_errors=True)
            self._remove_marker(path)

    def _mirror(self, path: str):
        """Launch the detached uploader for a finished save (lead host)."""
        if not self.mirror_cmd or jax.process_index() != 0:
            return
        try:
            import shlex
            import subprocess
            self._ckptr.wait_until_finished()  # files on disk before upload
            # plain token replace + shell quoting: no str.format, so shell
            # braces (${USER}, awk '{print}') in the command are untouched
            cmd = self.mirror_cmd.replace("{path}", shlex.quote(path))
            self._mirror_proc = (cmd, subprocess.Popen(
                cmd, shell=True, start_new_session=True))
        except Exception:
            import logging
            logging.getLogger(__name__).warning(
                "checkpoint mirror launch failed", exc_info=True)

    def _reap_mirror(self, block: bool = False):
        """Collect the previous uploader; bounded kill when block=True."""
        if self._mirror_proc is None:
            return
        import logging
        import subprocess
        cmd, proc = self._mirror_proc
        try:
            rc = proc.wait(MIRROR_REAP_TIMEOUT_S) if block else proc.poll()
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            logging.getLogger(__name__).warning(
                "checkpoint mirror still running after %.0fs — killed: %s",
                MIRROR_REAP_TIMEOUT_S, cmd)
            self._mirror_proc = None
            return
        if rc is None:
            return  # still running (non-blocking poll)
        if rc != 0:
            logging.getLogger(__name__).warning(
                "checkpoint mirror command failed (rc=%d): %s", rc, cmd)
        self._mirror_proc = None

    @staticmethod
    def _save_tree(state: TrainState) -> dict:
        return {k: getattr(state, k) for k in SAVE_KEYS}

    def save_latest(self, state: TrainState):
        """Rolling checkpoint (reference: checkpoint_latest.pth every 5000
        steps, synthesis_task.py:625-632)."""
        # the span covers dispatch only — the save itself is async, so
        # this measures how long the TPU-side loop was actually held up
        # (mirror reap + previous-save settle + save dispatch)
        with telemetry.span("ckpt.save_latest", emit=True,
                            step=int(state.step)):
            # an in-flight mirror may still be reading checkpoint_latest;
            # finish (or kill) it before force-overwriting its source
            self._reap_mirror(block=True)
            self._flush_commits()
            path = self._path(LATEST_NAME)
            # the old marker must not certify the dir while the overwrite
            # is in flight — a crash mid-save then correctly reads as
            # uncommitted
            self._remove_marker(path)
            self._ckptr.save(path, self._save_tree(state), force=True)
            self._pending_commits.append((path, int(state.step)))
            self._mirror(path)

    def save_step(self, state: TrainState):
        """Immutable per-eval checkpoint — unlike the reference's, it keeps
        the optimizer state (synthesis_task.py:650-652 drops it). A dir
        with a commit marker is final and skipped; a marker-less dir is a
        partial save from a crashed run and is overwritten (the old
        os.path.exists guard refused to ever re-save that step)."""
        with telemetry.span("ckpt.save_step", emit=True,
                            step=int(state.step)):
            self._flush_commits()
            path = self._path(STEP_FMT % int(state.step))
            if os.path.exists(path):
                if self.has_marker(path):
                    return
                self._warn("overwriting incomplete step checkpoint %s "
                           "(no commit marker — previous save did not "
                           "finish)", path)
            self._reap_mirror(block=True)  # one uploader at a time
            self._ckptr.save(path, self._save_tree(state), force=True)
            self._pending_commits.append((path, int(state.step)))
            self._mirror(path)
            self._retain()

    def wait(self):
        self._flush_commits()
        self._ckptr.wait_until_finished()
        # the final save's mirror must complete before the job exits, or
        # container teardown kills the detached upload mid-transfer
        self._reap_mirror(block=True)

    # ---------------- restore ----------------

    def _restore_tree(self, path: str, template: TrainState) -> TrainState:
        """One restore attempt against the stable 5-key on-disk tree; the
        guard buffer is re-injected from the template (counters are
        diagnostics of the CURRENT run — they reset on resume)."""
        abstract = {k: jax.tree_util.tree_map(ocp.utils.to_shape_dtype_struct,
                                              getattr(template, k))
                    for k in SAVE_KEYS}
        tree = self._ckptr.restore(path, abstract)
        return TrainState(guard=template.guard,
                          **{k: tree[k] for k in SAVE_KEYS})

    @staticmethod
    def _mismatch_hint(path: str, e: Exception) -> RuntimeError:
        # tree/structure mismatch out of orbax — almost always a config
        # change between runs; surface the original error text so IO or
        # corruption causes (which also raise ValueError) stay visible
        return RuntimeError(
            f"Failed to restore checkpoint at {path}: {e}\n"
            "If this is a tree-structure mismatch, the optimizer config "
            "likely changed between runs (e.g. training.grad_accum_steps "
            "toggled, which nests opt_state under optax.MultiSteps). "
            "Resume with the original config, or load weights only via "
            "training.pretrained_checkpoint_path (.npz).")

    def restore(self, template: TrainState,
                name: Optional[str] = None) -> Optional[TrainState]:
        """Restore into the template's structure/shardings; returns None when
        no checkpoint exists.

        With an explicit `name` only that checkpoint is tried. Without one
        the fallback chain runs: checkpoint_latest, then step checkpoints
        newest-first — a corrupt candidate logs a warning and degrades to
        the next instead of killing the run. Markers are advisory here
        (pre-marker workspaces restore fine). Only when every candidate
        fails does the chain raise, with the config-mismatch hint."""
        with telemetry.span("ckpt.restore", emit=True):
            return self._restore(template, name)

    def _restore(self, template: TrainState,
                 name: Optional[str] = None) -> Optional[TrainState]:
        self._flush_commits()
        if name is not None:
            path = name if os.path.isabs(name) else self._path(name)
            if not os.path.exists(path):
                return None
            try:
                return self._restore_tree(path, template)
            except (ValueError, KeyError, TypeError) as e:
                raise self._mismatch_hint(path, e) from e

        candidates = []
        latest = self._path(LATEST_NAME)
        if os.path.exists(latest):
            candidates.append(latest)
        candidates.extend(path for _, path in self.step_checkpoints())
        last = None  # (path, exception)
        for path in candidates:
            try:
                restored = self._restore_tree(path, template)
            except Exception as e:
                self._warn("failed to restore %s (%s: %s)%s", path,
                           type(e).__name__, e,
                           "" if self.has_marker(path) else
                           " — no commit marker, likely a partial save")
                last = (path, e)
                continue
            if last is not None:
                # a corrupt/partial candidate was skipped: count it — a
                # nonzero ckpt.restore_fallback after an incident review
                # means the durability story was load-bearing, not luck
                telemetry.counter("ckpt.restore_fallback").inc()
                telemetry.emit(
                    "ckpt.restore_fallback", restored=path,
                    step=int(np.asarray(restored.step)),
                    failed=last[0], error=f"{type(last[1]).__name__}")
                self._warn("restored fallback checkpoint %s at step %d",
                           path, int(np.asarray(restored.step)))
            return restored
        if last is not None:
            raise self._mismatch_hint(*last) from last[1]
        return None

    def latest_exists(self) -> bool:
        return os.path.exists(self._path(LATEST_NAME))


def load_pretrained_params(path: str, params, batch_stats=None, logger=None):
    """Non-strict restore from a converted .npz checkpoint (flattened 'a/b/c'
    keys; BatchNorm running stats under 'stats:a/b/c') — the torch-interop
    path, mirroring restore_model's tolerant model load (utils.py:40-67).

    Missing/extra keys are logged, matching keys replaced. Returns new params
    (and new batch_stats when a template is given).
    """
    data = np.load(path)

    def merge(tree, prefix_tag, tag):
        flat = _flatten("", tree)
        missing = [k for k in flat if prefix_tag + k not in data]
        if logger:
            logger.info("[MODEL_RESTORE] %s keys missing in checkpoint: %s",
                        tag, missing)

        def rebuild(prefix, t):
            out = {}
            for k, v in t.items():
                key = f"{prefix}/{k}" if prefix else k
                if isinstance(v, dict):
                    out[k] = rebuild(key, v)
                elif prefix_tag + key in data:
                    arr = np.asarray(data[prefix_tag + key])
                    out[k] = arr.astype(np.asarray(v).dtype).reshape(v.shape)
                else:
                    out[k] = v
            return out

        return rebuild("", tree)

    new_params = merge(params, "", "param")
    if logger:
        known = set(_flatten("", params))
        if batch_stats is not None:
            known |= {"stats:" + k for k in _flatten("", batch_stats)}
        extra = [k for k in data.files
                 if k not in known and not (k.startswith("stats:")
                                            and batch_stats is None)]
        logger.info("[MODEL_RESTORE] unused checkpoint keys: %s", extra)
    if batch_stats is None:
        return new_params
    return new_params, merge(batch_stats, "stats:", "batch_stats")


def _flatten(prefix, tree):
    flat = {}
    for k, v in tree.items():
        key = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            flat.update(_flatten(key, v))
        else:
            flat[key] = v
    return flat
