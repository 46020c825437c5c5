"""Host training loop: epochs, logging, eval cadence, checkpointing.

The driver half of the reference's SynthesisTask.train/train_epoch/run_eval
(synthesis_task.py:476-670) — same cadences (log every 10 steps, rolling
checkpoint every 5000, eval at step 2000 and every eval_interval with a step
checkpoint), same meters and tensorboard tags, but:
  * the whole step is one jitted call; the loop only feeds batches and logs
  * checkpoints carry step+RNG (resume is exact; reference restarts counters)
  * rank gating is jax.process_index()==0 (multi-host single-controller)
"""

from __future__ import annotations

import itertools
import os
import time
from collections import deque
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mine_tpu import telemetry
from mine_tpu.config import (resilience_config_from_dict,
                             serve_config_from_dict,
                             telemetry_config_from_dict)
from mine_tpu.data.common import PIPELINE_STATS, RetryPolicy, set_retry_policy
# prefetch is re-exported here for backward compatibility; it moved to the
# input-pipeline module alongside the threaded assembler + device stager
from mine_tpu.data.pipeline import DeviceStager, StagedBatch, prefetch  # noqa: F401
from mine_tpu.serve import PyramidCache, image_id_for
from mine_tpu.testing import faults
from mine_tpu.train import resilience
from mine_tpu.train.checkpoint import CheckpointManager
from mine_tpu.train.state import TrainState, current_lrs
# SynthesisTrainer is re-exported for callers that took it from here
from mine_tpu.train.step import SynthesisTrainer, sample_disparity  # noqa: F401
from mine_tpu.train.trainer import Trainer
from mine_tpu.utils import AverageMeter, disparity_normalization_vis, metrics_to_float

# host-side step-time breakdown (milliseconds, averaged per log interval):
#   step       wall-clock per step
#   host_wait  blocked waiting for the NEXT staged batch (host-bound time)
#   device     step minus host_wait (device compute + dispatch backpressure)
#   h2d        host->device copy of the step's batch, measured in the
#              stager thread (overlapped with compute unless host-bound)
# Printed per log interval as the FROZEN st1 step-time line
# (telemetry/stepline.py) and mirrored into the telemetry registry's
# train.* histograms + the JSONL event stream ("train.step" events).
TIME_METER_KEYS = ("step_ms", "host_wait_ms", "device_ms", "h2d_ms")


class TrainLoop:
    def __init__(self, trainer: Trainer,
                 train_dataset, val_dataset,
                 workspace: str,
                 logger=None,
                 tb_writer=None):
        self.trainer = trainer
        self.config = trainer.config
        self.train_dataset = train_dataset
        self.val_dataset = val_dataset
        self.logger = logger
        self.tb = tb_writer
        self._tb_broken = False  # a failing TB writer degrades, not kills
        self.resil = resilience_config_from_dict(self.config)
        self.ckpt = CheckpointManager(
            workspace,
            mirror_cmd=str(self.config.get("training.checkpoint_mirror_cmd",
                                           "") or ""),
            keep=self.resil.checkpoint_keep,
            logger=logger)
        set_retry_policy(RetryPolicy(
            max_item_retries=self.resil.max_item_retries,
            backoff_s=self.resil.item_retry_backoff))
        # SIGTERM/SIGINT -> emergency checkpoint at the next cadence
        # boundary; all hosts agree via resilience.global_any before the
        # collective save (installed for the duration of run())
        self.preempt = resilience.PreemptionHandler(logger)
        self.preempted = False
        self.guard_monitor = resilience.GuardMonitor(
            self.resil.guard_skip_threshold
            if self.resil.guard_nonfinite else 0, logger)

        self.is_lead = jax.process_index() == 0
        # the model family's own meters (Trainer.METER_KEYS)
        self.train_meters = {k: AverageMeter("train_" + k)
                             for k in trainer.METER_KEYS}
        self.val_meters = {k: AverageMeter("val_" + k)
                           for k in trainer.METER_KEYS}
        self.time_meters = {k: AverageMeter("time_" + k, ":.1f")
                            for k in TIME_METER_KEYS}

        # --- input pipeline knobs (see data/pipeline.py) ---
        # data.num_workers: assembler threads (0 = synchronous, the
        # reference's num_workers=0 semantics); batches are identical for
        # any worker count (counter-based per-item PRNG in data/common.py)
        self.num_workers = int(self.config.get("data.num_workers", 0) or 0)
        # bounded host-side queue depth of assembled numpy batches
        self.prefetch_batches = max(1, int(
            self.config.get("data.prefetch_batches", 2)))
        # device-resident staged batches in flight; >=2 overlaps the H2D
        # copy of batch k+1 with compute of step k, <=1 stages on the
        # training thread (synchronous, for debugging/A-B)
        self.staging_buffers = int(self.config.get("data.staging_buffers", 2))

        # meters update at log steps only (pulling metrics to host every
        # step would sync the device pipeline); clamp so epochs shorter
        # than the interval still log/meter instead of averaging nothing
        self.log_interval = max(1, min(
            int(self.config.get("training.log_interval", 10)),
            trainer.steps_per_epoch))
        self.ckpt_interval = int(self.config.get("training.checkpoint_interval", 5000))
        self.eval_interval = int(self.config.get("training.eval_interval", 10000))
        # per-host examples per step (per_gpu_batch_size x data-axis devices,
        # split across hosts); the jitted step sees the global batch
        self.local_batch_size = trainer.local_batch_size()
        self.seed = int(self.config.get("training.seed", 0))

        # --- encode-once eval (serve.eval_encode_once; README "Serving") ---
        # Encode each DISTINCT val source image once per eval and replay its
        # cached MPI pyramid for every target view — the eval-loop face of
        # the serving engine's encode/render asymmetry. Restricted to runs
        # where the split eval step needs no collectives and the pyramid is
        # a pure function of (src, disparity): otherwise fall back to the
        # fused eval_step with a logged reason.
        # --- telemetry (mine_tpu/telemetry; README "Observability") ---
        # events: low-frequency JSONL records (step-time at log cadence,
        # checkpoint spans, guard aborts, profiler windows); metrics: the
        # process registry obs_report/serve share. An outer harness that
        # exported MINE_TPU_TELEMETRY_EVENTS keeps owning the stream.
        self.telem = telemetry_config_from_dict(self.config)
        if self.telem.enabled:
            telemetry.ensure_configured(
                self.telem.events_path
                or os.path.join(workspace, "events.jsonl"),
                max_mb=self.telem.events_max_mb,
                keep=self.telem.events_keep)
        # flight recorder (telemetry.recorder.*, default off): black-box
        # rings fed at log cadence below; triggers on guard aborts (via
        # the events tee), preemption shutdown and data-error bursts
        # (explicit hooks), and SIGUSR2. Lead host only — one bundle
        # stream per run, like the profiler windows.
        self.recorder = None
        if (self.telem.enabled and self.telem.recorder_enabled
                and jax.process_index() == 0):
            self.recorder = telemetry.recorder.configure(
                self.telem.recorder_dir
                or os.path.join(workspace, "incidents"),
                events_tail=self.telem.recorder_events,
                steplines=self.telem.recorder_steplines,
                snapshots=self.telem.recorder_snapshots,
                debounce_s=self.telem.recorder_debounce_s,
                keep=self.telem.recorder_keep,
                arm_profile_steps=self.telem.recorder_arm_profile_steps,
                config=dict(self.config))
            self.recorder.install_sigusr2()
        # opt-in process-vitals gauges (telemetry.resource_sample_s)
        self._resource = telemetry.ResourceSampler(
            self.telem.resource_sample_s if self.telem.enabled else 0.0)
        # opt-in jax.profiler window over an exact step range, lead host
        # only (a per-host trace dir free-for-all helps nobody)
        self.profile = telemetry.ProfileWindow(
            self.telem.profile_steps if (self.telem.enabled
                                         and jax.process_index() == 0)
            else (),
            self.telem.profile_dir or os.path.join(workspace, "profile"),
            logger)

        # --- train-side ops plane (training.ops_port, default off) ---
        # The serve stack's OpsServer reused for training: /metrics (the
        # shared registry), /healthz (degraded on a live guard-skip streak
        # or data errors burning in the last log interval), /progress
        # (step/epoch position + ETA from the st1 step-time history). Lead
        # host only. The handlers read only this host-side state dict,
        # which is written at log cadence — the server can never add a
        # device sync, and with the port at 0 nothing is constructed, so
        # training outputs are bitwise identical on vs off.
        self.ops_port = int(self.config.get("training.ops_port", 0) or 0)
        self._ops = None
        self._step_hist = deque(maxlen=64)  # recent step_ms, log cadence
        self._ops_state = {"gstep": 0, "epoch": 0, "epochs": 0,
                           "guard_consecutive": 0.0, "data_errors": 0,
                           "data_errors_delta": 0}

        self.serve_cfg = serve_config_from_dict(self.config)
        self.eval_encode_once = bool(self.serve_cfg.eval_encode_once)
        if self.eval_encode_once:
            # Single remaining gate: multi-host (the split eval halves would
            # need collectives). Single-host mesh>1 works — the plain-jit
            # eval halves let GSPMD reshard on the fly — and num_bins_fine>0
            # goes through trainer.eval_encode_c2f, which replays the fused
            # step's fine-plane draws per example (train/step.py).
            if jax.process_count() > 1:
                self.eval_encode_once = False
                self._log("serve.eval_encode_once disabled: %s",
                          "multi-host run (eval steps are collective)")

    # ---------------- top-level ----------------

    def run(self, state: Optional[TrainState] = None,
            epochs: Optional[int] = None) -> TrainState:
        if state is None:
            state = self.trainer.init_state(self.trainer.global_batch_size())
        # Resume is attempted for a PASSED state too — train_cli always
        # passes one (it may carry pretrained weights), and gating restore
        # on `state is None` silently restarted CLI runs from scratch
        # (caught by the r5 on-TPU soak's kill/resume leg). A workspace
        # checkpoint outranks pretrained init, like the reference's
        # resume-from-workspace flow (synthesis_task.py:121-136).
        restored = self.ckpt.restore(state)
        if restored is not None:
            state = restored
            self._log("Resumed from checkpoint at step %d" % int(state.step))

        epochs = epochs or int(self.config.get("training.epochs", 1))
        steps_per_epoch = self.trainer.steps_per_epoch
        start_epoch = int(state.step) // steps_per_epoch + 1

        self._ops_state.update(epochs=epochs, gstep=int(state.step),
                               epoch=start_epoch)
        if self.recorder is not None:
            self.recorder.add_state_provider(
                "train", lambda: dict(self._ops_state))
        if self.ops_port and self.is_lead:
            self._ops = telemetry.OpsServer(
                port=self.ops_port, health=self._train_health,
                progress=self._train_progress,
                incidents=(self.recorder.list_incidents
                           if self.recorder is not None else None)).start()
            self._log("train ops endpoint at %s" % self._ops.url)

        self.preempt.install()
        try:
            for epoch in range(start_epoch, epochs + 1):
                state = self.train_epoch(state, epoch)
                if not self.preempted and self.preempt.global_requested():
                    self.preempted = True
                if self.preempted:
                    break
                if self.is_lead:
                    self._log("Epoch %d finished, average losses:" % epoch)
                    for m in self.train_meters.values():
                        self._log("    %s" % m)
                    if self.time_meters["step_ms"].count:
                        self._log("Epoch %d step-time breakdown (ms):" % epoch)
                        for m in self.time_meters.values():
                            self._log("    %s" % m)
            # final save: runs shorter than checkpoint_interval otherwise
            # leave NO checkpoint_latest at all — the fixture end-to-end
            # chain dies at eval and a killed short run has nothing to
            # resume from (advisor r5; collective, every process
            # participates). Under preemption this IS the emergency
            # checkpoint.
            self.ckpt.save_latest(state)
            self._log("%s checkpoint saved at step %d"
                      % ("Preemption" if self.preempted else "Final",
                         int(state.step)))
            self.ckpt.wait()
            if self.preempted and self.recorder is not None:
                # preemption-shutdown trigger: the emergency checkpoint is
                # on disk, so the bundle captures the final state the
                # resumed run will diff against (sync: the process is
                # about to exit — the worker thread might not get there)
                self.recorder.trigger("train.preempted",
                                      gstep=int(state.step))
        finally:
            self.preempt.uninstall()
            self.profile.stop()  # a window whose stop step never arrived
            if self._ops is not None:
                self._ops.close()  # join before the thread-leak tripwire
                self._ops = None
            self._resource.close()
            # the span ring (feed, step dispatch, declared readbacks) and
            # one end-of-run registry snapshot into the event stream so
            # obs_report sees final values without scraping logs
            telemetry.spans.export()
            telemetry.emit(
                "metrics.snapshot", scope="train.run_end",
                gstep=int(state.step),
                metrics=telemetry.REGISTRY.snapshot())
            if self.recorder is not None:
                # after the snapshot emit: the tee puts it in any
                # triggered-but-pending bundle's tail, then the worker
                # joins here
                telemetry.recorder.release(self.recorder)
                self.recorder = None
        return state

    # ---------------- epoch ----------------

    def _epoch_host_batches(self, epoch: int):
        """Numpy-batch iterator for one epoch: the multi-worker assembler
        when the loader supports it (all in-repo loaders route
        batch_iterator through data/common.iterate_pair_batches), else the
        loader's own iterator behind a single prefetch thread."""
        kwargs = dict(batch_size=self.local_batch_size,
                      shuffle=True,
                      seed=self.seed,
                      epoch=epoch,
                      drop_last=True,
                      shard_index=jax.process_index(),
                      num_shards=jax.process_count())
        try:
            return self.train_dataset.batch_iterator(
                workers=self.num_workers,
                prefetch_batches=self.prefetch_batches, **kwargs)
        except TypeError:  # out-of-tree loader without pipeline kwargs
            return prefetch(self.train_dataset.batch_iterator(**kwargs),
                            depth=self.prefetch_batches)

    def _staged_batches(self, host_batches):
        """StagedBatch iterator: background double-buffered device staging
        (data/pipeline.DeviceStager), or on-thread staging when
        data.staging_buffers <= 1 (the synchronous A/B reference)."""
        if self.staging_buffers >= 2:
            return iter(DeviceStager(host_batches, self.trainer.put_batch,
                                     depth=self.staging_buffers))

        def sync():
            for np_batch in host_batches:
                t0 = time.perf_counter()
                batch = self.trainer.put_batch(np_batch)
                jax.block_until_ready(batch)
                yield StagedBatch(batch, (time.perf_counter() - t0) * 1e3)
        return sync()

    def train_epoch(self, state: TrainState, epoch: int) -> TrainState:
        for m in self.train_meters.values():
            m.reset()
        for m in self.time_meters.values():
            m.reset()

        # gstep is tracked on the HOST (the jitted step increments
        # state.step by exactly 1): reading int(state.step) every
        # iteration would block on the step's completion and serialize
        # device compute with the host feed — the pre-pipeline loop paid
        # that sync each step. It is reconciled against the device counter
        # at every checkpoint boundary (below), so drift can't silently
        # shift the ckpt/eval cadence after resume.
        gstep = int(state.step)
        host_batches = self._epoch_host_batches(epoch)
        offset = gstep - (epoch - 1) * self.trainer.steps_per_epoch
        if offset > 0:
            # mid-epoch resume: the epoch iterator always starts at batch 0,
            # but the restored step counter is past it — skip the
            # already-trained host batches so the resumed sequence continues
            # exactly where the interrupted run stopped (cheap: skipped
            # batches never reach the device stager)
            self._log("Resuming epoch %d mid-way: skipping %d "
                      "already-trained batches" % (epoch, offset))
            host_batches = itertools.islice(host_batches, offset, None)
        staged = self._staged_batches(host_batches)

        step_in_epoch = offset if offset > 0 else 0
        t_last = time.perf_counter()
        host_wait_s = 0.0
        h2d_ms_acc = 0.0
        steps_since_log = 0
        stage_ms_acc = {}  # pipeline executor's per-stage breakdown
        while True:
            t0 = time.perf_counter()
            try:
                sb = next(staged)
            except StopIteration:
                break
            host_wait_s += time.perf_counter() - t0
            h2d_ms_acc += sb.h2d_ms
            # profiler window edges (telemetry.profile_steps; cheap int
            # compares when disabled): trace starts before step `start`
            # dispatches and stops after step `stop` completes. A flight-
            # recorder dump may ARM a window over the next K steps
            # (telemetry.recorder.arm_profile_steps) — retroactive-ish
            # profiling of an incident's aftermath; an already-armed or
            # active window is never clobbered.
            if self.recorder is not None and not self.profile.enabled:
                k = self.recorder.take_profile_request()
                if k:
                    self.profile = telemetry.ProfileWindow(
                        (gstep + 1, gstep + k),
                        self.profile.trace_dir, self.logger)
            self.profile.maybe_start(gstep + 1)
            state, metrics = self.trainer.train_step(state, sb.batch)
            step_in_epoch += 1
            gstep += 1
            steps_since_log += 1
            pipe = self.trainer._pipeline
            if pipe is not None and pipe.last_stage_ms:
                # host-side wall times the executor already measured — no
                # device sync here beyond what its own timing did
                for k, v in pipe.last_stage_ms.items():
                    stage_ms_acc[k] = stage_ms_acc.get(k, 0.0) + v
            self.profile.maybe_stop(gstep)
            faults.maybe_sigterm(gstep)  # chaos-test seam (no-op unplanned)

            at_log = step_in_epoch % self.log_interval == 0
            if at_log and self.guard_monitor.threshold > 0:
                # abort policy over the replicated guard counters: EVERY
                # host syncs the same two scalars and reaches the same
                # verdict (raising on the lead only would deadlock the
                # others in the next collective)
                with telemetry.host_readback("train.guard_monitor"):
                    gm = {k: float(metrics[k])
                          for k in ("skipped_steps", "guard_consecutive",
                                    "guard_last_bad_step") if k in metrics}
                try:
                    self.guard_monitor.check(gm, gstep)
                except resilience.GuardAbort:
                    # params are still at their last good values (the guard
                    # zero-updates poisoned steps) — save them before dying
                    telemetry.counter("train.guard.aborts").inc()
                    telemetry.emit("train.guard_abort", gstep=gstep, **gm)
                    self.ckpt.save_latest(state)
                    self.ckpt.wait()
                    raise

            if at_log and self.is_lead:
                with telemetry.host_readback("train.log_metrics"):
                    m = metrics_to_float(metrics)  # device sync, log steps only
                dt = (time.perf_counter() - t_last) / steps_since_log
                times = {
                    "step_ms": dt * 1e3,
                    "host_wait_ms": host_wait_s / steps_since_log * 1e3,
                    "h2d_ms": h2d_ms_acc / steps_since_log,
                }
                times["device_ms"] = max(
                    0.0, times["step_ms"] - times["host_wait_ms"])
                stage_ms = {k: v / steps_since_log
                            for k, v in stage_ms_acc.items()}
                self._log_training(epoch, step_in_epoch, gstep, m, times,
                                   stage_ms=stage_ms)
                t_last = time.perf_counter()
                host_wait_s = h2d_ms_acc = 0.0
                steps_since_log = 0
                stage_ms_acc = {}

            # checkpoint saves and eval are collective over the mesh: EVERY
            # process participates (orbax + jit would deadlock otherwise);
            # only logging/TB writes are lead-gated.
            did_pause = False
            if gstep > 0 and gstep % self.ckpt_interval == 0:
                # reconcile the host counter with the device's before the
                # cadence-bearing save (satellite: a drifted counter must
                # not silently shift ckpt/eval cadence after resume)
                dev_step = int(state.step)
                if dev_step != gstep:
                    if self.logger is not None:
                        self.logger.warning(
                            "host step counter drifted (host %d, device %d)"
                            " — reconciling to the device", gstep, dev_step)
                    gstep = dev_step
                self.ckpt.save_latest(state)
                self._log("Latest checkpoint saved at step %d" % gstep)
                did_pause = True
                if self.preempt.global_requested():
                    # all hosts agreed: the boundary save above is the
                    # emergency checkpoint — stop feeding and unwind
                    self.preempted = True
                    self._log("Preemption requested — stopping after the "
                              "step-%d checkpoint" % gstep)
                    break

            if gstep > 0 and (gstep == 2000 or gstep % self.eval_interval == 0) \
                    and self.val_dataset is not None:
                self.run_eval(state)
                self.ckpt.save_step(state)
                did_pause = True
            if did_pause:
                # don't charge checkpoint/eval wall-time to the step
                # breakdown of the next log interval
                t_last = time.perf_counter()
                host_wait_s = h2d_ms_acc = 0.0
                steps_since_log = 0
        return state

    # ---------------- eval ----------------

    def run_eval(self, state: TrainState) -> Dict[str, float]:
        """Full-val-set evaluation (synthesis_task.run_eval :476-507).

        Covers EVERY val example on any host count (reference: train.py:97-99
        drop_last=False). Hosts must make the same number of collective
        eval_step calls or the mesh jit deadlocks; stride-sharding is
        deterministic, so every host computes every host's batch counts
        locally and agrees without communicating. Full batches beyond the
        cross-host common count and remainder batches go through padded
        collective batches with a per-example validity weight — padding is
        excluded exactly from the weighted metrics (VERDICT r2 weak item 4
        closed: nothing is dropped multi-host)."""
        self._log("Start running evaluation on validation set:")
        for m in self.val_meters.values():
            m.reset()

        lbs = self.local_batch_size
        n_total = len(self.val_dataset)
        num_shards = jax.process_count()
        shard_counts = [(n_total - h + num_shards - 1) // num_shards
                        for h in range(num_shards)]
        common_full = min(c // lbs for c in shard_counts)
        leftover_counts = [c - common_full * lbs for c in shard_counts]
        tail_batches = -(-max(leftover_counts) // lbs)
        global_bs = self.trainer.global_batch_size()

        it = self.val_dataset.batch_iterator(
            batch_size=lbs, shuffle=False, drop_last=False,
            shard_index=jax.process_index(), num_shards=num_shards)
        eval_rng = jax.random.PRNGKey(0)
        gstep = int(state.step)
        # Fresh pyramid cache per eval: entries are keyed by image id only,
        # and the params this eval sees differ from the last one's.
        eval_cache = PyramidCache(
            capacity_bytes=self.serve_cfg.cache_bytes,
            quant=self.serve_cfg.eval_cache_quant) \
            if self.eval_encode_once else None
        full_seen = 0
        leftover = []  # host-local single-example dicts beyond common_full
        template = None  # any local example, for padding
        for i, np_batch in enumerate(it):
            n = np_batch["src_img"].shape[0]
            if template is None:
                template = {k: v[0:1] for k, v in np_batch.items()}
            if not (n == lbs and full_seen < common_full):
                leftover.extend({k: v[j:j + 1] for k, v in np_batch.items()}
                                for j in range(n))
                continue
            full_seen += 1
            if eval_cache is not None:
                batch, metrics, visuals = self._eval_batch_encode_once(
                    state, np_batch, jax.random.fold_in(eval_rng, i),
                    eval_cache)
            else:
                batch = self.trainer.put_batch(np_batch)
                metrics, visuals = self.trainer.eval_step(
                    state, batch, jax.random.fold_in(eval_rng, i))
            with telemetry.host_readback("eval.metrics"):
                m = metrics_to_float(metrics)
            for k, meter in self.val_meters.items():
                meter.update(m[k], n=global_bs)
            if i == 0 and self.tb is not None:
                self._log_val_images(gstep, batch, visuals)

        if tail_batches and template is None:
            # this host's stride shard was empty (val set smaller than the
            # host count) but it must still join the collective tail calls;
            # any real example serves as 0-weight padding content, so read
            # one through an unsharded iterator
            template = {k: v[0:1] for k, v in next(iter(
                self.val_dataset.batch_iterator(
                    batch_size=1, shuffle=False, drop_last=False,
                    shard_index=0, num_shards=1))).items()}

        for j in range(tail_batches):
            chunk = leftover[j * lbs:(j + 1) * lbs]
            w_local = np.zeros((lbs,), np.float32)
            w_local[:len(chunk)] = 1.0
            chunk = chunk + [template] * (lbs - len(chunk))
            local = {k: np.concatenate([c[k] for c in chunk], axis=0)
                     for k in chunk[0]}
            if eval_cache is not None:
                _, metrics, _ = self._eval_batch_encode_once(
                    state, local,
                    jax.random.fold_in(eval_rng, 1_000_000 + j),
                    eval_cache, w_local=w_local)
            else:
                batch = self.trainer.put_batch(local)
                weight = self.trainer.put_example_array(w_local)
                metrics = self.trainer.eval_step_masked(
                    state, batch,
                    jax.random.fold_in(eval_rng, 1_000_000 + j), weight)
            with telemetry.host_readback("eval.metrics"):
                m = metrics_to_float(metrics)
            # valid examples in THIS tail batch across all hosts
            # (deterministic from the shard counts)
            g_valid = sum(min(max(c - j * lbs, 0), lbs)
                          for c in leftover_counts)
            for k, meter in self.val_meters.items():
                meter.update(m[k], n=g_valid)

        self._log("Evaluation finished, average losses:")
        for m in self.val_meters.values():
            self._log("    %s" % m)
        if eval_cache is not None:
            s = eval_cache.stats()
            self._log("Encode-once eval: %d encodes, %d replays (%s cache, "
                      "%.1f MB)", s["misses"], s["hits"], s["quant"],
                      s["nbytes"] / 1e6)
        for k, meter in self.val_meters.items():
            self._tb("add_scalar", k + "/val", meter.avg, gstep)
        return {k: meter.avg for k, meter in self.val_meters.items()}

    def _eval_batch_encode_once(self, state: TrainState, np_batch, key,
                                eval_cache, w_local=None):
        """One eval batch with the encoder amortized across target views.

        Derives the SAME per-batch disparity sample as the fused eval step
        (fold_in(eval_rng, i) -> split -> sample_disparity), encodes only
        source images whose pyramid isn't cached (coarse-to-fine configs use
        the RNG-replaying eval_encode_c2f), and runs the batched
        render+loss half on the replayed pyramids. A source seen again
        reuses its first-seen disparity row — an RNG-level shift vs. the
        fused path (identical when val sources are distinct; the metric-
        parity test runs on a distinct-source set)."""
        B = np_batch["src_img"].shape[0]
        d_key, f_key = jax.random.split(key)  # split mirrors _eval_step_impl
        disparity = np.asarray(sample_disparity(d_key, B, self.trainer.cfg))
        c2f = self.trainer.cfg.num_bins_fine > 0
        rows = []
        for b in range(B):
            img_b = np_batch["src_img"][b:b + 1]
            iid = image_id_for(img_b)
            cached = eval_cache.get(iid)
            if cached is None:
                if c2f:
                    # coarse-to-fine: per-example encode replaying the fused
                    # step's row-b fine-plane draws (fine_rows slicing in
                    # ops/rendering.py); cache the FULL coarse+fine
                    # disparities alongside the pyramid
                    mpi_b, disp_all_b = self.trainer.eval_encode_c2f(
                        state, jnp.asarray(img_b),
                        jnp.asarray(disparity[b:b + 1]), f_key, b,
                        jnp.asarray(np_batch["K_src"][b:b + 1]), B)
                    disp_row = np.asarray(disp_all_b[0])
                else:
                    mpi_b = self.trainer.eval_encode(
                        state, jnp.asarray(img_b),
                        jnp.asarray(disparity[b:b + 1]))
                    disp_row = disparity[b]
                eval_cache.put(iid, [m[0] for m in mpi_b], disp_row)
                cached = eval_cache.get(iid)
            rows.append(cached)
        num_scales = len(rows[0][0])
        mpi_list = [jnp.stack([r[0][s] for r in rows], axis=0)
                    for s in range(num_scales)]
        disparity_all = jnp.stack([r[1] for r in rows], axis=0)
        batch = self.trainer.put_batch(np_batch)
        if w_local is None:
            metrics, visuals = self.trainer.eval_losses(
                state, mpi_list, disparity_all, batch)
            return batch, metrics, visuals
        metrics = self.trainer.eval_losses_masked(
            state, mpi_list, disparity_all, batch,
            self.trainer.put_example_array(w_local))
        return batch, metrics, None

    # ---------------- logging ----------------

    def _log(self, msg, *args):
        if self.logger is not None and self.is_lead:
            self.logger.info(msg, *args)

    def _tb(self, method, *args):
        """Non-fatal tensorboard write: a broken writer (full disk, dead
        tensorboardX backend) degrades to scalar-log-only instead of
        killing a multi-hour run; one warning, then silence."""
        if self.tb is None or self._tb_broken:
            return
        try:
            getattr(self.tb, method)(*args)
        except Exception:
            self._tb_broken = True
            if self.logger is not None:
                self.logger.warning(
                    "tensorboard writer failed — disabling TB output for "
                    "the rest of the run", exc_info=True)

    # ---------------- train-side ops plane ----------------

    def _train_health(self):
        """/healthz body: "degraded" while the non-finite guard is in a
        live skip streak or data errors burned in the last log interval.
        Reads only the log-cadence state dict — never a device value."""
        s = self._ops_state
        reasons = []
        if s["guard_consecutive"] > 0:
            reasons.append("guard skip streak: %d consecutive "
                           "non-finite steps" % int(s["guard_consecutive"]))
        if s["data_errors_delta"] > 0:
            reasons.append("%d data errors in the last log interval"
                           % int(s["data_errors_delta"]))
        return {"status": "degraded" if reasons else "ok",
                "reasons": reasons, "gstep": int(s["gstep"]),
                "data_errors": int(s["data_errors"])}

    def _train_progress(self):
        """/progress body: position plus an ETA extrapolated from the
        recent st1 step_ms history (None until the first log interval)."""
        s = self._ops_state
        total = int(s["epochs"]) * self.trainer.steps_per_epoch
        avg_ms = (sum(self._step_hist) / len(self._step_hist)
                  if self._step_hist else None)
        remaining = max(0, total - int(s["gstep"]))
        return {"gstep": int(s["gstep"]), "epoch": int(s["epoch"]),
                "epochs": int(s["epochs"]),
                "steps_per_epoch": self.trainer.steps_per_epoch,
                "total_steps": total,
                "step_ms_avg": None if avg_ms is None else round(avg_ms, 3),
                "eta_s": None if avg_ms is None
                else round(remaining * avg_ms / 1e3, 1)}

    def _log_training(self, epoch, step, gstep, m, times, stage_ms=None):
        lrs = current_lrs(self.config, self.trainer.steps_per_epoch, gstep)
        data_stats = PIPELINE_STATS.snapshot()
        # ops-plane state: written only here (log cadence, lead host), read
        # by the /healthz and /progress handlers
        self._step_hist.append(times["step_ms"])
        prev_errors = self._ops_state["data_errors"]
        self._ops_state.update(
            gstep=gstep, epoch=epoch,
            guard_consecutive=m.get("guard_consecutive", 0.0),
            data_errors=data_stats["data_errors"],
            data_errors_delta=max(
                0, data_stats["data_errors"] - prev_errors))
        # the FROZEN parseable step-time line (schema st1 — see
        # telemetry/stepline.py; tools/step_breakdown.py and obs_report
        # both read it through the one shared parser)
        # appended stage_*_ms keys (pipeline executor breakdown) ride the
        # same line under the append-only rule — absent when pipelining
        # is off, so non-pipeline logs are byte-identical to before
        step_line = telemetry.format_step_line(times,
                                               data_stats["data_errors"],
                                               extra=stage_ms or None)
        lr_label, lr_group = self.trainer.LOG_LR
        self._log(
            "epoch [%.3d] step [%d] global_step = %d total_loss = %.4f "
            "%s = %.7f step_time = %.3fs\n%s        %s"
            % (epoch, step, gstep, m["loss"], lr_label, lrs[lr_group],
               times["step_ms"] / 1e3, self.trainer.log_summary(m),
               step_line))
        diag = " ".join("%s = %.6g" % (k, m[k]) for k in (
            "skipped_steps", "guard_consecutive", "warp_fallback_frac",
            "warp_subband_frac") if k in m)
        if diag:
            self._log("        diag: " + diag)
        if self.telem.enabled:
            # registry mirror: per-interval time breakdown histograms, the
            # guard's cumulative counters as gauges (they live in the
            # TrainState buffer; the registry mirrors at log cadence only —
            # no new per-step host sync), pipeline health gauges
            for k in TIME_METER_KEYS:
                telemetry.histogram("train." + k).record(times[k])
            for src_key, gauge_name in (
                    ("skipped_steps", "train.guard.skipped_steps"),
                    ("guard_consecutive", "train.guard.consecutive"),
                    ("warp_fallback_frac", "train.warp_fallback_frac"),
                    ("warp_subband_frac", "train.warp_subband_frac")):
                if src_key in m:
                    telemetry.gauge(gauge_name).set(m[src_key])
            for gauge_name, value in self.trainer.log_gauges(m, times).items():
                telemetry.gauge(gauge_name).set(value)
            telemetry.emit(
                "train.step", gstep=gstep, epoch=epoch,
                loss=round(float(m["loss"]), 6),
                psnr_tgt=round(float(m.get("psnr_tgt", 0.0)), 4),
                **{k: round(times[k], 3) for k in TIME_METER_KEYS},
                data_errors=data_stats["data_errors"])
            # flight-recorder feeds, log cadence only: the st1 line and a
            # rolling registry snapshot land in the black-box rings; a
            # data-error burst past the configured floor trips a bundle
            # (async — this is the hot loop's logging path)
            if self.recorder is not None:
                self.recorder.observe_stepline(step_line)
                self.recorder.snapshot_metrics(scope="train")
                burst = self.telem.recorder_data_error_burst
                delta = self._ops_state["data_errors_delta"]
                if burst > 0 and delta >= burst:
                    self.recorder.trigger(
                        "train.data_error_burst", sync=False, gstep=gstep,
                        data_errors_delta=int(delta))
            # per-layer-group stats (training.layer_stats): the jitted step
            # returns them as "layers/<group>.<stat>" scalar metrics — they
            # arrived in the same log-cadence readback as everything else.
            # Regrouped into one train.layers event + registry histograms.
            layer_groups: Dict[str, Dict[str, float]] = {}
            for k in m:
                if not k.startswith("layers/"):
                    continue
                group, stat = k[len("layers/"):].split(".", 1)
                layer_groups.setdefault(group, {})[stat] = \
                    round(float(m[k]), 6)
                telemetry.histogram(
                    "train.layers." + k[len("layers/"):]).record(m[k])
            if layer_groups:
                telemetry.emit("train.layers", gstep=gstep,
                               groups=layer_groups)
        for k, meter in self.time_meters.items():
            meter.update(times[k])
            self._tb("add_scalar", "time/" + k, times[k], gstep)
        self._tb("add_scalar", "data/errors", data_stats["data_errors"],
                 gstep)
        # diagnostics beyond the fixed reference meter set (e.g.
        # warp_fallback_frac from the guarded warp backends, the
        # non-finite-guard counters) get meters on first sight so they
        # reach the epoch summaries and TB too
        for k in m:
            if k not in self.train_meters:
                self.train_meters[k] = AverageMeter("train_" + k)
        for k, meter in self.train_meters.items():
            if k not in m:
                continue  # meter from a previous backend config
            meter.update(m[k])
            self._tb("add_scalar", k + "/train", m[k], gstep)

    def _log_val_images(self, gstep, batch, visuals):
        """Tensorboard image grids (synthesis_task.log_val :509-548);
        non-fatal — see _tb. Declared readback: whole image tensors come
        to host here, once per eval."""
        with telemetry.host_readback("eval.val_images"):
            self._log_val_images_inner(gstep, batch, visuals)

    def _log_val_images_inner(self, gstep, batch, visuals):
        def grid(x_bchw):
            x = np.asarray(x_bchw)
            return np.clip(np.concatenate(list(x), axis=2), 0.0, 1.0)

        src = np.transpose(np.asarray(batch["src_img"]), (0, 3, 1, 2))
        tgt = np.transpose(np.asarray(batch["tgt_img"]), (0, 3, 1, 2))
        self._tb("add_image", "00_src_images", grid(src), gstep)
        self._tb("add_image", "01_gt_tgt_images", grid(tgt), gstep)
        self._tb("add_image", "02_syn_src_images/step_%d" % gstep,
                 grid(visuals["src_imgs_syn"]), gstep)
        self._tb("add_image", "03_syn_src_disparity_map/step_%d" % gstep,
                 grid(disparity_normalization_vis(
                     np.asarray(visuals["src_disparity_syn"]))), gstep)
        self._tb("add_image", "04_syn_tgt_images/step_%d" % gstep,
                 grid(visuals["tgt_imgs_syn"]), gstep)
        self._tb("add_image", "05_syn_tgt_disparity_map/step_%d" % gstep,
                 grid(disparity_normalization_vis(
                     np.asarray(visuals["tgt_disparity_syn"]))), gstep)
