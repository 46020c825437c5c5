"""Streaming video sessions: keyframe-cadenced temporal reuse of the cache.

The serving stack renders novel views of STATIC cached MPIs; source video is
temporally redundant, so re-encoding every frame wastes the encoder on
content the previous frame already paid for. A `StreamSession` carries a
compact cached state forward instead — the PAPERS.md O(1)
autoregressive-caching idea applied to MINE's encode-once engine:

  * every Kth frame (`serve.session.keyframe_every`) is a KEYFRAME: its
    pixels ride the submit as `image=`, the engine's sync-encode path
    predicts a fresh MPI (exactly one `serve.sync_encode` per keyframe),
    and the planes land in the plane cache under a session-sticky id;
  * the frames in between are INTERPOLATED: render-only requests against
    the cached keyframe MPI at the frame's pose RELATIVE to the keyframe
    — the same jitted, pow2-bucketed render program static serving uses
    (no new compile surface beyond `serve.max_bucket`), submitted with the
    keyframe's pixels attached so a lost cache entry (shard failover,
    eviction) transparently re-encodes instead of failing the frame;
  * an ADAPTIVE mode re-keys early when a cheap drift proxy exceeds
    `serve.session.drift_budget`: mean |rendered - observed| on a
    stride-downsampled probe (causal — frame n's drift gates frame n+1),
    or the pose-delta norm against the keyframe pose (gates frame n
    itself, no render needed).

SHARD STICKINESS: every keyframe id starts with the session's fixed 8-hex
key prefix (`session_key_prefix`), so `fleet.py`'s key-range routing sends
the whole stream to ONE owner shard — a session never hops shards
mid-stream, and its keyframe residency never fragments across the fleet.
Superseded keyframes are retired from the cache (`pop`, no eviction count)
once their last in-flight frame resolves.

Keyframe encodes are tiered ABOVE interpolated renders (default
`serve.session.keyframe_tier` = critical): under admission pressure the
fleet sheds interpolation, never the encode the next K frames depend on.

Telemetry: `serve.session.*` counters/gauges (per-session drift and
keyframe age), KIND_FIELDS-pinned `serve.session_start` / `_keyframe` /
`_frame` / `_end` events, and span events distinguishing
`serve.session.keyframe_encode` from `serve.session.interp_render`.
`SessionManager` (serve/stream.py) multiplexes concurrent sessions through
the fleet's `ContinuousBatcher`.
"""

from __future__ import annotations

import hashlib
import time
from typing import Callable, Dict, Optional, Set

import numpy as np

from mine_tpu import telemetry
from mine_tpu.analysis.locks import ordered_lock

DRIFT_MODES = ("probe", "pose")

# re-key reasons carried by the serve.session_keyframe event
REASON_FIRST = "first"
REASON_CADENCE = "cadence"
REASON_DRIFT = "drift"
REASON_MANUAL = "manual"


def session_key_prefix(session_id: str) -> str:
    """Fixed leading-8-hex key prefix of a session: every keyframe id
    starts with it, so `fleet.shard_for_key` (which reads exactly the
    leading 8 hex digits) maps the WHOLE stream to one owner shard."""
    return hashlib.sha1(str(session_id).encode()).hexdigest()[:8]


def keyframe_id(prefix: str, session_id: str, frame: int) -> str:
    """Cache id of a session's keyframe at `frame`: the sticky prefix plus
    a per-keyframe unique suffix — same 40-hex shape as the content-hash
    ids (serve/cache.py image_id_for), constant key position."""
    suffix = hashlib.sha1(
        f"{session_id}/keyframe/{frame}".encode()).hexdigest()[:32]
    return prefix + suffix


def relative_pose(pose_44: np.ndarray, key_pose_44: np.ndarray) -> np.ndarray:
    """G_tgt_src from the frame's camera-from-world extrinsics to the
    keyframe's: the pose the render program warps the cached keyframe MPI
    by. Identity when the frame IS the keyframe (callers special-case that
    to keep K=1 bitwise-identical to the per-frame-encode path)."""
    return np.asarray(pose_44, np.float32) @ np.linalg.inv(
        np.asarray(key_pose_44, np.float32))


def probe_drift(rendered_3hw: np.ndarray, observed_hwc: np.ndarray,
                stride: int = 4) -> Optional[float]:
    """Cheap host-side drift proxy: mean |rendered - observed| over a
    stride-downsampled probe. Both sides are already host numpy (the
    engine's output fetch is the declared readback), so this adds no
    device sync and no compile surface. None when the shapes disagree —
    a caller streaming frames at a different resolution than the render
    simply gets no probe signal (pose mode still works)."""
    r = np.asarray(rendered_3hw, np.float32)
    o = np.asarray(observed_hwc, np.float32)
    if (o.ndim == 3 and o.shape != r.shape
            and (o.shape[2],) + o.shape[:2] == r.shape):
        o = np.transpose(o, (2, 0, 1))  # HWC -> CHW
    if r.shape != o.shape:
        return None
    s = max(1, int(stride))
    return float(np.mean(np.abs(r[:, ::s, ::s] - o[:, ::s, ::s])))


class StreamSession:
    """One streaming video session over the serve plane.

    `backend_submit(image_id, pose_44, tier=, image=) -> Future` is the
    fleet's (or a bare batcher's) submit; `cache` (optional) lets the
    session retire superseded keyframes. `process_frame` is the per-frame
    entry point — call it from ONE producer thread in frame order (the
    session lock serializes the submit, so queue order matches frame
    order). All session state sits under the rank-ordered "serve.session"
    lock (analysis/locks.py), which is safely held across the fleet submit.
    """

    def __init__(self, session_id: str,
                 backend_submit: Callable,
                 cache=None, *,
                 keyframe_every: int = 1,
                 drift_budget: float = 0.0,
                 drift_mode: str = "probe",
                 probe_stride: int = 4,
                 keyframe_tier: int = 2,
                 interp_tier: Optional[int] = None,
                 key_prefix: Optional[str] = None,
                 on_close: Optional[Callable] = None):
        if keyframe_every < 1:
            raise ValueError(
                f"keyframe_every must be >= 1, got {keyframe_every}")
        if drift_budget < 0:
            raise ValueError(
                f"drift_budget must be >= 0, got {drift_budget}")
        if drift_mode not in DRIFT_MODES:
            raise ValueError(f"drift_mode must be one of "
                             f"{'|'.join(DRIFT_MODES)}, got {drift_mode!r}")
        if probe_stride < 1:
            raise ValueError(
                f"probe_stride must be >= 1, got {probe_stride}")
        self.session_id = str(session_id)
        self._submit = backend_submit
        self._cache = cache
        self.keyframe_every = int(keyframe_every)
        self.drift_budget = float(drift_budget)
        self.drift_mode = drift_mode
        self.probe_stride = int(probe_stride)
        self.keyframe_tier = int(keyframe_tier)
        self.interp_tier = interp_tier
        self.key_prefix = (key_prefix if key_prefix is not None
                           else session_key_prefix(self.session_id))
        self._on_close = on_close
        self._lock = ordered_lock("serve.session")
        self._closed = False
        self._frame_idx = 0
        self._keyframe_id: Optional[str] = None
        self._keyframe_seq = -1
        self._keyframe_pose: Optional[np.ndarray] = None
        self._keyframe_pixels = None
        self._last_drift = 0.0
        # in-flight frames per keyframe id + ids superseded but not yet
        # poppable (their last frame is still rendering)
        self._outstanding: Dict[str, int] = {}
        self._retired: Set[str] = set()
        self.frames = 0
        self.keyframes = 0
        self.rekeys = 0  # adaptive (drift-triggered) keyframes only
        self.failed_frames = 0
        telemetry.counter("serve.session.opened").inc()
        telemetry.emit("serve.session_start", session=self.session_id,
                       keyframe_every=self.keyframe_every,
                       drift_mode=self.drift_mode,
                       drift_budget=self.drift_budget,
                       key_prefix=self.key_prefix)

    # ---------------- per-frame policy ----------------

    def _keyframe_reason(self, n: int, pose: np.ndarray) -> Optional[str]:
        """Why frame n re-keys, or None to interpolate (caller holds the
        session lock). The probe proxy is causal/lagged — frame n-1's
        measured drift gates frame n; the pose proxy gates frame n itself
        (no render needed to evaluate it)."""
        if self._keyframe_id is None:
            return REASON_FIRST
        if n - self._keyframe_seq >= self.keyframe_every:
            return REASON_CADENCE
        if self.drift_budget > 0:
            if self.drift_mode == "pose":
                delta = float(np.linalg.norm(
                    np.asarray(pose, np.float32) - self._keyframe_pose))
                if delta > self.drift_budget:
                    return REASON_DRIFT
            elif self._last_drift > self.drift_budget:
                return REASON_DRIFT
        return None

    def process_frame(self, frame, pose_44=None, force_keyframe: bool = False):
        """Submit one source frame; returns the request Future resolving to
        (rgb [3,H,W], depth [1,H,W]) f32 numpy. `frame` is the observed
        pixels in whatever form the fleet's encode_fn accepts (HWC float at
        the render resolution enables the probe drift proxy); `pose_44` the
        frame's camera extrinsics (None = static camera)."""
        pose = (np.eye(4, dtype=np.float32) if pose_44 is None
                else np.asarray(pose_44, np.float32))
        with self._lock:
            if self._closed:
                raise RuntimeError(
                    f"session {self.session_id} is closed")
            n = self._frame_idx
            self._frame_idx += 1
            reason = (REASON_MANUAL if force_keyframe
                      else self._keyframe_reason(n, pose))
            if reason is not None:
                kid = keyframe_id(self.key_prefix, self.session_id, n)
                old = self._keyframe_id
                self._keyframe_id = kid
                self._keyframe_seq = n
                self._keyframe_pose = pose
                self._keyframe_pixels = frame
                self.keyframes += 1
                if reason == REASON_DRIFT:
                    self.rekeys += 1
                    telemetry.counter("serve.session.rekeys").inc()
                telemetry.counter("serve.session.keyframes").inc()
                telemetry.emit("serve.session_keyframe",
                               session=self.session_id, frame=n,
                               image_id=kid[:12], reason=reason)
                if old is not None:
                    self._retired.add(old)
                    self._maybe_pop(old)
                # the keyframe renders at identity EXACTLY (never
                # pose @ inv(pose), which is only numerically identity):
                # K=1 streaming must stay bitwise-identical to the
                # per-frame-encode path
                rel = np.eye(4, dtype=np.float32)
                tier = self.keyframe_tier
                image = frame
                kind = "keyframe"
            else:
                kid = self._keyframe_id
                rel = relative_pose(pose, self._keyframe_pose)
                tier = self.interp_tier
                # the keyframe's pixels ride along: a lost cache entry
                # (shard death, eviction) re-encodes the KEYFRAME
                # transparently instead of failing the frame
                image = self._keyframe_pixels
                kind = "interp"
            age = n - self._keyframe_seq
            self.frames += 1
            self._outstanding[kid] = self._outstanding.get(kid, 0) + 1
            telemetry.counter("serve.session.frames").inc()
            # submit under the session lock: queue order = frame order
            # (lock ranks: session 5 < batcher.cv 10 < fleet.cache 15)
            fut = self._submit(kid, rel, tier=tier, image=image)
        t0 = time.perf_counter()
        probe = frame if (kind == "interp"
                          and self.drift_mode == "probe") else None
        fut.add_done_callback(
            lambda f: self._complete(f, kind, kid, n, age, probe, t0))
        return fut

    # ---------------- completion path ----------------

    def _complete(self, fut, kind, kid, n, age, probe, t0) -> None:
        """Done-callback: runs on the resolving (flush) thread, which holds
        no batcher locks at set_result time — safe to take the session lock
        and touch the cache. Records the keyframe-vs-interpolated span
        split, the drift proxy, and the per-frame event."""
        ms = (time.perf_counter() - t0) * 1e3
        sid = self.session_id
        if fut.exception() is not None:
            telemetry.counter("serve.session.failed_frames").inc()
            with self._lock:
                self.failed_frames += 1
                self._settle(kid)
            telemetry.emit("serve.session_frame", session=sid, frame=n,
                           age=age, drift=None, ok=False)
            return
        name = ("serve.session.keyframe_encode" if kind == "keyframe"
                else "serve.session.interp_render")
        # a done-callback interval (submit -> resolved): a pre-measured span
        telemetry.spans.record(name, int(t0 * 1e9), int(t0 * 1e9 + ms * 1e6),
                               emit=True, session=sid)
        drift = 0.0
        if probe is not None:
            rgb, _ = fut.result()
            d = probe_drift(rgb, probe, stride=self.probe_stride)
            if d is not None:
                drift = d
        with self._lock:
            if kind == "interp" and probe is not None:
                self._last_drift = drift
            self._settle(kid)
        telemetry.gauge(f"serve.session.drift.{sid}").set(drift)
        telemetry.gauge(f"serve.session.age.{sid}").set(age)
        telemetry.emit("serve.session_frame", session=sid, frame=n,
                       age=age, drift=round(drift, 6))

    def _settle(self, kid: str) -> None:
        """One in-flight frame of `kid` resolved (caller holds the session
        lock); a retired keyframe with nothing left in flight pops."""
        left = self._outstanding.get(kid, 0) - 1
        if left > 0:
            self._outstanding[kid] = left
        else:
            self._outstanding.pop(kid, None)
            if kid in self._retired:
                self._retired.discard(kid)
                self._pop(kid)

    def _maybe_pop(self, kid: str) -> None:
        """Pop `kid` now if nothing is in flight against it (caller holds
        the session lock)."""
        if self._outstanding.get(kid, 0) <= 0:
            self._retired.discard(kid)
            self._pop(kid)

    def _pop(self, kid: str) -> None:
        """Best-effort cache retirement — the LRU would get there anyway;
        failures (no cache attached, entry already evicted, a shard mid-
        failover) are not a session's problem."""
        if self._cache is None:
            return
        try:
            if self._cache.pop(kid) is not None:
                telemetry.counter("serve.session.keyframes_retired").inc()
        except Exception:
            pass

    # ---------------- introspection / lifecycle ----------------

    @property
    def last_drift(self) -> float:
        with self._lock:
            return self._last_drift

    @property
    def keyframe_age(self) -> int:
        """Frames since the current keyframe (-1 before the first)."""
        with self._lock:
            if self._keyframe_seq < 0:
                return -1
            return self._frame_idx - 1 - self._keyframe_seq

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed

    def stats(self) -> dict:
        with self._lock:
            return {"session": self.session_id,
                    "frames": self.frames,
                    "keyframes": self.keyframes,
                    "rekeys": self.rekeys,
                    "failed_frames": self.failed_frames,
                    "keyframe_every": self.keyframe_every,
                    "drift_mode": self.drift_mode,
                    "drift_budget": self.drift_budget,
                    "last_drift": self._last_drift,
                    "in_flight": sum(self._outstanding.values()),
                    "closed": self._closed}

    def close(self) -> None:
        """End the stream: emit `serve.session_end`, retire the current
        keyframe (popped once its last in-flight frame resolves), and
        detach from the manager. Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            if self._keyframe_id is not None:
                self._retired.add(self._keyframe_id)
                self._maybe_pop(self._keyframe_id)
            frames, keyframes = self.frames, self.keyframes
        telemetry.counter("serve.session.closed").inc()
        telemetry.emit("serve.session_end", session=self.session_id,
                       frames=frames, keyframes=keyframes,
                       rekeys=self.rekeys,
                       failed_frames=self.failed_frames)
        if self._on_close is not None:
            self._on_close(self.session_id)
