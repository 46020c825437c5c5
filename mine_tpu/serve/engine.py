"""Render-only serving engine: cached quantized MPIs -> novel views.

Decouples MPI *prediction* (the expensive encoder-decoder pass) from view
*synthesis* (warp + composite, exactly the `render_tgt_rgb_depth` math). One
jitted program renders P poses from R cached MPIs in a single device call:

    planes [R,S,4,H,W] (quantized)   ──dequant──┐
    disparity [R,S], K/K_inv [R,3,3] ───────────┤ gather by idx [P]
    idx [P] int32, G_tgt_src [P,4,4] ───────────┴─> render_tgt_rgb_depth
                                                    -> rgb [P,3,H,W], depth

The four cached channels are all the warp moves: the plane points the
composite needs are evaluated in closed form at the warp's coordinates
(geometry.plane_xyz_tgt_at), so nothing is built over the R resident images.

Pose and entry counts are padded to power-of-two buckets (identity poses /
repeated entries, results sliced back), so the compile set is BOUNDED by
log2(max_bucket) x log2(max_requests) per (shape, quant, warp_impl) instead
of one executable per request size; `warmup` pre-traces the buckets through
the persistent compile cache (utils.configure_compile_cache). Every op in
the program is per-batch-row independent (einsums over the batch dim,
gather, elementwise, cumprod over S), so padding does not perturb real rows
— the engine parity tests assert this bitwise on CPU.

Dequantization is fused into the jitted program: the cache-resident form
(bf16 / int8, serve/cache.py) is what crosses HBM, and the bf16 widening
cast keeps the render bitwise-identical to rendering host-dequantized
planes.
"""

from __future__ import annotations

import logging
import random
import time
import warnings
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from mine_tpu import geometry, telemetry
from mine_tpu.ops import rendering
from mine_tpu.serve.cache import MPICache, MPIEntry, image_id_for
from mine_tpu.testing import faults

_log = logging.getLogger(__name__)

_warned_sync_encode = set()

# the graceful-degradation ladder's quant step-down (serve/admission.py):
# a degraded request's sync encode lands at the next-cheaper storage mode
DEGRADE_QUANT = {"float32": "bf16", "bf16": "int8", "int8": "int8"}


def _warn_sync_encode(engine_key, image_id: str) -> None:
    """One-time notice that a serve request missed the cache and forced a
    synchronous encode — the slow path must be visible in logs (same
    pattern as ops/rendering._warn_backend_fallback). The `serve.sync_encode`
    counter records EVERY occurrence (the warning only fires once per
    engine, which made sustained slow-path traffic invisible)."""
    if engine_key not in _warned_sync_encode:
        _warned_sync_encode.add(engine_key)
        warnings.warn(
            f"serve cache miss for image {image_id[:12]}…: running a "
            f"SYNCHRONOUS encode on the request path (pre-encode via "
            f"RenderEngine.put/encode to keep serving render-only)")


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (>=1): the static-shape bucket a request
    count pads to, so the compile set grows with log2 of the largest batch
    ever seen instead of one executable per batch size."""
    if n < 1:
        raise ValueError(f"need at least one element, got {n}")
    b = 1
    while b < n:
        b *= 2
    return b


def _identity_poses(n: int) -> np.ndarray:
    return np.tile(np.eye(4, dtype=np.float32), (n, 1, 1))


class RenderEngine:
    """Shape-bucketed jitted render over an encode-once MPI cache.

    Single-MPI path (`render`): chunk P poses through `max_bucket`-sized
    device calls (the video generator's path). Multi-MPI path
    (`render_many`): coalesce requests against DISTINCT cached entries into
    one call (the micro-batcher's flush path, serve/batcher.py).
    """

    def __init__(self,
                 use_alpha: bool = False,
                 is_bg_depth_inf: bool = False,
                 backend: str = "xla",
                 warp_impl: str = "xla",
                 warp_band: int = 48,
                 warp_dtype: str = "float32",
                 max_bucket: int = 8,
                 cache: Optional[MPICache] = None,
                 encode_fn: Optional[Callable] = None,
                 encode_retries: int = 0,
                 encode_backoff_ms: float = 10.0,
                 aot_store=None):
        if max_bucket < 1 or (max_bucket & (max_bucket - 1)) != 0:
            raise ValueError(
                f"serve.max_bucket must be a power of two >= 1, "
                f"got {max_bucket}")
        self.use_alpha = use_alpha
        self.is_bg_depth_inf = is_bg_depth_inf
        self.backend = backend
        self.warp_impl = warp_impl
        self.warp_band = warp_band
        self.warp_dtype = warp_dtype
        self.max_bucket = max_bucket
        self.cache = cache if cache is not None else MPICache()
        # encode_fn(img_hwc) -> (mpi_rgb [S,3,H,W], mpi_sigma [S,1,H,W],
        # disparity [S], K [3,3]) — the synchronous fallback for cache
        # misses; None keeps the engine strictly render-only (miss raises)
        self.encode_fn = encode_fn
        # bounded retry for TRANSIENT sync-encode failures (a flaky encoder
        # or a shard placement racing failover): `encode_retries` extra
        # attempts with jittered exponential backoff from
        # `encode_backoff_ms`; 0 retries = fail on the first error
        self.encode_retries = int(encode_retries)
        self.encode_backoff_ms = float(encode_backoff_ms)
        # optional serve/aot.py AOTStore: first dispatch of a bucket tries
        # a store load before tracing, and live compiles write back. None
        # (the default) keeps the dispatch path byte-identical to before.
        self.aot_store = aot_store
        self.device_calls = 0
        self.sync_encodes = 0
        # cold-bucket accounting, split by how the executable arrived:
        # a live jit trace+compile vs a deserialized store artifact
        self.bucket_compiles = 0
        self.bucket_loads = 0
        # pose buckets never drop below this (the mesh subclass raises it
        # to its "batch" axis size so buckets split evenly across devices)
        self._min_pose_bucket = 1
        # (Rb, Pb, warp_impl, planes dtype) keys already dispatched: a
        # first-seen key means jit traces + compiles a new executable —
        # the compile-set growth the pow2 bucketing is meant to bound
        self._seen_buckets = set()
        # aval-key -> Compiled executable (store-loaded or live-lowered);
        # only populated when an AOTStore is attached — without one every
        # dispatch goes through the plain jit below, exactly as before
        self._aot_execs = {}
        self._render = jax.jit(self._render_impl,
                               static_argnames=("warp_impl",))

    # ---------------- cache facade ----------------

    def put(self, image_id: str, mpi_rgb_S3HW, mpi_sigma_S1HW,
            disparity_S, K_33) -> MPIEntry:
        return self.cache.put(image_id, mpi_rgb_S3HW, mpi_sigma_S1HW,
                              disparity_S, K_33)

    def encode(self, img_hwc: np.ndarray,
               image_id: Optional[str] = None) -> str:
        """Encode an image through `encode_fn` and cache the MPI; returns
        the cache key (content hash unless given)."""
        if self.encode_fn is None:
            raise ValueError("RenderEngine has no encode_fn")
        if image_id is None:
            image_id = image_id_for(img_hwc)
        if image_id not in self.cache:
            self.cache.put(image_id, *self.encode_fn(img_hwc))
        return image_id

    def _entry(self, image_id: str, image=None, traces=(),
               degraded: bool = False) -> MPIEntry:
        entry = self.cache.get(image_id)
        if entry is not None:
            return entry
        if self.encode_fn is None or image is None:
            raise KeyError(
                f"image {image_id[:12]}… not cached and no synchronous "
                f"encode path (pass image= and set encode_fn)")
        # exactly once per miss, whatever the retry loop does below — the
        # counter's contract is "every sync encode", not "every attempt"
        self.sync_encodes += 1
        telemetry.counter("serve.sync_encode").inc()
        quant = None
        if degraded:
            # degradation ladder: a degraded request's encode lands at the
            # next-cheaper storage mode (None = already at the floor)
            step = DEGRADE_QUANT.get(self.cache.quant)
            quant = step if step != self.cache.quant else None
        attempts = max(0, self.encode_retries) + 1
        # every traced request waiting on this entry pays the encode: the
        # span lands in each of their traces ("encode"), not just the one
        # that missed
        with telemetry.span("serve.sync_encode", riders=traces,
                            rider_name="encode", image_id=image_id[:12],
                            sync=True):
            for attempt in range(attempts):
                try:
                    faults.on_encode(image_id)  # chaos seam (no-op unplanned)
                    result = self.encode_fn(image)
                    entry = (self.cache.put(image_id, *result, quant=quant)
                             if quant is not None
                             else self.cache.put(image_id, *result))
                    break
                except Exception:
                    if attempt + 1 >= attempts:
                        raise
                    telemetry.counter("serve.encode_retry").inc()
                    # jittered exponential backoff: transient faults heal,
                    # and concurrent retriers decorrelate
                    delay_s = (self.encode_backoff_ms / 1e3) * (2 ** attempt)
                    time.sleep(delay_s * (0.5 + 0.5 * random.random()))
        if attempt:
            # a retry recovered: the one-time warning would cry wolf about
            # a path that self-healed — log at debug, keep the warning slot
            # unconsumed for a genuine clean-miss slow path
            telemetry.counter("serve.encode_retry_recovered").inc()
            _log.debug("sync encode for %s recovered after %d retr%s",
                       image_id[:12], attempt,
                       "y" if attempt == 1 else "ies")
        else:
            _warn_sync_encode(id(self), image_id)
        telemetry.emit("serve.sync_encode", image_id=image_id[:12],
                       total=self.sync_encodes, retries=attempt,
                       degraded=degraded)
        return entry

    # ---------------- jitted render ----------------

    def _render_impl(self, planes, scales, disp, K, K_inv, idx, G,
                     warp_impl: str):
        """planes [R,S,4,H,W] (quantized) + request gather idx [P] +
        poses G [P,4,4] -> (rgb [P,3,H,W], depth [P,1,H,W])."""
        x = planes.astype(jnp.float32)
        if planes.dtype == jnp.int8:
            x = x * scales  # fused dequant: int8 never leaves this program
        rgb = x[:, :, 0:3]
        sigma = x[:, :, 3:4]
        res = rendering.render_tgt_rgb_depth(
            rgb[idx], sigma[idx], disp[idx], G,
            K_inv[idx], K[idx],
            use_alpha=self.use_alpha,
            is_bg_depth_inf=self.is_bg_depth_inf,
            backend=self.backend,
            warp_impl=warp_impl,
            warp_band=self.warp_band,
            warp_dtype=self.warp_dtype)
        return res.rgb, res.depth

    def _place(self, planes, scales, disp, K, K_inv, idx, poses):
        """Device-placement hook before dispatch. The base engine lets jit
        commit operands to the default device; the mesh engine
        (serve/shardmap.py) overrides this to device_put each operand under
        its NamedSharding so the jitted program spans the serving mesh."""
        return planes, scales, disp, K, K_inv, idx, poses

    def _render_span_fields(self) -> dict:
        """Extra fields for a request trace's "render" span; the mesh
        subclass adds its mesh shape so a waterfall shows which fleet
        topology rendered the request."""
        return {}

    # ---------------- AOT executable store (serve/aot.py) ----------------

    def _mesh_desc(self) -> str:
        """Mesh-shape component of the AOT program key; the mesh subclass
        overrides so e.g. a 2x1 fleet never loads a 1x1 executable."""
        return "1x1"

    def _aval_key(self, Rb: int, Pb: int, warp_impl: str, dtype: str,
                  S: int, H: int, W: int, has_scales: bool) -> tuple:
        """The in-process executable-cache key: everything that changes the
        program's input avals. Derivable both from staged arrays (dispatch)
        and from entry metadata + bucket sizes (warmup-from-store)."""
        return (Rb, Pb, warp_impl, dtype, S, H, W, has_scales)

    def _program_key(self, Rb: int, Pb: int, warp_impl: str, dtype: str,
                     S: int, H: int, W: int, has_scales: bool) -> dict:
        """The store's content-address input: the aval key plus every
        engine static baked into the traced program, the mesh shape, and
        the environment fingerprint (serve/aot.py)."""
        from mine_tpu.serve import aot as _aot
        return {
            "program": "serve_render",
            "entries_bucket": Rb, "poses_bucket": Pb,
            "warp_impl": warp_impl, "dtype": dtype,
            "planes": [S, H, W], "scaled": has_scales,
            "mesh": self._mesh_desc(),
            "engine": {
                "use_alpha": self.use_alpha,
                "is_bg_depth_inf": self.is_bg_depth_inf,
                "backend": self.backend,
                "warp_band": self.warp_band,
                "warp_dtype": self.warp_dtype,
            },
            "fingerprint": _aot.env_fingerprint(),
        }

    def _dispatch(self, args, warp_impl: str):
        """Run the render program on staged args. Without a store this IS
        `self._render` (plain jit). With one, resolve a Compiled executable
        per aval key — store load, else a live `lower().compile()` written
        back — and invoke it with the DYNAMIC args only (`warp_impl` is
        baked into the compiled program). Returns (rgb, depth, source)
        where source is "jit" | "load" | "compile"."""
        if self.aot_store is None:
            rgb, depth = self._render(*args, warp_impl)
            return rgb, depth, "jit"
        planes, scales, _, _, _, _, poses = args
        key = self._aval_key(planes.shape[0], poses.shape[0], warp_impl,
                             str(planes.dtype), planes.shape[1],
                             planes.shape[-2], planes.shape[-1],
                             scales is not None)
        exe = self._aot_execs.get(key)
        source = "warm"
        if exe is None:
            pkey = self._program_key(*key)
            exe = self.aot_store.load(pkey)
            source = "load"
            if exe is None:
                # miss or failed deserialize: live compile, write back so
                # the NEXT replica boots warm (the store is an accelerator,
                # never a correctness dependency)
                from mine_tpu.serve import aot as _aot
                with _aot.fresh_compile():
                    exe = self._render.lower(*args,
                                             warp_impl=warp_impl).compile()
                self.aot_store.save(pkey, exe)
                source = "compile"
            self._aot_execs[key] = exe
        rgb, depth = exe(*args)
        return rgb, depth, source

    def _register_store_hit(self, bucket, key) -> bool:
        """Warmup hook: try loading `bucket`'s executable from the store;
        on a hit register it (no trace, no render) and account the
        cold-bucket event as a LOAD. Returns hit."""
        pkey = self._program_key(*key)
        t0 = time.perf_counter()
        exe = self.aot_store.load(pkey)
        if exe is None:
            return False
        self._aot_execs[key] = exe
        self._seen_buckets.add(bucket)
        load_ms = (time.perf_counter() - t0) * 1e3
        self.bucket_loads += 1
        telemetry.counter("serve.bucket_loads").inc()
        telemetry.emit("serve.bucket_compile", entries_bucket=bucket[0],
                       poses_bucket=bucket[1], warp_impl=bucket[2],
                       dtype=bucket[3], compile_ms=round(load_ms, 3),
                       store_hit=True, backend=bucket[2])
        return True

    def _call(self, entries: Sequence[MPIEntry], idx: np.ndarray,
              poses: np.ndarray, warp_impl: Optional[str],
              traces: Optional[Sequence] = None):
        """Bucket R and P, pad, dispatch ONE device call, slice. One
        `serve.render_call` span whose children are `serve.render.pad_place`
        (stack, pad, place; a traced rider's "pad") and `serve.render.device`
        (dispatch -> views on the host; a rider's "render"), itself split
        into `.dispatch`, `.device_wait` and `serve.render_fetch`."""
        traces = traces or ()
        warp_impl = warp_impl or self.warp_impl
        P = poses.shape[0]
        Pb = max(pow2_bucket(P), self._min_pose_bucket)
        Rb = pow2_bucket(len(entries))
        with telemetry.span("serve.render_call", entries_bucket=Rb,
                            poses_bucket=Pb, poses=P) as call:
            with telemetry.span("serve.render.pad_place", riders=traces,
                                rider_name="pad", entries_bucket=Rb,
                                poses_bucket=Pb, padded_poses=Pb - P):
                args = self._stack_pad_place(entries, idx, poses, Rb, Pb)
            dtype = str(args[0].dtype)
            bucket = (Rb, Pb, warp_impl, dtype)
            compiled = bucket not in self._seen_buckets
            # serve.render_call_ms is the WARM latency: a bucket's first
            # visit is a serve.bucket_compile event instead (below)
            call.histogram = not compiled
            call.fields["compiled"] = compiled
            with telemetry.span("serve.render.device", riders=traces,
                                rider_name="render", warp_impl=warp_impl,
                                compiled=compiled,
                                **self._render_span_fields()):
                faults.on_render()  # chaos seam: slow device (no-op unplanned)
                with telemetry.span("serve.render.dispatch"):
                    rgb, depth, source = self._dispatch(args, warp_impl)
                self.device_calls += 1
                # waiting and copying are two spans; np.asarray below would
                # make this same sync
                with telemetry.span("serve.render.device_wait"):
                    jax.block_until_ready((rgb, depth))
                with telemetry.host_readback("serve.render_fetch"):
                    out = np.asarray(rgb[:P]), np.asarray(depth[:P])
        if compiled:
            # first dispatch of this (shape-bucket, impl, dtype) key: the
            # executable arrived either via a live jit trace+compile or a
            # store load (serve/aot.py), so this call's time is cold-path
            # dominated — recorded as a cold-bucket event, NOT into the
            # warm-latency histogram it would wreck
            self._seen_buckets.add(bucket)
            store_hit = source == "load"
            if store_hit:
                self.bucket_loads += 1
                telemetry.counter("serve.bucket_loads").inc()
            else:
                self.bucket_compiles += 1
                telemetry.counter("serve.bucket_compiles").inc()
            telemetry.emit("serve.bucket_compile", entries_bucket=Rb,
                           poses_bucket=Pb, warp_impl=warp_impl,
                           dtype=dtype, compile_ms=round(call.ms, 3),
                           store_hit=store_hit, backend=warp_impl)
        else:
            # per-backend label (a separate registry name, not a schema
            # change): lets obs_report attribute warm render-time movement
            # to the kernel backend that produced it
            telemetry.histogram(
                f"serve.render_call_ms[{warp_impl}]").record(call.ms)
        return out

    def _stack_pad_place(self, entries: Sequence[MPIEntry], idx: np.ndarray,
                         poses: np.ndarray, Rb: int, Pb: int):
        """The render program's arguments for one call: entries stacked and
        padded to Rb, poses and gather indices padded to Pb, placed."""
        P, R = poses.shape[0], len(entries)
        if P < Pb:
            poses = np.concatenate([poses, _identity_poses(Pb - P)], axis=0)
            idx = np.concatenate([idx, np.zeros(Pb - P, idx.dtype)])
        if len({str(e.planes.dtype) for e in entries}) > 1:
            # degraded placements (serve/admission.py) can coalesce entries
            # of different storage dtypes into one batch; stacking would
            # silently promote. Widen host-side to f32 — the dequant the
            # program would fuse anyway, so values are identical, at the
            # cost of this one call's HBM compression
            planes = jnp.stack([e.dequantized() for e in entries])
            scales = None
        else:
            planes = jnp.stack([e.planes for e in entries])
            scales = None
            if entries[0].scales is not None:
                scales = jnp.stack([e.scales for e in entries])
        disp = jnp.stack([e.disparity for e in entries])
        K = jnp.stack([e.K for e in entries])
        if R < Rb:
            # pad by repeating entry 0: all-valid data, never gathered
            def pad_r(a):
                return jnp.concatenate(
                    [a, jnp.broadcast_to(a[:1], (Rb - R,) + a.shape[1:])])
            planes, disp, K = pad_r(planes), pad_r(disp), pad_r(K)
            if scales is not None:
                scales = pad_r(scales)
        K_inv = geometry.inverse_intrinsics(K)
        return self._place(planes, scales, disp, K, K_inv,
                           jnp.asarray(idx, jnp.int32),
                           jnp.asarray(poses, jnp.float32))

    # ---------------- public render paths ----------------

    def render(self, image_id: str, poses_P44: np.ndarray,
               warp_impl: Optional[str] = None,
               image=None, trace=None) -> Tuple[np.ndarray, np.ndarray]:
        """All P poses against ONE cached MPI -> (rgb [P,3,H,W],
        depth [P,1,H,W]) f32 numpy. Full max_bucket chunks, then one
        pow2-bucketed remainder call. `trace` attaches a request trace
        (telemetry/tracing.py): every chunk's pad/render spans — and a
        sync encode, if this call pays one — land in it."""
        chunk_traces = [trace] if trace is not None else None
        entry = self._entry(image_id, image=image,
                            traces=chunk_traces or ())
        poses = np.asarray(poses_P44, np.float32)
        P = poses.shape[0]
        rgbs, depths = [], []
        for i in range(0, P, self.max_bucket):
            chunk = poses[i:i + self.max_bucket]
            rgb, depth = self._call(
                [entry], np.zeros(chunk.shape[0], np.int32), chunk,
                warp_impl, traces=chunk_traces)
            rgbs.append(rgb)
            depths.append(depth)
        return np.concatenate(rgbs), np.concatenate(depths)

    def render_many(self, requests: Sequence[Tuple[str, np.ndarray]],
                    warp_impl: Optional[str] = None,
                    traces: Optional[Sequence] = None,
                    images: Optional[Sequence] = None,
                    degraded: Optional[Sequence[bool]] = None
                    ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Coalesced path: [(image_id, pose [4,4])...] across DISTINCT
        cached MPIs -> one device call; per-request (rgb, depth) in order.
        `traces` aligns with `requests` (None entries fine): each traced
        request gets this dispatch's pad/render spans. `images` aligns too:
        a request carrying its source pixels lets a cache miss fall back to
        the synchronous encode exactly like `render(image=...)` — the
        batcher's flush path forwards them. `degraded` (also aligned): an
        entry whose EVERY requester is degraded encodes at the stepped-down
        cache quant on a miss (one full-fidelity rider keeps the shared
        entry full-fidelity)."""
        if not requests:
            return []
        if traces is None:
            traces = [None] * len(requests)
        if images is None:
            images = [None] * len(requests)
        if degraded is None:
            degraded = [False] * len(requests)
        # de-duplication and the cache look-ups (a miss pays its own
        # serve.sync_encode span inside)
        with telemetry.span("serve.render.gather", n=len(requests)):
            order: List[str] = []
            for image_id, _ in requests:
                if image_id not in order:
                    order.append(image_id)
            entries = [
                self._entry(i,
                            image=next((im for (rid, _), im
                                        in zip(requests, images)
                                        if im is not None and rid == i),
                                       None),
                            traces=[t for (rid, _), t
                                    in zip(requests, traces)
                                    if t is not None and rid == i],
                            degraded=all(d for (rid, _), d
                                         in zip(requests, degraded)
                                         if rid == i))
                for i in order]
            idx = np.asarray([order.index(i) for i, _ in requests], np.int32)
            poses = np.stack([np.asarray(p, np.float32)
                              for _, p in requests])
        rgb, depth = self._call(entries, idx, poses, warp_impl,
                                traces=[t for t in traces if t is not None])
        return [(rgb[j], depth[j]) for j in range(len(requests))]

    def warmup(self, image_id: str,
               pose_counts: Optional[Sequence[int]] = None,
               warp_impl: Optional[str] = None,
               entries_counts: Sequence[int] = (1,)) -> None:
        """Make the bucketed programs hot against a cached entry. Without
        an AOT store this pre-traces through JAX's persistent compile cache
        (utils.configure_compile_cache), exactly as before. With one
        (serve/aot.py), each bucket first tries a store load — registering
        the executable with zero program compiles — and only a miss falls
        back to the live render (which compiles and writes back). A store
        warmup then sweeps one cheap render per pose count that pads into
        a warmed bucket: the render programs are loaded, but the
        post-dispatch output slice/fetch for a REMAINDER count still
        compiles lazily per count, and on a truly cold replica those tiny
        compiles would otherwise land on the first odd-sized requests
        (cold-p99 must ~= warm-p99, the ROADMAP metric). `entries_counts`
        extends coverage to multi-entry buckets (the coalesced
        render_many path); the default matches the historic single-entry
        warmup."""
        from mine_tpu.utils import configure_compile_cache
        configure_compile_cache()
        if pose_counts is None:
            pose_counts, b = [], 1
            while b <= self.max_bucket:
                pose_counts.append(b)
                b *= 2
        warp = warp_impl or self.warp_impl
        entry = (self._entry(image_id)
                 if self.aot_store is not None
                 or any(r > 1 for r in entries_counts) else None)
        for r in entries_counts:
            for n in pose_counts:
                if self.aot_store is not None:
                    Rb = pow2_bucket(r)
                    Pb = max(pow2_bucket(n), self._min_pose_bucket)
                    dtype = str(entry.planes.dtype)
                    bucket = (Rb, Pb, warp, dtype)
                    if bucket in self._seen_buckets:
                        continue
                    S, _, H, W = entry.planes.shape
                    key = self._aval_key(Rb, Pb, warp, dtype, S, H, W,
                                         entry.scales is not None)
                    if self._register_store_hit(bucket, key):
                        continue
                if r == 1:
                    self.render(image_id, _identity_poses(n),
                                warp_impl=warp_impl)
                else:
                    self._call([entry] * r, np.zeros(n, np.int32),
                               _identity_poses(n), warp_impl)
        if self.aot_store is not None and pose_counts:
            limit = min(self.max_bucket,
                        max(max(pow2_bucket(n), self._min_pose_bucket)
                            for n in pose_counts))
            for n in range(1, limit + 1):
                self.render(image_id, _identity_poses(n),
                            warp_impl=warp_impl)
