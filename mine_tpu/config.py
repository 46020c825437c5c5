"""Flat-key YAML configuration, CLI-compatible with the reference.

The reference merges three levels (default YAML <- dataset YAML <- extra JSON)
and rejects unknown keys with asserts (reference: train.py:30-56). We keep the
exact same key space (reference: configs/params_default.yaml) so reference
configs remain usable, and add a typed accessor layer on top.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional, Tuple

import yaml

# Directory with our shipped configs (same key space as reference configs/).
CONFIG_DIR = os.path.join(os.path.dirname(__file__), "configs")

# What a config may name as the homography warp (ops/warp.homography_warp):
# "xla" = the gather, "pallas_diff" = the banded MXU kernels fwd+bwd with a
# runtime gather fallback (kernels/warp_vjp.py). "auto" picks by platform.
TRAINING_WARP_BACKENDS = ("auto", "xla", "pallas_diff")
SERVE_WARP_BACKENDS = ("xla", "pallas_diff")


def load_config(config_path: str,
                extra_config: Optional[str] = None,
                default_config_path: Optional[str] = None) -> Dict[str, Any]:
    """3-level config merge: default YAML <- dataset YAML <- extra JSON string.

    Unknown keys in the dataset/extra levels raise (reference: train.py:39,43).
    """
    if default_config_path is None:
        default_config_path = os.path.join(os.path.dirname(config_path) or CONFIG_DIR,
                                           "params_default.yaml")
        if not os.path.exists(default_config_path):
            default_config_path = os.path.join(CONFIG_DIR, "params_default.yaml")

    with open(default_config_path, "r") as f:
        config = yaml.safe_load(f)

    if config_path and os.path.abspath(config_path) != os.path.abspath(default_config_path):
        with open(config_path, "r") as f:
            dataset_config = yaml.safe_load(f) or {}
        for k in dataset_config:
            if k not in config:
                raise KeyError(f"Unknown config key in {config_path}: {k}")
        config.update(dataset_config)

    if extra_config:
        extra = json.loads(extra_config) if isinstance(extra_config, str) else extra_config
        for k in extra:
            if k not in config:
                raise KeyError(f"Unknown extra config key: {k}")
        config.update(extra)

    return postprocess(config)


def postprocess(config: Dict[str, Any]) -> Dict[str, Any]:
    """Comma-string -> int list for gpus/decay steps (reference: train.py:54-55)."""
    for key in ("training.gpus", "lr.decay_steps"):
        if key in config and not isinstance(config[key], list):
            config[key] = [int(s) for s in str(config[key]).split(",")]
    return config


def save_config(config: Dict[str, Any], path: str) -> None:
    cfg = {k: v for k, v in config.items() if _is_yaml_safe(v)}
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)


def _is_yaml_safe(v: Any) -> bool:
    if isinstance(v, (str, int, float, bool, type(None))):
        return True
    if isinstance(v, (list, tuple)):
        return all(_is_yaml_safe(x) for x in v)
    if isinstance(v, dict):
        return all(_is_yaml_safe(x) for x in v.values())
    return False


@dataclasses.dataclass(frozen=True)
class MPIConfig:
    """Static (trace-time) hyperparameters of the MPI rendering path.

    Hashable so it can close over jitted functions. Mirrors the `mpi.*`,
    `loss.*` and relevant `training.*`/`data.*` keys of the reference config.
    """
    # mpi.*
    num_bins_coarse: int = 32
    num_bins_fine: int = 0
    disparity_start: float = 1.0
    disparity_end: float = 0.001
    use_alpha: bool = False
    is_bg_depth_inf: bool = False
    valid_mask_threshold: float = 2.0
    fix_disparity: bool = False
    # loss.*
    smoothness_lambda_v1: float = 0.0
    smoothness_lambda_v2: float = 0.01
    smoothness_gmin: float = 2.0
    smoothness_grad_ratio: float = 0.1
    # training.* / data.*
    src_rgb_blending: bool = True
    use_multi_scale: bool = True
    # "xla" | "pallas_diff" | "plane_scan": backend for the novel-view
    # composite inside the loss graph (pallas_diff = fused Pallas forward +
    # custom-VJP backward; plane_scan = distributed plane-axis transparency
    # scan for plane-parallel meshes, ops/plane_scan.py)
    # dataclass defaults are the NEUTRAL xla backends (safe on any
    # platform); the shipped YAML default is "auto", resolved by
    # mpi_config_from_dict to pallas_diff on TPU / xla elsewhere
    composite_backend: str = "xla"
    # a resolved TRAINING_WARP_BACKENDS value: training-path homography warp
    warp_backend: str = "xla"
    # fwd AND bwd band: since the round-4 transposed-splat backward the
    # Pallas VJP mirrors the forward's band placement, so one knob covers
    # both (the earlier backward-specific "oband" — sized for the 54+-row
    # target touch spans of vertically-compressing near planes — is gone;
    # the transposed form has no such constraint)
    warp_band: int = 48
    # warp value dtype ("float32" | "bfloat16"): matmul operands in the
    # pallas_diff kernels (bf16 doubles MXU rate) AND gather storage on the
    # xla backend (bf16 halves the volume's HBM traffic); either
    # way ~2^-8 relative value rounding, accumulation/lerp stays f32
    warp_dtype: str = "float32"
    # SSIM Toeplitz-einsum matmul precision ("highest" | "default"):
    # "highest" forces f32 MXU passes for the 11x11 Gaussian blur —
    # matches the reference's conv2d numerics exactly; "default" lets the
    # platform pick (bf16 passes on TPU: ~2e-3 blur / ~3e-3 SSIM shift,
    # but 57ms -> 2ms on v5e). Mirrors the warp_dtype speed/accuracy knob.
    ssim_precision: str = "highest"
    use_disparity_loss: bool = True   # disp_lambda=0 for flowers/kitti_raw/dtu
    use_scale_factor: bool = True     # scale_factor=1 for flowers/kitti_raw/dtu
    img_h: int = 384
    img_w: int = 512
    # model.*
    pos_encoding_multires: int = 10
    num_layers: int = 50
    sigma_dropout_rate: float = 0.0
    # optional explicit disparity bin edges (S+1 descending values); active
    # only when its length is num_bins_coarse+1 (synthesis_task.py:36,46)
    disparity_list: tuple = ()

    @property
    def num_bins_total(self) -> int:
        return self.num_bins_coarse + self.num_bins_fine


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Fault-tolerance knobs (train/resilience.py; README "Fault
    tolerance"). All host-side policy — nothing here changes the numerics
    of a healthy run."""
    # training.guard_nonfinite: all-finite check over loss + global
    # grad-norm inside the jitted step; a poisoned step becomes a
    # zero-update (step still increments)
    guard_nonfinite: bool = True
    # training.guard_skip_threshold: abort after this many CONSECUTIVE
    # skipped steps (<=0: never abort, keep skipping)
    guard_skip_threshold: int = 25
    # training.checkpoint_keep: retain only the newest K step checkpoints
    # (0 = keep all)
    checkpoint_keep: int = 0
    # data.max_item_retries / data.item_retry_backoff: bounded per-item
    # load retry before deterministic quarantine-and-replace
    max_item_retries: int = 2
    item_retry_backoff: float = 0.05


def resilience_config_from_dict(config: Dict[str, Any]) -> ResilienceConfig:
    g = config.get
    out = ResilienceConfig(
        guard_nonfinite=bool(g("training.guard_nonfinite", True)),
        guard_skip_threshold=int(g("training.guard_skip_threshold", 25)),
        checkpoint_keep=int(g("training.checkpoint_keep", 0) or 0),
        max_item_retries=int(g("data.max_item_retries", 2)),
        item_retry_backoff=float(g("data.item_retry_backoff", 0.05)),
    )
    if out.checkpoint_keep < 0:
        raise ValueError(
            f"training.checkpoint_keep must be >= 0, got {out.checkpoint_keep}")
    if out.max_item_retries < 0:
        raise ValueError(
            f"data.max_item_retries must be >= 0, got {out.max_item_retries}")
    if out.item_retry_backoff < 0:
        raise ValueError(f"data.item_retry_backoff must be >= 0, "
                         f"got {out.item_retry_backoff}")
    return out


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Pipeline-parallel training knobs (mine_tpu/parallel/pipeline.py;
    README "Pipeline training"). All default off: with enabled=False the
    fused train step runs untouched (bitwise-parity bar, like the other
    default-off subsystems)."""
    # training.pipeline.enabled: route train_step through the staged
    # GPipe-style executor instead of the fused jitted step
    enabled: bool = False
    # training.pipeline.microbatches: microbatches per optimizer step; the
    # global batch must divide evenly. Grads/metrics are averaged over
    # microbatches; BN stats thread sequentially (ghost BN, like
    # training.decoder_plane_chunks)
    microbatches: int = 1
    # training.pipeline.stages: mesh sub-slices the stage chain is placed
    # on; must divide the mesh's data axis (1 = all stages share the full
    # mesh, the single-host default)
    stages: int = 1
    # training.pipeline.hbm_budget_gb: per-chip HBM budget the planner
    # (tools/pipeline_plan.py) cuts stages under; 0 = unconstrained
    hbm_budget_gb: float = 0.0


def pipeline_config_from_dict(config: Dict[str, Any]) -> PipelineConfig:
    g = config.get

    def val(key, default):
        # None (an empty YAML value) means the default; an explicit 0 does
        # NOT — it must reach the range checks below, not coerce to 1
        v = g(key, default)
        return default if v is None else v

    out = PipelineConfig(
        enabled=bool(g("training.pipeline.enabled", False)),
        microbatches=int(val("training.pipeline.microbatches", 1)),
        stages=int(val("training.pipeline.stages", 1)),
        hbm_budget_gb=float(val("training.pipeline.hbm_budget_gb", 0.0)),
    )
    if out.microbatches < 1:
        raise ValueError(
            f"training.pipeline.microbatches must be >= 1, "
            f"got {out.microbatches}")
    if out.stages < 1:
        raise ValueError(
            f"training.pipeline.stages must be >= 1, got {out.stages}")
    if out.stages > 4:
        # the stage chain is encoder -> decoder -> render -> loss: there is
        # nothing to place on a fifth slice
        raise ValueError(
            f"training.pipeline.stages must be <= 4 (the staged step has "
            f"4 sub-programs), got {out.stages}")
    if out.hbm_budget_gb < 0:
        raise ValueError(
            f"training.pipeline.hbm_budget_gb must be >= 0, "
            f"got {out.hbm_budget_gb}")
    return out


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Render-only serving knobs (mine_tpu/serve; README "Serving").

    Host-side policy plus trace-time shape/quant choices — nothing here
    changes the numerics of the bf16/float32 render paths (bf16 dequant is
    a widening cast; serve/cache.py)."""
    # serve.cache_bytes: LRU byte budget for cached quantized MPI planes
    # (0 = unbounded)
    cache_bytes: int = 0
    # serve.cache_quant: float32 | bf16 | int8 cache storage (serve/cache.py)
    cache_quant: str = "bf16"
    # serve.max_bucket: poses per device call; pose counts pad to
    # power-of-two buckets <= this, bounding the compile set
    max_bucket: int = 8
    # serve.max_requests / serve.max_wait_ms: request coalescing — the
    # batch the scheduler fills / the deadline it holds a request to
    # (serve/batcher.py)
    max_requests: int = 8
    max_wait_ms: float = 2.0
    # serve.mesh_batch / serve.mesh_model: serving mesh axes (pow2) — poses
    # along "batch", the S plane axis along "model" (serve/shardmap.py);
    # 1x1 keeps the single-device engine
    mesh_batch: int = 1
    mesh_model: int = 1
    # serve.cache_shards: key-range partition of the plane cache; each
    # shard owns a contiguous hash range under cache_bytes/shards
    # (serve/fleet.py)
    cache_shards: int = 1
    # serve.scheduler: continuous (deadline loop keeping pow2 buckets
    # filled, the fleet default) | micro (the PR-5 one-shot linger)
    scheduler: str = "continuous"
    # serve.eval_encode_once: eval loop encodes each DISTINCT source image
    # once and reuses the cached MPI pyramid for all its target views
    # (single-host, num_bins_fine=0; train/loop.py run_eval)
    eval_encode_once: bool = False
    # serve.eval_cache_quant: quantization of the eval-loop encode cache;
    # float32 (default) keeps metric parity with the per-pair path exact
    eval_cache_quant: str = "float32"
    # serve.ops_port: opt-in HTTP ops endpoint (/metrics /healthz /slo
    # /traces/recent; telemetry/export.py) on 127.0.0.1:<port>; 0 = off
    ops_port: int = 0
    # serve.slo_objective_ms / slo_target / slo_window_s: rolling-window
    # SLO tracking (telemetry/slo.py) — breach when the window's p99
    # exceeds the objective; objective 0 disables breach detection while
    # the window percentiles keep flowing to /slo and the gauges
    slo_objective_ms: float = 0.0
    slo_target: float = 0.99
    slo_window_s: float = 60.0
    # serve.default_tier: priority class for requests that don't name one
    # (0 best-effort, 1 standard, >= 2 critical; serve/admission.py)
    default_tier: int = 1
    # serve.request_deadline_ms: default end-to-end deadline — requests
    # still queued past it are purged un-rendered and resolve to
    # DeadlineExceeded; 0 = no deadline
    request_deadline_ms: float = 0.0
    # serve.encode_retries / encode_backoff_ms: bounded retry of transient
    # sync-encode failures with exponential jittered backoff
    # (serve/engine.py); 0 retries = fail on first error (PR-10 behavior)
    encode_retries: int = 0
    encode_backoff_ms: float = 10.0
    # serve.shard_fail_threshold: consecutive placement failures that mark
    # a cache shard dead and fail its key range over (serve/fleet.py)
    shard_fail_threshold: int = 3
    # serve.admission.*: load-shedding controller (serve/admission.py) —
    # disabled by default so the serve path is bitwise-identical to the
    # pre-admission behavior until opted in. Signals with threshold <= 0
    # are ignored; shed_factor scales each threshold up to the shed level;
    # hysteresis < 1 makes de-escalation sticky (no flapping).
    admission_enabled: bool = False
    admission_burn_max: float = 1.0
    admission_queue_high: int = 64
    admission_inflight_high: int = 256
    admission_shed_factor: float = 2.0
    admission_hysteresis: float = 0.7
    # serve.aot_store_dir: directory of serialized compiled render
    # executables (serve/aot.py) — warmup loads instead of tracing, live
    # compiles write back; "" (default) disables the store entirely
    aot_store_dir: str = ""
    # serve.encoder_quant: off | int8 — int8 stores the sync-encode
    # encoder weights symmetric per-output-channel with dequant fused into
    # the jitted encode (serve/encoder.py); off is byte-identical to the
    # pre-quantization path
    encoder_quant: str = "off"
    # serve.session.*: streaming video sessions (serve/session.py) — every
    # Kth frame keyframe-encodes, the frames between render against the
    # cached keyframe MPI. keyframe_every=1 (the default) encodes EVERY
    # frame: bitwise-identical to the per-frame-encode path, i.e. the
    # feature is effectively off until the cadence is raised.
    session_keyframe_every: int = 1
    # serve.session.drift_budget: adaptive re-key threshold; 0 (default)
    # disables adaptive mode (the fixed cadence alone decides)
    session_drift_budget: float = 0.0
    # serve.session.drift_mode: probe (mean |rendered - observed| on a
    # stride-downsampled probe, causal/lagged) | pose (pose-delta norm
    # against the keyframe pose, gates the current frame)
    session_drift_mode: str = "probe"
    # serve.session.probe_stride: downsample stride of the probe proxy
    session_probe_stride: int = 4
    # serve.session.keyframe_tier: priority of keyframe encodes (default
    # critical — under admission pressure interpolation sheds first)
    session_keyframe_tier: int = 2
    # serve.warp_backend: warp backend of the serving engine, one of
    # SERVE_WARP_BACKENDS
    warp_backend: str = "xla"
    # serve.ring.*: multi-host elastic ring (serve/ring.py, serve/hostnet.py)
    # — a front tier routes requests by content-hash key range to owner
    # HOSTS (the fleet.shard_for_key discipline, one ring across the
    # fleet), each host running today's ServeFleet as its local slice
    # behind a stdlib HTTP/JSON transport. Disabled by default: ring-off
    # is bitwise-identical to the single-process fleet.
    ring_enabled: bool = False
    # serve.ring.hosts: comma-separated host:port peers forming the ring
    # (ring-slot order = list order); "" with ring enabled = a one-host
    # ring of this process only
    ring_hosts: str = ""
    # serve.ring.drain_timeout_s: max seconds a SIGTERM'd/drained host
    # waits for in-flight requests before closing anyway
    ring_drain_timeout_s: float = 30.0
    # serve.ring.autoscale.*: the pressure-driven host autoscaler
    # (serve/ring.py Autoscaler). Pressure >= 1.0 for `evals` consecutive
    # evaluations grows the fleet one host; pressure < hysteresis for
    # `evals` consecutive evaluations shrinks it one host; cooldown_s of
    # quiet follows every action — the admission ladder's stickiness, so
    # it never oscillates. Off constructs nothing.
    autoscale_enabled: bool = False
    autoscale_min_hosts: int = 1
    autoscale_max_hosts: int = 4
    autoscale_evals: int = 3
    autoscale_hysteresis: float = 0.5
    autoscale_cooldown_s: float = 30.0
    # serve.net.*: wire hardening of the ring transport (serve/hostnet.py
    # NetPolicy) — split connect/read timeouts, bounded jittered retries,
    # per-host circuit breakers, deadline propagation over the hop, and
    # the front's heartbeat failure detector (suspect = route around,
    # front-local; only sustained connection-REFUSED marks dead).
    # Disabled by default: net-off constructs none of it and the wire
    # behavior is bitwise-identical to the unhardened transport.
    net_enabled: bool = False
    net_connect_timeout_s: float = 5.0
    net_read_timeout_s: float = 60.0
    net_retries: int = 2
    net_backoff_ms: float = 20.0
    net_breaker_threshold: int = 5
    net_breaker_reset_s: float = 10.0
    net_probe_interval_s: float = 0.0
    net_suspect_misses: int = 3
    net_dead_misses: int = 10
    net_revive_probes: int = 2
    # serve.wire.*: the binary wire fabric (serve/wire.py WirePolicy) —
    # mtpu-wire1 length-prefixed frames with raw little-endian tensors
    # instead of JSON/base64, an f32|bf16|int8 tensor codec for
    # image/rgb/depth payloads, and the front's owner-coalescer (N
    # same-owner requests per linger window leave as ONE batch frame).
    # ALL default off: wire-off negotiates nothing, frames nothing, and
    # the transport is bitwise-identical to the JSON path (test-pinned).
    wire_format: str = "json"
    wire_codec: str = "f32"
    wire_coalesce_ms: float = 0.0
    wire_coalesce_max: int = 8


@dataclasses.dataclass(frozen=True)
class LMServeConfig:
    """The token server's knobs (`serve.lm.*`; serve/lm_scheduler.py)."""
    max_step_tokens: int = 2048
    max_running: int = 32
    page_size: int = 256
    cache_tokens: int = 393216
    chunk_buckets: Tuple[int, ...] = (512, 2048)
    context_buckets: Tuple[int, ...] = (8192, 16384, 34816)
    window_cache_tokens: int = 0


def lm_serve_config_from_dict(config: Dict[str, Any]) -> LMServeConfig:
    g = lambda k: config["serve.lm." + k]                      # noqa: E731
    out = LMServeConfig(
        max_step_tokens=int(g("max_step_tokens")),
        max_running=int(g("max_running")), page_size=int(g("page_size")),
        cache_tokens=int(g("cache_tokens")),
        chunk_buckets=tuple(int(c) for c in g("chunk_buckets")),
        context_buckets=tuple(int(c) for c in g("context_buckets")),
        window_cache_tokens=int(g("window_cache_tokens") or 0))
    if out.window_cache_tokens % out.page_size:
        raise ValueError("serve.lm.window_cache_tokens must be whole pages "
                         "of serve.lm.page_size")
    if out.cache_tokens % out.page_size:
        raise ValueError("serve.lm.cache_tokens must be whole pages of "
                         "serve.lm.page_size")
    if max(out.chunk_buckets) > out.max_step_tokens:
        raise ValueError("serve.lm.chunk_buckets may not exceed "
                         "serve.lm.max_step_tokens")
    if any(c % out.page_size for c in out.context_buckets):
        raise ValueError("serve.lm.context_buckets must be whole pages")
    return out


def serve_config_from_dict(config: Dict[str, Any]) -> ServeConfig:
    g = config.get
    out = ServeConfig(
        cache_bytes=int(g("serve.cache_bytes", 0) or 0),
        cache_quant=str(g("serve.cache_quant", "bf16")),
        max_bucket=int(g("serve.max_bucket", 8)),
        max_requests=int(g("serve.max_requests", 8)),
        max_wait_ms=float(g("serve.max_wait_ms", 2.0)),
        mesh_batch=int(g("serve.mesh_batch", 1)),
        mesh_model=int(g("serve.mesh_model", 1)),
        cache_shards=int(g("serve.cache_shards", 1)),
        scheduler=str(g("serve.scheduler", "continuous")),
        eval_encode_once=bool(g("serve.eval_encode_once", False)),
        eval_cache_quant=str(g("serve.eval_cache_quant", "float32")),
        ops_port=int(g("serve.ops_port", 0) or 0),
        slo_objective_ms=float(g("serve.slo_objective_ms", 0.0) or 0.0),
        slo_target=float(g("serve.slo_target", 0.99)),
        slo_window_s=float(g("serve.slo_window_s", 60.0)),
        default_tier=int(g("serve.default_tier", 1)),
        request_deadline_ms=float(g("serve.request_deadline_ms", 0.0) or 0.0),
        encode_retries=int(g("serve.encode_retries", 0) or 0),
        encode_backoff_ms=float(g("serve.encode_backoff_ms", 10.0)),
        shard_fail_threshold=int(g("serve.shard_fail_threshold", 3)),
        admission_enabled=bool(g("serve.admission.enabled", False)),
        admission_burn_max=float(g("serve.admission.burn_max", 1.0) or 0.0),
        admission_queue_high=int(g("serve.admission.queue_high", 64) or 0),
        admission_inflight_high=int(
            g("serve.admission.inflight_high", 256) or 0),
        admission_shed_factor=float(g("serve.admission.shed_factor", 2.0)),
        admission_hysteresis=float(g("serve.admission.hysteresis", 0.7)),
        aot_store_dir=str(g("serve.aot_store_dir", "") or ""),
        # YAML 1.1 reads a bare `off` as boolean False — accept it
        encoder_quant=("off" if g("serve.encoder_quant", "off") is False
                       else str(g("serve.encoder_quant", "off"))),
        session_keyframe_every=int(g("serve.session.keyframe_every", 1)),
        session_drift_budget=float(
            g("serve.session.drift_budget", 0.0) or 0.0),
        session_drift_mode=str(g("serve.session.drift_mode", "probe")),
        session_probe_stride=int(g("serve.session.probe_stride", 4)),
        session_keyframe_tier=int(g("serve.session.keyframe_tier", 2)),
        warp_backend=str(g("serve.warp_backend", "xla")),
        ring_enabled=bool(g("serve.ring.enabled", False)),
        ring_hosts=str(g("serve.ring.hosts", "") or ""),
        ring_drain_timeout_s=float(
            g("serve.ring.drain_timeout_s", 30.0) or 0.0),
        autoscale_enabled=bool(g("serve.ring.autoscale.enabled", False)),
        autoscale_min_hosts=int(g("serve.ring.autoscale.min_hosts", 1)),
        autoscale_max_hosts=int(g("serve.ring.autoscale.max_hosts", 4)),
        autoscale_evals=int(g("serve.ring.autoscale.evals", 3)),
        autoscale_hysteresis=float(
            g("serve.ring.autoscale.hysteresis", 0.5)),
        autoscale_cooldown_s=float(
            g("serve.ring.autoscale.cooldown_s", 30.0) or 0.0),
        net_enabled=bool(g("serve.net.enabled", False)),
        net_connect_timeout_s=float(
            g("serve.net.connect_timeout_s", 5.0)),
        net_read_timeout_s=float(g("serve.net.read_timeout_s", 60.0)),
        net_retries=int(g("serve.net.retries", 2)),
        net_backoff_ms=float(g("serve.net.backoff_ms", 20.0)),
        net_breaker_threshold=int(g("serve.net.breaker_threshold", 5)),
        net_breaker_reset_s=float(g("serve.net.breaker_reset_s", 10.0)),
        net_probe_interval_s=float(
            g("serve.net.probe_interval_s", 0.0) or 0.0),
        net_suspect_misses=int(g("serve.net.suspect_misses", 3)),
        net_dead_misses=int(g("serve.net.dead_misses", 10)),
        net_revive_probes=int(g("serve.net.revive_probes", 2)),
        wire_format=str(g("serve.wire.format", "json")),
        wire_codec=str(g("serve.wire.codec", "f32")),
        wire_coalesce_ms=float(g("serve.wire.coalesce_ms", 0.0) or 0.0),
        wire_coalesce_max=int(g("serve.wire.coalesce_max", 8)),
    )
    from mine_tpu.serve.cache import QUANT_MODES
    for key, val in (("serve.cache_quant", out.cache_quant),
                     ("serve.eval_cache_quant", out.eval_cache_quant)):
        if val not in QUANT_MODES:
            raise ValueError(
                f"{key} must be one of {'|'.join(QUANT_MODES)}, got {val!r}")
    if out.cache_bytes < 0:
        raise ValueError(
            f"serve.cache_bytes must be >= 0, got {out.cache_bytes}")
    if out.max_bucket < 1 or (out.max_bucket & (out.max_bucket - 1)) != 0:
        raise ValueError(
            f"serve.max_bucket must be a power of two >= 1, "
            f"got {out.max_bucket}")
    if out.max_requests < 1:
        raise ValueError(
            f"serve.max_requests must be >= 1, got {out.max_requests}")
    if out.max_wait_ms < 0:
        raise ValueError(
            f"serve.max_wait_ms must be >= 0, got {out.max_wait_ms}")
    for key, val in (("serve.mesh_batch", out.mesh_batch),
                     ("serve.mesh_model", out.mesh_model)):
        # pow2 mesh axes compose with the engine's pow2 shape buckets:
        # every bucket divides evenly across the mesh (serve/shardmap.py)
        if val < 1 or (val & (val - 1)) != 0:
            raise ValueError(
                f"{key} must be a power of two >= 1, got {val}")
    if out.cache_shards < 1:
        raise ValueError(
            f"serve.cache_shards must be >= 1, got {out.cache_shards}")
    if out.scheduler not in ("continuous", "micro"):
        raise ValueError(
            f"serve.scheduler must be continuous|micro, "
            f"got {out.scheduler!r}")
    if out.warp_backend not in SERVE_WARP_BACKENDS:
        raise ValueError(
            f"serve.warp_backend must be {'|'.join(SERVE_WARP_BACKENDS)}, "
            f"got {out.warp_backend!r}")
    if not 0 <= out.ops_port <= 65535:
        raise ValueError(
            f"serve.ops_port must be in [0, 65535], got {out.ops_port}")
    if out.slo_objective_ms < 0:
        raise ValueError(
            f"serve.slo_objective_ms must be >= 0, "
            f"got {out.slo_objective_ms}")
    if not 0.0 < out.slo_target < 1.0:
        raise ValueError(
            f"serve.slo_target must be in (0, 1), got {out.slo_target}")
    if out.slo_window_s <= 0:
        raise ValueError(
            f"serve.slo_window_s must be > 0, got {out.slo_window_s}")
    if out.default_tier < 0:
        raise ValueError(
            f"serve.default_tier must be >= 0, got {out.default_tier}")
    if out.request_deadline_ms < 0:
        raise ValueError(
            f"serve.request_deadline_ms must be >= 0, "
            f"got {out.request_deadline_ms}")
    if out.encode_retries < 0:
        raise ValueError(
            f"serve.encode_retries must be >= 0, got {out.encode_retries}")
    if out.encode_backoff_ms < 0:
        raise ValueError(
            f"serve.encode_backoff_ms must be >= 0, "
            f"got {out.encode_backoff_ms}")
    if out.shard_fail_threshold < 1:
        raise ValueError(
            f"serve.shard_fail_threshold must be >= 1, "
            f"got {out.shard_fail_threshold}")
    if out.admission_shed_factor <= 1.0:
        raise ValueError(
            f"serve.admission.shed_factor must be > 1, "
            f"got {out.admission_shed_factor}")
    if not 0.0 < out.admission_hysteresis <= 1.0:
        raise ValueError(
            f"serve.admission.hysteresis must be in (0, 1], "
            f"got {out.admission_hysteresis}")
    from mine_tpu.serve.encoder import ENCODER_QUANT_MODES
    if out.encoder_quant not in ENCODER_QUANT_MODES:
        raise ValueError(
            f"serve.encoder_quant must be one of "
            f"{'|'.join(ENCODER_QUANT_MODES)}, got {out.encoder_quant!r}")
    if out.session_keyframe_every < 1:
        raise ValueError(
            f"serve.session.keyframe_every must be >= 1, "
            f"got {out.session_keyframe_every}")
    if out.session_drift_budget < 0:
        raise ValueError(
            f"serve.session.drift_budget must be >= 0, "
            f"got {out.session_drift_budget}")
    from mine_tpu.serve.session import DRIFT_MODES
    if out.session_drift_mode not in DRIFT_MODES:
        raise ValueError(
            f"serve.session.drift_mode must be one of "
            f"{'|'.join(DRIFT_MODES)}, got {out.session_drift_mode!r}")
    if out.session_probe_stride < 1:
        raise ValueError(
            f"serve.session.probe_stride must be >= 1, "
            f"got {out.session_probe_stride}")
    if out.session_keyframe_tier < 0:
        raise ValueError(
            f"serve.session.keyframe_tier must be >= 0, "
            f"got {out.session_keyframe_tier}")
    if out.ring_drain_timeout_s < 0:
        raise ValueError(
            f"serve.ring.drain_timeout_s must be >= 0, "
            f"got {out.ring_drain_timeout_s}")
    for host in (h.strip() for h in out.ring_hosts.split(",") if h.strip()):
        # host:port peers; the split-off tail must be a port number
        if ":" not in host or not host.rsplit(":", 1)[1].isdigit():
            raise ValueError(
                f"serve.ring.hosts entries must be host:port, got {host!r}")
    if out.autoscale_min_hosts < 1:
        raise ValueError(
            f"serve.ring.autoscale.min_hosts must be >= 1, "
            f"got {out.autoscale_min_hosts}")
    if out.autoscale_max_hosts < out.autoscale_min_hosts:
        raise ValueError(
            f"serve.ring.autoscale.max_hosts must be >= min_hosts "
            f"({out.autoscale_min_hosts}), got {out.autoscale_max_hosts}")
    if out.autoscale_evals < 1:
        raise ValueError(
            f"serve.ring.autoscale.evals must be >= 1, "
            f"got {out.autoscale_evals}")
    if not 0.0 < out.autoscale_hysteresis < 1.0:
        raise ValueError(
            f"serve.ring.autoscale.hysteresis must be in (0, 1), "
            f"got {out.autoscale_hysteresis}")
    if out.autoscale_cooldown_s < 0:
        raise ValueError(
            f"serve.ring.autoscale.cooldown_s must be >= 0, "
            f"got {out.autoscale_cooldown_s}")
    if out.net_connect_timeout_s <= 0:
        raise ValueError(
            f"serve.net.connect_timeout_s must be > 0, "
            f"got {out.net_connect_timeout_s}")
    if out.net_read_timeout_s <= 0:
        raise ValueError(
            f"serve.net.read_timeout_s must be > 0, "
            f"got {out.net_read_timeout_s}")
    if out.net_retries < 0:
        raise ValueError(
            f"serve.net.retries must be >= 0, got {out.net_retries}")
    if out.net_backoff_ms < 0:
        raise ValueError(
            f"serve.net.backoff_ms must be >= 0, got {out.net_backoff_ms}")
    if out.net_breaker_threshold < 1:
        raise ValueError(
            f"serve.net.breaker_threshold must be >= 1, "
            f"got {out.net_breaker_threshold}")
    if out.net_breaker_reset_s < 0:
        raise ValueError(
            f"serve.net.breaker_reset_s must be >= 0, "
            f"got {out.net_breaker_reset_s}")
    if out.net_probe_interval_s < 0:
        raise ValueError(
            f"serve.net.probe_interval_s must be >= 0, "
            f"got {out.net_probe_interval_s}")
    if out.net_suspect_misses < 1:
        raise ValueError(
            f"serve.net.suspect_misses must be >= 1, "
            f"got {out.net_suspect_misses}")
    if out.net_dead_misses < 1:
        raise ValueError(
            f"serve.net.dead_misses must be >= 1, "
            f"got {out.net_dead_misses}")
    if out.net_revive_probes < 1:
        raise ValueError(
            f"serve.net.revive_probes must be >= 1, "
            f"got {out.net_revive_probes}")
    from mine_tpu.serve.wire import WIRE_CODECS, WIRE_FORMATS
    if out.wire_format not in WIRE_FORMATS:
        raise ValueError(
            f"serve.wire.format must be one of {'|'.join(WIRE_FORMATS)}, "
            f"got {out.wire_format!r}")
    if out.wire_codec not in WIRE_CODECS:
        raise ValueError(
            f"serve.wire.codec must be one of {'|'.join(WIRE_CODECS)}, "
            f"got {out.wire_codec!r}")
    if out.wire_coalesce_ms < 0:
        raise ValueError(
            f"serve.wire.coalesce_ms must be >= 0, "
            f"got {out.wire_coalesce_ms}")
    if out.wire_coalesce_max < 1:
        raise ValueError(
            f"serve.wire.coalesce_max must be >= 1, "
            f"got {out.wire_coalesce_max}")
    return out


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Observability knobs (mine_tpu/telemetry; README "Observability").

    Entirely host-side — nothing here changes jitted numerics or adds a
    per-step device sync (tests/test_telemetry.py pins that bitwise)."""
    # telemetry.enabled: master switch for the metrics registry mirror and
    # the JSONL event sink wiring in the train loop / serve CLI (the frozen
    # step-time LOG line prints regardless — it predates this layer)
    enabled: bool = True
    # telemetry.events_path: JSONL event stream destination; "" defaults to
    # <workspace>/events.jsonl (train loop) or <output_dir>/events.jsonl
    # (serve_cli). The MINE_TPU_TELEMETRY_EVENTS env var outranks both.
    events_path: str = ""
    # telemetry.profile_steps: [start, stop] global-step range (inclusive)
    # to capture under jax.profiler; empty/null disables
    profile_steps: tuple = ()
    # telemetry.profile_dir: trace destination; "" -> <workspace>/profile
    profile_dir: str = ""
    # telemetry.trace_sample: request-trace head-sampling rate in [0, 1]
    # (telemetry/tracing.py); 0 disables tracing, 1 traces every request.
    # Sampling gates TRACES only — metrics/SLO see every request.
    trace_sample: float = 0.0
    # telemetry.events_max_mb: rotate the JSONL event stream when it
    # crosses this size (MiB), keeping telemetry.events_keep rotated
    # segments; 0 = today's unbounded single file
    events_max_mb: float = 0.0
    # telemetry.events_keep: rotated segments retained alongside the live
    # file (events.jsonl.1 newest ... .K oldest)
    events_keep: int = 3
    # telemetry.resource_sample_s: process-vitals sampler cadence in
    # seconds (telemetry/resource.py: RSS/threads/fds/GC gauges); 0 = off
    resource_sample_s: float = 0.0
    # telemetry.recorder.*: the flight recorder (telemetry/recorder.py).
    # enabled=False constructs nothing — bitwise-parity bar unchanged.
    recorder_enabled: bool = False
    # telemetry.recorder.dir: incident bundle directory; "" defaults to
    # <workspace>/incidents (train) or alongside the events stream (serve)
    recorder_dir: str = ""
    # telemetry.recorder.events: ring size of the retained event tail
    recorder_events: int = 256
    # telemetry.recorder.steplines: retained recent st1 step lines
    recorder_steplines: int = 64
    # telemetry.recorder.snapshots: retained rolling registry snapshots
    # (the pre-incident baselines tools/postmortem.py diffs against)
    recorder_snapshots: int = 16
    # telemetry.recorder.debounce_s: minimum seconds between bundles — a
    # breach storm inside one window collapses to ONE bundle
    recorder_debounce_s: float = 60.0
    # telemetry.recorder.keep: keep-last-K bundle retention
    recorder_keep: int = 5
    # telemetry.recorder.arm_profile_steps: after a train-plane dump, arm
    # a profiler window over the next K steps (0 = off)
    recorder_arm_profile_steps: int = 0
    # telemetry.recorder.data_error_burst: trigger a bundle when one log
    # interval absorbs >= this many NEW data-pipeline errors (0 = off)
    recorder_data_error_burst: int = 0


def telemetry_config_from_dict(config: Dict[str, Any]) -> TelemetryConfig:
    g = config.get
    steps = g("telemetry.profile_steps") or ()
    if isinstance(steps, (int, float, str)):
        raise ValueError(
            f"telemetry.profile_steps must be a [start, stop] list, "
            f"got {steps!r}")
    out = TelemetryConfig(
        enabled=bool(g("telemetry.enabled", True)),
        events_path=str(g("telemetry.events_path", "") or ""),
        profile_steps=tuple(int(s) for s in steps),
        profile_dir=str(g("telemetry.profile_dir", "") or ""),
        trace_sample=float(g("telemetry.trace_sample", 0.0) or 0.0),
        events_max_mb=float(g("telemetry.events_max_mb", 0.0) or 0.0),
        events_keep=int(g("telemetry.events_keep", 3) or 3),
        resource_sample_s=float(
            g("telemetry.resource_sample_s", 0.0) or 0.0),
        recorder_enabled=bool(g("telemetry.recorder.enabled", False)),
        recorder_dir=str(g("telemetry.recorder.dir", "") or ""),
        recorder_events=int(g("telemetry.recorder.events", 256) or 256),
        recorder_steplines=int(
            g("telemetry.recorder.steplines", 64) or 64),
        recorder_snapshots=int(
            g("telemetry.recorder.snapshots", 16) or 16),
        recorder_debounce_s=float(
            g("telemetry.recorder.debounce_s", 60.0) or 0.0),
        recorder_keep=int(g("telemetry.recorder.keep", 5) or 5),
        recorder_arm_profile_steps=int(
            g("telemetry.recorder.arm_profile_steps", 0) or 0),
        recorder_data_error_burst=int(
            g("telemetry.recorder.data_error_burst", 0) or 0),
    )
    if out.profile_steps and (
            len(out.profile_steps) != 2 or out.profile_steps[0] < 1
            or out.profile_steps[1] < out.profile_steps[0]):
        raise ValueError(
            "telemetry.profile_steps must be [start, stop] with "
            f"1 <= start <= stop, got {list(out.profile_steps)}")
    if not 0.0 <= out.trace_sample <= 1.0:
        raise ValueError(
            f"telemetry.trace_sample must be in [0, 1], "
            f"got {out.trace_sample}")
    if out.events_max_mb < 0:
        raise ValueError(
            f"telemetry.events_max_mb must be >= 0, got {out.events_max_mb}")
    if out.events_keep < 1:
        raise ValueError(
            f"telemetry.events_keep must be >= 1, got {out.events_keep}")
    if out.resource_sample_s < 0:
        raise ValueError(
            f"telemetry.resource_sample_s must be >= 0, "
            f"got {out.resource_sample_s}")
    for field, floor in (("recorder_events", 1), ("recorder_steplines", 1),
                         ("recorder_snapshots", 1), ("recorder_keep", 1),
                         ("recorder_arm_profile_steps", 0),
                         ("recorder_data_error_burst", 0)):
        v = getattr(out, field)
        if v < floor:
            key = "telemetry.recorder." + field[len("recorder_"):]
            raise ValueError(f"{key} must be >= {floor}, got {v}")
    if out.recorder_debounce_s < 0:
        raise ValueError(
            f"telemetry.recorder.debounce_s must be >= 0, "
            f"got {out.recorder_debounce_s}")
    return out


# Datasets for which the sparse-3D-point disparity loss and scale factor are
# disabled (reference: synthesis_task.py:213-214,297).
_NO_DISP_DATASETS = ("flowers", "kitti_raw", "dtu")


def validate_model_shapes(cfg: "MPIConfig") -> None:
    """The encoder taps strides 2..32 and the decoder's upsample ladder
    doubles back up — non-multiple-of-32 shapes desync the skip concats
    deep in the graph (opaque concatenate errors). Model consumers
    (SynthesisTrainer, VideoGenerator) call this; dataset loaders don't,
    since loader-side resizing has no stride constraint."""
    for k in ("img_h", "img_w"):
        v = int(getattr(cfg, k))
        if v % 32 != 0:
            raise ValueError(
                f"data.{k}={v} must be a multiple of 32 (encoder stride-32 "
                f"taps + decoder upsample ladder); nearest valid: "
                f"{v // 32 * 32} or {-(-v // 32) * 32}")


def _resolve_auto_backend(value: str) -> str:
    """"auto" -> the backend for the RUNNING platform: the Pallas
    custom-VJP pair on TPU, plain XLA elsewhere (on CPU the Pallas kernels
    would run in interpret mode — orders of magnitude slower than XLA)."""
    if value != "auto":
        return value
    from mine_tpu.kernels import on_tpu_backend
    return "pallas_diff" if on_tpu_backend() else "xla"


def mpi_config_from_dict(config: Dict[str, Any]) -> MPIConfig:
    g = config.get
    name = g("data.name", "llff")
    backend = _resolve_auto_backend(g("training.composite_backend", "auto"))
    # "pallas" (forward-only) is an internal render-path backend; the training
    # loss graph differentiates through the composite, so only the custom-VJP
    # variant is valid here.
    if backend not in ("xla", "pallas_diff", "plane_scan"):
        raise ValueError(
            f"training.composite_backend must be auto|xla|pallas_diff|"
            f"plane_scan, got {backend!r}")
    warp_backend = g("training.warp_backend", "auto")
    if warp_backend not in TRAINING_WARP_BACKENDS:
        raise ValueError(
            f"training.warp_backend must be "
            f"{'|'.join(TRAINING_WARP_BACKENDS)}, got {warp_backend!r}")
    warp_backend = _resolve_auto_backend(warp_backend)
    warp_dtype = g("training.warp_dtype", "float32")
    if warp_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"training.warp_dtype must be float32|bfloat16, "
            f"got {warp_dtype!r}")
    ssim_precision = g("training.ssim_precision", "highest")
    if ssim_precision not in ("highest", "default"):
        raise ValueError(
            f"training.ssim_precision must be highest|default, "
            f"got {ssim_precision!r}")
    return MPIConfig(
        num_bins_coarse=g("mpi.num_bins_coarse", 32),
        num_bins_fine=g("mpi.num_bins_fine", 0),
        disparity_start=g("mpi.disparity_start", 1.0),
        disparity_end=g("mpi.disparity_end", 0.001),
        use_alpha=g("mpi.use_alpha", False),
        # NOTE: the reference passes config["mpi.render_tgt_rgb_depth"] (a key
        # that never exists -> always False) where it means is_bg_depth_inf
        # (synthesis_task.py:265,273,427). We honor the key that exists.
        is_bg_depth_inf=g("mpi.is_bg_depth_inf", False),
        valid_mask_threshold=float(g("mpi.valid_mask_threshold", 2)),
        fix_disparity=g("mpi.fix_disparity", False),
        smoothness_lambda_v1=g("loss.smoothness_lambda_v1", 0.5),
        smoothness_lambda_v2=g("loss.smoothness_lambda_v2", 1.0),
        smoothness_gmin=g("loss.smoothness_gmin", 2.0),
        smoothness_grad_ratio=g("loss.smoothness_grad_ratio", 0.1),
        src_rgb_blending=g("training.src_rgb_blending", True),
        use_multi_scale=g("training.use_multi_scale", True),
        composite_backend=backend,
        warp_backend=warp_backend,
        warp_band=int(g("training.warp_band", 48)),
        warp_dtype=warp_dtype,
        ssim_precision=ssim_precision,
        # visible_point_count == 0 also disables the sparse-point terms —
        # datasets with no SfM points (public RealEstate10K) train scale-free
        use_disparity_loss=(name not in _NO_DISP_DATASETS
                            and int(g("data.visible_point_count", 256) or 0) > 0),
        use_scale_factor=(name not in _NO_DISP_DATASETS
                          and int(g("data.visible_point_count", 256) or 0) > 0),
        img_h=g("data.img_h", 384),
        img_w=g("data.img_w", 512),
        pos_encoding_multires=g("model.pos_encoding_multires", 10),
        num_layers=g("model.num_layers", 50),
        sigma_dropout_rate=float(g("model.sigma_dropout_rate", 0.0) or 0.0),
        disparity_list=tuple(float(d) for d in (g("mpi.disparity_list") or ())),
    )
