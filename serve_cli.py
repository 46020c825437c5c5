#!/usr/bin/env python
"""Render-only serving CLI: encode each image once, render trajectories from
the shared quantized MPI cache (README "Serving").

  python serve_cli.py --checkpoint_path ws/v1/checkpoint_latest \
      --data_path photos/ --output_dir out/

Where infer_cli.py is one-shot (one image -> its videos), this CLI is the
serving engine's front door: ONE RenderEngine + MPICache (serve.* config
keys) shared across every input image, so repeated or interleaved requests
for the same image skip the encoder entirely. Prints the cache stats line
and views/s at exit. Accepts a single image file or a directory of images;
checkpoint handling (params.yaml next to the checkpoint, .npz or orbax)
matches infer_cli.py.

A token model is served through the same door (`model.family: moe_mla`):

  python serve_cli.py --config_path mine_tpu/configs/params_kimi_k2p5.yaml \
      --data_path requests.jsonl --output_dir out/ [--seed 0]

builds the token server (`serve/lm_scheduler.py build_server`: weights from
`--seed`, latent cache, step engine, scheduler), sends every line of
`requests.jsonl` ({"doc_id", "document": [ids], "question": [ids],
"max_tokens"}) and writes `answers.jsonl`. Which member of the family is
served follows from the YAML's `lm.*` keys alone:
`mine_tpu/configs/params_dots3_note.yaml` (full layers under a learned
sparse selection mixed with sliding-window layers; three kinds of cached
row) goes through the same server.
"""

import argparse
import json
import os
import time

IMG_EXTS = (".jpg", ".jpeg", ".png", ".bmp", ".webp")


def _image_paths(data_path):
    if os.path.isdir(data_path):
        names = sorted(n for n in os.listdir(data_path)
                       if n.lower().endswith(IMG_EXTS))
        return [os.path.join(data_path, n) for n in names]
    return [data_path]


def serve_token_model(args, config, compile_cache):
    """`model.family: moe_mla`: every request of --data_path through the
    token server, answers and the stats line out."""
    import numpy as np

    from mine_tpu import telemetry
    from mine_tpu.config import telemetry_config_from_dict
    from mine_tpu.serve.lm_scheduler import LMRequest, build_server
    from mine_tpu.utils import describe_runtime, make_logger

    os.makedirs(args.output_dir, exist_ok=True)
    logger = make_logger(os.path.join(args.output_dir, "serve.log"))
    logger.info("Runtime: %s", json.dumps(
        dict(describe_runtime(), compile_cache=compile_cache)))
    telem_cfg = telemetry_config_from_dict(config)
    if telem_cfg.enabled:
        telemetry.ensure_configured(
            telem_cfg.events_path
            or os.path.join(args.output_dir, "events.jsonl"),
            max_mb=telem_cfg.events_max_mb, keep=telem_cfg.events_keep)
    t0 = time.perf_counter()
    server = build_server(config, seed=args.seed)
    engine = server.engine
    logger.info("token server: %d step programs %s warmed in %.1fs; latent "
                "cache %s", len(engine.buckets()), engine.buckets(),
                time.perf_counter() - t0, engine.cache.stats())
    with open(args.data_path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    t0 = time.perf_counter()
    futures = [server.submit(LMRequest(
        question=np.asarray(r["question"], np.int32),
        max_tokens=int(r["max_tokens"]), doc_id=r.get("doc_id"),
        document=(np.asarray(r["document"], np.int32)
                  if r.get("document") else None))) for r in lines]
    results = [f.result() for f in futures]
    dt = time.perf_counter() - t0
    server.close()
    with open(os.path.join(args.output_dir, "answers.jsonl"), "w") as f:
        for r, res in zip(lines, results):
            f.write(json.dumps({"doc_id": r.get("doc_id"),
                                "tokens": [int(t) for t in res.tokens],
                                "prompt_tokens": res.prompt_tokens,
                                "cached_tokens": res.cached_tokens}) + "\n")
    stats = telemetry.REGISTRY.snapshot("serve.lm.")
    tokens_out = sum(len(res.tokens) for res in results)
    logger.info("lm serve stats: requests=%d tokens_out=%d prompt_tokens=%d "
                "prompt_tokens_cached=%d steps=%d evictions=%d "
                "dropped_tokens=%d", len(results), tokens_out,
                sum(res.prompt_tokens for res in results),
                sum(res.cached_tokens for res in results), engine.steps,
                int(stats.get("serve.lm.evictions", 0)),
                int(stats.get("serve.lm.dropped_tokens", 0)))
    logger.info("answered %d requests, %d tokens in %.2fs (%.1f tokens/s)",
                len(results), tokens_out, dt, tokens_out / max(dt, 1e-9))
    telemetry.spans.export()
    telemetry.emit("metrics.snapshot", scope="serve_cli_end",
                   metrics=stats)


def main():
    parser = argparse.ArgumentParser(description="Render-only serving")
    parser.add_argument("--checkpoint_path", type=str, default=None,
                        help="required unless --config_path names a token "
                             "model (model.family: moe_mla)")
    parser.add_argument("--config_path", type=str, default=None,
                        help="a model YAML; with model.family moe_mla the "
                             "token server answers --data_path's requests")
    parser.add_argument("--seed", type=int, default=0,
                        help="token model: the seed of its weights")
    parser.add_argument("--data_path", type=str, required=True,
                        help="image file or directory of images")
    parser.add_argument("--output_dir", type=str, required=True)
    parser.add_argument("--gpus", type=str, default=None,
                        help="ignored (reference-CLI parity)")
    parser.add_argument("--extra_config", type=str, default="{}",
                        help='JSON config overrides, e.g. '
                             '\'{"serve.cache_quant": "int8"}\'')
    parser.add_argument("--warmup", action="store_true",
                        help="pre-compile every pose bucket before timing")
    args = parser.parse_args()

    from mine_tpu.utils import configure_compile_cache
    compile_cache = configure_compile_cache()

    if args.config_path is not None:
        from mine_tpu.config import load_config
        lm_config = load_config(args.config_path,
                                extra_config=args.extra_config)
        if lm_config.get("model.family") == "moe_mla":
            return serve_token_model(args, lm_config, compile_cache)
    if args.checkpoint_path is None:
        parser.error("--checkpoint_path is required for model.family mine")

    import cv2
    import numpy as np
    import yaml

    from mine_tpu import telemetry
    from mine_tpu.config import (CONFIG_DIR, load_config, postprocess,
                                 serve_config_from_dict,
                                 telemetry_config_from_dict)
    from mine_tpu.infer.video import (WARP_BAND, VideoGenerator,
                                      generate_trajectories)
    from mine_tpu.kernels import on_tpu_backend
    from mine_tpu.serve import (AOTStore, MPICache, RenderEngine, ServeFleet,
                                quantize_weights_int8)
    from mine_tpu.train.step import SynthesisTrainer
    from mine_tpu.utils import describe_runtime, make_logger

    os.makedirs(args.output_dir, exist_ok=True)
    logger = make_logger(os.path.join(args.output_dir, "serve.log"))
    logger.info("Runtime: %s", json.dumps(
        dict(describe_runtime(), compile_cache=compile_cache)))

    ckpt_dir = os.path.dirname(os.path.abspath(args.checkpoint_path))
    params_yaml = os.path.join(ckpt_dir, "params.yaml")
    if os.path.exists(params_yaml):
        with open(params_yaml) as f:
            config = postprocess(yaml.safe_load(f))
        config.update(json.loads(args.extra_config))
    else:
        logger.info("No params.yaml next to checkpoint; using LLFF defaults")
        config = load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"),
                             extra_config=args.extra_config)
    serve_cfg = serve_config_from_dict(config)
    telem_cfg = telemetry_config_from_dict(config)
    if telem_cfg.enabled:
        # event stream next to the log (telemetry.events_path / the
        # MINE_TPU_TELEMETRY_EVENTS env var override both win over the
        # output-dir default); size-capped rotation per events_max_mb
        telemetry.ensure_configured(
            telem_cfg.events_path
            or os.path.join(args.output_dir, "events.jsonl"),
            max_mb=telem_cfg.events_max_mb, keep=telem_cfg.events_keep)
    recorder = None
    if telem_cfg.enabled and telem_cfg.recorder_enabled:
        # flight recorder (telemetry/recorder.py): black-box capture +
        # triggered incident bundles; the fleet below registers its state
        recorder = telemetry.recorder.configure(
            telem_cfg.recorder_dir
            or os.path.join(args.output_dir, "incidents"),
            events_tail=telem_cfg.recorder_events,
            steplines=telem_cfg.recorder_steplines,
            snapshots=telem_cfg.recorder_snapshots,
            debounce_s=telem_cfg.recorder_debounce_s,
            keep=telem_cfg.recorder_keep,
            config=dict(config))
        sig = recorder.install_sigusr2()
        logger.info("flight recorder armed: %s%s", recorder.out_dir,
                    " (SIGUSR2 -> bundle)" if sig else "")
    resource_sampler = telemetry.ResourceSampler(
        telem_cfg.resource_sample_s if telem_cfg.enabled else 0.0)
    if telem_cfg.trace_sample > 0:
        # head-sampled request traces: each sampled request/image emits a
        # trace.span tree into the event stream (telemetry/tracing.py)
        telemetry.tracing.configure(sample=telem_cfg.trace_sample)
        logger.info("request tracing on: sample=%.3g",
                    telem_cfg.trace_sample)

    trainer = SynthesisTrainer(config, steps_per_epoch=1)
    state = trainer.init_state(batch_size=1)
    params, batch_stats = state.params, state.batch_stats

    if args.checkpoint_path.endswith(".npz"):
        from mine_tpu.train.checkpoint import load_pretrained_params
        params, batch_stats = load_pretrained_params(
            args.checkpoint_path, params, batch_stats, logger)
    else:
        from mine_tpu.train.checkpoint import CheckpointManager
        mgr = CheckpointManager(ckpt_dir or ".")
        restored = mgr.restore(state, os.path.abspath(args.checkpoint_path))
        if restored is None:
            raise FileNotFoundError(args.checkpoint_path)
        params, batch_stats = restored.params, restored.batch_stats
        logger.info("Restored checkpoint at step %d", int(restored.step))

    if serve_cfg.encoder_quant == "int8":
        # quantize ONCE here, not per image: VideoGenerator detects an
        # already-quantized tree and fuses the dequant into its jitted
        # encode (mine_tpu/serve/encoder.py)
        params = quantize_weights_int8(params)
        logger.info("encoder weights quantized to int8 "
                    "(serve.encoder_quant)")

    # ONE engine + cache for the whole run: every VideoGenerator below
    # deposits its encode here, trajectories render through the same
    # compile-once bucketed program (mine_tpu/serve/engine.py). A fleet
    # config (serve.mesh_* > 1 or serve.cache_shards > 1) builds the
    # ServeFleet instead — mesh render program + key-range-sharded cache
    # (mine_tpu/serve/fleet.py); the video path renders synchronously, so
    # the fleet's scheduler thread is left unstarted.
    backend = "pallas" if on_tpu_backend() else "xla"
    logger.info("Backends: composite=%s warp=%s", backend,
                serve_cfg.warp_backend)
    engine_kw = dict(
        use_alpha=bool(config.get("mpi.use_alpha", False)),
        is_bg_depth_inf=bool(config.get("mpi.is_bg_depth_inf", False)),
        backend=backend,
        warp_impl=serve_cfg.warp_backend,
        warp_band=WARP_BAND)
    aot_store = (AOTStore(serve_cfg.aot_store_dir)
                 if serve_cfg.aot_store_dir else None)
    if aot_store is not None:
        logger.info("AOT executable store: %s (%d artifact(s); build "
                    "offline with tools/aot_warmstore.py)",
                    aot_store.root, len(aot_store.entries()))
    fleet = None
    ops = None
    if (serve_cfg.mesh_batch * serve_cfg.mesh_model > 1
            or serve_cfg.cache_shards > 1):
        fleet = ServeFleet.from_config(serve_cfg, start=False,
                                       recorder=recorder, **engine_kw)
        engine = fleet.engine
        slo = fleet.slo
        ops = fleet.ops  # fleet owns the endpoint (closed by fleet.close)
        logger.info("serving fleet: mesh=%dx%d cache_shards=%d scheduler=%s",
                    serve_cfg.mesh_batch, serve_cfg.mesh_model,
                    serve_cfg.cache_shards, serve_cfg.scheduler)
        if fleet.admission is not None:
            logger.info("admission control: burn_max=%.2f queue_high=%d "
                        "inflight_high=%d shed_factor=%.2f hysteresis=%.2f",
                        serve_cfg.admission_burn_max,
                        serve_cfg.admission_queue_high,
                        serve_cfg.admission_inflight_high,
                        serve_cfg.admission_shed_factor,
                        serve_cfg.admission_hysteresis)
    else:
        engine = RenderEngine(
            max_bucket=serve_cfg.max_bucket,
            cache=MPICache(capacity_bytes=serve_cfg.cache_bytes,
                           quant=serve_cfg.cache_quant),
            encode_retries=serve_cfg.encode_retries,
            encode_backoff_ms=serve_cfg.encode_backoff_ms,
            aot_store=aot_store,
            **engine_kw)
        slo = telemetry.SLOTracker(objective_ms=serve_cfg.slo_objective_ms,
                                   target=serve_cfg.slo_target,
                                   window_s=serve_cfg.slo_window_s)
        if recorder is not None:
            recorder.set_slo(slo)
        if serve_cfg.ops_port > 0:
            ops = telemetry.OpsServer(
                port=serve_cfg.ops_port, slo=slo,
                incidents=(recorder.list_incidents
                           if recorder is not None else None)).start()
    if ops is not None:
        logger.info("ops endpoint: %s (/metrics /healthz /slo "
                    "/traces/recent)", ops.url)

    # multi-host ring view (serve.ring.* keys, default off): this process
    # joins a HostRing as one member and probes its serve.ring.hosts peers
    # once over the hostnet transport, so /healthz, /metrics and the exit
    # stats line surface real ring state (hosts alive/draining, coverage,
    # autoscaler level). The multi-host DATA path — RingFront routing to
    # HostClient handles — lives in tools/serve_chaos_soak.py and the
    # serve_multihost bench; this CLI renders locally either way, which is
    # what keeps ring-off bitwise-identical to the single-process fleet.
    ring = None
    scaler = None
    peer_clients = {}
    if serve_cfg.ring_enabled:
        from mine_tpu.serve import (Autoscaler, HostClient, HostRing,
                                    NetPolicy, WirePolicy, pressure_score)
        # wire hardening (serve.net.*, default off): peer probes get the
        # split timeouts/retries/breakers, and /healthz surfaces every
        # peer's breaker state next to the ring view
        net_policy = None
        if serve_cfg.net_enabled:
            net_policy = NetPolicy(
                enabled=True,
                connect_timeout_s=serve_cfg.net_connect_timeout_s,
                read_timeout_s=serve_cfg.net_read_timeout_s,
                retries=serve_cfg.net_retries,
                backoff_ms=serve_cfg.net_backoff_ms,
                breaker_threshold=serve_cfg.net_breaker_threshold,
                breaker_reset_s=serve_cfg.net_breaker_reset_s,
                probe_interval_s=serve_cfg.net_probe_interval_s,
                suspect_misses=serve_cfg.net_suspect_misses,
                dead_misses=serve_cfg.net_dead_misses,
                revive_probes=serve_cfg.net_revive_probes)
            logger.info("net hardening: connect=%.1fs read=%.1fs "
                        "retries=%d breaker_threshold=%d probe=%.1fs",
                        net_policy.connect_timeout_s,
                        net_policy.read_timeout_s, net_policy.retries,
                        net_policy.breaker_threshold,
                        net_policy.probe_interval_s)
        # binary wire fabric (serve.wire.*, default off): peer clients
        # negotiate mtpu-wire1 frames + the configured tensor codec;
        # wire-off builds no policy and the transport is byte-identical
        wire_policy = None
        if serve_cfg.wire_format == "binary":
            wire_policy = WirePolicy(
                format=serve_cfg.wire_format,
                codec=serve_cfg.wire_codec,
                coalesce_ms=serve_cfg.wire_coalesce_ms,
                coalesce_max=serve_cfg.wire_coalesce_max)
            logger.info("binary wire: codec=%s coalesce_ms=%.1f "
                        "coalesce_max=%d", wire_policy.codec,
                        wire_policy.coalesce_ms, wire_policy.coalesce_max)
        ring = HostRing()
        ring.join("self", aot_loads=engine.bucket_loads,
                  aot_compiles=engine.bucket_compiles)
        for addr in filter(None, (a.strip()
                                  for a in serve_cfg.ring_hosts.split(","))):
            ring.join(addr)
            client = HostClient(addr, timeout_s=2.0, policy=net_policy,
                                net_src="self", net_name=addr,
                                wire_policy=wire_policy)
            if net_policy is not None:
                peer_clients[addr] = client  # kept for breaker snapshots
            try:
                client.healthz()
            except Exception:  # noqa: BLE001 - unreachable peer = dead slot
                ring.mark_dead(addr)
        if serve_cfg.autoscale_enabled:
            # pressure here is the SLO error-budget burn (the only load
            # signal the synchronous render path produces); no actuator is
            # wired — the serve.autoscale trail records what an operator
            # (or the soak's spawn/drain actuators) should do
            burn_max = serve_cfg.admission_burn_max or 1.0
            scaler = Autoscaler(
                min_hosts=serve_cfg.autoscale_min_hosts,
                max_hosts=serve_cfg.autoscale_max_hosts,
                evals=serve_cfg.autoscale_evals,
                hysteresis=serve_cfg.autoscale_hysteresis,
                cooldown_s=serve_cfg.autoscale_cooldown_s,
                score_fn=lambda: pressure_score(burn=slo.burn,
                                                burn_max=burn_max),
                hosts_fn=lambda: len(ring.alive()))
        rs = ring.stats()
        logger.info("host ring: hosts=%d alive=%d coverage=%.2f "
                    "autoscale=%s", rs["hosts"], len(rs["alive"]),
                    rs["coverage"], "on" if scaler is not None else "off")
        if ops is not None:
            base_health = ops.health
            ops.health = lambda: dict(
                (base_health() if base_health is not None
                 else {"status": "ok"}),
                ring=ring.stats(),
                **({"autoscale": scaler.stats()}
                   if scaler is not None else {}),
                **({"net": {"breakers": {
                    a: c.breaker_snapshot()
                    for a, c in peer_clients.items()}}}
                   if peer_clients else {}))

    paths = _image_paths(args.data_path)
    if not paths:
        raise FileNotFoundError(f"no images under {args.data_path}")
    t0 = time.perf_counter()
    views = 0
    for path in paths:
        img = cv2.imread(path, cv2.IMREAD_COLOR)
        if img is None:
            logger.info("skipping unreadable %s", path)
            continue
        img = cv2.cvtColor(img, cv2.COLOR_BGR2RGB)
        gen = VideoGenerator(config, params, batch_stats, img,
                             chunk=serve_cfg.max_bucket, engine=engine,
                             encoder_quant=serve_cfg.encoder_quant)
        logger.info("image %s: id=%s encode=%s", os.path.basename(path),
                    gen.image_id[:12], "ran" if gen.encoded else "cached")
        if args.warmup and views == 0:
            engine.warmup(gen.image_id)
            if engine.aot_store is not None:
                logger.info("warmup: %d store load(s), %d live compile(s)",
                            engine.bucket_loads, engine.bucket_compiles)
            t0 = time.perf_counter()  # don't bill compiles to throughput
        name = os.path.basename(path).rsplit(".", 1)[0]
        # one trace per input image (this CLI's unit of request): the
        # video-render block is its single child span; the SLO tracker
        # sees every image regardless of the sampling verdict
        trace = telemetry.tracing.start("serve.image", image=name)
        t_img = time.perf_counter()
        if trace is not None:
            with trace.child("render_videos"):
                for w in gen.render_videos(args.output_dir, name):
                    logger.info("wrote %s", w)
        else:
            for w in gen.render_videos(args.output_dir, name):
                logger.info("wrote %s", w)
        slo.record((time.perf_counter() - t_img) * 1e3,
                   bucket=serve_cfg.max_bucket)
        if scaler is not None:
            # one control tick per image: the hysteretic streaks make the
            # serve.autoscale trail meaningful even on short runs
            scaler.evaluate()
        telemetry.tracing.finish(trace)
        views += sum(t.shape[0] for t in generate_trajectories(
            config.get("data.name", "_default"))[0])
    dt = time.perf_counter() - t0

    stats = engine.cache.stats()
    # the fleet's routing counters ride the ONE stats line (a sharded
    # cache's stats() carries them; a plain MPICache reads as zeros), and
    # so do the AOT store's (serve/aot.py; zeros when no store configured)
    logger.info("serve stats: entries=%d nbytes=%d hits=%d misses=%d "
                "evictions=%d quant=%s device_calls=%d sync_encodes=%d "
                "owner_hits=%d remote_routes=%d owner_encodes=%d "
                "rebalances=%d aot_hits=%d aot_misses=%d aot_saves=%d "
                "load_errors=%d",
                stats["entries"], stats["nbytes"], stats["hits"],
                stats["misses"], stats["evictions"], stats["quant"],
                engine.device_calls, engine.sync_encodes,
                stats.get("owner_hits", 0), stats.get("remote_routes", 0),
                stats.get("owner_encodes", 0), stats.get("rebalances", 0),
                aot_store.hits if aot_store is not None else 0,
                aot_store.misses if aot_store is not None else 0,
                aot_store.saves if aot_store is not None else 0,
                aot_store.load_errors if aot_store is not None else 0)
    if ring is not None:
        rs = ring.stats()
        logger.info("ring stats: hosts=%d alive=%d draining=%d dead=%d "
                    "coverage=%.2f rebalances=%d autoscale_level=%s "
                    "autoscale_decisions=%s",
                    rs["hosts"], len(rs["alive"]), len(rs["draining"]),
                    len(rs["dead"]), rs["coverage"], rs["rebalances"],
                    scaler.level if scaler is not None else "-",
                    scaler.decisions if scaler is not None else "-")
    if fleet is not None:
        fs = fleet.stats()
        logger.info("fleet stats: mesh=%s shards=%d slo_breaches=%d "
                    "shed=%d degraded=%d expired=%d dead_shards=%s",
                    fs["mesh"], fs["shards"], fs["slo_breaches"],
                    fs["shed"], fs["degraded"], fs["expired"],
                    fs["dead_shards"])
        fleet.close()
    elif ops is not None:
        ops.close()
    logger.info("rendered %d views from %d images in %.2fs (%.2f views/s)",
                views, len(paths), dt, views / max(dt, 1e-9))
    telemetry.emit("serve.stats", views=views, images=len(paths),
                   seconds=round(dt, 3), device_calls=engine.device_calls,
                   sync_encodes=engine.sync_encodes, **stats)
    telemetry.spans.export()  # the span ring, as `span` events
    telemetry.emit("metrics.snapshot", scope="serve_cli_end",
                   metrics=telemetry.REGISTRY.snapshot("serve."))
    resource_sampler.close()
    telemetry.recorder.release(recorder)


if __name__ == "__main__":
    main()
