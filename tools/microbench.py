#!/usr/bin/env python
"""Per-component timing at the benchmark config: where does the step go?

Times each hot component of the train step in isolation on the real chip —
encoder, full model forward, homography warp (XLA gather vs banded Pallas,
forward and forward+backward), and the MPI composite (XLA vs fused Pallas)
— at the north-star shapes (B=2, S=32, 256x384; SURVEY.md section 6). This
is the kernel win/loss table the round-1 verdict asked for, and it gives a
time attribution even if the full-step profile trace can't be captured.

Each case runs in its own subprocess under bench.py's watchdog, one after
the other (this parent never initialises JAX, so each child has the chip to
itself), sharing the persistent compile cache. Prints one JSON object
mapping case -> ms/iter (or an error string).

Usage: python tools/microbench.py [case ...]   (default: all cases)
  MINE_TPU_MICRO_SMOKE=1  tiny CPU self-test of the harness (not a timing)
"""

import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = os.environ.get("MINE_TPU_MICRO_SMOKE") == "1"
B = 2
S = 4 if SMOKE else 32
H, W = (64, 64) if SMOKE else (256, 384)
WARMUP = 1 if SMOKE else 2
ITERS = 2 if SMOKE else 10
TIMEOUT = 300 if SMOKE else 900

CASES = [
    "encoder_fwd", "model_fwd",
    "warp_xla_fwd", "warp_pallas_fwd",
    "warp_xla_fwdbwd", "warp_pallas_diff_fwdbwd",
    "comp_xla_fwd", "comp_pallas_fwd",
    "comp_xla_fwdbwd", "comp_pallas_diff_fwdbwd",
    # inference hot loop: one F-pose chunk of novel-view rendering (the
    # reference renders video frames one by one, image_to_video.py:219-255;
    # ours batches the pose axis — infer/video.py). frames/sec =
    # RENDER_POSES / (ms_per_iter / 1e3).
    "render_poses_xla", "render_poses_pallas",
]
RENDER_POSES = 2 if SMOKE else 8
# the forward-only Pallas warp paths run in interpret mode off-TPU
# (ops/warp.py plumbs interpret=not on_tpu_backend()), so smoke covers
# every case
SMOKE_SKIP = set()


def _warp_inputs():
    """Realistic warp coords: synthetic-scene poses at bench shapes."""
    import jax
    import jax.numpy as jnp

    from mine_tpu import geometry
    from mine_tpu.data.synthetic import make_batch

    batch = make_batch(B, H, W, num_points=8)
    disp = jnp.linspace(1.0, 0.05, S)                      # [S]
    depth = (1.0 / disp)[None].repeat(B, 0).reshape(B * S)  # [B*S]
    vol = jax.random.uniform(jax.random.PRNGKey(0), (B * S, 7, H, W))
    G = jnp.repeat(jnp.asarray(batch["G_src_tgt"]), S, axis=0)
    K = jnp.repeat(jnp.asarray(batch["K_src"]), S, axis=0)
    K_inv = geometry.inverse_intrinsics(K)
    grid = geometry.cached_pixel_grid(H, W)
    return vol, depth, G, K_inv, K, grid


def _comp_inputs():
    import jax
    import jax.numpy as jnp
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    rgb = jax.random.uniform(k1, (B, S, 3, H, W))
    sigma = jax.random.uniform(k2, (B, S, 1, H, W)) * 5.0
    # plausible camera-frame xyz: z decreasing with plane index
    z = jnp.linspace(1.0, 20.0, S)[None, :, None, None, None]
    xyz = jax.random.normal(k3, (B, S, 3, H, W)) * 0.1 + z
    return rgb, sigma, xyz


def _case_fn(case: str):
    """Returns (fn, args): fn(*args) -> array(s) to block on."""
    import jax
    import jax.numpy as jnp

    interp = SMOKE  # Pallas kernels interpret on the CPU self-test

    if case == "encoder_fwd":
        from mine_tpu.models.resnet import ResnetEncoder
        m = ResnetEncoder(num_layers=18 if SMOKE else 50, dtype=jnp.bfloat16)
        img = jax.random.uniform(jax.random.PRNGKey(0), (B, H, W, 3))
        vars_ = m.init(jax.random.PRNGKey(1), img, train=False)
        return jax.jit(lambda v, i: m.apply(v, i, train=False)), (vars_, img)

    if case == "model_fwd":
        from mine_tpu.models.mpi import MPIPredictor
        m = MPIPredictor(num_layers=18 if SMOKE else 50, dtype=jnp.bfloat16)
        img = jax.random.uniform(jax.random.PRNGKey(0), (B, H, W, 3))
        disp = jnp.linspace(1.0, 0.05, S)[None].repeat(B, 0)
        vars_ = m.init(jax.random.PRNGKey(1), img, disp, train=False)
        return (jax.jit(lambda v, i, d: m.apply(v, i, d, train=False)),
                (vars_, img, disp))

    if case.startswith("warp_"):
        from mine_tpu.ops.warp import homography_warp
        vol, depth, G, K_inv, K, grid = _warp_inputs()
        impl = {"warp_xla_fwd": "xla", "warp_pallas_fwd": "pallas",
                "warp_xla_fwdbwd": "xla",
                "warp_pallas_diff_fwdbwd": "pallas_diff"}[case]

        def fwd(v):
            out, _ = homography_warp(v, depth, G, K_inv, K, grid, impl=impl)
            return out

        if case.endswith("fwdbwd"):
            fn = jax.jit(jax.grad(lambda v: jnp.sum(fwd(v) ** 2)))
        else:
            fn = jax.jit(fwd)
        return fn, (vol,)

    if case.startswith("comp_"):
        rgb, sigma, xyz = _comp_inputs()
        if "pallas" in case:
            if case.endswith("fwdbwd"):
                from mine_tpu.kernels.composite_vjp import \
                    fused_volume_render_diff
                base = lambda r, s, x: fused_volume_render_diff(  # noqa: E731
                    r, s, x, True, False, interp)
            else:
                from mine_tpu.kernels.composite import fused_volume_render
                base = lambda r, s, x: fused_volume_render(  # noqa: E731
                    r, s, x, z_mask=True, is_bg_depth_inf=False,
                    interpret=interp)
        else:
            from mine_tpu.ops import rendering

            def base(r, s, x):
                s = jnp.where(x[:, :, 2:3] >= 0.0, s, 0.0)
                out = rendering.render(r, s, x)
                return out[0], out[1]

        if case.endswith("fwdbwd"):
            def loss(r, s, x):
                rgb_o, depth_o = base(r, s, x)
                return jnp.sum(rgb_o ** 2) + jnp.sum(depth_o ** 2)
            fn = jax.jit(jax.grad(loss, argnums=(0, 1)))
        else:
            fn = jax.jit(base)
        return fn, (rgb, sigma, xyz)

    if case.startswith("render_poses_"):
        from mine_tpu import geometry
        from mine_tpu.ops import rendering
        backend = case.rsplit("_", 1)[1]          # xla | pallas
        warp_impl = "xla" if backend == "xla" else "pallas"
        F = RENDER_POSES
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        rgb = jax.random.uniform(k1, (1, S, 3, H, W))
        sigma = jax.random.uniform(k2, (1, S, 1, H, W)) * 5.0
        disp = jnp.linspace(1.0, 0.05, S)[None]    # [1,S]
        K = jnp.asarray(geometry.intrinsics_from_fov(H, W, 90.0))[None]
        K_inv = geometry.inverse_intrinsics(K)
        # straight-line dolly: small translations keep the warp in-band
        ts = jnp.linspace(-0.05, 0.05, F)
        G = jnp.broadcast_to(jnp.eye(4), (F, 4, 4)).at[:, 0, 3].set(ts)

        def tile(x):
            return jnp.broadcast_to(x, (F,) + x.shape[1:])

        def render(rgb_, sigma_, G_):
            res = rendering.render_tgt_rgb_depth(
                tile(rgb_), tile(sigma_), tile(disp), G_,
                tile(K_inv), tile(K), backend=backend,
                warp_impl=warp_impl, warp_band=32)
            return res.rgb, res.depth

        return jax.jit(render), (rgb, sigma, G)

    raise ValueError(case)


def _child(case: str, outdir: str) -> None:
    import bench

    def write(payload):
        bench.write_result(outdir, payload)

    try:
        if SMOKE:
            os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        from mine_tpu.utils import configure_compile_cache
        configure_compile_cache()
        jax.devices()
        open(os.path.join(outdir, "INIT_OK"), "w").close()

        fn, args = _case_fn(case)
        for _ in range(WARMUP):
            jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(ITERS):
            out = fn(*args)
        # real device->host readback, not just block_until_ready (see
        # bench.py _measure); iterations serialize on the device queue, so
        # the last result's value completes after all of them
        jax.device_get(jax.tree.leaves(out)[0])
        ms = (time.perf_counter() - t0) / ITERS * 1e3
        write({"ms_per_iter": round(ms, 3)})
        print("[%s] %.3f ms/iter" % (case, ms), file=sys.stderr)
    except Exception as e:
        msg = (str(e).splitlines() or [repr(e)])[0][:200]
        write({"error": msg})


def main():
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        _child(sys.argv[2], sys.argv[3])
        return

    import shutil

    import bench

    cases = sys.argv[1:] or CASES
    unknown = [c for c in cases if c not in CASES]
    if unknown:
        print("unknown cases %s (known %s)" % (unknown, CASES))
        sys.exit(2)
    if SMOKE:
        cases = [c for c in cases if c not in SMOKE_SKIP]

    report = {}
    for case in cases:
        outdir = tempfile.mkdtemp(prefix="micro_%s_" % case)
        try:
            payload, err, wedged = bench.run_child_watchdog(
                [sys.executable, os.path.abspath(__file__), "--child", case,
                 outdir],
                outdir, 240, TIMEOUT)
        finally:
            shutil.rmtree(outdir, ignore_errors=True)
        report[case] = payload["ms_per_iter"] if payload else "error: " + err
        print("case %s: %s" % (case, report[case]), file=sys.stderr)
        if wedged:
            for rest in cases[cases.index(case) + 1:]:
                report[rest] = "skipped: no device came up"
            break

    print(json.dumps(report))


if __name__ == "__main__":
    main()
