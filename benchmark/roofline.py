"""Operations and bytes of the warp and composite calls, from their shapes.

XLA's `cost_analysis()` does not see inside a `tpu_custom_call`, so the
Pallas kernels are priced here, by hand, from what the ALGORITHM needs: a
bilinear warp reads each source texel once and writes each output once; a
transparency composite reads the plane volume once. What the kernels do as
written (the banded warp re-reads a 48-row band for every 8 output rows and
spends a [C*band, W] x [W, W] one-hot matmul per output row) is counted
beside it as `as_written`, for the reader; the roofline share uses the
algorithm's floor, so a better formulation of the same warp can only raise
it and the share cannot pass 100%.

floor_s of a call = max(ops / peak FLOP/s, bytes / peak bytes/s), and
`bound` says which of the two binds. Peaks come from benchmark/peaks.json.

Shapes (ops/rendering.render_tgt_rgb_depth): the warp runs over
n = batch * planes images of C = 7 channels (rgb 3, sigma 1, xyz 3), float32
in and out, with two float32 coordinate fields per image; the composite
reads rgb 3 + sigma 1 + xyz 3 channels per plane and writes rgb 3 + depth 1.
"""

from __future__ import annotations

from benchmark import trace_reduce

F32 = 4
WARP_CHANNELS = 7
ROWS_PER_BLOCK = 8      # kernels/warp.py rows_per_block
# a bilinear sample: 4 taps, 3 lerps of (1 sub, 2 mul, 1 add) ~ 8 flops per
# output value, plus ~12 per pixel for the two weights and the clamps
WARP_FLOPS_PER_VALUE = 8
WARP_FLOPS_PER_PIXEL = 12
# per plane and pixel: z mask, exp, the transparency product, the weight and
# four weighted sums (rgb, depth) ~ 16 flops
COMPOSITE_FLOPS_PER_PLANE_PIXEL = 16


def _floor(ops: float, nbytes: float, peaks) -> dict:
    t_ops = ops / (peaks["peak_tflops_bf16"] * 1e12)
    t_bytes = nbytes / (peaks["hbm_gbps"] * 1e9)
    return {"ops": ops, "bytes": nbytes, "floor_s": max(t_ops, t_bytes),
            "bound": "compute" if t_ops > t_bytes else "memory"}


def warp_call(n: int, height: int, width: int, peaks, band: int = 48,
              channels: int = WARP_CHANNELS) -> dict:
    """One warp over `n` plane images, forward or backward: the backward
    (the transposed splat) reads the cotangent and the coordinates and
    writes the source gradient, the same traffic the other way round."""
    px = n * height * width
    ops = px * (channels * WARP_FLOPS_PER_VALUE + WARP_FLOPS_PER_PIXEL)
    nbytes = px * F32 * (channels + 2 + channels)  # src, coords x/y, out
    out = _floor(ops, nbytes, peaks)
    band = min(band, height)
    out["as_written"] = {
        # per output row: [C*band, W] @ [W, W] one-hot matmul
        "ops": 2.0 * n * height * channels * band * width * width,
        # per block of 8 output rows: one [C, band, W] band read
        "bytes": px * F32 * (channels * band / ROWS_PER_BLOCK + 2 + channels),
    }
    return out


def composite_call(batch: int, planes: int, height: int, width: int, peaks,
                   backward: bool = False) -> dict:
    """One transparency composite over [batch, planes] of 7 input channels.
    Forward: read the volume, write rgb + depth. Backward: read the volume
    and the 4 output cotangents, write the rgb and sigma gradients."""
    px = batch * height * width
    ops = px * planes * COMPOSITE_FLOPS_PER_PLANE_PIXEL * (2 if backward
                                                           else 1)
    nbytes = px * F32 * (7 * planes + 4 + (4 * planes if backward else 0))
    return _floor(ops, nbytes, peaks)


def train_step_calls(shapes, peaks):
    """The Pallas calls of one train step on one device: warp forward and
    backward and composite forward and backward at every loss scale (each
    scale halves height and width)."""
    calls = []
    b, s = shapes["batch_per_device"], shapes["planes"]
    for k in range(int(shapes.get("scales", 4))):
        h, w = shapes["height"] >> k, shapes["width"] >> k
        for name in ("warp_fwd", "warp_bwd"):
            calls.append(dict(warp_call(b * s, h, w, peaks,
                                        band=shapes.get("band", 48)),
                              name="%s@%dx%d" % (name, h, w)))
        calls.append(dict(composite_call(b, s, h, w, peaks),
                          name="composite_fwd@%dx%d" % (h, w)))
        calls.append(dict(composite_call(b, s, h, w, peaks, backward=True),
                          name="composite_bwd@%dx%d" % (h, w)))
    return calls


def serve_view_calls(shapes, peaks):
    """The Pallas calls ONE rendered view needs: a forward warp over the
    image's planes and a forward composite. Padded pose slots of a bucket
    are work the device does and no view needs, so they lower the share."""
    s, h, w = shapes["planes"], shapes["height"], shapes["width"]
    return [dict(warp_call(s, h, w, peaks, band=shapes.get("band", 32)),
                 name="warp_fwd@%dx%d" % (h, w)),
            dict(composite_call(1, s, h, w, peaks),
                 name="composite_fwd@%dx%d" % (h, w))]


def train_share(obs, kind: str):
    """Percent of its floor that one step's `kind` calls ("warp" or
    "composite") reached in the traced run; None where nothing was read."""
    if obs["trace"] is None or obs["shapes"].get("kind") != "train":
        return None
    secs, runs = trace_reduce.per_run(
        obs["trace"], obs["counters"].get("step_program", "train_step"),
        trace_reduce.is_kernel(kind))
    if not runs or not secs:
        return None
    floor_s = sum(c["floor_s"] for c in train_step_calls(
        obs["shapes"], obs["peaks"]) if c["name"].startswith(kind))
    return 100.0 * floor_s / secs


def serve_share(obs, kind: str):
    """Percent of its floor that the `kind` calls of the views completed in
    the traced window reached; None where nothing was read."""
    views = obs["counters"].get("views_in_trace_window")
    if obs["trace"] is None or not views \
            or obs["shapes"].get("kind") != "serve":
        return None
    secs = trace_reduce.op_seconds(obs["trace"], trace_reduce.is_kernel(kind))
    if not secs:
        return None
    floor_s = views * sum(c["floor_s"] for c in serve_view_calls(
        obs["shapes"], obs["peaks"]) if c["name"].startswith(kind))
    return 100.0 * floor_s / secs
