"""The yardstick against hand-worked cases: roofline.py's operations and
bytes, reference.py's render, trace_reduce.py's interval arithmetic."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, reference, roofline, trace_reduce

PEAKS = {"peak_tflops_bf16": 197.0, "hbm_gbps": 819.0}


# ---------------- roofline.py ----------------

def test_warp_call_hand_counted():
    # params_llff.yaml: B*S = 64 plane images of 7 channels at 384x512
    call = roofline.warp_call(64, 384, 512, PEAKS, band=48)
    px = 64 * 384 * 512
    assert px == 12_582_912
    # 8 flops per output value x 7 channels + 12 per pixel
    assert call["ops"] == px * 68 == 855_638_016
    # float32: 7 channels read, 2 coordinates read, 7 channels written
    assert call["bytes"] == px * 4 * 16 == 805_306_368
    assert call["bound"] == "memory"
    assert call["floor_s"] == pytest.approx(805_306_368 / 819e9)
    assert call["floor_s"] == pytest.approx(0.98328e-3, rel=1e-4)
    # as written: a [7*48, 512] x [512, 512] matmul per output row, and a
    # 48-row band read for every 8 output rows
    assert call["as_written"]["ops"] == 2.0 * 64 * 384 * 7 * 48 * 512 * 512
    assert call["as_written"]["bytes"] == px * 4 * (7 * 6 + 2 + 7)


def test_composite_call_hand_counted():
    fwd = roofline.composite_call(2, 32, 384, 512, PEAKS)
    px = 2 * 384 * 512
    # 7 channels x 32 planes read, rgb + depth written
    assert fwd["bytes"] == px * 4 * (7 * 32 + 4) == 358_612_992
    assert fwd["ops"] == px * 32 * 16
    assert fwd["floor_s"] == pytest.approx(0.43787e-3, rel=1e-4)
    bwd = roofline.composite_call(2, 32, 384, 512, PEAKS, backward=True)
    # also the rgb + sigma gradients written, 4 channels x 32 planes
    assert bwd["bytes"] == px * 4 * (7 * 32 + 4 + 4 * 32) == 559_939_584


def test_train_step_and_view_calls():
    shapes = {"batch_per_device": 2, "planes": 32, "height": 384,
              "width": 512, "scales": 4, "band": 48}
    calls = roofline.train_step_calls(shapes, PEAKS)
    assert len(calls) == 16  # the step's 16 tpu_custom_calls (PR 24)
    full = sum(c["floor_s"] for c in calls if c["name"].endswith("384x512"))
    # every further scale is a quarter of the one before
    assert sum(c["floor_s"] for c in calls) == pytest.approx(
        full * (1 + 1 / 4 + 1 / 16 + 1 / 64))
    view = roofline.serve_view_calls(dict(shapes, band=32), PEAKS)
    assert [c["name"] for c in view] == ["warp_fwd@384x512",
                                         "composite_fwd@384x512"]
    # one view warps 32 plane images: half of the train step's 64
    assert view[0]["bytes"] * 2 == calls[0]["bytes"]


# ---------------- reference.py ----------------

K = np.array([[2.0, 0, 2.0], [0, 2.0, 2.0], [0, 0, 1.0]], np.float32)


def _planes(rgb0, sigma0, rgb1, sigma1):
    p = np.zeros((2, 4, 4, 4), np.float32)
    p[0, :3], p[0, 3], p[1, :3], p[1, 3] = rgb0, sigma0, rgb1, sigma1
    return p


def test_reference_composite_by_hand():
    """2 planes at depths 1 and 2, 4x4, identity pose. At the principal
    point (2, 2) the ray is (0, 0, 1): dist_0 = 1, dist_1 = 1e3. With
    sigma_0 = ln 2: T_0 = 1/2, w_0 = 1/2, w_1 = (1/2 + 1e-6)(1 - 0)."""
    planes = _planes(0.2, np.log(2.0), 0.8, 5.0)
    rgb, depth = reference.render_view(
        planes, np.array([1.0, 0.5], np.float32), K, np.eye(4,
                                                            dtype=np.float32))
    w0, w1 = 0.5, 0.5 + 1e-6
    assert np.asarray(rgb)[:, 2, 2] == pytest.approx(
        [w0 * 0.2 + w1 * 0.8] * 3, abs=1e-6)
    assert float(np.asarray(depth)[0, 2, 2]) == pytest.approx(
        (w0 * 1.0 + w1 * 2.0) / (w0 + w1 + 1e-5), abs=1e-5)
    # off the axis the ray is longer, the near plane more opaque: pixel
    # (0, 0) has ray (-1, -1, 1), dist_0 = sqrt(3)
    t0 = 0.5 ** np.sqrt(3.0)
    assert float(np.asarray(rgb)[0, 0, 0]) == pytest.approx(
        (1 - t0) * 0.2 + (t0 + 1e-6) * 0.8, abs=1e-6)


def test_reference_warp_by_hand():
    """Camera moved by t_x = 0.5 (fx = 2): the plane at depth 1 shifts by
    fx t_x / d = 1 px, the plane at depth 2 by half a pixel. The near plane
    is empty (sigma 0), the far one opaque and carries a ramp r(x) = x, so
    target x = 2 reads the ramp at 1.5; at x = 0 the sample is clamped to
    the border, r = 0."""
    ramp = np.tile(np.arange(4, dtype=np.float32), (4, 1))
    planes = _planes(0.0, 0.0, ramp, 50.0)
    G = np.eye(4, dtype=np.float32)
    G[0, 3] = 0.5
    rgb, depth = reference.render_view(
        planes, np.array([1.0, 0.5], np.float32), K, G)
    row = np.asarray(rgb)[0, 2]
    assert row == pytest.approx(
        np.array([0.0, 0.5, 1.5, 2.5]) * (1 + 1e-6), abs=1e-5)
    assert float(np.asarray(depth)[0, 2, 2]) == pytest.approx(2.0, abs=1e-3)


def test_reference_sees_plane_order_and_dropped_plane():
    rng = np.random.RandomState(0)
    planes = rng.uniform(0, 1, (4, 4, 8, 8)).astype(np.float32)
    disp = np.linspace(1.0, 0.25, 4).astype(np.float32)
    Kb = np.array([[4.0, 0, 4.0], [0, 4.0, 4.0], [0, 0, 1.0]], np.float32)
    G = np.eye(4, dtype=np.float32)
    G[2, 3] = -0.1
    base = np.asarray(reference.render_view(planes, disp, Kb, G)[0])
    swapped = np.asarray(reference.render_view(planes[::-1].copy(), disp,
                                               Kb, G)[0])
    dropped = planes.copy()
    dropped[0, 3] = 0.0
    dropped = np.asarray(reference.render_view(dropped, disp, Kb, G)[0])
    assert np.abs(swapped - base).mean() > 0.02
    assert np.abs(dropped - base).mean() > 0.02


# ---------------- trace_reduce.py ----------------

def test_interval_arithmetic():
    u = trace_reduce.union([[5, 7], [0, 2], [1, 3], [7, 8]])
    assert u == [[0, 3], [5, 8]]
    assert trace_reduce.total(u) == 6
    assert trace_reduce.complement(u, 0, 10) == [[3, 5], [8, 10]]
    assert trace_reduce.subtract([[0, 10]], [[2, 3], [5, 7]]) == [
        [0, 2], [3, 5], [7, 10]]
    # a `while` of 10 that encloses two bodies of 3: self time 4
    assert trace_reduce.self_times([(0, 10), (1, 4), (5, 8)]) == [4, 3, 3]


def _hand_trace():
    ms = 1e6  # ns
    ops = [["fusion.1", 0 * ms, 2 * ms, {}],
           ["while.2", 2 * ms, 4 * ms, {}],            # encloses the next two
           ["custom-call.3", 2 * ms, 1 * ms, {"hlo_category": "pallas"}],
           ["all-reduce.4", 4 * ms, 1 * ms, {}],
           # idle 6..9 ms: the host was in feed.next
           ["fusion.1", 9 * ms, 1 * ms, {}]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ["jit__train_step_impl(1)", 0, 6 * ms, {}],
                ["jit__train_step_impl(1)", 9 * ms, 3 * ms, {}]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.trace_window", 0, 10 * ms, {}],
            ["bench.step.dispatch", 0, 0.5 * ms, {}],
            ["bench.feed.next", 5.5 * ms, 3.4 * ms, {}],
            ["bench.step.dispatch", 8.9 * ms, 0.2 * ms, {}]]}]}]}


def test_reduce_hand_built_trace():
    r = trace_reduce.reduce(_hand_trace())
    assert r["window_s"] == pytest.approx(10e-3)
    # busy: 0..6 and 9..10 -> 7 ms, the union, not the 9 ms sum of durations
    assert r["busy_s"] == pytest.approx(7e-3)
    assert r["idle_share"] == pytest.approx(0.3)
    ops = dict(r["device_ops"])
    assert ops["while.2"] == pytest.approx(2e-3)      # 4 - (1 + 1) enclosed
    assert ops["fusion.1"] == pytest.approx(3e-3)     # both executions
    # the one gap, 6..9 ms, named by the span that covered most of it
    assert r["idle_gaps"] == [["feed.next", pytest.approx(3e-3)]]
    assert trace_reduce.op_seconds(r, trace_reduce.is_pallas_call) == \
        pytest.approx(1e-3)
    assert trace_reduce.exposed_seconds(r, trace_reduce.is_collective) == \
        pytest.approx(1e-3)
    # only the first execution lies whole inside the window
    assert trace_reduce.module_runs(r, "_train_step_impl") == 1
    secs, runs = trace_reduce.per_run(r, "_train_step_impl")
    assert runs == 1 and secs == pytest.approx(6e-3)


def test_reduce_without_device_plane():
    host_only = {"planes": [p for p in _hand_trace()["planes"]
                            if p["name"].startswith("/host")]}
    assert trace_reduce.reduce(host_only) is None


def test_percentile_counts_failures_as_missing():
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    inf = float("inf")
    assert harness.percentile([1.0] * 90 + [inf] * 10, 95) == inf
    assert harness.percentile([1.0] * 99 + [inf], 95) == 1.0


def test_reduce_recorded_v5e_trace():
    """A trace recorded on the chip (cut down; see its `about`): the host
    had dispatched its steps ahead and sat in the loop's log sync, the
    device ran three steps back to back."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e_llff_train_cut.json")
    with open(path) as f:
        r = trace_reduce.reduce(json.load(f))
    assert r["window_s"] == pytest.approx(0.866216714)
    assert r["busy_s"] == pytest.approx(0.772617306)
    assert r["idle_share"] == pytest.approx(0.10805542)
    # busy is a union: never more than the sum of the kept self times
    assert r["busy_s"] <= sum(op["self_s"]
                              for op in r["devices"][0]["ops"]) + 1e-9
    # every gap of the cut file falls inside the host's one long sync
    assert r["idle_gaps"] == [["loop.log_sync", pytest.approx(0.093599408)]]
    assert r["device_ops"][0][0] == \
        "pallas_bilinear_sample.16 custom-call f32[64,7,384,512]"
    warp, runs = trace_reduce.per_run(r, "_train_step_impl",
                                      trace_reduce.is_kernel("warp"))
    comp, _ = trace_reduce.per_run(r, "_train_step_impl",
                                   trace_reduce.is_kernel("composite"))
    both, _ = trace_reduce.per_run(r, "_train_step_impl",
                                   trace_reduce.is_pallas_call)
    assert runs == 3
    assert warp == pytest.approx(0.066953802)
    assert comp == pytest.approx(0.001781681)
    assert both == pytest.approx(warp + comp, rel=1e-4)  # none unnamed
    # the composite sits near its memory floor, the warp far from it
    shapes = {"batch_per_device": 2, "planes": 32, "height": 384,
              "width": 512, "scales": 4, "band": 48}
    floors = roofline.train_step_calls(shapes, PEAKS)
    comp_share = sum(c["floor_s"] for c in floors
                     if c["name"].startswith("composite")) / comp
    warp_share = sum(c["floor_s"] for c in floors
                     if c["name"].startswith("warp")) / warp
    assert 0.5 < comp_share < 1.0 and 0.02 < warp_share < 0.06
