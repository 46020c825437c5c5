"""Packed-head decoder variant (model.decoder_variant: "packed").

The reference geometry's stride-2->1 output stage is its worst MXU stage
(16/128 output lanes at the largest pixel counts — lane table of the
round-3 notes, git history). The packed variant computes that stage at stride 2 with 4x channels
and a depth-to-space head (models/decoder.py). These tests pin down:

  * the conversion story: reference stage-0 weights map EXACTLY onto the
    packed kernels via phase decomposition (tools/convert_torch_weights.py
    packed_head_transform) — eval-mode outputs agree in the interior, and
    the untouched scales 1-3 agree everywhere;
  * the variant trains (finite loss through a full SynthesisTrainer step).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

from convert_torch_weights import packed_head_transform  # noqa: E402

from mine_tpu.models.decoder import MPIDecoder, depth_to_space_2x

NUM_CH_ENC = (64, 64, 128, 256, 512)  # resnet18-family taps


def _flatten(prefix, tree, into):
    for k, v in tree.items():
        key = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            _flatten(key, v, into)
        else:
            into[key] = v
    return into


def _unflatten_into(template, flat, prefix_tag=""):
    """Template-shaped copy of `template` with values taken from flat keys."""
    def rebuild(prefix, t):
        out = {}
        for k, v in t.items():
            key = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                out[k] = rebuild(key, v)
            else:
                arr = flat[prefix_tag + key]
                out[k] = jnp.asarray(arr, dtype=v.dtype).reshape(v.shape)
        return out
    return rebuild("", template)


def _fake_features(rng, B=1, H=64, W=64):
    feats = []
    for s, c in zip((2, 4, 8, 16, 32), NUM_CH_ENC):
        rng, k = jax.random.split(rng)
        feats.append(jax.random.normal(k, (B, H // s, W // s, c),
                                       jnp.float32) * 0.5)
    return feats


def test_depth_to_space_layout():
    """Phase-major layout: channel (dy*2+dx)*C + c -> spatial (dy, dx)."""
    C = 3
    x = np.zeros((1, 2, 2, 4 * C), np.float32)
    for ph in range(4):
        x[..., ph * C:(ph + 1) * C] = ph + 1
    y = np.asarray(depth_to_space_2x(jnp.asarray(x)))
    assert y.shape == (1, 4, 4, C)
    # phase (dy, dx) = value dy*2+dx+1 at output (2i+dy, 2j+dx)
    for dy in range(2):
        for dx in range(2):
            assert (y[0, dy::2, dx::2, :] == dy * 2 + dx + 1).all()


def test_packed_head_transform_is_interior_exact():
    """Reference-variant decoder with randomized weights vs packed-variant
    decoder with the TRANSFORMED weights: scales 1-3 identical (shared
    trunk), scale 0 identical away from the border (reflect padding at
    stride 2 vs 1 differs in a few-pixel rim — the documented caveat)."""
    B, S, H, W = 1, 2, 64, 64
    rng = jax.random.PRNGKey(0)
    feats = _fake_features(rng, B, H, W)
    disparity = jnp.asarray([[0.9, 0.4]], jnp.float32)

    ref = MPIDecoder(num_ch_enc=NUM_CH_ENC, variant="reference")
    packed = MPIDecoder(num_ch_enc=NUM_CH_ENC, variant="packed")
    v_ref = ref.init(jax.random.PRNGKey(1), feats, disparity, train=False)
    v_pk = packed.init(jax.random.PRNGKey(2), feats, disparity, train=False)

    # randomize the reference weights (incl. BN stats) so the transform has
    # teeth — fresh-init BN (scale 1, mean 0) would make tiling trivially
    # correct
    flat = {}
    _flatten("decoder", v_ref["params"], flat)
    stats = {}
    _flatten("decoder", v_ref["batch_stats"], stats)
    rs = np.random.RandomState(7)
    for k, v in list(flat.items()):
        flat[k] = (0.2 * rs.normal(size=v.shape)).astype(np.float32)
    for k, v in list(stats.items()):
        a = rs.normal(size=v.shape).astype(np.float32)
        stats["stats:" + k] = np.abs(a) + 0.5 if k.endswith("/var") else 0.3 * a
        del stats[k]
    flat.update(stats)

    moved = packed_head_transform(flat)

    def strip(d):
        return {k[len("decoder/"):] if not k.startswith("stats:")
                else "stats:" + k[len("stats:decoder/"):]: v
                for k, v in d.items()}

    flat_s, moved_s = strip(flat), strip(moved)
    vr = {"params": _unflatten_into(v_ref["params"], flat_s),
          "batch_stats": _unflatten_into(v_ref["batch_stats"], flat_s,
                                         "stats:")}
    vp = {"params": _unflatten_into(v_pk["params"], moved_s),
          "batch_stats": _unflatten_into(v_pk["batch_stats"], moved_s,
                                         "stats:")}

    out_ref = ref.apply(vr, feats, disparity, train=False)
    out_pk = packed.apply(vp, feats, disparity, train=False)

    for s in (1, 2, 3):  # untouched trunk: bitwise-equal paths
        np.testing.assert_allclose(np.asarray(out_pk[s]),
                                   np.asarray(out_ref[s]), rtol=0, atol=1e-6)
    a, b = np.asarray(out_ref[0]), np.asarray(out_pk[0])  # [B,S,4,H,W]
    assert a.shape == b.shape == (B, S, 4, H, W)
    m = 6  # documented border caveat: reflect-pad mismatch rim
    np.testing.assert_allclose(b[..., m:-m, m:-m], a[..., m:-m, m:-m],
                               rtol=2e-4, atol=2e-5)
    # and the border is genuinely different (otherwise the crop is theater)
    assert not np.allclose(b, a, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_packed_variant_trains():
    """One full SynthesisTrainer step with model.decoder_variant=packed."""
    from mine_tpu.config import CONFIG_DIR, load_config
    from mine_tpu.data.synthetic import make_batch
    from mine_tpu.train.step import SynthesisTrainer

    config = load_config(os.path.join(CONFIG_DIR, "params_default.yaml"))
    config.update({
        "data.name": "synthetic",
        "data.img_h": 64, "data.img_w": 64,
        "data.per_gpu_batch_size": 1,
        "mpi.num_bins_coarse": 4,
        "mpi.disparity_end": 0.2,
        "model.num_layers": 18,
        "model.decoder_variant": "packed",
        "training.dtype": "float32",
    })
    trainer = SynthesisTrainer(config, steps_per_epoch=10)
    state = trainer.init_state(batch_size=1)
    batch = {k: jnp.asarray(v)
             for k, v in make_batch(1, 64, 64, num_points=16).items()}
    state, metrics = trainer.train_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
