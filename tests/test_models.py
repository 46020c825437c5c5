import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu.models import embedder
from mine_tpu.models.decoder import MPIDecoder
from mine_tpu.models.mpi import MPIPredictor
from mine_tpu.models.resnet import ResnetEncoder, num_ch_enc


def test_positional_encoding_matches_reference_formula():
    """Reference Embedder (utils.py:144-193): [x, sin(2^0 x), cos(2^0 x), ...]"""
    x = jnp.asarray([[0.3], [1.7]])
    out = np.asarray(embedder.positional_encoding(x, multires=10))
    assert out.shape == (2, 21)
    np.testing.assert_allclose(out[:, 0], [0.3, 1.7], rtol=1e-6)
    for i, f in enumerate(2.0 ** np.arange(10)):
        np.testing.assert_allclose(out[:, 1 + 2 * i], np.sin([0.3 * f, 1.7 * f]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(out[:, 2 + 2 * i], np.cos([0.3 * f, 1.7 * f]),
                                   rtol=1e-4, atol=1e-5)
    assert embedder.embedding_dim(10) == 21


def test_resnet50_feature_shapes_and_channels():
    B, H, W = 1, 64, 96
    model = ResnetEncoder(num_layers=50)
    img = jnp.zeros((B, H, W, 3))
    variables = model.init(jax.random.PRNGKey(0), img, train=False)
    feats = model.apply(variables, img, train=False)
    chans = num_ch_enc(50)
    assert chans == (64, 256, 512, 1024, 2048)
    for i, f in enumerate(feats):
        stride = 2 ** (i + 1)
        assert f.shape == (B, H // stride, W // stride, chans[i]), (i, f.shape)


def test_resnet18_feature_shapes():
    model = ResnetEncoder(num_layers=18)
    img = jnp.zeros((1, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), img, train=False)
    feats = model.apply(variables, img, train=False)
    assert [f.shape[-1] for f in feats] == [64, 64, 128, 256, 512]


def test_resnet_matches_torch_conv_padding():
    """conv1 (7x7 s2 p3) + maxpool output sizes must match torch exactly for
    the reference's training resolutions."""
    for H, W in [(384, 512), (256, 384), (128, 384)]:
        model = ResnetEncoder(num_layers=18)
        img = jnp.zeros((1, H, W, 3))
        variables = model.init(jax.random.PRNGKey(0), img, train=False)
        feats = model.apply(variables, img, train=False)
        # torch: conv1 -> (H+6-7)//2+1 = H//2; maxpool -> H//4
        assert feats[0].shape[1:3] == (H // 2, W // 2)
        assert feats[1].shape[1:3] == (H // 4, W // 4)


def test_decoder_output_shapes_and_ranges():
    B, S, H, W = 1, 4, 64, 96
    chans = num_ch_enc(18)
    feats = [jnp.ones((B, H // 2 ** (i + 1), W // 2 ** (i + 1), c))
             for i, c in enumerate(chans)]
    disparity = jnp.broadcast_to(jnp.linspace(1.0, 0.1, S)[None], (B, S))
    model = MPIDecoder(num_ch_enc=chans)
    variables = model.init(jax.random.PRNGKey(0), feats, disparity, train=False)
    outs = model.apply(variables, feats, disparity, train=False)
    assert sorted(outs.keys()) == [0, 1, 2, 3]
    for s, mpi in outs.items():
        assert mpi.shape == (B, S, 4, H // 2 ** s, W // 2 ** s)
        rgb = np.asarray(mpi[:, :, 0:3])
        sigma = np.asarray(mpi[:, :, 3:])
        assert rgb.min() >= 0.0 and rgb.max() <= 1.0
        assert sigma.min() >= 1e-4  # |x| + 1e-4


def test_decoder_sigma_alpha_mode():
    B, S, H, W = 1, 2, 32, 32
    chans = num_ch_enc(18)
    feats = [jnp.ones((B, H // 2 ** (i + 1), W // 2 ** (i + 1), c))
             for i, c in enumerate(chans)]
    disparity = jnp.ones((B, S)) * 0.5
    model = MPIDecoder(num_ch_enc=chans, use_alpha=True)
    variables = model.init(jax.random.PRNGKey(0), feats, disparity, train=False)
    outs = model.apply(variables, feats, disparity, train=False)
    sigma = np.asarray(outs[0][:, :, 3:])
    assert sigma.min() >= 0.0 and sigma.max() <= 1.0


def test_decoder_is_disparity_sensitive():
    """Different plane disparities must produce different planes — the core
    'continuous depth' conditioning (depth_decoder.py:92-116)."""
    B, S, H, W = 1, 2, 32, 32
    chans = num_ch_enc(18)
    rng = np.random.RandomState(0)
    feats = [jnp.asarray(rng.normal(size=(B, H // 2 ** (i + 1), W // 2 ** (i + 1),
                                          c)).astype(np.float32))
             for i, c in enumerate(chans)]
    model = MPIDecoder(num_ch_enc=chans)
    d1 = jnp.asarray([[1.0, 0.9]])
    variables = model.init(jax.random.PRNGKey(0), feats, d1, train=False)
    out1 = model.apply(variables, feats, d1, train=False)[0]
    out2 = model.apply(variables, feats, jnp.asarray([[0.2, 0.1]]), train=False)[0]
    assert np.abs(np.asarray(out1) - np.asarray(out2)).max() > 1e-4


def test_mpi_predictor_end_to_end_shapes():
    B, S, H, W = 1, 3, 64, 64
    model = MPIPredictor(num_layers=18)
    img = jnp.ones((B, H, W, 3)) * 0.5
    disparity = jnp.broadcast_to(jnp.linspace(1.0, 0.1, S)[None], (B, S))
    variables = model.init(jax.random.PRNGKey(0), img, disparity, train=False)
    outs = model.apply(variables, img, disparity, train=False)
    assert len(outs) == 4
    for s, mpi in enumerate(outs):
        assert mpi.shape == (B, S, 4, H // 2 ** s, W // 2 ** s)


def test_plane_chunked_decoder_eval_exact_and_rematted():
    """plane_chunks>1 must (a) leave eval outputs exactly unchanged — the
    decoder is a pure function of (params, running stats) per plane, so
    chunk boundaries cannot show — (b) wrap each chunk in its own remat
    region (the B=8 HBM fix: backward holds ONE chunk's activations), and
    (c) fall back to a single call when S is not divisible (coarse-to-fine
    refinement passes)."""
    B, S, H, W = 1, 8, 64, 64
    img = jax.random.uniform(jax.random.PRNGKey(0), (B, H, W, 3))
    disparity = jnp.broadcast_to(jnp.linspace(1.0, 0.2, S)[None], (B, S))
    m1 = MPIPredictor(num_layers=18, plane_chunks=1)
    m4 = MPIPredictor(num_layers=18, plane_chunks=4)
    variables = m1.init(jax.random.PRNGKey(1), img, disparity, train=False)

    o1 = m1.apply(variables, img, disparity, train=False)
    o4 = m4.apply(variables, img, disparity, train=False)
    for a, b in zip(o1, o4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    # structural remat evidence: one remat2 region per chunk in the grad
    # jaxpr (jax.checkpoint lowers to the remat2 primitive)
    def loss(params):
        out, _ = m4.apply(params, img, disparity, train=True,
                          mutable=["batch_stats"],
                          rngs={"dropout": jax.random.PRNGKey(2)})
        return sum(jnp.mean(o) for o in out)
    jaxpr_text = str(jax.make_jaxpr(jax.grad(loss))(variables))
    import re
    # one remat2 region per chunk + one for the once-per-step neck call
    assert len(re.findall(r"\bremat2\b", jaxpr_text)) == 5

    # non-divisible S: silently un-chunked, still exact
    disparity6 = jnp.broadcast_to(jnp.linspace(1.0, 0.2, 6)[None], (B, 6))
    o1b = m1.apply(variables, img, disparity6, train=False)
    o4b = m4.apply(variables, img, disparity6, train=False)
    for a, b in zip(o1b, o4b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_batchnorm_train_updates_stats():
    model = MPIPredictor(num_layers=18)
    img = jnp.ones((2, 32, 32, 3)) * 0.3
    disparity = jnp.ones((2, 2)) * 0.5
    variables = model.init(jax.random.PRNGKey(0), img, disparity, train=False)
    _, mutated = model.apply(variables, img, disparity, train=True,
                             mutable=["batch_stats"])
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mutated["batch_stats"])
    diffs = [float(np.abs(np.asarray(a) - np.asarray(b)).max())
             for a, b in zip(after, before)]
    assert max(diffs) > 0.0


def test_bfloat16_forward_finite():
    model = MPIPredictor(num_layers=18, dtype=jnp.bfloat16)
    img = jnp.ones((1, 32, 32, 3)) * 0.5
    disparity = jnp.ones((1, 2)) * 0.5
    variables = model.init(jax.random.PRNGKey(0), img, disparity, train=False)
    outs = model.apply(variables, img, disparity, train=False)
    assert outs[0].dtype == jnp.float32  # rendering path gets fp32
    assert np.all(np.isfinite(np.asarray(outs[0])))


# --- the three-part conv (layers._PartsConv) ------------------------------

def _bf16_exact(tree):
    """Round every leaf to a bfloat16-representable float32, so that a
    bfloat16 run's only errors are the roundings of its results."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), tree)


def _parts_case(case):
    B, S, h, w, Cx, Cs, E = 2, 3, 6, 5, 4, 5, 3
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    parts = {
        "x": jax.random.normal(ks[0], (B * S, h, w, Cx)),
        "shared": jax.random.normal(ks[1], (B, h, w, Cs)),
        "const_tail": jax.random.normal(ks[2], (B * S, E)),
    }
    if case == "shared_tail":   # the decoder's stem: no per-plane part
        parts["x"] = None
    elif case == "x_tail":      # the const-tail path the parent had
        parts["shared"] = None
    cot = jax.random.normal(ks[3], (B * S, h, w, 6))
    return _bf16_exact(parts), _bf16_exact(cot), (B, S, h, w)


def _concat_of(parts, dims):
    """What the reference decoder builds: [x, expand(shared), emb maps]."""
    B, S, h, w = dims
    cols = []
    if parts["x"] is not None:
        cols.append(parts["x"])
    if parts["shared"] is not None:
        s = parts["shared"]
        cols.append(jnp.broadcast_to(s[:, None], (B, S) + s.shape[1:])
                    .reshape((B * S,) + s.shape[1:]))
    t = parts["const_tail"]
    cols.append(jnp.broadcast_to(t[:, None, None, :], (B * S, h, w,
                                                       t.shape[-1])))
    return jnp.concatenate(cols, axis=-1)


def _parent_const_tail_conv(params, parts, dims, dt):
    """The parent's path (its _SplitTailConv): the skip expanded into the
    conv's input, the tail term and the bias added in `dt` one by one."""
    kernel, bias = params["conv"]["kernel"], params["conv"]["bias"]
    E = parts["const_tail"].shape[-1]
    xin = _concat_of(parts, dims)[..., :-E]
    xin = jnp.pad(xin, ((0, 0), (1, 1), (1, 1), (0, 0)), mode="reflect")
    y = jax.lax.conv_general_dilated(
        xin.astype(dt), kernel[:, :, :-E].astype(dt), (1, 1),
        ((0, 0), (0, 0)), dimension_numbers=("NHWC", "HWIO", "NHWC"))
    w_tail = jnp.sum(kernel[:, :, -E:], axis=(0, 1))
    y = y + (parts["const_tail"].astype(dt)
             @ w_tail.astype(dt))[:, None, None, :]
    return y + bias.astype(dt)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["x_shared_tail", "shared_tail", "x_tail"])
def test_parts_conv_equals_conv_over_concat(case, dtype):
    """Conv(x, shared=, const_tail=) is the explicit concat -> reflect pad ->
    conv from the SAME parameters: forward, and gradients to x, the shared
    input, the tail, the kernel and the bias."""
    from mine_tpu.models.layers import Conv
    dt = jnp.dtype(dtype)
    parts, cot, dims = _parts_case(case)
    split = Conv(6, 3, pad_mode="reflect", dtype=dt)
    plain = Conv(6, 3, pad_mode="reflect")  # float32, over the concat
    params = _bf16_exact(split.init(jax.random.PRNGKey(3), **parts)["params"])
    full_in = sum(t.shape[-1] for t in parts.values() if t is not None)
    assert params["conv"]["kernel"].shape == (3, 3, full_in, 6)
    live = {k: v for k, v in parts.items() if v is not None}

    def run(fn, in_dtype):
        """Inputs arrive in the conv's dtype, as the decoder hands them."""
        def loss(p, live):
            y = fn(p, {**parts, **live}).astype(jnp.float32)
            return jnp.sum(y * cot), y
        (_, y), g = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, {k: v.astype(in_dtype) for k, v in live.items()})
        return {"y": y, "kernel": g[0]["conv"]["kernel"],
                "bias": g[0]["conv"]["bias"],
                **{k: v.astype(jnp.float32) for k, v in g[1].items()}}

    want = run(lambda p, q: plain.apply({"params": p}, _concat_of(q, dims)),
               jnp.float32)
    got = run(lambda p, q: split.apply({"params": p}, **q), dt)

    def err(a, name):
        return float(jnp.max(jnp.abs(a[name] - want[name]))
                     / jnp.max(jnp.abs(want[name])))

    if dtype == "float32":
        for name in want:
            assert err(got, name) < 1e-5, (name, err(got, name))
        return
    # bfloat16: two roundings (the per-plane conv's result, then the sum)
    # of at most half a unit in the last place, 2**-8 of the scale, each
    parent = run(lambda p, q: _parent_const_tail_conv(p, q, dims, dt), dt)
    for name in want:
        assert err(got, name) <= 2 * 2.0 ** -8, (name, err(got, name))
        assert err(got, name) <= 1.05 * err(parent, name) + 1e-6, (
            name, err(got, name), err(parent, name))


# --- row strips folded into the batch (decoder.fold_strips) ---------------

@pytest.mark.parametrize("per_device, rows, want", [
    (64, 48, 2),     # llff_n32: B*S = 64 planes a chip, 48 rows at the entry
    (32, 48, 4),     # eval and the serve encode: B = 1
    (96, 48, 4),     # gcd 32
    (128, 32, 1),    # re10k_n32: the lanes are full
    (256, 32, 1),
    (16, 48, 1),     # 8 strips: more halo than padding
    (8, 4, 1),       # the tiny programs of tools/analysis_baseline.json
    (64, 12, 1),     # strips of 6 rows
    (64, 17, 1),     # rows the strips do not divide
])
def test_fold_strips_follows_from_shapes(per_device, rows, want):
    from mine_tpu.models.decoder import fold_strips
    assert fold_strips(per_device, rows) == want


@pytest.mark.parametrize("k", [1, 2, 3, 4])
def test_reflect_pad_of_strips_is_the_strips_of_the_padded_image(k):
    """Bit for bit: strip s of the halo pad is rows [s*hs, s*hs + hs + 2)
    of jnp.pad(mode="reflect") of the unfolded image."""
    from mine_tpu.models.layers import reflect_pad_strips
    N, hs, w, C = 3, 4, 5, 2
    img = jax.random.normal(jax.random.PRNGKey(k), (N, k * hs, w, C))
    got = reflect_pad_strips(img.reshape(N * k, hs, w, C), k)
    padded = jnp.pad(img, ((0, 0), (1, 1), (1, 1), (0, 0)), mode="reflect")
    want = jnp.stack([padded[:, s * hs:s * hs + hs + 2] for s in range(k)],
                     axis=1).reshape(N * k, hs + 2, w + 2, C)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _fold_gauge():
    from mine_tpu import telemetry
    return telemetry.REGISTRY.snapshot()["model.decoder.fold_strips"]


def _decoder_run(k, variant, use_skips, chunks, natural=False, dropout=0.0):
    """A jitted decoder forward + backward: outputs, batch_stats updates
    and gradients w.r.t. every parameter and encoder feature. `natural`:
    the least shapes at which k strips engage by themselves (128 / k
    planes a call, strips of 8 rows at the entry); else a few planes of
    strips of 8 / k rows, for a test that forces k."""
    if natural:
        (B, S), H = {2: (2, 32), 4: (1, 32)}[k], 64 * k
    else:
        (B, S), H = (2, 3), 64
    S, W = S * chunks, 64
    chans = num_ch_enc(18)
    rng = np.random.RandomState(k)
    feats = [jnp.asarray(rng.normal(size=(
        B, H // 2 ** (i + 1), W // 2 ** (i + 1), c)).astype(np.float32))
        for i, c in enumerate(chans)]
    disp = jnp.asarray(rng.uniform(0.1, 1.0, size=(B, S)).astype(np.float32))
    if chunks == 1:
        model = MPIDecoder(num_ch_enc=chans, variant=variant,
                           use_skips=use_skips, sigma_dropout_rate=dropout)
        variables = jax.jit(lambda: model.init(
            jax.random.PRNGKey(0), feats, disp, False))()
        method = None
    else:
        assert use_skips and not dropout
        model = MPIPredictor(num_layers=18, decoder_variant=variant,
                             plane_chunks=chunks)
        variables = jax.jit(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((B, H, W, 3)), disp,
            train=False))()
        method = "decode"

    def loss(params, feats):
        out, mut = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            feats, disp, True, method=method, mutable=["batch_stats"],
            rngs={"dropout": jax.random.PRNGKey(5)})
        out = [out[s] for s in sorted(out)] if isinstance(out, dict) else out
        return sum(jnp.mean(jnp.sin(3.0 * o)) for o in out), (out, mut)

    def run():
        (_, (out, mut)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(variables["params"], feats)
        return {"out": out, "batch_stats": mut["batch_stats"],
                "params": grads[0], "features": grads[1]}
    return run


FOLD_CASES = [(k, variant, use_skips, chunks, False)
              for k in (2, 4) for variant in ("reference", "packed")
              for use_skips, chunks in ((True, 1), (False, 1), (True, 2))]
FOLD_CASES += [(2, "reference", True, 1, True), (4, "reference", True, 1, True)]


@pytest.mark.parametrize("k, variant, use_skips, chunks, natural", FOLD_CASES)
def test_folded_decoder_is_the_plain_decoder(k, variant, use_skips, chunks,
                                             natural, monkeypatch):
    """Float32: the four outputs, the batch_stats updates and every
    gradient of the decoder on k strips equal the k = 1 decoder's, to the
    noise of float32 sums taken in another order (readings up to 1.0e-4
    of a module's scale, on a conv bias whose gradient sums a whole
    level's cotangents; one wrong halo row reads 1e-1). The natural cases
    (N = 64 and N = 32 planes) fold by themselves; the others are a few
    planes with k forced, as the k = 1 side always is."""
    from mine_tpu.models import decoder
    run = _decoder_run(k, variant, use_skips, chunks, natural)
    if not natural:
        monkeypatch.setattr(decoder, "fold_strips", lambda *a: k)
    got = run()
    assert _fold_gauge() == k
    monkeypatch.setattr(decoder, "fold_strips", lambda *a: 1)
    want = run()
    assert _fold_gauge() == 1

    def scale_of(leaf, module):
        """A conv bias under a BatchNorm has a zero gradient: both sides
        hold rounding noise there, so a leaf's scale is its module's."""
        return max(float(jnp.max(jnp.abs(leaf))),
                   max(float(jnp.max(jnp.abs(v)))
                       for v in jax.tree_util.tree_leaves(module)))

    for name in want:
        flat_w = jax.tree_util.tree_flatten_with_path(want[name])[0]
        flat_g = jax.tree_util.tree_leaves(got[name])
        assert len(flat_w) == len(flat_g)
        for (path, w), g in zip(flat_w, flat_g):
            module = want[name]
            for key in path[:-2] if name == "params" else ():
                module = module[key.key]
            # (the backbone's gradients through `decode` are zeros)
            err = float(jnp.max(jnp.abs(g - w))) / max(scale_of(w, module),
                                                       1e-30)
            assert g.shape == w.shape and err < 5e-4, (
                name, jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("k", [2, 4])
def test_sigma_dropout_draws_one_mask_a_plane_on_strips(k, monkeypatch):
    """Whole-plane dropout stays whole-plane where a plane is k batch
    entries: every plane's sigma is all zero or nowhere zero, at every
    scale, and the planes dropped are the k = 1 decoder's."""
    from mine_tpu.models import decoder
    run = _decoder_run(k, "reference", True, 1, dropout=0.5)
    monkeypatch.setattr(decoder, "fold_strips", lambda *a: k)
    got = run()["out"]
    assert _fold_gauge() == k
    monkeypatch.setattr(decoder, "fold_strips", lambda *a: 1)
    want = run()["out"]
    for g, w in zip(got, want):
        dropped = np.asarray(g[:, :, 3] == 0.0)      # [B, S, h, w]
        per_plane = dropped.reshape(dropped.shape[:2] + (-1,))
        assert np.all(per_plane.all(-1) | ~per_plane.any(-1))
        assert 0 < per_plane.all(-1).sum() < per_plane[..., 0].size
        np.testing.assert_array_equal(dropped, np.asarray(w[:, :, 3] == 0.0))


def test_fold_counts_the_planes_of_a_device_on_a_mesh(monkeypatch):
    """B*S = 256 planes over a data mesh of 4 are 64 a device: two strips,
    as on one chip at B*S = 64. The rule reads the mesh, not a key."""
    from mine_tpu.models import decoder
    from mine_tpu.parallel.mesh import make_mesh
    seen = []
    real = decoder.fold_strips
    monkeypatch.setattr(decoder, "fold_strips",
                        lambda n, rows: seen.append(n) or real(n, rows))
    B, S, H, W = 8, 32, 128, 64
    chans = num_ch_enc(18)
    feats = [jnp.zeros((B, H // 2 ** (i + 1), W // 2 ** (i + 1), c))
             for i, c in enumerate(chans)]
    disp = jnp.full((B, S), 0.5)
    for mesh, want in ((make_mesh(data=4, devices=jax.devices()[:4]), 64),
                       (make_mesh(data=2, plane=2,
                                  devices=jax.devices()[:4]), 64),
                       (None, 256)):
        dec = MPIDecoder(num_ch_enc=chans, mesh=mesh)
        jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), feats, disp,
                                        False))
        assert seen[-1] == want
        assert _fold_gauge() == (2 if want == 64 else 1)


# --- what the decoder lowers to, and what its checkpoints hold -------------

def _lowered_convs(text):
    """(batch, C_in, out height) of every stablehlo.convolution in NHWC x
    HWIO form in a lowered module's text."""
    import re
    pat = re.compile(
        r"stablehlo\.convolution.*?\[b, 0, 1, f\]x\[0, 1, i, o\]->"
        r"\[b, 0, 1, f\].*?: \(tensor<(\d+)x\d+x\d+x(\d+)x\w+>, "
        r"tensor<[^>]+>\) -> tensor<\d+x(\d+)x")
    return [tuple(int(g) for g in m.groups()) for m in pat.finditer(text)]


def test_decoder_convolves_each_skip_once_per_image():
    """The structural counter of the split: in the lowered forward no
    convolution at batch B*S reads more than its level's decoder width
    (the skip and neck channels never ride the B*S batch), and beside the
    neck's four, exactly five convolutions run at batch B: upconv_4_0's
    over the neck output and the four upconv_{4..1}_1's over the skips."""
    from mine_tpu.models.decoder import NUM_CH_DEC
    B, S, H, W = 2, 4, 64, 96
    chans = num_ch_enc(50)
    dec = MPIDecoder(num_ch_enc=chans)
    feats = [jnp.zeros((B, H // 2 ** (i + 1), W // 2 ** (i + 1), c))
             for i, c in enumerate(chans)]
    disp = jnp.full((B, S), 0.5)
    variables = jax.eval_shape(
        lambda: dec.init(jax.random.PRNGKey(0), feats, disp, False))

    def fwd(v, feats, disp):
        return dec.apply(v, feats, disp, True, mutable=["batch_stats"])[0]
    convs = _lowered_convs(jax.jit(fwd).lower(variables, feats, disp)
                           .as_text())
    assert len(convs) == 4 + 5 + 13, convs  # neck, shared, per-plane
    per_plane = [c for c in convs if c[0] == B * S]
    assert len(per_plane) == 13  # every upconv but the stem's, 4 dispconvs
    for _, c_in, h_out in per_plane:
        level = {H // 2 ** k: k for k in range(5)}[h_out]
        assert c_in <= 2 * NUM_CH_DEC[level] and c_in <= 256, (c_in, h_out)
    at_b = sorted((c_in, h_out) for n, c_in, h_out in convs if n == B)
    neck = sorted([(2048, 1), (512, 1), (256, 2), (256, 4)])
    shared = sorted([(2048, 2), (1024, 4), (512, 8), (256, 16), (64, 32)])
    assert at_b == sorted(neck + shared), at_b


def test_predictor_parameter_tree_is_the_parents():
    """Paths, shapes and dtypes of MPIPredictor(num_layers=50)'s variables
    are, entry for entry, what the commit before the split conv created
    (tests/mpi_predictor_r50_tree.json, written from that commit): its
    checkpoints and converted reference checkpoints load unchanged."""
    import json
    import os
    model = MPIPredictor(num_layers=50)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)),
                           jnp.ones((1, 2)), train=False))
    flat = jax.tree_util.tree_flatten_with_path(variables)[0]
    got = [["/".join(k.key for k in path), list(leaf.shape), str(leaf.dtype)]
           for path, leaf in flat]
    with open(os.path.join(os.path.dirname(__file__),
                           "mpi_predictor_r50_tree.json")) as f:
        assert got == json.load(f)
