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
