"""Plane parallelism must DISTRIBUTE the decoder, not just annotate the loss
graph (VERDICT r1 weak item 3): on the virtual 8-device mesh, compiled
per-device cost with the decoder's B*S sharding constraints must be a
fraction of the unconstrained (plane-replicated) program's.

The decoder is where B*S lives (depth_decoder.py:105-116); without internal
constraints GSPMD replicates its conv stack across the "plane" axis and
plane_parallel>1 buys nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu.models.mpi import MPIPredictor
from mine_tpu.parallel import mesh as mesh_lib


def _compiled_forward(mesh, model_mesh):
    model = MPIPredictor(num_layers=18, mesh=model_mesh)
    B, H, W, S = 2, 32, 32, 8
    img = jnp.zeros((B, H, W, 3))
    disp = jnp.full((B, S), 0.5)
    vars_ = model.init(jax.random.PRNGKey(0), img, disp, train=False)

    def fwd(v, img, disp):
        outs = model.apply(v, img, disp, train=False)
        return sum(jnp.sum(o) for o in outs)

    repl = mesh_lib.replicated(mesh)
    bs = mesh_lib.batch_sharding(mesh)
    return jax.jit(fwd, in_shardings=(repl, bs, bs)).lower(
        vars_, img, disp).compile()


def _flops(compiled):
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    return float(ca["flops"])


def test_decoder_plane_sharding_distributes_flops():
    mesh = mesh_lib.make_mesh(data=2, plane=4)
    sharded = _flops(_compiled_forward(mesh, mesh))
    replicated = _flops(_compiled_forward(mesh, None))
    # decoder dominates; plane=4 should cut per-device work by ~3-4x.
    # (measured at commit time: 186M vs 590M = 3.2x)
    assert sharded < 0.5 * replicated, (sharded, replicated)


def test_decoder_plane_sharding_preserves_numerics():
    """Same forward values with and without the decoder mesh constraints."""
    mesh = mesh_lib.make_mesh(data=2, plane=4)
    B, H, W, S = 2, 32, 32, 8
    img = jax.random.uniform(jax.random.PRNGKey(1), (B, H, W, 3))
    disp = jnp.broadcast_to(jnp.linspace(1.0, 0.2, S)[None], (B, S))

    outs = {}
    for name, mm in (("sharded", mesh), ("plain", None)):
        model = MPIPredictor(num_layers=18, mesh=mm)
        vars_ = model.init(jax.random.PRNGKey(0), img, disp, train=False)
        repl = mesh_lib.replicated(mesh)
        bs = mesh_lib.batch_sharding(mesh)
        f = jax.jit(lambda v, i, d: model.apply(v, i, d, train=False),
                    in_shardings=(repl, bs, bs))
        outs[name] = [np.asarray(o) for o in f(vars_, img, disp)]

    for a, b in zip(outs["sharded"], outs["plain"]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("data,plane", [(4, 1), (2, 2)])
def test_decoder_shared_skip_broadcast_holds_under_gspmd(data, plane):
    """The decoder convolves each skip once at batch B and adds it to the
    B*S tensor through a [B*S] <-> [B, S] reshape of the B-major axis. Under
    `data: 4` (llff_train_dp4's mesh) and `data: 2, plane: 2` a train-mode
    forward (SyncBN statistics) and the gradients to the parameters and to
    the encoder's features must be the unsharded ones."""
    from mine_tpu.models.decoder import MPIDecoder
    from mine_tpu.models.resnet import num_ch_enc
    mesh = mesh_lib.make_mesh(data=data, plane=plane,
                              devices=jax.devices()[:data * plane])
    B, H, W, S = 4, 32, 32, 4
    chans = num_ch_enc(18)
    ks = jax.random.split(jax.random.PRNGKey(2), len(chans))
    feats = [jax.random.normal(k, (B, H // 2 ** (i + 1), W // 2 ** (i + 1), c))
             for i, (k, c) in enumerate(zip(ks, chans))]
    disp = jnp.broadcast_to(jnp.linspace(1.0, 0.2, S)[None], (B, S))

    results = {}
    for name, mm in (("sharded", mesh), ("plain", None)):
        dec = MPIDecoder(num_ch_enc=chans, mesh=mm)
        vars_ = dec.init(jax.random.PRNGKey(0), feats, disp, False)

        def loss(params, feats, disp):
            out, _ = dec.apply({**vars_, "params": params}, feats, disp,
                               True, mutable=["batch_stats"])
            return sum(jnp.mean(o ** 2) for o in out.values()), out

        f = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
        if mm is not None:
            repl = mesh_lib.replicated(mesh)
            bs = mesh_lib.batch_sharding(mesh)
            f = jax.jit(f, in_shardings=(repl, bs, bs))
        results[name] = jax.tree_util.tree_map(
            np.asarray, f(vars_["params"], feats, disp))

    # one scale a group (loss and outputs, parameter gradients, feature
    # gradients): a conv bias in front of a BatchNorm has a zero gradient,
    # so its own scale is rounding noise
    (loss_s, outs_s), (gp_s, gf_s) = results["sharded"]
    (loss_p, outs_p), (gp_p, gf_p) = results["plain"]
    for got, want in (((loss_s, outs_s), (loss_p, outs_p)),
                      (gp_s, gp_p), (gf_s, gf_p)):
        got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
        assert len(got) == len(want)
        scale = max(float(np.abs(b).max()) for b in want)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4 * scale)
