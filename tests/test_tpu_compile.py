"""The main path's Pallas kernels, handed to the chip's compiler at every
shape the benchmark's MINE cells run — without a chip.

libtpu compiles for a *described* v5e here on the CPU host (the
on-chip-measurement guide, section 2): what Mosaic refuses — a band slice
off the sublane tile, a scalar table past SMEM, too much VMEM — fails
these tests at no chip time. Interpret mode cannot show any of that, so
every call site's `interpret=not on_tpu_backend()` is steered to False
from here. Nothing runs: a compile that passes is not a chip run
(`python chip_smoke.py` is).

The topology is described inside a fixture, never at import: only one
process may load libtpu, and under xdist every worker imports this file.
Compiles happen in this process, with the persistent compile cache off
around them (an entry written for a described device cannot be read back
and only warns). Keep these tests in this one file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

S = 32                   # mpi.num_bins_coarse of both configurations
# the 4-scale loss pyramid of each train cell -> its per-chip batch:
# params_llff.yaml 384x512 at B = 2 (B*S = 64), params_realestate.yaml
# 256x384 at B = 4 (B*S = 128; W = 192 is one and a half lane tiles)
LLFF = [(384, 512), (192, 256), (96, 128), (48, 64)]
RE10K = [(256, 384), (128, 192), (64, 96), (32, 48)]
BATCH = {**{hw: 2 for hw in LLFF}, **{hw: 4 for hw in RE10K}}
BAND = 48                # training.warp_band default; all of a 48-row image
SERVE_BAND = 32          # infer/video.py WARP_BAND
SERVE_POSES = 8          # serve.max_bucket default
# params_ouro_2p6b.yaml / benchmark/traffic/packed_docs_4k.json
LM_ROWS, LM_SEQ = 2, 4096


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sq(out):
    return sum(jnp.sum(o.astype(jnp.float32) ** 2)
               for o in jax.tree_util.tree_leaves(out))


def _warp_case(hw):
    """fwd + VJP of the guarded training warp over all B*S planes (4
    channels: rgb + sigma, ops/rendering.py)."""
    def build():
        from mine_tpu.kernels.warp_vjp import bilinear_sample_diff_guarded
        fn = functools.partial(bilinear_sample_diff_guarded, band=BAND,
                               interpret=False)
        H, W = hw
        n = BATCH[hw] * S
        shapes = [((n, 4, H, W), jnp.float32),
                  ((n, H, W), jnp.float32), ((n, H, W), jnp.float32)]
        return jax.grad(lambda s, x, y: _sq(fn(s, x, y))), shapes
    return build


def _composite_case(hw):
    """fwd + VJP of the training composite, called as ops/rendering.py calls
    it: rgb and sigma are slices of the warped 4-channel volume, xyz is the
    closed-form field beside it (no cotangent: nothing learns from it). (With
    three entry parameters and this file's sum-of-squares loss the compiler
    places the loss's fused cotangents beside the kernel and refuses 32x48:
    18.41 MiB of scoped VMEM against 16. The step never builds that program;
    re10k_n32's whole step compiles with this kernel in it.)"""
    def build():
        from mine_tpu.kernels.composite_vjp import fused_volume_render_diff
        H, W = hw
        shapes = [((BATCH[hw], S, c, H, W), jnp.float32) for c in (4, 3)]
        return jax.grad(
            lambda v, xyz: _sq(fused_volume_render_diff(
                v[:, :, 0:3], v[:, :, 3:4], xyz, True, False,
                False))), shapes
    return build


def _src_blend_case(hw):
    def build():
        from mine_tpu.kernels.composite import fused_src_render_blend
        H, W = hw
        shapes = [((1, S, c, H, W), jnp.float32) for c in (3, 1, 3)]
        shapes.append(((1, 3, H, W), jnp.float32))
        return functools.partial(fused_src_render_blend,
                                 is_bg_depth_inf=False,
                                 interpret=False), shapes
    return build


def _serve_warp_case(hw, views=SERVE_POSES):
    """The render engine's forward warp of one pose bucket: views x S
    planes of the 4-channel volume (`warp_bilinear_sample_fwd`
    f32[32|64|128|256, 4, 384, 512] in llff_serve_steady's device_ops)."""
    def build():
        from mine_tpu.kernels.warp import pallas_bilinear_sample
        H, W = hw
        n = views * S
        return (functools.partial(pallas_bilinear_sample, band=SERVE_BAND,
                                  interpret=False),
                [((n, 4, H, W), jnp.float32), ((n, H, W), jnp.float32),
                 ((n, H, W), jnp.float32)])
    return build


def _serve_composite_case(hw):
    def build():
        from mine_tpu.kernels.composite import fused_volume_render
        H, W = hw
        return (functools.partial(fused_volume_render, interpret=False),
                [((SERVE_POSES, S, c, H, W), jnp.float32)
                 for c in (3, 1, 3)])
    return build


def _attention_case():
    """kernels/attention.py forward + both backward kernels at the looped
    language model's cell: 2 rows of 4096 tokens, 16 heads x 128, bf16."""
    from mine_tpu.kernels.attention import flash_attention
    shapes = [((LM_ROWS, LM_SEQ, 16 * 128), jnp.bfloat16)] * 3
    return jax.grad(lambda q, k, v: _sq(flash_attention(
        q, k, v, 16, interpret=False)), argnums=(0, 1, 2)), shapes


def _index_scores_case(chunk, context):
    """kernels/attention.py `index_scores` at the sparse serve cell's
    buckets (params_dots3_note.yaml): a chunk's 64 index heads x 128 against
    a block table's index keys."""
    def build():
        from mine_tpu.kernels.attention import index_scores
        return (lambda q, w, k: index_scores(q, w, k, context - chunk),
                [((chunk, 64, 128), jnp.bfloat16), ((chunk, 64), jnp.float32),
                 ((context, 128), jnp.bfloat16)])
    return build


def _window_attention_case(chunk):
    """`window_attention` as a sliding layer's chunk runs it: 64 heads of
    192 + 64, values 128, the chunk's rows behind a window of 512 keys."""
    def build():
        from mine_tpu.kernels.attention import window_attention
        keys = chunk + 512
        return (lambda q, k, v: window_attention(q, k, v, 64, 512, 256 ** -0.5,
                                                 513, 100),
                [((chunk, 64 * 256), jnp.bfloat16),
                 ((keys, 64 * 256), jnp.bfloat16),
                 ((keys, 64 * 128), jnp.bfloat16)])
    return build


def _masked_attention_case(chunk, context):
    """`masked_prefix_attention` as a short context's selected attention
    runs it: 16 heads a call (moe_mla.DENSE_HEADS) of 128 + 64, values 128,
    the selection an int8 mask."""
    def build():
        from mine_tpu.kernels.attention import masked_prefix_attention
        return (lambda qn, qr, kn, kr, v, m: masked_prefix_attention(
            qn, qr, kn, kr, v, m, 16, context - chunk, 192 ** -0.5),
                [((chunk, 16 * 128), jnp.bfloat16),
                 ((16, chunk, 64), jnp.bfloat16),
                 ((context, 16 * 128), jnp.bfloat16),
                 ((context, 64), jnp.bfloat16),
                 ((context, 16 * 128), jnp.bfloat16),
                 ((chunk, context), jnp.int8)])
    return build


CASES = {"attention_vjp-4096x16x128": _attention_case}
for _chunk in (768, 2048):
    for _context in (16384, 65536):
        CASES["masked_attention-%dx%d" % (_chunk, _context)] = (
            _masked_attention_case(_chunk, _context))
    CASES["window_attention-%d" % _chunk] = _window_attention_case(_chunk)
    for _context in (16384, 32768, 65536, 133120):
        CASES["index_scores-%dx%d" % (_chunk, _context)] = _index_scores_case(
            _chunk, _context)
for _hw in LLFF + RE10K:
    CASES["warp_diff_vjp-%dx%d" % _hw] = _warp_case(_hw)
    CASES["composite_vjp-%dx%d" % _hw] = _composite_case(_hw)
    CASES["src_render_blend-%dx%d" % _hw] = _src_blend_case(_hw)
for _hw in (LLFF[0], RE10K[0]):
    CASES["serve_warp_fwd-%dx%d" % _hw] = _serve_warp_case(_hw)
    CASES["serve_composite_fwd-%dx%d" % _hw] = _serve_composite_case(_hw)
for _views in (1, 2, 4):   # the smaller pow2 pose buckets of the serve cell
    CASES["serve_warp_fwd-384x512-views%d" % _views] = _serve_warp_case(
        LLFF[0], _views)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    # conftest.py raises the matmul precision to "highest" for the CPU
    # numerics tests; the CLIs run at JAX's default, and so does this
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{case}: compiled, but no Pallas kernel is in the program")


def _decoder_fwd_bwd(topo, devices, batch):
    """The MPI decoder's forward + backward at llff_n32's shapes (384x512,
    32 planes, ResNet-50 features, bfloat16) lowered for `devices` of the
    described slice: one chip, or a data mesh with the batch sharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mine_tpu.models.decoder import MPIDecoder
    from mine_tpu.models.resnet import num_ch_enc
    from mine_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(data=len(devices), devices=devices)
    repl, by_batch = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    (H, W), chans = LLFF[0], num_ch_enc(50)
    dec = MPIDecoder(num_ch_enc=chans, dtype=jnp.bfloat16,
                     mesh=mesh if len(devices) > 1 else None)
    feats = [jax.ShapeDtypeStruct(
        (batch, H // 2 ** (i + 1), W // 2 ** (i + 1), c), jnp.bfloat16,
        sharding=by_batch) for i, c in enumerate(chans)]
    disp = jax.ShapeDtypeStruct((batch, S), jnp.float32, sharding=by_batch)
    variables = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
        jax.eval_shape(lambda f, d: dec.init(jax.random.PRNGKey(0), f, d,
                                             False), feats, disp))

    def loss(params, stats, feats, disp):
        out, mut = dec.apply({"params": params, "batch_stats": stats},
                             feats, disp, True, mutable=["batch_stats"])
        return _sq(out), mut
    with jax.default_matmul_precision("default"):
        return jax.jit(jax.grad(loss, argnums=(0, 2), has_aux=True)).lower(
            variables["params"], variables["batch_stats"], feats,
            disp).compile()


def test_decoder_fills_the_lanes_at_llff_n32(topo, no_compile_cache):
    """B*S = 64 planes a chip run the narrow stages as 128 half-height
    strips: the compiler keeps those tensors batch-minor ({0,3,2,1}: the
    batch in the 128 lanes, now full, the channels on sublanes), and the
    decoder's scratch is the full-lane program's (6.38 GB before the fold,
    where each tile of 64 was padded to 128; 4.46 GB with it).

    Not batch-minor, and allowed: a halo's single rows (h = 1), and the
    float32 sums of upconv_2_1 / upconv_1_1 with their per-image side term,
    which the compiler lays out W- or H-minor at B*S = 128 without any
    fold too (re10k_n32)."""
    import re
    compiled = _decoder_fwd_bwd(topo, topo.devices[:1], batch=2)
    assert compiled.memory_analysis().temp_size_in_bytes < 5.0e9
    narrow = re.findall(r"(?:bf16|f32)\[(\d+),(\d+),\d+,(?:16|32|64)\]"
                        r"\{([\d,]+)", compiled.as_text())
    assert not [m for m in narrow if m[0] == "64"], "unfolded planes"
    folded = [layout for n, h, layout in narrow if n == "128" and h != "1"]
    off = [layout for layout in folded if layout != "0,3,2,1"]
    assert len(folded) > 1000 and len(off) <= 4, (len(folded), off)


def test_fold_adds_no_collective_on_the_2x2_mesh(topo, no_compile_cache,
                                                 monkeypatch):
    """mesh data: 4 at the per-chip batch of llff_train_dp4 (64 planes a
    device, two strips): a device's shard of the folded batch is its own
    planes' strips, so the halo crosses no device, and the decoder's
    forward + backward holds the collectives of the unfolded one (SyncBN
    statistics and the gradients' all-reduces)."""
    import re

    from mine_tpu import telemetry
    from mine_tpu.models import decoder

    def collectives():
        """Collectives by channel: the compiler writes one all-gather out
        as a chain of steps that share its channel_id."""
        text = _decoder_fwd_bwd(topo, topo.devices, batch=8).as_text()
        return {op: len(set(re.findall(
            r" %s(?:-start)?\(.*?channel_id=(\d+)" % op, text)))
            for op in ("all-reduce", "all-gather", "collective-permute",
                       "all-to-all", "reduce-scatter")}
    folded = collectives()
    assert telemetry.REGISTRY.snapshot()["model.decoder.fold_strips"] == 2
    monkeypatch.setattr(decoder, "fold_strips", lambda *a: 1)
    assert folded == collectives()
    assert folded["all-reduce"] > 0 and folded["collective-permute"] == 0


def test_looplm_step_compiles_and_fits_v5e(one_chip, no_compile_cache,
                                           monkeypatch):
    """The looped language model's whole train step at its cell's sizes
    (params_ouro_2p6b.yaml: 8 layers x 4 passes at the published widths, 2
    rows of 4096 tokens, float32 state + Adam): the chip's compiler takes
    it, the attention kernels are in it, and its peak fits the chip."""
    import os

    from mine_tpu.config import CONFIG_DIR, load_config
    from mine_tpu.models import looplm
    from mine_tpu.train.lm_step import LoopLMTrainer

    # `default_attention` asks for the running backend, which is the CPU here
    monkeypatch.setattr(looplm, "on_tpu_backend", lambda: True)
    config = load_config(os.path.join(CONFIG_DIR, "params_ouro_2p6b.yaml"))
    assert (config["data.per_gpu_batch_size"], config["data.seq_len"]) == (
        LM_ROWS, LM_SEQ)
    trainer = LoopLMTrainer(config, steps_per_epoch=32)
    put = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    state = put(jax.eval_shape(
        lambda seed: trainer._init_state_impl(LM_ROWS, seed), jnp.int32(0)))
    batch = put({"tokens": jax.ShapeDtypeStruct((LM_ROWS, LM_SEQ), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((LM_ROWS, LM_SEQ), jnp.int32),
                 "mask": jax.ShapeDtypeStruct((LM_ROWS, LM_SEQ),
                                              jnp.float32)})
    with jax.default_matmul_precision("default"):
        compiled = trainer._train_step.lower(state, batch).compile()
    text = compiled.as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                   "flash_attention_bwd_dq"):
        assert kernel in text, kernel
    analysis = compiled.memory_analysis()
    state_bytes = 12 * 612_438_017   # float32 parameters + Adam's two moments
    assert analysis.argument_size_in_bytes >= state_bytes
    # a v5e has 16 GB of HBM, 15.75 GiB of them usable
    assert analysis.peak_memory_in_bytes <= 15.75 * 2**30, (
        analysis.peak_memory_in_bytes / 2**30)


def test_sparse_serve_largest_bucket_fits_v5e(one_chip, no_compile_cache):
    """The token server's largest step program of params_dots3_note.yaml
    (2,048 chunk rows on a block table of 133,120 tokens, 16 decode rows)
    beside its weights and three caches: the engine's byte budget
    (`lm_engine.DENSE_SELECTED_BYTES`) sends this bucket to the GATHERED
    form of a chunk's selected attention, and the 65,536-token one to the
    dense form; the chip's compiler takes the program and its peak fits."""
    import os

    from mine_tpu.config import (CONFIG_DIR, lm_serve_config_from_dict,
                                 load_config)
    from mine_tpu.models import moe_mla
    from mine_tpu.serve import lm_engine

    config = load_config(os.path.join(CONFIG_DIR, "params_dots3_note.yaml"))
    cfg = moe_mla.moe_mla_config_from_dict(config)
    serve = lm_serve_config_from_dict(config)
    ps, D = serve.page_size, serve.max_running
    Tc, tokens = serve.chunk_buckets[-1], serve.context_buckets[-1]
    dense = {t: lm_engine.selected_dense_bytes(cfg, Tc, t)
             <= lm_engine.DENSE_SELECTED_BYTES for t in serve.context_buckets}
    assert dense == {16384: True, 32768: True, 65536: True, 133120: False}
    put = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = put(jax.eval_shape(lambda: moe_mla.init_params(
        jax.random.key(0, impl="rbg"), cfg)))
    swa = moe_mla.of_kind(cfg, moe_mla.SLIDING)
    rows = lambda t, width: jax.ShapeDtypeStruct(  # noqa: E731
        (t[0], (t[1] // ps + 1) * ps, width), jnp.bfloat16, sharding=one_chip)
    full = (cfg.layers_of(moe_mla.FULL), serve.cache_tokens)
    caches = {"latent": rows(full, -(-cfg.latent_width // 128) * 128),
              "index": rows(full, cfg.index_head_dim),
              "window": rows((cfg.layers_of(moe_mla.SLIDING),
                              serve.window_cache_tokens),
                             -(-swa.latent_width // 128) * 128)}
    P, W, T, R = tokens // ps, cfg.sliding_window_size, Tc + D, D + 1
    ints = jax.ShapeDtypeStruct(
        (5 * T + P + D * P + D + R + 1 + T + (W - 1) + D * W,), jnp.int32,
        sharding=one_chip)
    feedback = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda *args: lm_engine._step_impl(
        *args, cfg=cfg, chunk_rows=Tc, pages=P, decode_pages=P, running=D,
        logit_rows=R, page_size=ps, impl="pallas",
        dense_selected=dense[tokens]), donate_argnums=(1,))
    with jax.default_matmul_precision("default"):
        analysis = step.lower(params, caches, ints, feedback).compile(
            ).memory_analysis()
    # weights 9.23 GB + caches 3.70 GB: what the configuration's file states
    assert 12.9e9 < analysis.argument_size_in_bytes < 13.0e9
    assert analysis.peak_memory_in_bytes <= 15.75 * 2**30, (
        analysis.peak_memory_in_bytes / 2**30)
