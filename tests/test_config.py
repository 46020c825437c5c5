import os

import pytest

from mine_tpu.config import (CONFIG_DIR, load_config, mpi_config_from_dict,
                             postprocess, serve_config_from_dict)


def test_load_llff_config_merges_defaults():
    cfg = load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"))
    assert cfg["data.name"] == "llff"
    assert cfg["mpi.num_bins_coarse"] == 32        # from default
    assert cfg["loss.smoothness_gmin"] == 0.8      # llff override
    assert cfg["lr.decay_steps"] == [60, 90, 120]  # comma-string -> ints


def test_unknown_dataset_key_rejected(tmp_path):
    bad = tmp_path / "params_bad.yaml"
    bad.write_text("data.not_a_key: 1\n")
    with pytest.raises(KeyError):
        load_config(str(bad),
                    default_config_path=os.path.join(CONFIG_DIR,
                                                     "params_default.yaml"))


def test_unknown_extra_key_rejected():
    with pytest.raises(KeyError):
        load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"),
                    extra_config='{"no.such.key": 2}')


def test_extra_config_overrides():
    cfg = load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"),
                      extra_config='{"training.epochs": 3}')
    assert cfg["training.epochs"] == 3


def test_reference_configs_load_through_our_loader():
    """Key-space parity: the reference repo's own dataset YAMLs must load
    (reference: train.py:30-44 contract)."""
    ref_dir = "/root/reference/configs"
    if not os.path.isdir(ref_dir):
        pytest.skip("reference not mounted")
    for name in ("params_llff.yaml", "params_realestate.yaml",
                 "params_kitti_raw.yaml", "params_flowers.yaml",
                 "params_dtu.yaml"):
        cfg = load_config(os.path.join(ref_dir, name),
                          default_config_path=os.path.join(
                              CONFIG_DIR, "params_default.yaml"))
        assert "data.name" in cfg


def test_postprocess_gpus():
    cfg = postprocess({"training.gpus": "0,1,2", "lr.decay_steps": [5, 10]})
    assert cfg["training.gpus"] == [0, 1, 2]
    assert cfg["lr.decay_steps"] == [5, 10]


def test_mpi_config_static():
    cfg = load_config(os.path.join(CONFIG_DIR, "params_dtu.yaml"))
    mc = mpi_config_from_dict(cfg)
    assert mc.is_bg_depth_inf is True      # dtu honors mpi.is_bg_depth_inf
    assert mc.use_disparity_loss is False  # dtu in the no-disp set
    assert mc.valid_mask_threshold == 0.0
    assert hash(mc)  # hashable -> usable as a jit static arg

    llff = mpi_config_from_dict(load_config(
        os.path.join(CONFIG_DIR, "params_llff.yaml")))
    assert llff.use_disparity_loss is True
    assert llff.num_bins_total == 32


# --- the warp backends PR 33 removed: an old name must fail, and say what is
# valid, wherever a user or a caller can still write one ---------------------

REMOVED_WARP_BACKENDS = ("xla_banded", "separable", "pallas_sep",
                         "pallas_fused")


def _train_key(name):
    with pytest.raises(ValueError, match=r"auto\|xla\|pallas_diff"):
        mpi_config_from_dict({"training.warp_backend": name})


def _serve_key(name):
    with pytest.raises(ValueError, match=r"must be xla\|pallas_diff"):
        serve_config_from_dict({"serve.warp_backend": name})


def _warp_impl(name):
    import jax.numpy as jnp

    from mine_tpu import geometry
    from mine_tpu.ops import warp
    K = jnp.asarray([[[8.0, 0, 4.0], [0, 8.0, 4.0], [0, 0, 1]]])
    with pytest.raises(ValueError, match="'xla', 'pallas', 'pallas_diff'"):
        warp.homography_warp(
            jnp.zeros((1, 1, 8, 8)), jnp.ones((1,)), jnp.eye(4)[None],
            geometry.inverse_intrinsics(K), K,
            geometry.pixel_grid_homogeneous(8, 8), impl=name)


def _sep_tol_key(_):
    with pytest.raises(KeyError, match="training.warp_sep_tol"):
        load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"),
                    extra_config='{"training.warp_sep_tol": 0.5}')


def _one_list_of_names(_):
    from mine_tpu import config
    from mine_tpu.analysis import programs
    from mine_tpu.ops import warp
    from tests import test_serve
    assert config.TRAINING_WARP_BACKENDS == ("auto",) + \
        config.SERVE_WARP_BACKENDS
    assert programs.WARP_IMPLS is config.SERVE_WARP_BACKENDS
    assert test_serve.ENGINE_WARP_IMPLS == \
        config.SERVE_WARP_BACKENDS + ("pallas",)
    assert set(test_serve.ENGINE_WARP_IMPLS) == set(warp.WARP_IMPLS)
    assert not set(REMOVED_WARP_BACKENDS) & set(warp.WARP_IMPLS)


@pytest.mark.parametrize("check,name", [
    *[(c, n) for c in (_train_key, _serve_key, _warp_impl)
      for n in REMOVED_WARP_BACKENDS],
    (_warp_impl, "palas_diff"),          # a misspelling, not an old name
    (_sep_tol_key, "training.warp_sep_tol"),
    (_one_list_of_names, "tuples"),
], ids=lambda v: v if isinstance(v, str) else v.__name__.lstrip("_"))
def test_removed_warp_backends_fail_loudly(check, name):
    check(name)
