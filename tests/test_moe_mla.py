"""The served token model (models/moe_mla.py) against the plain reference
(benchmark/reference_moe_mla.py) at a small size on the CPU: the forward, the
two forms of latent attention, the chip's share of an expert layer, routing
under a skewed load, and the serving kernels of kernels/attention.py."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_moe_mla as R  # noqa: E402
from mine_tpu.config import CONFIG_DIR, load_config  # noqa: E402
from mine_tpu.kernels import attention as A  # noqa: E402
from mine_tpu.models import moe_mla  # noqa: E402

TINY = {"lm.hidden_size": 64, "lm.intermediate_size": 96,
        "lm.moe_intermediate_size": 32, "lm.num_hidden_layers": 3,
        "lm.num_attention_heads": 4, "lm.q_lora_rank": 48,
        "lm.kv_lora_rank": 32, "lm.qk_nope_head_dim": 16,
        "lm.qk_rope_head_dim": 8, "lm.v_head_dim": 16,
        "lm.n_routed_experts": 16, "lm.num_experts_per_tok": 4,
        "lm.vocab_size": 512, "lm.experts_held": 4, "lm.expert_offset": 4,
        "lm.vocab_held": 128}
YAML = os.path.join(CONFIG_DIR, "params_kimi_k2p5.yaml")


def tiny_config(**extra):
    return load_config(YAML, extra_config=dict(TINY, **extra))


reference_config = R.config_from_flat


@pytest.fixture
def float32_model(monkeypatch):
    """The model's operands in float32, so that it and the reference differ
    by accumulation order alone."""
    monkeypatch.setattr(moe_mla, "DTYPE", jnp.float32)


def _params(cfg, seed=0):
    return jax.jit(lambda: moe_mla.init_params(jax.random.key(seed), cfg))()


def _forward(params, tokens, cfg):
    return jax.jit(moe_mla.forward, static_argnums=(2,))(params, tokens, cfg)


# ---- (i) the model's forward against the reference -----------------------

@pytest.mark.parametrize("held", [(4, 4), (0, 16)])
def test_forward_matches_reference(float32_model, held):
    config = tiny_config(**{"lm.expert_offset": held[0],
                            "lm.experts_held": held[1]})
    cfg = moe_mla.moe_mla_config_from_dict(config)
    params = _params(cfg)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 128, 40))
    got = _forward(params, tokens, cfg)
    want, infos = R.forward(params, tokens, reference_config(config), held)
    assert got.shape == (40, 128) and got.dtype == jnp.float32
    assert R.rel_err(got, want) < 1e-5
    # the router chose over ALL experts, whatever share is held
    assert held[1] == 16 or int(np.max(infos[1]["chosen"])) >= sum(held)


def test_forward_in_bfloat16_is_within_its_roundings():
    config = tiny_config()
    cfg = moe_mla.moe_mla_config_from_dict(config)
    params = _params(cfg)
    assert params["dense"]["wqa"].dtype == jnp.bfloat16
    assert params["moe"]["router"].dtype == jnp.float32
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, 128, 40))
    got = _forward(params, tokens, cfg)
    want, _ = R.forward(params, tokens, reference_config(config), (4, 4))
    assert R.rel_err(got, want) < 3e-2


def test_yarn_frequencies_and_scale_are_the_references():
    config = load_config(YAML)
    cfg = moe_mla.moe_mla_config_from_dict(config)
    ref = reference_config(config)
    np.testing.assert_allclose(moe_mla.yarn_inv_freq(cfg),
                               R.yarn_inv_freq(ref), rtol=1e-7)
    inv = moe_mla.yarn_inv_freq(cfg)
    plain = 1.0 / 50000.0 ** (np.arange(0, 64, 2) / 64)
    # high frequencies unchanged, low ones interpolated by the factor 64
    np.testing.assert_allclose(inv[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(inv[-1], plain[-1] / 64, rtol=1e-6)
    assert abs(cfg.softmax_scale - 192 ** -0.5 * 1.4158883 ** 2) < 1e-6
    assert abs(R.softmax_scale(ref) - cfg.softmax_scale) < 1e-9


def test_what_is_not_implemented_fails_at_construction():
    for key, value in (("lm.n_group", 8), ("lm.scoring_func", "softmax"),
                       ("lm.rope_scaling.type", "linear")):
        with pytest.raises(ValueError, match=key):
            moe_mla.moe_mla_config_from_dict(tiny_config(**{key: value}))
    with pytest.raises(ValueError, match="expert_offset"):
        moe_mla.moe_mla_config_from_dict(tiny_config(
            **{"lm.expert_offset": 14}))


# ---- (ii) the absorbed form is the up-projected form ---------------------

def test_absorbed_form_equals_up_projected_form(float32_model):
    config = tiny_config()
    cfg = moe_mla.moe_mla_config_from_dict(config)
    w = _params(cfg)["dense"]
    S, ps = 21, 8
    x = jax.random.normal(jax.random.key(3), (S, cfg.hidden_size))
    cos, sin = moe_mla.rope_tables(jnp.arange(S), cfg)
    q_nope, q_rope, latent = moe_mla.mla_project(x, w, cfg, cos, sin)
    up = moe_mla.mla_prefill(q_nope, q_rope, latent, w, cfg, 0, "xla")
    # the same rows through pages 3, 1, 2 of a cache, one query a "sequence"
    table = [3, 1, 2]
    cache = jnp.zeros((2, 5 * ps, 128))
    rows = jnp.asarray([table[p // ps] * ps + p % ps for p in range(S)])
    cache = cache.at[1, rows, :cfg.latent_width].set(latent)
    for at in (0, 7, 8, S - 1):
        got = moe_mla.mla_decode(
            q_nope[at:at + 1], q_rope[at:at + 1], cache, 1,
            jnp.asarray([table]), jnp.asarray([at + 1]), w, cfg, ps, "xla")
        np.testing.assert_allclose(got[0], up[at], rtol=2e-5, atol=2e-6)


# ---- (iii) the shares add up ---------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        float32_model):
    """Over all n / held shares, the routed parts summed plus the shared
    expert counted once equal the uncut reference's layer."""
    E, held, T = 16, 4, 24
    config = tiny_config(**{"lm.expert_offset": 0, "lm.experts_held": E})
    whole = moe_mla.moe_mla_config_from_dict(config)
    params = _params(whole, seed=5)
    moe = params["moe"]
    w = moe_mla.moe_layer_weights(params, 0)
    u = jax.random.normal(jax.random.key(6), (T, whole.hidden_size))
    sigma, chosen, weights = moe_mla.route(u, w["router"], w["router_bias"],
                                           whole)
    routed = jnp.zeros((T, whole.hidden_size))
    rows = 0
    for offset in range(0, E, held):
        share = moe_mla.moe_mla_config_from_dict(tiny_config(
            **{"lm.expert_offset": offset, "lm.experts_held": held}))
        sl = slice(offset, offset + held)          # layer 0's experts
        y, sizes, pairs = moe_mla.moe_experts(
            u, chosen, weights, moe["eg"][sl], moe["eu"][sl], moe["ed"][sl],
            0, share, "xla")
        assert int(sizes.sum()) == int(pairs)
        rows += int(pairs)
        routed = routed + y
    assert rows == T * whole.num_experts_per_tok     # every pair, once
    shared = moe_mla.swiglu(u, w["sg"], w["su"], w["sd"])
    ref_w = R.layer_weights(params, 1)
    ref_sigma, biased = R.route(u, ref_w, reference_config(config))
    ref_chosen, _, _ = R.choose(biased, whole.num_experts_per_tok)
    want = R.experts(u, ref_w, reference_config(config), ref_sigma,
                     jnp.asarray(ref_chosen), (0, E))
    assert R.rel_err(routed + shared, want) < 1e-5


# ---- (vii) routing under load --------------------------------------------

def test_routing_under_load_drops_no_token_and_equals_the_per_token_loop(
        float32_model):
    """2,048 tokens of which half carry one id: every (token, expert) pair
    held here is computed, and the grouped product equals the loop."""
    config = tiny_config(**{"lm.hidden_size": 32,
                            "lm.moe_intermediate_size": 16})
    cfg = moe_mla.moe_mla_config_from_dict(config)
    params = _params(cfg, seed=7)
    moe = params["moe"]
    rng = np.random.RandomState(8)
    ids = rng.randint(0, 128, 2048)
    ids[rng.permutation(2048)[:1024]] = 5
    u = moe_mla.embed(params, jnp.asarray(ids))
    w = moe_mla.moe_layer_weights(params, 1)
    held = cfg.experts_held

    @jax.jit
    def layer(u, valid):
        sigma, chosen, weights = moe_mla.route(u, w["router"],
                                               w["router_bias"], cfg)
        return (chosen, weights) + moe_mla.moe_experts(
            u, chosen, weights, moe["eg"], moe["eu"], moe["ed"], held, cfg,
            "xla", valid)

    chosen, weights, y, sizes, pairs = layer(u, jnp.ones(2048, bool))
    here = (chosen >= cfg.expert_offset) & (chosen < cfg.expert_offset + held)
    assert int(pairs) == int(here.sum()) == int(sizes.sum())
    assert int(sizes.max()) >= 1024 or int(here.sum()) < 1024  # one is hot
    want = jnp.zeros_like(y)
    for e in range(held):                   # the loop: every token, expert e
        out = moe_mla.swiglu(u, moe["eg"][held + e], moe["eu"][held + e],
                             moe["ed"][held + e])
        w_e = jnp.sum(jnp.where(chosen == cfg.expert_offset + e, weights,
                                0.0), axis=-1)
        want = want + w_e[:, None] * out
    assert R.rel_err(y, want) < 1e-5
    # padding rows go to no expert
    _, _, _, sizes_v, pairs_v = layer(u, jnp.arange(2048) < 100)
    assert int(pairs_v) == int(here[:100].sum()) == int(sizes_v.sum())


def test_grouped_matmul_kernel_matches_ragged_dot_in_interpret_mode():
    rng = np.random.RandomState(9)
    lhs = jnp.asarray(rng.randn(256, 128), jnp.float32)
    rhs = jnp.asarray(rng.randn(6, 128, 128), jnp.float32)
    sizes = jnp.asarray([0, 0, 100, 28, 0, 0], jnp.int32)   # two live groups
    want = moe_mla.grouped_matmul(lhs, rhs, sizes, "xla")
    got = moe_mla.grouped_matmul(lhs, rhs, sizes, "interpret")
    np.testing.assert_allclose(got[:128], want[:128], rtol=1e-4, atol=1e-4)


# ---- (viii) the serving kernels, interpreted, against plain XLA -----------

@pytest.mark.parametrize("offset", [0, 300, 768])
def test_prefix_attention_kernel_at_192_128_with_a_query_offset(offset):
    """Query/key heads 128 + 64 wide, value heads 128, the chunk's queries
    offset against a longer run of keys."""
    H, dn, dr, dv, Tq, Tk = 2, 128, 64, 128, 256, 1024
    ks = jax.random.split(jax.random.key(10), 5)
    qn = jax.random.normal(ks[0], (Tq, H * dn))
    qr = jax.random.normal(ks[1], (H, Tq, dr))
    kn = jax.random.normal(ks[2], (Tk, H * dn))
    kr = jax.random.normal(ks[3], (Tk, dr))
    v = jax.random.normal(ks[4], (Tk, H * dv))
    scale = 192 ** -0.5
    got = A.prefix_attention(qn, qr, kn, kr, v, H, offset, scale,
                             impl="interpret")
    want = A.prefix_attention(qn, qr, kn, kr, v, H, offset, scale, impl="xla")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if offset == 0:
        # the same function as the looped model's plain attention, with the
        # two parts of a head's query and key joined to 192
        q = jnp.concatenate([qn.reshape(Tq, H, dn),
                             qr.transpose(1, 0, 2)], -1).reshape(1, Tq, -1)
        k = jnp.concatenate([kn.reshape(Tk, H, dn)[:Tq], jnp.broadcast_to(
            kr[:Tq, None], (Tq, H, dr))], -1).reshape(1, Tq, -1)
        vv = jnp.pad(v[:Tq].reshape(Tq, H, dv), ((0, 0), (0, 0), (0, 64)))
        plain = A.plain_attention(q, k, vv.reshape(1, Tq, -1), H)
        np.testing.assert_allclose(
            plain.reshape(Tq, H, 192)[:, :, :dv].reshape(Tq, -1), got,
            rtol=1e-4, atol=1e-5)


def test_paged_latent_attention_kernel_reads_each_sequence_through_its_table():
    L, ps, pages, width, rank = 2, 16, 12, 128, 96
    ks = jax.random.split(jax.random.key(11), 2)
    cache = jax.random.normal(ks[0], (L, pages * ps, width))
    q = jax.random.normal(ks[1], (3, 8, width))
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [0, 0, 0, 0]])
    lengths = jnp.asarray([50, 17, 0])
    got = A.paged_latent_attention(q, cache, 1, tables, lengths, rank, ps,
                                   0.2, impl="interpret")
    want = A.paged_latent_attention(q, cache, 1, tables, lengths, rank, ps,
                                    0.2, impl="xla")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(got[2]).max()) == 0.0     # an empty slot


def test_new_scopes_map_to_the_serve_steps_layers():
    from mine_tpu.telemetry import programs
    assert programs.layer_of("jit(f)/while/body/lm_mla_prefill/dot") == (
        "mla_prefill")
    assert programs.layer_of("jit(f)/lm_head/argmax") == "head"
    assert programs.layer_of("jit(f)/lm_head_loss/reduce") == "head_loss"
    assert programs.layer_of("jit(f)/lm_moe_experts/gmm") == "moe_experts"
    assert set(programs.FAMILY_LAYERS["moe_mla"]) <= set(programs.LAYERS)
