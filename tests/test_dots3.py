"""The served token model's second member (models/moe_mla.py under the keys
of configs/params_dots3_note.yaml: full layers under a learned sparse
selection mixed with sliding-window layers, a gate, the latents' rescale)
against its plain reference (benchmark/reference_dots3.py) at a small size on
the CPU, and the new kernels of kernels/attention.py interpreted."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_dots3 as R  # noqa: E402
from benchmark import reference_moe_mla as RK  # noqa: E402
from mine_tpu.config import CONFIG_DIR, load_config  # noqa: E402
from mine_tpu.kernels import attention as A  # noqa: E402
from mine_tpu.models import moe_mla  # noqa: E402

# layer 0 + one period of four; index_topk 8 with contexts several times
# that; a window of 9 (two or three pages of 4 tokens)
TINY = {"lm.hidden_size": 64, "lm.intermediate_size": 96,
        "lm.moe_intermediate_size": 32, "lm.num_hidden_layers": 5,
        "lm.layer_types": ["full_attention", "full_attention",
                           "sliding_attention", "sliding_attention",
                           "sliding_attention"],
        "lm.num_attention_heads": 4, "lm.q_lora_rank": 48,
        "lm.kv_lora_rank": 32, "lm.qk_nope_head_dim": 16,
        "lm.qk_rope_head_dim": 8, "lm.v_head_dim": 16,
        "lm.index_n_heads": 4, "lm.index_head_dim": 16, "lm.index_topk": 8,
        "lm.sliding_window_size": 9, "lm.swa_num_attention_heads": 2,
        "lm.swa_q_lora_rank": 48, "lm.swa_kv_lora_rank": 40,
        "lm.swa_qk_nope_head_dim": 24, "lm.swa_qk_rope_head_dim": 8,
        "lm.swa_v_head_dim": 16,
        "lm.n_routed_experts": 16, "lm.num_experts_per_tok": 4,
        "lm.vocab_size": 512, "lm.experts_held": 4, "lm.expert_offset": 4,
        "lm.vocab_held": 128}
YAML = os.path.join(CONFIG_DIR, "params_dots3_note.yaml")


def tiny_config(**extra):
    return load_config(YAML, extra_config=dict(TINY, **extra))


reference_config = R.config_from_flat


@pytest.fixture
def float32_model(monkeypatch):
    monkeypatch.setattr(moe_mla, "DTYPE", jnp.float32)


def _params(cfg, seed=0):
    return jax.jit(lambda: moe_mla.init_params(jax.random.key(seed), cfg))()


def _forward(params, tokens, cfg):
    return jax.jit(moe_mla.forward, static_argnums=(2,))(params, tokens, cfg)


def test_published_config_reads_as_two_periods_of_four():
    cfg = moe_mla.moe_mla_config_from_dict(load_config(YAML))
    assert cfg.period == (moe_mla.FULL,) + (moe_mla.SLIDING,) * 3
    assert cfg.layers_of(moe_mla.FULL) == 3
    assert cfg.layers_of(moe_mla.SLIDING) == 6
    swa = moe_mla.of_kind(cfg, moe_mla.SLIDING)
    assert (cfg.latent_width, swa.latent_width) == (576, 1088)
    assert abs(cfg.softmax_scale - 192 ** -0.5) < 1e-9
    assert abs(swa.softmax_scale - 256 ** -0.5) < 1e-9
    assert np.allclose(cfg.lora_scales, (5 ** 0.5, 10 ** 0.5))
    assert np.allclose(swa.lora_scales, (5 ** 0.5, 5 ** 0.5))
    assert swa.window == 513 and not swa.index_topk and cfg.index_topk == 2048
    # plain RoPE, a base a kind
    plain = 1.0 / 8e7 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(moe_mla.yarn_inv_freq(cfg), plain, rtol=1e-6)
    np.testing.assert_allclose(moe_mla.yarn_inv_freq(swa)[-1],
                               1.0 / 5e4 ** (62 / 64), rtol=1e-6)


@pytest.mark.parametrize("held", [(4, 4), (0, 16)])
def test_forward_matches_reference(float32_model, held):
    config = tiny_config(**{"lm.expert_offset": held[0],
                            "lm.experts_held": held[1]})
    cfg = moe_mla.moe_mla_config_from_dict(config)
    params = _params(cfg)
    assert "wiq" in params["dense"] and "wgate" in params["moe"]["swa"]
    assert "wiq" not in params["moe"]["swa"]
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 128, 40))
    got = _forward(params, tokens, cfg)
    want, infos = R.forward(params, tokens, reference_config(config), held)
    assert got.shape == (40, 128) and got.dtype == jnp.float32
    assert R.rel_err(got, want) < 1e-5
    assert "index_k" in infos[0] and "index_k" not in infos[2]


# ---- the token server through three kinds of cache -------------------------

from mine_tpu.serve.lm_scheduler import LMRequest, build_server  # noqa: E402

# pages of 4 tokens: a window of 9 spans three; the window pool is small
SERVE = {"serve.lm.max_step_tokens": 20, "serve.lm.max_running": 4,
         "serve.lm.page_size": 4, "serve.lm.cache_tokens": 256,
         "serve.lm.window_cache_tokens": 96,
         "serve.lm.chunk_buckets": [16], "serve.lm.context_buckets": [64, 96]}


@pytest.fixture(scope="module", params=[0, 64], ids=["gathered",
                                                     "dense_to_64"])
def server(request):
    """The tiny server, a chunk's selected attention in both forms: every
    block table gathers its selected rows, or those of up to 64 tokens
    attend densely under the selection's mask (and the 96-token ones
    gather): the engine's byte budget, set to what that takes here."""
    from mine_tpu.serve import lm_engine
    saved = moe_mla.DTYPE, lm_engine.DENSE_SELECTED_BYTES
    moe_mla.DTYPE = jnp.float32
    config = tiny_config(**SERVE)
    lm_engine.DENSE_SELECTED_BYTES = request.param and (
        lm_engine.selected_dense_bytes(
            moe_mla.moe_mla_config_from_dict(config), 16, request.param))
    try:
        yield build_server(config, seed=3, start=False, prompt_logits=16)
    finally:
        moe_mla.DTYPE, lm_engine.DENSE_SELECTED_BYTES = saved


def run(srv, requests):
    futures = [srv.submit(r) for r in requests]
    for _ in range(10000):
        if not srv.step():
            break
    assert all(f.done() for f in futures)
    return [f.result() for f in futures]


def reference_logits(srv, request, result, config=None):
    config = config or tiny_config(**SERVE)
    seq = np.concatenate([request.document, request.question,
                          np.asarray(result.tokens, np.int32)])
    logits, infos = R.forward(srv.engine.params, jnp.asarray(seq),
                              reference_config(config), (4, 4))
    return np.asarray(logits), infos


def check_against_reference(srv, request, result, first_position=0):
    want, _ = reference_logits(srv, request, result)
    seen = sorted(d["position"] for d in result.detail)
    last = result.prompt_tokens + len(result.tokens) - 2
    assert seen == list(range(first_position, last + 1)), seen
    for d in result.detail:
        assert R.rel_err(d["logits"], want[d["position"]]) < 3e-5
    for i, token in enumerate(result.tokens):
        assert token == int(np.argmax(want[result.prompt_tokens - 1 + i]))


def test_chunked_prefill_and_decode_through_three_caches_match_one_forward(
        server):
    """Prefill in chunks, then decode, contexts several times index_topk
    and the window: every returned position against the reference's one
    full forward; then a question on the shared document (its window rows
    at the page boundary) equals the same tokens prefilled fresh."""
    from mine_tpu import telemetry
    rng = np.random.RandomState(0)
    doc_a, doc_b = rng.randint(0, 128, 43), rng.randint(0, 128, 22)
    first = LMRequest(question=rng.randint(0, 128, 5), max_tokens=6,
                      doc_id="a", document=doc_a, detail_steps=99)
    other = LMRequest(question=rng.randint(0, 128, 3), max_tokens=4,
                      doc_id="b", document=doc_b, detail_steps=99)
    dropped = telemetry.REGISTRY.snapshot("serve.lm.").get(
        "serve.lm.dropped_tokens", 0)
    res_first, res_other = run(server, [first, other])
    assert res_first.cached_tokens == 0 and res_first.prompt_tokens == 48
    check_against_reference(server, first, res_first)
    check_against_reference(server, other, res_other)
    cache = server.engine.cache
    # the documents keep the window pages of their last 8 tokens before the
    # last page boundary (40 and 20), and every other window page came back
    assert cache.documents["a"].window.first == 8
    assert len(cache.documents["a"].window.pages) == 2
    assert cache.documents["b"].window.first == 3
    assert cache.window_pages_used == 4 and cache.window_reserved == 0
    assert cache.pages_used == 10 + 5
    second = LMRequest(question=rng.randint(0, 128, 7), max_tokens=5,
                       doc_id="a", document=doc_a, detail_steps=99)
    (res_second,) = run(server, [second])
    assert res_second.cached_tokens == 40
    check_against_reference(server, second, res_second, first_position=40)
    # the same tokens prefilled fresh (no document id): the same logits
    fresh = LMRequest(question=np.concatenate([doc_a, second.question]),
                      max_tokens=5, detail_steps=99)
    (res_fresh,) = run(server, [fresh])
    assert res_fresh.cached_tokens == 0
    assert res_fresh.tokens == res_second.tokens
    by_pos = {d["position"]: d for d in res_fresh.detail}
    for d in res_second.detail:
        np.testing.assert_allclose(d["logits"], by_pos[d["position"]][
            "logits"], rtol=2e-5, atol=2e-6)
    assert cache.window_pages_used == 4 and cache.window_reserved == 0
    assert telemetry.REGISTRY.snapshot("serve.lm.")[
        "serve.lm.dropped_tokens"] == dropped


def test_selection_and_attention_detail_match_the_reference(server):
    """What a step returns of the indexer: its scores and S_t of the
    returned rows against the reference's own, and each layer's attention
    output."""
    rng = np.random.RandomState(7)
    request = LMRequest(question=rng.randint(0, 128, 30), max_tokens=3,
                        detail_steps=99)
    (result,) = run(server, [request])
    request.document = np.zeros(0, np.int32)
    rows = tuple(sorted(d["position"] for d in result.detail))
    config = tiny_config(**SERVE)
    seq = np.concatenate([request.question, np.asarray(result.tokens)])
    _, infos = R.forward(server.engine.params, jnp.asarray(seq),
                         reference_config(config), (4, 4), keep_rows=rows)
    full = [i for i, kind in enumerate(config["lm.layer_types"])
            if kind == "full_attention"]
    for d in result.detail:
        t = d["position"]
        assert d["attn_out"].shape == (5, 64)
        assert d["selected"].shape == (len(full), 8)
        for n, layer in enumerate(full):
            want = infos[layer]["index_scores"][t]
            np.testing.assert_allclose(d["index_scores"][n][:t + 1], want,
                                       rtol=1e-4, atol=1e-5)
            picked = d["selected"][n]
            picked = set(picked[picked >= 0].tolist())
            assert len(picked) == min(8, t + 1)
            assert picked == set(np.argsort(-want, kind="stable")[:8].tolist())
        for layer in range(5):
            assert R.rel_err(d["attn_out"][layer],
                             infos[layer]["attn_out"][t]) < 1e-4


def test_window_pages_are_released_behind_the_window_and_never_read_after(
        server, monkeypatch):
    """Every page the pool takes back is overwritten with NaN at once: what
    a later step reads of it would show in the logits."""
    from mine_tpu import telemetry
    cache = server.engine.cache
    give = cache.give_window

    def poisoned(pages, reserve):
        rows = (np.asarray(pages, np.int32)[:, None] * cache.page_size
                + np.arange(cache.page_size)[None, :]).reshape(-1)
        if len(rows):
            cache.window_rows = cache.window_rows.at[:, rows].set(jnp.nan)
        give(pages, reserve)

    monkeypatch.setattr(cache, "give_window", poisoned)
    released = telemetry.REGISTRY.snapshot("serve.lm.")[
        "serve.lm.window_pages_released"]
    rng = np.random.RandomState(8)
    request = LMRequest(question=rng.randint(0, 128, 50), max_tokens=12,
                        detail_steps=99)
    peak = []
    futures = [server.submit(request)]
    while server.step():
        peak.append(cache.window_pages_used)
    (result,) = [f.result() for f in futures]
    request.document = np.zeros(0, np.int32)
    check_against_reference(server, request, result)
    # 62 tokens span 16 pages; the sequence held a window's worth at a time
    assert telemetry.REGISTRY.snapshot("serve.lm.")[
        "serve.lm.window_pages_released"] - released >= 12
    assert max(peak) <= 4 + 7        # two documents' + (8 + 16) / 4 + 1
    assert cache.window_pages_used == 4 and cache.window_reserved == 0


def test_eviction_frees_all_three_kinds_and_a_full_pool_admits_nothing():
    from mine_tpu.serve.latent_cache import LatentCache, WindowTable
    cache = LatentCache(layers=2, tokens=64, page_size=4, width=40,
                        dtype="float32", index_width=16, window_layers=3,
                        window_tokens=24, window_width=48, window=9)
    assert set(cache.arrays()) == {"latent", "index", "window"}
    assert cache.index_rows.shape == (2, 68, 16)
    assert cache.window_rows.shape == (3, 28, 128)
    doc = cache.reserve_document("d", 16)
    assert cache.reserve_window(4)
    doc.window = WindowTable(2, cache.take_window(2))
    cache.unreserve_window(2)
    assert cache.pages_used == 4 and cache.window_pages_used == 2
    # the pool promises what is free and what idle documents hold, no more
    assert not cache.reserve_window(7) and cache.window_pages_used == 2
    assert cache.reserve_window(6) and "d" not in cache.documents
    assert cache.pages_used == 0 and cache.window_pages_used == 0
    with pytest.raises(RuntimeError, match="not reserved"):
        cache.take_window(7)


def test_a_small_window_pool_never_runs_out_mid_way():
    """Six sequences through a pool that holds two at a time: admission
    waits, and nothing ever finds the pool empty."""
    saved = moe_mla.DTYPE
    moe_mla.DTYPE = jnp.float32
    try:
        srv = build_server(tiny_config(**dict(
            SERVE, **{"serve.lm.window_cache_tokens": 56})), seed=3,
            start=False)
    finally:
        moe_mla.DTYPE = saved
    rng = np.random.RandomState(9)
    requests = [LMRequest(question=rng.randint(0, 128, 20 + 3 * i),
                          max_tokens=5) for i in range(6)]
    running = []
    futures = [srv.submit(r) for r in requests]
    while srv.step():
        running.append(len(srv.scheduler.running))
    assert all(len(f.result().tokens) == 5 for f in futures)
    assert max(running) < 4     # the pool, not max_running, held them back
    assert srv.engine.cache.window_pages_used == 0
    assert srv.engine.cache.window_reserved == 0


# ---- the selection, exactly -------------------------------------------------

@pytest.mark.parametrize("shape,k", [((5, 300), 40), ((70, 1024), 128),
                                     ((3, 64), 64)])
def test_dsa_select_is_the_exact_top_k(shape, k):
    """Against `lax.top_k`, as sets; rows that see fewer than k keys; ties
    at the k-th score go to the lower positions."""
    R, n = shape
    rng = np.random.RandomState(R)
    scores = rng.randn(R, n).astype(np.float32)
    scores[0, :] = np.round(scores[0, :])            # many ties
    scores[-1, 5:] = -3.0                            # ties at the k-th
    seen = rng.randint(1, n + 1, R)
    seen[0], seen[-1] = n, n
    masked = np.where(np.arange(n)[None, :] < seen[:, None], scores, A.MASKED)
    cfg = moe_mla.moe_mla_config_from_dict(tiny_config(
        **{"lm.index_topk": k}))
    ids, valid = jax.jit(lambda s, m: moe_mla.dsa_select(s, m, cfg))(
        jnp.asarray(masked), jnp.asarray(seen))
    ids, valid = np.asarray(ids), np.asarray(valid)
    for r in range(R):
        want = min(k, seen[r])
        assert valid[r].sum() == want
        got = ids[r][valid[r]]
        assert np.all(np.diff(got) > 0) and got.max() < seen[r]
        # the stable order: by score descending, then by position
        order = np.lexsort((np.arange(n), -masked[r]))[:want]
        assert set(got.tolist()) == set(order.tolist()), r


# ---- the layer against its special cases ------------------------------------

def test_with_index_topk_over_the_context_the_full_layer_is_dense_mla(
        float32_model):
    """The selection then keeps every key: the logits are those of the same
    weights with no indexer at all (dense latent attention)."""
    import dataclasses
    config = tiny_config(**{"lm.index_topk": 64})
    cfg = moe_mla.moe_mla_config_from_dict(config)
    params = _params(cfg, seed=2)
    tokens = jnp.asarray(np.random.RandomState(3).randint(0, 128, 48))
    sparse = _forward(params, tokens, cfg)
    dense = _forward(params, tokens, dataclasses.replace(cfg, index_topk=0))
    assert R.rel_err(sparse, dense) < 1e-5
    # and with 8 of 48 keys it is another function
    few = _forward(params, tokens, dataclasses.replace(cfg, index_topk=8))
    assert R.rel_err(few, dense) > 1e-3


def test_the_references_latent_space_attention_is_the_up_projected_one():
    """benchmark/reference_dots3.py attends over gathered latent rows;
    reference_moe_mla.attention up-projects every key. Where S_t is every
    key, no gate and no rescale, they are the same function."""
    rng = np.random.RandomState(4)
    S, h, H, qr, r, dn, dr, dv = 24, 32, 2, 16, 12, 8, 4, 8
    w = {"attn_norm": np.ones(h), "q_norm": np.ones(qr),
         "kv_norm": np.ones(r), "wqa": rng.randn(h, qr) * 0.2,
         "wqb_nope": rng.randn(qr, H * dn) * 0.2,
         "wqb_rope": rng.randn(qr, H * dr) * 0.2,
         "wkva": rng.randn(h, r + dr) * 0.2,
         "wkvb_k": rng.randn(r, H * dn) * 0.2,
         "wkvb_v": rng.randn(r, H * dv) * 0.2, "wo": rng.randn(H * dv, h)}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    x = jnp.asarray(rng.randn(S, h), jnp.float32)
    pos = jnp.arange(S)
    kimi = {"num_attention_heads": H, "qk_nope_head_dim": dn,
            "qk_rope_head_dim": dr, "v_head_dim": dv, "kv_lora_rank": r,
            "rms_norm_eps": 1e-5, "rope_theta": 1e4, "rope_scaling": {
                "factor": 1, "mscale": 1, "mscale_all_dim": 1,
                "beta_fast": 32, "beta_slow": 1,
                "original_max_position_embeddings": 64}}
    want, latent = RK.attention(x, w, kimi, pos)
    kw = {"heads": H, "q_rank": qr, "kv_rank": r, "nope": dn, "rope": dr,
          "v": dv, "theta": 1e4, "gate": None, "window": 0, "topk": 0,
          "a_q": 1, "a_kv": 1, "scale": (dn + dr) ** -0.5}
    pr = R.projections(x, w, {"rms_norm_eps": 1e-5}, kw, pos)
    np.testing.assert_allclose(pr["latent"], latent, rtol=1e-5, atol=1e-6)
    q_nope = (pr["c_q"] @ w["wqb_nope"]).reshape(S, H, dn)
    q_rope = R.rope((pr["c_q"] @ w["wqb_rope"]).reshape(S, H, dr).transpose(
        1, 0, 2), pos, 1e4).transpose(1, 0, 2)
    ids = jnp.broadcast_to(jnp.arange(S)[None, :], (S, S))
    o = R.sparse_attention(q_nope, q_rope, pr["latent"], ids, ids <= pos[
        :, None], w, kw)
    np.testing.assert_allclose(o @ w["wo"], want, rtol=2e-4, atol=2e-5)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        float32_model):
    """PR 35's test at this model's keys (routed_scaling_factor 1, a sliding
    layer's FFN): over all shares the routed parts, with the shared expert
    counted once, equal the uncut reference's layer."""
    E, held, T = 16, 4, 24
    config = tiny_config(**{"lm.expert_offset": 0, "lm.experts_held": E})
    whole = moe_mla.moe_mla_config_from_dict(config)
    params = _params(whole, seed=5)
    moe = params["moe"]
    layer = 2                                   # a sliding layer's FFN
    w = moe_mla.moe_layer_weights(params, layer - 1, whole)
    u = jax.random.normal(jax.random.key(6), (T, whole.hidden_size))
    sigma, chosen, weights = moe_mla.route(u, w["router"], w["router_bias"],
                                           whole)
    routed, rows = jnp.zeros((T, whole.hidden_size)), 0
    for offset in range(0, E, held):
        share = moe_mla.moe_mla_config_from_dict(tiny_config(
            **{"lm.expert_offset": offset, "lm.experts_held": held}))
        sl = slice((layer - 1) * E + offset, (layer - 1) * E + offset + held)
        y, sizes, pairs = moe_mla.moe_experts(
            u, chosen, weights, moe["eg"][sl], moe["eu"][sl], moe["ed"][sl],
            0, share, "xla")
        assert int(sizes.sum()) == int(pairs)
        rows += int(pairs)
        routed = routed + y
    assert rows == T * whole.num_experts_per_tok
    ref_cfg = reference_config(config)
    ref_w = R.layer_weights(params, layer, ref_cfg["layer_types"])
    ref_sigma = jax.nn.sigmoid(u @ ref_w["router"])
    ref_chosen, _, _ = R.choose(ref_sigma + ref_w["router_bias"], 4)
    want = R.swiglu(u, ref_w["sg"], ref_w["su"], ref_w["sd"])
    ref_weights = R.expert_weights(ref_sigma, ref_chosen, ref_cfg)
    for e in range(E):
        w_e = np.where(ref_chosen == e, ref_weights, 0.0).sum(axis=-1)
        want = want + w_e[:, None] * R.swiglu(u, ref_w["eg"][e],
                                              ref_w["eu"][e], ref_w["ed"][e])
    shared = moe_mla.swiglu(u, w["sg"], w["su"], w["sd"])
    assert R.rel_err(routed + shared, want) < 1e-5


def test_what_is_not_implemented_fails_at_construction():
    for key, value, match in (
            ("lm.attention_gate_type", "elementwise", "attention_gate_type"),
            ("lm.layer_types", ["full_attention"] * 4, "layer_types"),
            ("lm.layer_types", ["full_attention"] * 4 + ["linear"],
             "layer_types"),
            ("lm.sliding_window_size", None, "sliding"),
            ("lm.index_n_heads", None, "index_topk")):
        with pytest.raises(ValueError, match=match):
            moe_mla.moe_mla_config_from_dict(tiny_config(**{key: value}))


def test_new_scopes_map_to_the_serve_steps_layers():
    from mine_tpu.telemetry import programs
    for scope, layer in (("lm_dsa_index", "dsa_index"),
                         ("lm_dsa_select", "dsa_select"),
                         ("lm_dsa_prefill", "dsa_prefill"),
                         ("lm_dsa_decode", "dsa_decode"),
                         ("lm_swa_proj", "swa_proj"),
                         ("lm_swa_prefill", "swa_prefill"),
                         ("lm_swa_decode", "swa_decode"),
                         ("lm_attn_gate", "attn_gate")):
        assert programs.layer_of("jit(f)/while/body/%s/dot" % scope) == layer
        assert layer in programs.FAMILY_LAYERS["moe_mla"]
    assert programs.layer_of("jit(f)/lm_mla_proj/dot") == "mla_proj"


# ---- the new kernels, interpreted, against plain XLA -----------------------

@pytest.mark.parametrize("offset", [0, 200, 768])
def test_index_scores_kernel_with_a_query_offset(offset):
    J, d, Tq, Tk = 4, 128, 256, 1024
    ks = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(ks[0], (Tq, J, d))
    w = jax.random.normal(ks[1], (Tq, J))
    k = jax.random.normal(ks[2], (Tk, d))
    got = A.index_scores(q, w, k, offset, impl="interpret",
                         blocks=(128, 256))
    want = A.index_scores(q, w, k, offset, impl="xla")
    seen = np.asarray(want) > A.MASKED / 2
    assert seen.sum() == sum(min(offset + r + 1, Tk) for r in range(Tq))
    np.testing.assert_array_equal(np.asarray(got) > A.MASKED / 2, seen)
    np.testing.assert_allclose(np.where(seen, got, 0), np.where(seen, want, 0),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("offset,first_valid", [(128, 0), (128, 100),
                                                (512, 0)])
def test_window_attention_kernel_masks_the_window_and_the_start(offset,
                                                                first_valid):
    """A head 256 wide, value heads 128, a window of 129; the keys hold the
    window before the chunk, of which `first_valid` stand before the
    sequence's start."""
    H, d, dv, Tq, Tk, W = 2, 256, 128, 256, 768, 129
    ks = jax.random.split(jax.random.key(13), 3)
    q = jax.random.normal(ks[0], (Tq, H * d))
    k = jax.random.normal(ks[1], (Tk, H * d))
    v = jax.random.normal(ks[2], (Tk, H * dv))
    got = A.window_attention(q, k, v, H, offset, 0.0625, W, first_valid,
                             impl="interpret", blocks=(128, 128))
    want = A.window_attention(q, k, v, H, offset, 0.0625, W, first_valid,
                              impl="xla")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # the window alone: the same as dense causal attention over its keys
    r = 5
    lo = max(offset + r - (W - 1), first_valid)
    keys = k[lo:offset + r + 1].reshape(-1, H, d)
    vals = v[lo:offset + r + 1].reshape(-1, H, dv)
    p = jax.nn.softmax(jnp.einsum("hd,khd->hk", q[r].reshape(H, d), keys)
                       * 0.0625, axis=-1)
    np.testing.assert_allclose(jnp.einsum("hk,khd->hd", p, vals).reshape(-1),
                               want[r], rtol=1e-4, atol=1e-5)


def test_gathered_latent_attention_in_blocks_equals_one_call():
    rng = np.random.RandomState(14)
    N, width, rank, R_, K, H = 300, 128, 96, 150, 20, 3
    rows = jnp.asarray(rng.randn(N, width), jnp.float32)
    q = jnp.asarray(rng.randn(R_, H, width), jnp.float32)
    ids = jnp.asarray(rng.randint(0, N, (R_, K)))
    valid = jnp.asarray(rng.rand(R_, K) < 0.8).at[:, 0].set(True)
    fn = lambda q, i, v: A.gathered_latent_attention(  # noqa: E731
        q, rows, i, v, rank, 0.3)
    whole = fn(q, ids, valid)
    np.testing.assert_allclose(A.in_blocks(fn, q, ids, valid, block=64),
                               whole, rtol=1e-5, atol=1e-6)
    # an invalid row's content never shows
    poisoned = rows.at[ids[0, 1]].set(jnp.where(valid[0, 1], rows[ids[0, 1]],
                                                1e9))
    np.testing.assert_allclose(A.gathered_latent_attention(
        q[:1], poisoned, ids[:1], valid[:1], rank, 0.3), whole[:1],
        rtol=1e-5, atol=1e-6)


def test_masked_prefix_attention_kernel_attends_the_marked_keys_alone():
    H, dn, dr, dv, Tq, Tk, offset = 2, 128, 64, 128, 256, 1024, 300
    ks = jax.random.split(jax.random.key(15), 6)
    qn = jax.random.normal(ks[0], (Tq, H * dn))
    qr = jax.random.normal(ks[1], (H, Tq, dr))
    kn = jax.random.normal(ks[2], (Tk, H * dn))
    kr = jax.random.normal(ks[3], (Tk, dr))
    v = jax.random.normal(ks[4], (Tk, H * dv))
    causal = jnp.arange(Tk)[None, :] <= offset + jnp.arange(Tq)[:, None]
    mask = (causal & (jax.random.uniform(ks[5], (Tq, Tk)) < 0.1)).at[
        :, 0].set(True).astype(jnp.int8)
    got = A.masked_prefix_attention(qn, qr, kn, kr, v, mask, H, offset, 0.07,
                                    impl="interpret", blocks=(128, 256))
    want = A.masked_prefix_attention(qn, qr, kn, kr, v, mask, H, offset, 0.07,
                                     impl="xla")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # every causal key marked: plain prefix attention
    everything = A.masked_prefix_attention(
        qn, qr, kn, kr, v, causal.astype(jnp.int8), H, offset, 0.07,
        impl="interpret", blocks=(128, 256))
    np.testing.assert_allclose(
        everything, A.prefix_attention(qn, qr, kn, kr, v, H, offset, 0.07,
                                       impl="xla"), rtol=1e-4, atol=1e-5)


def test_dsa_positions_names_the_caches_rows_through_the_block_table():
    """With pages of whole 128-column blocks a position's page comes out of
    the compaction itself; with smaller pages it is looked up: the same
    rows."""
    R, n, k, ps = 70, 1024, 64, 256       # more rows than SELECT_ROWS
    rng = np.random.RandomState(16)
    scores = jnp.asarray(rng.randn(R, n), jnp.float32)
    seen = jnp.asarray([n, n, 700, 30, n, 1] + [n] * (R - 6))
    masked = jnp.where(jnp.arange(n)[None, :] < seen[:, None], scores,
                       A.MASKED)
    tables = jnp.asarray(rng.randint(1, 40, (R, 4)))
    cfg = moe_mla.moe_mla_config_from_dict(tiny_config(
        **{"lm.index_topk": k}))

    def both(s, m, t):
        tau, bound = moe_mla.dsa_threshold(s, m, cfg)
        return moe_mla.dsa_positions(s, m, tau, bound, cfg, t, ps)
    ids, valid, rows = jax.jit(both)(masked, seen, tables)
    ids, valid, rows = map(np.asarray, (ids, valid, rows))
    want = np.take_along_axis(np.asarray(tables), ids // ps, axis=1) * ps + (
        ids % ps)
    np.testing.assert_array_equal(rows[valid], want[valid])
    assert valid.sum(axis=1)[:6].tolist() == [64, 64, 64, 30, 64, 1]
    # one table for every row (a chunk's)
    ids1, _, rows1 = jax.jit(lambda s, m, t: both(s, m, t))(
        masked, seen, tables[0])
    want1 = np.asarray(tables)[0][np.asarray(ids1) // ps] * ps + (
        np.asarray(ids1) % ps)
    np.testing.assert_array_equal(np.asarray(rows1)[valid], want1[valid])
