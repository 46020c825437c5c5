"""A language model of the Kimi-K2 / DeepSeek-V3 family for the serve path:
latent attention (MLA) with YaRN RoPE over a cache of latent rows, one leading
dense SwiGLU layer, then layers of routed experts beside a shared expert, of
which this chip holds a share.

With x the residual stream [T, hidden] and N an RMSNorm, per layer

    x <- x + Attn(N(x));  x <- x + FFN(N(x));   logits = N_f(x) W_head

  Attn   c_q = N(x W_qa);  q = c_q W_qb -> heads x (nope | rope)
         x W_kva -> (c_kv | k_rope);  c_kv = N(c_kv);  RoPE on q_rope, k_rope
         THE CACHE HOLDS [c_kv | k_rope] of every token: kv_lora_rank +
         qk_rope_head_dim values a token and layer.
         up-projected form (a prompt chunk): c_kv W_kvb -> heads x (k_nope |
         v) for every cached row of the sequence, then causal attention of
         the chunk's queries (offset by the cached prefix) over them.
         absorbed form (decode): q_lat = q_nope W_kvb^K[h], scores against
         the cached c_kv and k_rope directly, o = (P c_kv) W_kvb^V[h]: the
         cache is read once for all heads and never up-projected.
         Both are the same function (tests/test_moe_mla.py).
  FFN 0  (silu(u W_g) * (u W_u)) W_d
  FFN l  sigma = sigmoid(u W_r) in float32 over ALL `n_routed_experts`; the
         choice is the top k of sigma + b; the weights sigma_e /
         sum_chosen(sigma) * routed_scaling_factor; y = sum over the chosen
         experts HELD HERE (`experts_held` from `expert_offset`) of
         w_e E_e(u), plus Shared(u). What the absent experts would add is
         left out and the partial result goes on: on one chip the layer runs
         without its exchange. No token is dropped at any load: the held
         experts are applied by a grouped product over the (token, expert)
         pairs sorted by expert (`grouped_swiglu`), never through a
         capacity-padded dispatch.

Precision: weights bfloat16 (the router's W_r and b, and norm scales,
float32); matmul operands bfloat16 with float32 accumulation; residual
bfloat16; norms, softmax, RoPE, router scores and top-k float32; cache
bfloat16; logits float32.

Named scopes (telemetry/programs.py): `lm_embed`, `lm_mla_proj`,
`lm_mla_prefill`, `lm_mla_decode`, `lm_dense_mlp`, `lm_moe_router`,
`lm_moe_experts`, `lm_moe_shared`, `lm_head`.

Shared with the looped model (models/looplm.py): `rms_norm`, `apply_rope`,
`_mm`, the SwiGLU form. Pure functions over a parameter tree.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mine_tpu.kernels import attention as attn_kernels
from mine_tpu.models.looplm import INIT_STD, _mm, apply_rope, rms_norm

DTYPE = jnp.bfloat16          # weights, operands, residual stream
ROUTER_DTYPE = jnp.float32    # router scores and top-k


@dataclasses.dataclass(frozen=True)
class MoeMlaConfig:
    """The `lm.*` keys, which mirror the source `config.json` key for key,
    and the chip's share: `experts_held` of `n_routed_experts` from
    `expert_offset`, `vocab_held` of `vocab_size`."""
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    rope_factor: float
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale: float
    rope_mscale_all_dim: float
    rope_original_max_position_embeddings: int
    max_position_embeddings: int
    experts_held: int
    expert_offset: int
    vocab_held: int

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


def moe_mla_config_from_dict(config: Dict[str, Any]) -> MoeMlaConfig:
    lm = lambda k: config["lm." + k]                          # noqa: E731
    cfg = MoeMlaConfig(
        hidden_size=int(lm("hidden_size")),
        num_attention_heads=int(lm("num_attention_heads")),
        q_lora_rank=int(lm("q_lora_rank")),
        kv_lora_rank=int(lm("kv_lora_rank")),
        qk_nope_head_dim=int(lm("qk_nope_head_dim")),
        qk_rope_head_dim=int(lm("qk_rope_head_dim")),
        v_head_dim=int(lm("v_head_dim")),
        intermediate_size=int(lm("intermediate_size")),
        moe_intermediate_size=int(lm("moe_intermediate_size")),
        num_hidden_layers=int(lm("num_hidden_layers")),
        first_k_dense_replace=int(lm("first_k_dense_replace")),
        n_routed_experts=int(lm("n_routed_experts")),
        n_shared_experts=int(lm("n_shared_experts")),
        num_experts_per_tok=int(lm("num_experts_per_tok")),
        routed_scaling_factor=float(lm("routed_scaling_factor")),
        norm_topk_prob=bool(lm("norm_topk_prob")),
        vocab_size=int(lm("vocab_size")),
        rms_norm_eps=float(lm("rms_norm_eps")),
        rope_theta=float(lm("rope_theta")),
        rope_factor=float(lm("rope_scaling.factor")),
        rope_beta_fast=float(lm("rope_scaling.beta_fast")),
        rope_beta_slow=float(lm("rope_scaling.beta_slow")),
        rope_mscale=float(lm("rope_scaling.mscale")),
        rope_mscale_all_dim=float(lm("rope_scaling.mscale_all_dim")),
        rope_original_max_position_embeddings=int(
            lm("rope_scaling.original_max_position_embeddings")),
        max_position_embeddings=int(lm("max_position_embeddings")),
        experts_held=int(lm("experts_held")),
        expert_offset=int(lm("expert_offset")),
        vocab_held=int(lm("vocab_held")))
    # what this model code does not implement fails at construction, not as
    # a silently different model
    must = {"lm.hidden_act": "silu", "lm.scoring_func": "sigmoid",
            "lm.topk_method": "noaux_tc", "lm.rope_scaling.type": "yarn",
            "lm.n_group": 1, "lm.topk_group": 1, "lm.moe_layer_freq": 1,
            "lm.first_k_dense_replace": 1, "lm.n_shared_experts": 1,
            "lm.attention_bias": False, "lm.tie_word_embeddings": False}
    for key, want in must.items():
        if config[key] != want:
            raise ValueError("%s = %r is not implemented (only %r is)"
                             % (key, config[key], want))
    if cfg.moe_layers < 1:
        raise ValueError("lm.num_hidden_layers must exceed the dense layers")
    if not (0 < cfg.experts_held
            and cfg.expert_offset + cfg.experts_held <= cfg.n_routed_experts):
        raise ValueError("lm.expert_offset + lm.experts_held must lie within "
                         "lm.n_routed_experts")
    if not 0 < cfg.vocab_held <= cfg.vocab_size:
        raise ValueError("lm.vocab_held must lie within lm.vocab_size")
    return cfg


def init_params(key: jax.Array, cfg: MoeMlaConfig) -> Dict[str, Any]:
    """Weights normal(0, 0.02) drawn in bfloat16 directly (the tree at
    published widths is 9.7 GB: it must never exist in float32), norm scales
    one, the router's bias zero. The tree:
      embed [V, h], head [h, V], final_norm [h]
      dense  {attention leaves, wg, wu, wd}                       layer 0
      moe    {attention leaves stacked [Lm, ...], router [Lm, h, E] f32,
              router_bias [Lm, E] f32, sg / su / sd stacked, eg / eu / ed
              FLAT over layers [Lm * held, ...]}                  layers 1..
    W_qb and W_kvb are two matrices each, columns grouped by kind."""
    h, H, V = cfg.hidden_size, cfg.num_attention_heads, cfg.vocab_held
    Lm, held, E = cfg.moe_layers, cfg.experts_held, cfg.n_routed_experts
    I, Im = cfg.intermediate_size, cfg.moe_intermediate_size
    attn = {"wqa": (h, cfg.q_lora_rank),
            "wqb_nope": (cfg.q_lora_rank, H * cfg.qk_nope_head_dim),
            "wqb_rope": (cfg.q_lora_rank, H * cfg.qk_rope_head_dim),
            "wkva": (h, cfg.latent_width),
            "wkvb_k": (cfg.kv_lora_rank, H * cfg.qk_nope_head_dim),
            "wkvb_v": (cfg.kv_lora_rank, H * cfg.v_head_dim),
            "wo": (H * cfg.v_head_dim, h)}
    norms = {"attn_norm": h, "q_norm": cfg.q_lora_rank,
             "kv_norm": cfg.kv_lora_rank, "ffn_norm": h}
    counter = iter(range(1 << 30))

    def mat(shape, dtype=DTYPE):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, dtype) * INIT_STD).astype(dtype)

    def attn_leaves(lead):
        out = {k: mat(lead + s) for k, s in attn.items()}
        out.update({k: jnp.ones(lead + (n,), jnp.float32)
                    for k, n in norms.items()})
        return out

    dense = dict(attn_leaves(()), wg=mat((h, I)), wu=mat((h, I)),
                 wd=mat((I, h)))
    moe = dict(attn_leaves((Lm,)),
               router=mat((Lm, h, E), ROUTER_DTYPE),
               router_bias=jnp.zeros((Lm, E), ROUTER_DTYPE),
               sg=mat((Lm, h, Im)), su=mat((Lm, h, Im)), sd=mat((Lm, Im, h)),
               eg=mat((Lm * held, h, Im)), eu=mat((Lm * held, h, Im)),
               ed=mat((Lm * held, Im, h)))
    return {"embed": mat((V, h)), "head": mat((h, V)),
            "final_norm": jnp.ones((h,), jnp.float32),
            "dense": dense, "moe": moe}


# ---------------- YaRN RoPE ----------------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: MoeMlaConfig) -> np.ndarray:
    """The blended inverse frequencies of the family's public modelling
    code: interpolated by 1 / factor below the correction range,
    unchanged above it, a linear ramp between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** exponent
    inter = 1.0 / (cfg.rope_factor * base ** exponent)

    def correction_dim(rotations):
        return dim * math.log(cfg.rope_original_max_position_embeddings
                              / (rotations * 2 * math.pi)) / (
                                  2 * math.log(base))
    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_tables(positions, cfg: MoeMlaConfig):
    """cos, sin [T, rope dim] float32 at `positions` [T] (rotate-half);
    scaled by mscale / mscale_all_dim of the YaRN settings."""
    scale = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(cfg))[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


# ---------------- latent attention ----------------

def mla_project(x, w, cfg: MoeMlaConfig, cos, sin):
    """x [T, hidden] -> (q_nope [T, H*dn], q_rope [T, H*dr] rotated, latent
    [T, rank + dr] = [normed c_kv | rotated k_rope]: what the cache holds)."""
    eps, H = cfg.rms_norm_eps, cfg.num_attention_heads
    with jax.named_scope("lm_mla_proj"):
        u = rms_norm(x, w["attn_norm"], eps, DTYPE)
        c_q = rms_norm(_mm(u, w["wqa"], DTYPE), w["q_norm"], eps, DTYPE)
        q_nope = _mm(c_q, w["wqb_nope"], DTYPE).astype(DTYPE)
        q_rope = apply_rope(_mm(c_q, w["wqb_rope"], DTYPE)[None], cos, sin,
                            H)[0].astype(DTYPE)
        kv = _mm(u, w["wkva"], DTYPE)
        c_kv = rms_norm(kv[:, :cfg.kv_lora_rank], w["kv_norm"], eps, DTYPE)
        k_rope = apply_rope(kv[None, :, cfg.kv_lora_rank:], cos, sin,
                            1)[0].astype(DTYPE)
        return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)


def mla_prefill(q_nope, q_rope, latent, w, cfg: MoeMlaConfig, q_offset,
                impl: str):
    """Up-projected form: queries of a prompt chunk [Tq, ...] at positions
    `q_offset`.. against the sequence's latent rows [Tk, rank + dr] (the
    cached prefix and the chunk itself, position i in row i) -> [Tq, H*dv]."""
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    with jax.named_scope("lm_mla_prefill"):
        latent = latent.astype(DTYPE)
        c_kv, k_rope = latent[:, :r], latent[:, r:]
        k_nope = _mm(c_kv, w["wkvb_k"], DTYPE).astype(DTYPE)
        v = _mm(c_kv, w["wkvb_v"], DTYPE).astype(DTYPE)
        q_rope = q_rope.reshape(q_rope.shape[0], H, -1).transpose(1, 0, 2)
        return attn_kernels.prefix_attention(
            q_nope, q_rope, k_nope, k_rope, v, H, q_offset,
            cfg.softmax_scale, impl=impl)


def mla_decode(q_nope, q_rope, cache, layer, tables, lengths, w,
               cfg: MoeMlaConfig, page_size: int, impl: str):
    """Absorbed form: one query token a sequence [B, ...] against that
    sequence's pages of `cache` [L, rows, rank + dr] (`tables` [B, P] page
    ids, `lengths` [B] tokens including the current one) -> [B, H*dv]."""
    H, r, dn = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    B = q_nope.shape[0]
    with jax.named_scope("lm_mla_decode"):
        wk = w["wkvb_k"].reshape(r, H, dn)
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope.reshape(B, H, dn), wk,
                           preferred_element_type=jnp.float32).astype(DTYPE)
        q = jnp.concatenate([q_lat, q_rope.reshape(B, H, -1)], axis=-1)
        # the cache's rows are padded to whole lanes; so is the query
        q = jnp.pad(q, ((0, 0), (0, 0), (0, cache.shape[-1] - q.shape[-1])))
        o_lat = attn_kernels.paged_latent_attention(
            q, cache, layer, tables, lengths, r, page_size,
            cfg.softmax_scale, impl=impl)
        wv = w["wkvb_v"].reshape(r, H, cfg.v_head_dim)
        o = jnp.einsum("bhc,chd->bhd", o_lat.astype(DTYPE), wv,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, H * cfg.v_head_dim).astype(DTYPE)


def attention_out(x, o, w):
    """The residual stream after attention: x + o W_o."""
    with jax.named_scope("lm_mla_proj"):
        return (x.astype(jnp.float32) + _mm(o, w["wo"], DTYPE)).astype(DTYPE)


# ---------------- feed-forward ----------------

def swiglu(u, wg, wu, wd):
    act = (jax.nn.silu(_mm(u, wg, DTYPE)) * _mm(u, wu, DTYPE)).astype(DTYPE)
    return _mm(act, wd, DTYPE)


def dense_mlp(x, w, cfg: MoeMlaConfig):
    with jax.named_scope("lm_dense_mlp"):
        u = rms_norm(x, w["ffn_norm"], cfg.rms_norm_eps, DTYPE)
        return (x.astype(jnp.float32)
                + swiglu(u, w["wg"], w["wu"], w["wd"])).astype(DTYPE)


def route(u, router, bias, cfg: MoeMlaConfig):
    """(sigma [T, E], chosen [T, k] expert ids, weights [T, k]) over ALL
    routed experts, in `ROUTER_DTYPE`."""
    with jax.named_scope("lm_moe_router"):
        logits = jnp.dot(u.astype(ROUTER_DTYPE), router.astype(ROUTER_DTYPE),
                         precision=lax.Precision.HIGHEST,
                         preferred_element_type=ROUTER_DTYPE)
        sigma = jax.nn.sigmoid(logits)
        _, chosen = lax.top_k(sigma + bias.astype(ROUTER_DTYPE),
                              cfg.num_experts_per_tok)
        picked = jnp.take_along_axis(sigma, chosen, axis=-1)
        weights = picked.astype(jnp.float32)
        if cfg.norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
        return sigma, chosen, weights * cfg.routed_scaling_factor


GMM_TILE = (256, 1024, 1024)   # rows, contraction, columns of one tile


def grouped_matmul(lhs, rhs, group_sizes, impl: str):
    """lhs rows sorted by group [M, K] x rhs [G, K, N] -> [M, N] float32:
    row i of group g times rhs[g]. Rows past sum(group_sizes) are
    unspecified. On the chip the megablox kernel (its grid covers the
    groups' rows only, so the time follows the load and empty groups cost
    nothing); elsewhere `lax.ragged_dot`."""
    if impl == "xla":
        return lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    M, K = lhs.shape
    N = rhs.shape[-1]
    tiling = (min(M, GMM_TILE[0]), min(K, GMM_TILE[1]), min(N, GMM_TILE[2]))
    return gmm(lhs, rhs, group_sizes.astype(jnp.int32),
               preferred_element_type=jnp.float32, tiling=tiling,
               interpret=(impl == "interpret"))


def grouped_swiglu(xs, eg, eu, ed, group_sizes, impl: str):
    """SwiGLU of sorted rows xs [M, h], each group by its own expert."""
    gate = grouped_matmul(xs, eg, group_sizes, impl)
    up = grouped_matmul(xs, eu, group_sizes, impl)
    act = (jax.nn.silu(gate) * up).astype(DTYPE)
    return grouped_matmul(act, ed, group_sizes, impl)


def moe_experts(u, chosen, weights, eg, eu, ed, first_group,
                cfg: MoeMlaConfig, impl: str, valid=None):
    """The held experts' part of the layer: sum over the chosen experts held
    here of w_e E_e(u). `eg` / `eu` / `ed` hold `first_group` groups before
    this layer's (the flat stack over layers: the kernel is handed the whole
    stack and empty groups, so no layer's experts are ever copied out).
    Rows where `valid` [T] is false (a step's padding) go to no expert.

    The (token, expert) pairs held here, sorted by expert, are computed a
    block of rows at a time, as many blocks as the load needs: one at the
    load a share expects (a quarter pair a token), more when the ids are
    skewed towards a held expert, T * k rows at the worst. Work follows the
    load and no pair is ever dropped.
    -> (y [T, h] float32, rows a held expert [held], pairs held here)"""
    T, k, held = u.shape[0], cfg.num_experts_per_tok, cfg.experts_held
    n = T * k
    block = min(n, GMM_TILE[0] * -(-T // GMM_TILE[0]))
    with jax.named_scope("lm_moe_experts"):
        local = chosen - cfg.expert_offset
        here = (local >= 0) & (local < held)
        if valid is not None:
            here = here & valid[:, None]
        key = jnp.where(here, local, held).reshape(-1)            # [T*k]
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
        starts, pairs = ends - sizes, ends[-1]
        order = jnp.pad(order, (0, -n % block))
        w_pair = jnp.where(here, weights, 0.0).reshape(-1)
        groups = eg.shape[0]

        def one_block(b, y):
            lo = b * block
            pair = lax.dynamic_slice(order, (lo,), (block,))
            token = pair // k
            in_block = jnp.clip(jnp.minimum(ends, lo + block)
                                - jnp.maximum(starts, lo), 0)
            all_sizes = in_block if groups == held else (
                lax.dynamic_update_slice(jnp.zeros((groups,), jnp.int32),
                                         in_block, (first_group,)))
            out = grouped_swiglu(jnp.take(u, token, axis=0), eg, eu, ed,
                                 all_sizes, impl)
            live = lo + jnp.arange(block) < pairs     # the rest: unspecified
            out = jnp.where(live[:, None],
                            out * jnp.take(w_pair, pair)[:, None], 0.0)
            return y.at[token].add(out)

        y = lax.fori_loop(0, (pairs + block - 1) // block, one_block,
                          jnp.zeros((T, u.shape[1]), jnp.float32))
        return y, sizes, pairs


def moe_mlp(x, w, eg, eu, ed, first_group, cfg: MoeMlaConfig, impl: str,
            valid=None):
    """x + held experts' part + shared expert. -> (x', info) with the
    router's input, scores and choice of every row, and the experts' load."""
    u = rms_norm(x, w["ffn_norm"], cfg.rms_norm_eps, DTYPE)
    sigma, chosen, weights = route(u, w["router"], w["router_bias"], cfg)
    y, sizes, pairs = moe_experts(u, chosen, weights, eg, eu, ed,
                                  first_group, cfg, impl, valid)
    with jax.named_scope("lm_moe_shared"):
        y = y + swiglu(u, w["sg"], w["su"], w["sd"])
        x = (x.astype(jnp.float32) + y).astype(DTYPE)
    return x, {"router_input": u, "sigma": sigma, "chosen": chosen,
               "expert_rows": sizes, "held_pairs": pairs}


def embed(params, tokens):
    with jax.named_scope("lm_embed"):
        return jnp.take(params["embed"], tokens, axis=0).astype(DTYPE)


def head(params, x, cfg: MoeMlaConfig):
    """(logits [R, vocabulary slice] float32, the normed hidden rows)."""
    with jax.named_scope("lm_head"):
        hidden = rms_norm(x, params["final_norm"], cfg.rms_norm_eps, DTYPE)
        return _mm(hidden, params["head"], DTYPE), hidden


def moe_layer_weights(params, index: int):
    """Slice `index` of the stacked expert layers (not the flat experts)."""
    return {k: v[index] for k, v in params["moe"].items()
            if k not in ("eg", "eu", "ed")}


def forward(params, tokens, cfg: MoeMlaConfig, impl: str = "xla"):
    """Logits [S, vocabulary slice] of ONE whole sequence at positions
    0..S-1, no cache: every layer in the up-projected form. What the tests
    hold against the reference; the engine's step (serve/lm_engine.py) is
    built from the same functions."""
    S = tokens.shape[0]
    cos, sin = rope_tables(jnp.arange(S), cfg)
    x = embed(params, tokens)

    def attend(x, w):
        q_nope, q_rope, latent = mla_project(x, w, cfg, cos, sin)
        return attention_out(x, mla_prefill(q_nope, q_rope, latent, w, cfg,
                                            0, impl), w)

    x = dense_mlp(attend(x, params["dense"]), params["dense"], cfg)
    moe = params["moe"]
    for i in range(cfg.moe_layers):
        w = moe_layer_weights(params, i)
        x, _ = moe_mlp(attend(x, w), w, moe["eg"], moe["eu"], moe["ed"],
                       i * cfg.experts_held, cfg, impl)
    return head(params, x, cfg)[0]
