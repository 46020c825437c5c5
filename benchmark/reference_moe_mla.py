"""Plain reference of the Kimi-K2 / DeepSeek-V3 family's language model: latent
attention (MLA) with YaRN RoPE, one leading dense SwiGLU layer, then layers
of routed experts beside a shared expert. Straightforward `jax.numpy`,
float32, `jax.default_matmul_precision("highest")`; no cache, no absorbed
form, no kernels, no batching over requests; imports nothing of the program.

One sequence at a time: `tokens` [S] at positions 0..S-1, full causal
attention over the whole sequence. With x the residual stream [S, hidden]
and N an RMSNorm:

    x <- x + Attn(N(x));  x <- x + FFN(N(x));  logits = N_f(x) W_head

  Attn   c_q = N(x W_qa);  q = c_q W_qb -> heads x (nope | rope)
         x W_kva -> (c_kv | k_rope);  c_kv = N(c_kv);  one k_rope for all heads
         RoPE(q_rope), RoPE(k_rope);  c_kv W_kvb -> heads x (k_nope | v)
         softmax((q_nope.k_nope + q_rope.k_rope) * s + causal) v, heads
         joined, W_o;  s = (nope + rope)^-0.5 * m^2, m = 0.1 * mscale_all_dim
         * ln(factor) + 1
  FFN 0  (silu(u W_g) * (u W_u)) W_d
  FFN l  sigma = sigmoid(u W_r) over ALL routed experts; the choice is the
         top k of sigma + b; the weights sigma_e / sum_chosen(sigma) * scale;
         y = sum over the chosen experts HELD HERE of w_e E_e(u) + Shared(u)

The weights arrive in the program's parameter tree (`layer_weights` names
the leaves) in whatever dtype they are stored; every function upcasts what it
is handed to float32, so a caller may hand over one layer at a time.

Departures from the published description, each for a reason:
  * Only the experts `held = (offset, count)` are applied; what the absent
    experts would add is left out and the partial result goes on (the chip's
    share of an expert-parallel deployment; `held = (0, n_routed_experts)`
    is the uncut layer). The router still scores and chooses over all.
  * The vocabulary is the slice the embedding and the head are handed.
  * W_qb and W_kvb are stored as two matrices each (`wqb_nope` / `wqb_rope`,
    `wkvb_k` / `wkvb_v`): the same columns, grouped by kind instead of by
    head. With weights from a seed the two are the same model.
  * RoPE pairs dimension i with i + d/2 (halves) where the published code
    first de-interleaves its weights' columns (2i, 2i+1): a permutation of
    W's columns, stated under `assumed` in the configuration's file.
  * `n_group` = `topk_group` = 1 (the source's values): no group limit.
  * Router ties: `choose` takes the program's choice for a token where that
    choice is a valid top-k of the reference's own sigma + b up to
    `ROUTER_TIE_TOL`, and counts it; any other differing choice is reported.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# two values of sigma + b closer than this are a tie: bfloat16 operands move
# a router logit of scale ~1.7 by up to ~2e-2, sigma's slope is <= 1/4
ROUTER_TIE_TOL = 1e-2
QUERY_BLOCK = 4096   # rows of the score matrix held at once, a head

# ---- tolerances of the serving check, each with its reason ---------------
# Relative L2 error of a vector (the reference in the denominator). The
# program computes with bfloat16 weights and matmul operands, float32
# accumulation, a bfloat16 residual stream and a bfloat16 latent cache; the
# reference is float32 throughout. Each limit lies between two readings
# taken on the chip at the timed sizes (PERF.md section 6, PR 35): the
# largest the program read over its seeds (some forty runs), and what the
# nearest lower precision reads (tools/lm_serve_fault_readings.py).
TOLERANCES = {
    # logits over the vocabulary slice at the prompt's last position and at
    # each of the first decode steps, through 7 layers and the paged cache.
    # Program 0.0114 to 0.0141 and 0.0130 to 0.0145; a float8 cache, a
    # bfloat16 router and bfloat16 logits TOGETHER 0.063 to 0.074. No ONE
    # of them moves these two out of the program's range (a bfloat16 head
    # alone, a bfloat16 router alone 0.0119 / 0.0135, a float8 cache on
    # layers 1.. alone 0.0119 to 0.0139): they hold the path as a whole (a
    # wrong layer, position, page, expert or weight); each precision is
    # held by the limit of its own block below.
    "logits.prefill_last": 3e-2,
    "logits.decode": 3e-2,
    # one block alone, the reference's float32 on the program's own input:
    # what is left is accumulation order and the float32 math, so a result
    # or an operand rounded to a lower precision shows whole
    "same.head": 1e-4,       # program 0 (bit for bit); bfloat16 logits 1.66e-3
    "same.router": 1e-4,     # program <= 1.0e-7; a bfloat16 router 1.48e-3
    # layer 0's latent rows as the cache holds them against the reference's
    # float32 rows from the same embedding: one rounding to the cache's
    # dtype (program, bfloat16: 0.00230 to 0.00242; float8_e4m3: 0.0265)
    "cache.layer0": 8e-3,
    # the LAST layer's rows of the flagged request's document (its whole
    # pages, thousands of rows, written by an earlier step) against the
    # reference's own forward, the median over the rows of a row's relative
    # error: six layers of the operands' roundings plus the cache's one.
    # End to end a float8 cache on layers 1.. does not show (with weights
    # from a seed attention is near uniform over thousands of rows and averages
    # the rows' noise away: `logits.*` read 0.0128 / 0.0138 under it), so
    # the later layers' rows are held here directly. Program 0.0122 and
    # 0.0125; float8_e4m3 rows on layers 1.. 0.0293 and 0.0294. Left out
    # (and said so in the run's line) where the document was evicted first.
    "cache.last": 2e-2,
    # the program's choices of experts that are no valid top-k of the
    # reference's scores even up to ROUTER_TIE_TOL: none allowed (program 0;
    # a bfloat16 router 3 to 4 of a request's 102)
    "router.bad_choices": 0.5,
}


def config_from_flat(config: dict) -> dict:
    """`config_from` of a flat key space that holds the source's keys as
    `lm.<key>` and the rope group as `lm.rope_scaling.<key>`."""
    group = "lm.rope_scaling."
    lm = {k[3:]: v for k, v in config.items()
          if k.startswith("lm.") and not k.startswith(group)}
    lm["rope_scaling"] = {k[len(group):]: v for k, v in config.items()
                          if k.startswith(group)}
    return config_from(lm)


def config_from(lm: dict) -> dict:
    """The numbers the equations need, from the source config.json's keys
    (a plain dict: the configuration file's top level, or `lm.*` stripped)."""
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "n_routed_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
            "rope_theta", "rope_scaling", "first_k_dense_replace")
    return {k: lm[k] for k in keys}


# ---------------- pieces ----------------

def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(F32)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg) -> np.ndarray:
    """The blended inverse frequencies of the family's modelling code:
    interpolated (1 / factor) below the correction range, extrapolated
    (unchanged) above it, a linear ramp between."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], float(
        cfg["rope_theta"])
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** exponent
    inter = 1.0 / (float(rs["factor"]) * base ** exponent)

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (
                                  2 * math.log(base))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    mask = 1.0 - ramp
    return (inter * (1.0 - mask) + extra * mask).astype(np.float32)


def rope(x, positions, cfg):
    """x [..., S, d] rotated at `positions` [S] (halves pairing); the cos /
    sin scale is mscale / mscale_all_dim of the YaRN settings."""
    rs = cfg["rope_scaling"]
    scale = (yarn_mscale(rs["factor"], rs["mscale"])
             / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    ang = positions.astype(F32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))
    ang = jnp.concatenate([ang, ang], axis=-1)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    d = x.shape[-1]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


def softmax_scale(cfg) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def latent_rows(u, w, cfg, positions):
    """What the cache holds of normed input u [S, hidden]: [c_kv | k_rope]."""
    r = cfg["kv_lora_rank"]
    kv = u @ w["wkva"].astype(F32)
    c_kv = rms_norm(kv[:, :r], w["kv_norm"], cfg["rms_norm_eps"])
    return jnp.concatenate([c_kv, rope(kv[:, r:], positions, cfg)], axis=-1)


def attention(x, w, cfg, positions):
    """Latent attention of one whole sequence, up-projected form."""
    H, dn, dr, dv, r = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                        cfg["kv_lora_rank"])
    S, eps = x.shape[0], cfg["rms_norm_eps"]
    u = rms_norm(x, w["attn_norm"], eps)
    c_q = rms_norm(u @ w["wqa"].astype(F32), w["q_norm"], eps)
    q_nope = (c_q @ w["wqb_nope"].astype(F32)).reshape(S, H, dn)
    q_rope = (c_q @ w["wqb_rope"].astype(F32)).reshape(S, H, dr)
    q_rope = rope(q_rope.transpose(1, 0, 2), positions, cfg)     # [H, S, dr]
    lat = latent_rows(u, w, cfg, positions)
    c_kv, k_rope = lat[:, :r], lat[:, r:]
    k_nope = (c_kv @ w["wkvb_k"].astype(F32)).reshape(S, H, dn)
    v = (c_kv @ w["wkvb_v"].astype(F32)).reshape(S, H, dv)
    scale = softmax_scale(cfg)
    causal = positions[None, :] <= positions[:, None]

    def one_head(args):
        qn, qr, kn, vh = args                                    # [S, d]
        outs = []
        for lo in range(0, S, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, S)
            s = (qn[lo:hi] @ kn.T + qr[lo:hi] @ k_rope.T) * scale
            s = jnp.where(causal[lo:hi], s, -jnp.inf)
            outs.append(jax.nn.softmax(s, axis=-1) @ vh)
        return jnp.concatenate(outs, axis=0)

    o = jax.lax.map(one_head, (q_nope.transpose(1, 0, 2), q_rope,
                               k_nope.transpose(1, 0, 2),
                               v.transpose(1, 0, 2)))             # [H, S, dv]
    o = o.transpose(1, 0, 2).reshape(S, H * dv)
    return o @ w["wo"].astype(F32), lat


def swiglu(u, wg, wu, wd):
    """(silu(u W_g) * (u W_u)) W_d, a block of rows at a time."""
    wg, wu, wd = wg.astype(F32), wu.astype(F32), wd.astype(F32)
    return jnp.concatenate([
        (jax.nn.silu(u[lo:lo + QUERY_BLOCK] @ wg)
         * (u[lo:lo + QUERY_BLOCK] @ wu)) @ wd
        for lo in range(0, u.shape[0], QUERY_BLOCK)], axis=0)


def route(u, w, cfg):
    """(sigma [S, E], sigma + b [S, E]) over ALL routed experts."""
    sigma = jax.nn.sigmoid(u @ w["router"].astype(F32))
    return sigma, sigma + w["router_bias"].astype(F32)


def choose(biased, k: int, program_choice=None):
    """The top k of sigma + b a row, on the host. `program_choice` {row: [k]
    expert ids}: the program's choice for some rows; where it differs from
    the reference's own it is taken if it is a valid top k up to
    ROUTER_TIE_TOL (a tie, counted), and reported otherwise.
    -> (chosen [S, k], ties, bad choices)"""
    b = np.asarray(biased)
    chosen = np.argsort(-b, axis=-1, kind="stable")[:, :k]
    ties, bad = 0, 0
    for row, theirs in (program_choice or {}).items():
        theirs = np.asarray(theirs).astype(int)
        if set(theirs.tolist()) == set(chosen[row].tolist()):
            continue
        rest = np.ones(b.shape[1], bool)
        rest[theirs] = False
        if b[row, theirs].min() >= b[row, rest].max() - ROUTER_TIE_TOL:
            chosen[row] = theirs
            ties += 1
        else:
            bad += 1
    return chosen, ties, bad


def experts(u, w, cfg, sigma, chosen, held):
    """sum over the chosen experts held here of w_e E_e(u), + Shared(u).
    `held` = (offset, count) of the experts in `w["eg"]` / `eu` / `ed`."""
    offset, count = held
    picked = jnp.take_along_axis(sigma, chosen, axis=-1)
    weights = picked
    if cfg["norm_topk_prob"]:
        weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    weights = weights * cfg["routed_scaling_factor"]
    y = swiglu(u, w["sg"], w["su"], w["sd"])

    def add_expert(y, expert):      # one expert at a time: one upcast live
        e, wg, wu, wd = expert
        w_e = jnp.sum(jnp.where(chosen == offset + e, weights, 0.0), axis=-1)
        return y + w_e[:, None] * swiglu(u, wg, wu, wd), None

    y, _ = jax.lax.scan(add_expert, y, (jnp.arange(count), w["eg"], w["eu"],
                                        w["ed"]))
    return y


# ---------------- the parameter tree, a layer at a time ----------------

ATTN_LEAVES = ("attn_norm", "wqa", "q_norm", "wqb_nope", "wqb_rope", "wkva",
               "kv_norm", "wkvb_k", "wkvb_v", "wo", "ffn_norm")


def num_layers(params) -> int:
    return 1 + params["moe"]["router"].shape[0]


@functools.partial(jax.jit, static_argnums=(1,))
def layer_weights(params, index: int):
    """Layer `index`'s weights out of the program's tree: layer 0 is
    `params["dense"]`; layer l >= 1 is slice l - 1 of the stacked
    `params["moe"]`, its experts rows (l-1)*held .. l*held of the flat
    `eg` / `eu` / `ed`."""
    if index == 0:
        return dict(params["dense"])
    moe_w, i = params["moe"], index - 1
    held = moe_w["eg"].shape[0] // moe_w["router"].shape[0]
    w = {k: moe_w[k][i] for k in ATTN_LEAVES + (
        "router", "router_bias", "sg", "su", "sd")}
    for k in ("eg", "eu", "ed"):
        w[k] = moe_w[k][i * held:(i + 1) * held]
    return w


_JITTED = {}


def _jitted(cfg):
    """The layer's two halves under jit (between them the choice of experts
    is made on the host), compiled once a configuration."""
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    if key not in _JITTED:
        def first_half(x, w, positions):
            with jax.default_matmul_precision("highest"):
                a, lat = attention(x, w, cfg, positions)
                x = x + a
                u = rms_norm(x, w["ffn_norm"], cfg["rms_norm_eps"])
                if "router" in w:
                    return x, u, lat, route(u, w, cfg)
                return x + swiglu(u, w["wg"], w["wu"], w["wd"]), u, lat, None

        def second_half(x, u, w, sigma, chosen, held):
            with jax.default_matmul_precision("highest"):
                return x + experts(u, w, cfg, sigma, chosen, held)

        _JITTED[key] = (jax.jit(first_half),
                        jax.jit(second_half, static_argnames=("held",)))
    return _JITTED[key]


def layer(x, w, cfg, positions, held, program_choice=None):
    """One layer on the float32 residual stream x [S, hidden]; `w` (of
    `layer_weights`) in whatever dtype it is stored. -> (x', info)"""
    first_half, second_half = _jitted(cfg)
    x, u, lat, routed = first_half(x, w, positions)
    info = {"latent": lat, "ffn_input": u}
    if routed is not None:
        sigma, biased = routed
        chosen, ties, bad = choose(biased, cfg["num_experts_per_tok"],
                                   program_choice)
        x = second_half(x, u, w, sigma, jnp.asarray(chosen), tuple(held))
        info.update(sigma=sigma, chosen=chosen, router_ties=ties,
                    bad_choices=bad)
    return x, info


@jax.jit
def embed(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(F32)


def head(params, x, cfg):
    return _head(params["final_norm"], params["head"], x,
                 cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(3,))
def _head(final_norm, head_w, x, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, eps) @ head_w.astype(F32)


@jax.jit
def same_head(hidden, head_w):
    """The head alone on the program's own normed hidden rows."""
    with jax.default_matmul_precision("highest"):
        return hidden.astype(F32) @ head_w.astype(F32)


@jax.jit
def same_router(router_input, router_w):
    """The router's scores alone on the program's own input rows."""
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(router_input.astype(F32)
                              @ router_w.astype(F32))


def forward(params, tokens, cfg, held, program_choices=None):
    """Logits [S, vocabulary slice] of one sequence, and per-layer info.
    `program_choices` {layer index: {row: ids}} (see `moe`)."""
    positions = jnp.arange(tokens.shape[0])
    x = embed(params, tokens)
    infos = []
    for index in range(num_layers(params)):
        x, info = layer(x, layer_weights(params, index), cfg, positions,
                        held, (program_choices or {}).get(index))
        infos.append(info)
    return head(params, x, cfg), infos


def rel_err(got, want) -> float:
    g = np.asarray(got, np.float64).ravel()
    w = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))
