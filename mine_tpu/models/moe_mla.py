"""A language model of the Kimi-K2 / DeepSeek-V3 family for the serve path:
latent attention (MLA) over a cache of latent rows, one leading dense SwiGLU
layer, then layers of routed experts beside a shared expert, of which this
chip holds a share. The `lm.*` keys choose the members of the family: every
layer full attention under YaRN RoPE (Kimi-K2.5: no `lm.layer_types`, no
`lm.index_*`, no gate), or full layers under a learned sparse selection mixed
with sliding-window layers of their own widths (dots3-note-prev, below).

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

Layer kinds (`lm.layer_types`, one entry a layer; absent: all full), each
with widths, RoPE table and cache rows of its own (`of_kind`):

  full     the Attn above with `rope_theta`; where `lm.index_topk` is set, a
           DeepSeek-V3.2-style lightning indexer chooses what it reads:
           q_I = c_q W_Iq -> [index_n_heads, index_head_dim];
           k_I = LayerNorm(u W_Ik) -> [index_head_dim], one key for all index
           heads (u the layer's normed input); RoPE on the first
           qk_rope_head_dim dimensions of both; w = u W_Iw -> [index_n_heads];
           I[t, s] = n_I^-0.5 d_I^-0.5 sum_j w[t, j] relu(q_I[t, j] . k_I[s])
           for s <= t, in float32 (`dsa_index`); S_t = the `index_topk`
           positions of largest I[t, .], all of them while t + 1 <=
           index_topk (`dsa_select`: exact, `lax.top_k`); attention of token t
           is the softmax over s in S_t only. THE CACHE ALSO HOLDS k_I of
           every token. Both forms (a chunk's queries, decode rows) run in
           latent space over the GATHERED rows of S_t (`dsa_attend`): the
           absorbed form reads a selected row once for all heads.
  sliding  the same Attn with the `swa_*` widths and `swa_rope_theta`; token
           t attends s in [t - (sliding_window_size - 1), t]. Its cache rows
           are read only inside that window: a chunk attends its own rows
           and the window before it in the up-projected form, a decode row
           its gathered window in the absorbed form.
  gate     (`attention_gate_type: headwise`) g = sigmoid(u W_g) -> [heads];
           head h's attention output times g_h, before W_o.
  rescale  (`apply_mla_qkv_lora_rescale`) c_q = (hidden / q_lora_rank)^0.5
           N(x W_qa), c_kv = (hidden / kv_lora_rank)^0.5 N(c_kv), each kind
           by its own ranks (folded into the norms' scales).

The expert layers run as ONE `lax.scan` over whole periods of the layer
pattern (dots3: full, sliding, sliding, sliding; Kimi: a period of one
layer), the period's layers unrolled in the body, and not as one stack a
kind: a layer's input is the layer before it, so two stacks cannot be scanned
apart, and a scan over periods compiles one body of four layers where the
unrolled model compiles eight.

Precision: weights bfloat16 (the router's W_r and b, and norm scales,
float32); matmul operands bfloat16 with float32 accumulation; residual
bfloat16; norms, softmax, RoPE, router scores and top-k float32; cache
bfloat16; logits float32.

Named scopes (telemetry/programs.py): `lm_embed`, `lm_mla_proj`,
`lm_mla_prefill`, `lm_mla_decode`, `lm_dense_mlp`, `lm_moe_router`,
`lm_moe_experts`, `lm_moe_shared`, `lm_head`; `lm_dsa_index`, `lm_dsa_select`,
`lm_dsa_prefill`, `lm_dsa_decode`, `lm_swa_proj`, `lm_swa_prefill`,
`lm_swa_decode`, `lm_attn_gate`.

Shared with the looped model (models/looplm.py): `rms_norm`, `apply_rope`,
`_mm`, the SwiGLU form. Pure functions over a parameter tree.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

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
    # what follows is absent (None in the YAML) where every layer is full
    # attention under YaRN with no indexer, no gate and no rescale
    rope_scaling_type: Optional[str] = "yarn"
    layer_types: Tuple[str, ...] = ()          # (): every layer full
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0                        # 0: no indexer, dense
    attention_gate_type: Optional[str] = None
    apply_mla_qkv_lora_rescale: bool = False
    sliding_window_size: int = 0
    swa_num_attention_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    swa_attention_gate_type: Optional[str] = None
    window: int = 0      # set by `of_kind`: this kind attends a window

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kind of every layer."""
        return self.layer_types or (FULL,) * self.num_hidden_layers

    def layers_of(self, kind: str) -> int:
        return sum(k == kind for k in self.kinds)

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern of kinds that the expert layers repeat."""
        kinds = self.kinds[self.first_k_dense_replace:]
        for n in range(1, len(kinds) + 1):
            if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
                return kinds[:n]
        return kinds

    @property
    def lora_scales(self) -> Tuple[float, float]:
        """(a_q, a_kv) of `apply_mla_qkv_lora_rescale`."""
        if not self.apply_mla_qkv_lora_rescale:
            return 1.0, 1.0
        return ((self.hidden_size / self.q_lora_rank) ** 0.5,
                (self.hidden_size / self.kv_lora_rank) ** 0.5)

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


FULL, SLIDING = "full_attention", "sliding_attention"


def of_kind(cfg: MoeMlaConfig, kind: str) -> MoeMlaConfig:
    """The configuration as one kind of layer sees it: a sliding layer's
    widths, RoPE base and gate stand where the full layer's are, it has a
    `window` and no indexer; every function of this module that takes a
    `cfg` takes either."""
    if kind == FULL:
        return cfg
    return dataclasses.replace(
        cfg, num_attention_heads=cfg.swa_num_attention_heads,
        q_lora_rank=cfg.swa_q_lora_rank, kv_lora_rank=cfg.swa_kv_lora_rank,
        qk_nope_head_dim=cfg.swa_qk_nope_head_dim,
        qk_rope_head_dim=cfg.swa_qk_rope_head_dim,
        v_head_dim=cfg.swa_v_head_dim, rope_theta=cfg.swa_rope_theta,
        attention_gate_type=cfg.swa_attention_gate_type, index_topk=0,
        window=cfg.sliding_window_size)


def moe_mla_config_from_dict(config: Dict[str, Any]) -> MoeMlaConfig:
    lm = lambda k: config["lm." + k]                          # noqa: E731
    opt = lambda k, d: d if config.get("lm." + k) is None else config[  # noqa
        "lm." + k]
    yarn = config.get("lm.rope_scaling.type") is not None
    rs = lambda k, d: float(lm("rope_scaling." + k)) if yarn else d  # noqa
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
        rope_factor=rs("factor", 1.0),
        rope_beta_fast=rs("beta_fast", 32.0),
        rope_beta_slow=rs("beta_slow", 1.0),
        rope_mscale=rs("mscale", 1.0),
        rope_mscale_all_dim=rs("mscale_all_dim", 1.0),
        rope_original_max_position_embeddings=int(rs(
            "original_max_position_embeddings",
            lm("max_position_embeddings"))),
        max_position_embeddings=int(lm("max_position_embeddings")),
        experts_held=int(lm("experts_held")),
        expert_offset=int(lm("expert_offset")),
        vocab_held=int(lm("vocab_held")),
        rope_scaling_type=config.get("lm.rope_scaling.type"),
        layer_types=tuple(opt("layer_types", ())),
        index_n_heads=int(opt("index_n_heads", 0)),
        index_head_dim=int(opt("index_head_dim", 0)),
        index_topk=int(opt("index_topk", 0)),
        attention_gate_type=opt("attention_gate_type", None),
        apply_mla_qkv_lora_rescale=bool(opt("apply_mla_qkv_lora_rescale",
                                            False)),
        sliding_window_size=int(opt("sliding_window_size", 0)),
        swa_num_attention_heads=int(opt("swa_num_attention_heads", 0)),
        swa_q_lora_rank=int(opt("swa_q_lora_rank", 0)),
        swa_kv_lora_rank=int(opt("swa_kv_lora_rank", 0)),
        swa_qk_nope_head_dim=int(opt("swa_qk_nope_head_dim", 0)),
        swa_qk_rope_head_dim=int(opt("swa_qk_rope_head_dim", 0)),
        swa_v_head_dim=int(opt("swa_v_head_dim", 0)),
        swa_rope_theta=float(opt("swa_rope_theta", 0.0)),
        swa_attention_gate_type=opt("swa_attention_gate_type", None))
    # what this model code does not implement fails at construction, not as
    # a silently different model
    must = {"lm.hidden_act": "silu", "lm.scoring_func": "sigmoid",
            "lm.topk_method": "noaux_tc", "lm.n_group": 1,
            "lm.topk_group": 1, "lm.moe_layer_freq": 1,
            "lm.first_k_dense_replace": 1, "lm.n_shared_experts": 1,
            "lm.attention_bias": False, "lm.tie_word_embeddings": False}
    for key, want in must.items():
        if config[key] != want:
            raise ValueError("%s = %r is not implemented (only %r is)"
                             % (key, config[key], want))
    if cfg.rope_scaling_type not in (None, "yarn"):
        raise ValueError("lm.rope_scaling.type = %r is not implemented (only "
                         "'yarn' and none are)" % (cfg.rope_scaling_type,))
    for key in ("lm.attention_gate_type", "lm.swa_attention_gate_type"):
        if config.get(key) not in (None, "headwise"):
            raise ValueError("%s = %r is not implemented (only 'headwise' "
                             "is)" % (key, config[key]))
    if cfg.moe_layers < 1:
        raise ValueError("lm.num_hidden_layers must exceed the dense layers")
    if len(cfg.kinds) != cfg.num_hidden_layers or set(cfg.kinds) - {
            FULL, SLIDING}:
        raise ValueError("lm.layer_types must name lm.num_hidden_layers "
                         "layers, each %r or %r" % (FULL, SLIDING))
    if SLIDING in cfg.kinds and not (
            cfg.sliding_window_size > 1 and cfg.swa_num_attention_heads
            and cfg.swa_kv_lora_rank and cfg.swa_rope_theta):
        raise ValueError("lm.layer_types has sliding layers: "
                         "lm.sliding_window_size and the lm.swa_* widths "
                         "must be set")
    if cfg.index_topk and not (cfg.index_n_heads and cfg.index_head_dim
                               >= cfg.qk_rope_head_dim):
        raise ValueError("lm.index_topk needs lm.index_n_heads and an "
                         "lm.index_head_dim of at least lm.qk_rope_head_dim")
    if not (0 < cfg.experts_held
            and cfg.expert_offset + cfg.experts_held <= cfg.n_routed_experts):
        raise ValueError("lm.expert_offset + lm.experts_held must lie within "
                         "lm.n_routed_experts")
    if not 0 < cfg.vocab_held <= cfg.vocab_size:
        raise ValueError("lm.vocab_held must lie within lm.vocab_size")
    return cfg


def attention_leaves(cfg: MoeMlaConfig):
    """({matrix: shape}, {norm scale: length}, {zero vector: length}) of one
    attention sub-layer of the kind `cfg` describes (`of_kind`)."""
    h, H = cfg.hidden_size, cfg.num_attention_heads
    mats = {"wqa": (h, cfg.q_lora_rank),
            "wqb_nope": (cfg.q_lora_rank, H * cfg.qk_nope_head_dim),
            "wqb_rope": (cfg.q_lora_rank, H * cfg.qk_rope_head_dim),
            "wkva": (h, cfg.latent_width),
            "wkvb_k": (cfg.kv_lora_rank, H * cfg.qk_nope_head_dim),
            "wkvb_v": (cfg.kv_lora_rank, H * cfg.v_head_dim),
            "wo": (H * cfg.v_head_dim, h)}
    norms = {"attn_norm": h, "q_norm": cfg.q_lora_rank,
             "kv_norm": cfg.kv_lora_rank, "ffn_norm": h}
    zeros = {}
    if cfg.index_topk:
        mats.update(wiq=(cfg.q_lora_rank,
                         cfg.index_n_heads * cfg.index_head_dim),
                    wik=(h, cfg.index_head_dim), wiw=(h, cfg.index_n_heads))
        norms["ik_norm"] = cfg.index_head_dim
        zeros["ik_bias"] = cfg.index_head_dim
    if cfg.attention_gate_type:
        mats["wgate"] = (h, H)
    return mats, norms, zeros


def init_params(key: jax.Array, cfg: MoeMlaConfig) -> Dict[str, Any]:
    """Weights normal(0, 0.02) drawn in bfloat16 directly (the tree at
    published widths is 9.7 GB: it must never exist in float32), norm scales
    one, the router's bias zero. The tree:
      embed [V, h], head [h, V], final_norm [h]
      dense  {attention leaves, wg, wu, wd}                       layer 0
      moe    {the FULL expert layers' attention leaves stacked [Lf, ...],
              ffn_norm [Lm, h], router [Lm, h, E] f32,
              router_bias [Lm, E] f32, sg / su / sd stacked [Lm, ...],
              eg / eu / ed FLAT over layers [Lm * held, ...],
              swa {the SLIDING expert layers' attention leaves stacked
              [Ls, ...]}: only where there are sliding layers}    layers 1..
    Attention leaves (`attention_leaves`): W_qb and W_kvb are two matrices
    each, columns grouped by kind; a full layer under an indexer adds wiq,
    wik, wiw, ik_norm, ik_bias; a gated layer wgate."""
    h, V = cfg.hidden_size, cfg.vocab_held
    Lm, held, E = cfg.moe_layers, cfg.experts_held, cfg.n_routed_experts
    I, Im = cfg.intermediate_size, cfg.moe_intermediate_size
    counter = iter(range(1 << 30))

    def mat(shape, dtype=DTYPE):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, dtype) * INIT_STD).astype(dtype)

    def attn_leaves(lead, kind=FULL, ffn_norm=True):
        mats, norms, zeros = attention_leaves(of_kind(cfg, kind))
        out = {k: mat(lead + s) for k, s in mats.items()}
        out.update({k: jnp.ones(lead + (n,), jnp.float32)
                    for k, n in norms.items() if ffn_norm or k != "ffn_norm"})
        out.update({k: jnp.zeros(lead + (n,), jnp.float32)
                    for k, n in zeros.items()})
        return out

    kinds = cfg.kinds[cfg.first_k_dense_replace:]
    dense = dict(attn_leaves((), cfg.kinds[0]), wg=mat((h, I)),
                 wu=mat((h, I)), wd=mat((I, h)))
    mixed = SLIDING in kinds
    moe = dict(attn_leaves((kinds.count(FULL),), ffn_norm=not mixed),
               router=mat((Lm, h, E), ROUTER_DTYPE),
               router_bias=jnp.zeros((Lm, E), ROUTER_DTYPE),
               sg=mat((Lm, h, Im)), su=mat((Lm, h, Im)), sd=mat((Lm, Im, h)),
               eg=mat((Lm * held, h, Im)), eu=mat((Lm * held, h, Im)),
               ed=mat((Lm * held, Im, h)))
    if mixed:
        moe["ffn_norm"] = jnp.ones((Lm, h), jnp.float32)
        moe["swa"] = attn_leaves((kinds.count(SLIDING),), SLIDING,
                                 ffn_norm=False)
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
    if cfg.rope_scaling_type is None:
        return extra.astype(np.float32)         # plain RoPE
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
    scaled by mscale / mscale_all_dim of the YaRN settings (1 without)."""
    scale = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(cfg))[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


# ---------------- latent attention ----------------

def _scope(cfg: MoeMlaConfig, part: str) -> str:
    """A sliding layer's operations stand under scopes of their own."""
    return ("lm_swa_" if cfg.window else "lm_mla_") + part


def mla_inputs(x, w, cfg: MoeMlaConfig):
    """(u, c_q): the layer's normed input [T, hidden] and the query's
    latent [T, q rank] (normed, rescaled where the configuration says)."""
    eps, (a_q, _) = cfg.rms_norm_eps, cfg.lora_scales
    u = rms_norm(x, w["attn_norm"], eps, DTYPE)
    q_norm = w["q_norm"] if a_q == 1.0 else w["q_norm"] * a_q
    return u, rms_norm(_mm(u, w["wqa"], DTYPE), q_norm, eps, DTYPE)


def mla_project(x, w, cfg: MoeMlaConfig, cos, sin, inputs=None):
    """x [T, hidden] -> (q_nope [T, H*dn], q_rope [T, H*dr] rotated, latent
    [T, rank + dr] = [normed c_kv | rotated k_rope]: what the cache holds).
    `inputs`: `mla_inputs` of the same x, where the caller needs them too."""
    eps, H = cfg.rms_norm_eps, cfg.num_attention_heads
    with jax.named_scope(_scope(cfg, "proj")):
        u, c_q = inputs if inputs is not None else mla_inputs(x, w, cfg)
        q_nope = _mm(c_q, w["wqb_nope"], DTYPE).astype(DTYPE)
        q_rope = apply_rope(_mm(c_q, w["wqb_rope"], DTYPE)[None], cos, sin,
                            H)[0].astype(DTYPE)
        kv = _mm(u, w["wkva"], DTYPE)
        a_kv = cfg.lora_scales[1]
        kv_norm = w["kv_norm"] if a_kv == 1.0 else w["kv_norm"] * a_kv
        c_kv = rms_norm(kv[:, :cfg.kv_lora_rank], kv_norm, eps, DTYPE)
        k_rope = apply_rope(kv[None, :, cfg.kv_lora_rank:], cos, sin,
                            1)[0].astype(DTYPE)
        return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)


def mla_prefill(q_nope, q_rope, latent, w, cfg: MoeMlaConfig, q_offset,
                impl: str, first_valid=0):
    """Up-projected form: queries of a prompt chunk [Tq, ...] at positions
    `q_offset`.. against the sequence's latent rows [Tk, rank + dr] (the
    cached prefix and the chunk itself, position i in row i) -> [Tq, H*dv].
    A sliding layer (`cfg.window`) attends the window alone; its rows before
    `first_valid` stand before the sequence's start."""
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    with jax.named_scope(_scope(cfg, "prefill")):
        latent = latent.astype(DTYPE)
        c_kv, k_rope = latent[:, :r], latent[:, r:]
        k_nope = _mm(c_kv, w["wkvb_k"], DTYPE).astype(DTYPE)
        v = _mm(c_kv, w["wkvb_v"], DTYPE).astype(DTYPE)
        if cfg.window:
            # a head's two parts joined (192 + 64: whole lanes), the one
            # rotary key repeated a head
            Tq, Tk = q_nope.shape[0], k_nope.shape[0]
            q = jnp.concatenate([q_nope.reshape(Tq, H, -1),
                                 q_rope.reshape(Tq, H, -1)], axis=-1)
            k = jnp.concatenate([k_nope.reshape(Tk, H, -1), jnp.broadcast_to(
                k_rope[:, None, :], (Tk, H, k_rope.shape[-1]))], axis=-1)
            return attn_kernels.window_attention(
                q.reshape(Tq, -1), k.reshape(Tk, -1), v, H, q_offset,
                cfg.softmax_scale, cfg.window, first_valid, impl=impl)
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


def gathered_attend(q_nope, q_rope, rows, ids, valid, w, cfg: MoeMlaConfig,
                    scope: str):
    """Absorbed form over GATHERED rows: query r [R, ...] against the rows
    `ids[r]` [R, K] of `rows` [N, rank + dr, padded] where `valid[r]`; each
    gathered row is read once for all heads -> [R, H*dv]. What a full layer
    under the indexer runs in both forms (K = index_topk) and a sliding
    layer's decode rows (K = the window). A block of queries at a time, the
    absorbing products inside the block: no [R, H, rank] array is held."""
    H, r, dn = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    wk = w["wkvb_k"].reshape(r, H, dn)
    wv = w["wkvb_v"].reshape(r, H, cfg.v_head_dim)

    def block(q_nope, q_rope, ids, valid):
        B = q_nope.shape[0]
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope.reshape(B, H, dn), wk,
                           preferred_element_type=jnp.float32).astype(DTYPE)
        q = jnp.concatenate([q_lat, q_rope.reshape(B, H, -1)], axis=-1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, rows.shape[-1] - q.shape[-1])))
        o_lat = attn_kernels.gathered_latent_attention(
            q, rows, ids, valid, r, cfg.softmax_scale)
        o = jnp.einsum("bhc,chd->bhd", o_lat.astype(DTYPE), wv,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, H * cfg.v_head_dim).astype(DTYPE)

    with jax.named_scope(scope):
        return attn_kernels.in_blocks(block, q_nope, q_rope, ids, valid)


# ---------------- the indexer (learned sparse attention) ----------------

INDEX_NORM_EPS = 1e-6    # the LayerNorm on the index key


def dsa_project(u, c_q, w, cfg: MoeMlaConfig, cos, sin):
    """The indexer's side of a full layer: (q_I [T, n_I, d_I], k_I [T, d_I]:
    what the cache holds of it, w_I [T, n_I] float32 with the score's scale
    n_I^-0.5 d_I^-0.5 folded in). RoPE on the first rope dimensions."""
    nI, dI, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    T = u.shape[0]
    with jax.named_scope("lm_dsa_index"):
        q = _mm(c_q, w["wiq"], DTYPE).reshape(T, nI, dI)
        q_rot = apply_rope(q[..., :dr].reshape(1, T, nI * dr), cos, sin, nI)
        q = jnp.concatenate([q_rot.reshape(T, nI, dr), q[..., dr:]],
                            axis=-1).astype(DTYPE)
        k = _mm(u, w["wik"], DTYPE)
        mean = jnp.mean(k, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
        k = ((k - mean) * lax.rsqrt(var + INDEX_NORM_EPS) * w["ik_norm"]
             + w["ik_bias"])
        k = jnp.concatenate([apply_rope(k[None, :, :dr], cos, sin, 1)[0],
                             k[:, dr:]], axis=-1).astype(DTYPE)
        w_i = _mm(u, w["wiw"], DTYPE) * (nI ** -0.5 * dI ** -0.5)
        return q, k, w_i


def dsa_index(q_i, w_i, k_i, q_offset, impl: str):
    """I[r, s] [Tq, Tk] float32 of a chunk's rows at positions `q_offset`..
    against the index keys k_i [Tk, d_I] at positions 0..; a key after its
    query reads `attn_kernels.MASKED`."""
    with jax.named_scope("lm_dsa_index"):
        return attn_kernels.index_scores(q_i, w_i, k_i, q_offset, impl=impl)


def dsa_index_paged(q_i, w_i, index_rows, layer, tables, lengths,
                    page_size: int):
    """I[b, s] [B, P * page] float32 of one query a sequence against that
    sequence's pages of layer `layer` of `index_rows` [L, rows, d_I];
    position s of sequence b stands in column s; a column past `lengths[b]`
    reads MASKED."""
    B, P = tables.shape
    L, _, dI = index_rows.shape
    with jax.named_scope("lm_dsa_index"):
        paged = index_rows.reshape(L, -1, page_size, dI)

        def one(args):       # a sequence at a time: [n_I, P * page] scores
            q, w, table = args
            k = paged[layer, table].reshape(P * page_size, dI)
            s = jnp.dot(q, k.astype(q.dtype).T,
                        preferred_element_type=jnp.float32)
            return jnp.dot(w, jax.nn.relu(s),
                           precision=lax.Precision.HIGHEST)

        s = lax.map(one, (q_i, w_i, tables))
        seen = jnp.arange(P * page_size)[None, :] < lengths[:, None]
        return jnp.where(seen, s, attn_kernels.MASKED)


SELECT_ROWS = 32     # rows whose [rows, K, blocks] one-hot is held at once
_SELECT_BLOCK = 128  # columns a block of the compaction


def _threshold(key):
    """uint32 [R] that order as floats do (sign flipped, negatives
    complemented) -> the float32 they stand for."""
    bits = jnp.where(key >> 31 == 1, key & jnp.uint32(0x7FFFFFFF), ~key)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _kth_largest(scores, want):
    """The `want[r]`-th largest of scores [R, n] a row (want >= 1; no NaN):
    the largest float t with count(scores >= t) >= want, its ordered bit
    pattern built two bits a pass over the scores: 16 passes, each ONE read
    of them and nothing written (the scores are the loop's only operand; a
    pass's three counts are ONE reduction of three operands, where three
    sums were three passes: 43 ms a layer at [2048, 65536]; my chip run,
    PR 37)."""
    def two_bits(i, key):
        shift = (30 - 2 * i).astype(jnp.uint32)
        above = tuple(
            (scores >= _threshold(key | (jnp.uint32(v) << shift))[:, None]
             ).astype(jnp.int32) for v in (1, 2, 3))
        counts = lax.reduce(
            above, (jnp.int32(0),) * 3,
            lambda a, b: tuple(x + y for x, y in zip(a, b)), (1,))
        ok = sum((c >= want).astype(jnp.uint32) for c in counts)
        return key | (ok << shift)
    return _threshold(lax.fori_loop(
        0, 16, two_bits, jnp.zeros((scores.shape[0],), jnp.uint32)))


def _first_ties(scores, tau, need):
    """The bound c[r] with exactly `need[r]` of the columns that equal
    tau[r] at or before it (need >= 1, and there are at least that many):
    the smallest such column, by bisection."""
    R, n = scores.shape
    col = jnp.arange(n, dtype=jnp.int32)[None, :]

    def halve(_, lohi):
        lo, hi = lohi           # count(.. <= lo) < need <= count(.. <= hi)
        mid = (lo + hi) // 2
        enough = jnp.sum((scores == tau[:, None]) & (col <= mid[:, None]),
                         axis=1, dtype=jnp.int32) >= need
        return jnp.where(enough, lo, mid), jnp.where(enough, mid, hi)
    return lax.fori_loop(0, max(n - 1, 1).bit_length() + 1, halve,
                         (jnp.full((R,), -1, jnp.int32),
                          jnp.full((R,), n - 1, jnp.int32)))[1]


def _popcount8(x):
    x = x - ((x >> 1) & 0x55)
    x = (x & 0x33) + ((x >> 2) & 0x33)
    return (x + (x >> 4)) & 0x0F


def _compact(scores, tau, bound, K: int, pages=None, page_size: int = 0):
    """The columns a row selects (score above tau[r], or equal to it at or
    before column bound[r]; at most K a row), ascending, as positions
    [R, K] (the slots past a row's count hold anything, in or out of range);
    with `pages` [R, P] (the block table of each row's columns, whole blocks
    of 128 columns a page) also the cache's row of each position: a slot's
    page comes out of the same product as its block (a lookup of 4 M
    scalars in a table is 24 ms a layer on the chip; PR 37).
    No sort and no scatter: a slot finds its block of 128 columns by
    comparing against the blocks' running counts, fetches the block's 16
    bytes of mask through a one-hot product (the MXU gathers), and finds its
    bit in them by counting. SELECT_ROWS rows at a time."""
    R, n = scores.shape
    B = _SELECT_BLOCK
    pad = -n % B
    nb = (n + pad) // B
    slot = jnp.arange(K, dtype=jnp.int32)
    col = jnp.arange(n, dtype=jnp.int32)[None, :]
    halves = lambda a: [a >> 8, a & 0xFF]                         # noqa: E731

    def rows(scores, tau, bound, pages=None):
        r = scores.shape[0]
        mask = (scores > tau[:, None]) | ((scores == tau[:, None])
                                          & (col <= bound[:, None]))
        bits = jnp.pad(mask, ((0, 0), (0, pad))).reshape(
            r, nb, B // 8, 8).astype(jnp.int32)
        octets = jnp.sum(bits << jnp.arange(8), axis=-1)         # [r, nb, 16]
        count = jnp.sum(bits, axis=(-1, -2))                      # [r, nb]
        upto = jnp.cumsum(count, axis=1)
        before = upto - count
        block = jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32), (r, nb))
        # every entry < 256: exact in the MXU's bfloat16
        entries = halves(block) + halves(before)
        if pages is not None:
            entries += halves(pages[:, (np.arange(nb) * B) // page_size])
        table = jnp.concatenate(
            [octets] + [h[..., None] for h in entries],
            axis=-1).astype(jnp.bfloat16)                     # [r, nb, 20..]
        mine = ((before[:, None, :] <= slot[None, :, None])
                & (slot[None, :, None] < upto[:, None, :]))       # [r, K, nb]
        got = jnp.einsum("rkb,rbc->rkc", mine.astype(jnp.bfloat16), table,
                         preferred_element_type=jnp.float32
                         ).astype(jnp.int32)                      # [r, K, 20]
        octets, rest = got[..., :B // 8], got[..., B // 8:]
        block = (rest[..., 0] << 8) | rest[..., 1]
        j = slot[None, :] - ((rest[..., 2] << 8) | rest[..., 3])  # in block
        ones = _popcount8(octets)
        run = jnp.cumsum(ones, axis=-1)
        octet = jnp.sum(run <= j[..., None], axis=-1)             # which byte
        at = jnp.arange(B // 8)[None, None, :] == octet[..., None]
        j = j - jnp.sum(jnp.where(at, run - ones, 0), axis=-1)
        byte = jnp.sum(jnp.where(at, octets, 0), axis=-1)
        low = byte[..., None] & ((2 << jnp.arange(8)) - 1)        # bits <= i
        bit = jnp.sum(_popcount8(low) <= j[..., None], axis=-1)
        at = block * B + octet * 8 + bit
        if pages is None:
            return at
        page = (rest[..., 4] << 8) | rest[..., 5]
        return at, page * page_size + at % page_size

    if pages is None:
        return attn_kernels.in_blocks(rows, scores, tau, bound,
                                      block=SELECT_ROWS)
    return attn_kernels.in_blocks(rows, scores, tau, bound, pages,
                                  block=SELECT_ROWS)


def dsa_threshold(scores, seen, cfg: MoeMlaConfig):
    """What names S exactly: (tau [R], bound [R]): a row selects the columns
    whose score is above tau[r], and of those equal to it the ones at or
    before column bound[r]: min(index_topk, seen[r]) columns in all. `seen`
    [R]: the columns a row sees (the rest read MASKED).

    Not `lax.top_k`: at K = 2,048 the TPU's compiler sorts every row whole
    (a stable sort of [2048, 133k] with an index beside it: 412 ms and 4 GB
    of copies; my chip run, PR 37). Here the K-th largest score is found by
    bisection over a float's bits (`_kth_largest`), and ties at it are cut
    at a column (`_first_ties`, only where there are any)."""
    R, n = scores.shape
    with jax.named_scope("lm_dsa_select"):
        want = jnp.clip(seen, 1, min(cfg.index_topk, n)).astype(jnp.int32)
        tau = _kth_largest(scores, want)
        need = want - jnp.sum(scores > tau[:, None], axis=1,
                              dtype=jnp.int32)                      # >= 1
        ties = jnp.sum(scores == tau[:, None], axis=1, dtype=jnp.int32)
        bound = lax.cond(
            jnp.any(ties > need), lambda: _first_ties(scores, tau, need),
            lambda: jnp.full((R,), n - 1, jnp.int32))
        return tau, bound


def dsa_positions(scores, seen, tau, bound, cfg: MoeMlaConfig, pages=None,
                  page_size: int = 0):
    """S as (positions [R, K] ascending, valid [R, K]), K = min(index_topk,
    columns): the mask of `dsa_threshold` compacted without a sort
    (`_compact`); a row that sees fewer than K keys has the rest invalid.
    With `pages` [R, P] or [P] (the block table the columns stand in) also
    the cache's rows of the positions [R, K] (page 0's where invalid)."""
    R, n = scores.shape
    K = min(cfg.index_topk, n)
    with jax.named_scope("lm_dsa_select"):
        valid = jnp.arange(K)[None, :] < jnp.minimum(seen, K)[:, None]
        if pages is None:
            return jnp.where(valid, _compact(scores, tau, bound, K), 0), valid
        pages = jnp.broadcast_to(pages, (R, pages.shape[-1]))
        if page_size % _SELECT_BLOCK == 0:
            ids, rows = _compact(scores, tau, bound, K, pages, page_size)
        else:       # a page smaller than a block: look every position up
            ids = jnp.where(valid, _compact(scores, tau, bound, K), 0)
            rows = jnp.take_along_axis(pages, ids // page_size,
                                       axis=1) * page_size + ids % page_size
        return jnp.where(valid, ids, 0), valid, jnp.where(valid, rows, 0)


def dsa_mask(scores, tau, bound):
    """S as a mask [R, n] int8 (1: selected)."""
    with jax.named_scope("lm_dsa_select"):
        col = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
        return ((scores > tau[:, None]) | (
            (scores == tau[:, None]) & (col <= bound[:, None]))
                ).astype(jnp.int8)


def dsa_select(scores, seen, cfg: MoeMlaConfig):
    """S: (positions [R, K], valid [R, K]) of the min(index_topk, seen[r])
    columns of largest score a row, EXACTLY; between equal scores the lower
    position is taken. Positions ascend."""
    tau, bound = dsa_threshold(scores, seen, cfg)
    return dsa_positions(scores, seen, tau, bound, cfg)


DENSE_HEADS = 16   # heads whose up-projected keys and values are held at once


def masked_attend(q_nope, q_rope, latent, mask, w, cfg: MoeMlaConfig,
                  q_offset, impl: str):
    """A chunk's attention over its selection, DENSE: the up-projected form
    (`mla_prefill`) under the mask [Tq, Tk] of `dsa_mask`, DENSE_HEADS heads
    at a time (the whole context's keys and values of every head at once
    would be 65 KB a token). What a short context takes: no selected row is
    gathered, every (query, key) pair is computed and most are masked, at
    2 * (nope + rope + v) a head where the gathered form pays 2 * (2 rank +
    rope) a SELECTED pair and 20 ns a selected row (PERF.md section 6,
    PR 37) -> [Tq, H*dv]."""
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dv, G = cfg.qk_nope_head_dim, cfg.v_head_dim, DENSE_HEADS
    G = G if H % G == 0 else H
    Tq = q_nope.shape[0]
    with jax.named_scope("lm_dsa_prefill"):
        latent = latent.astype(DTYPE)
        c_kv, k_rope = latent[:, :r], latent[:, r:]
        by_group = lambda m, d: m.reshape(  # noqa: E731
            m.shape[0], H // G, G * d).transpose(1, 0, 2)
        q_rope = q_rope.reshape(Tq, H // G, G, -1).transpose(1, 2, 0, 3)

        def group(args):
            wk, wv, qn, qr = args
            k_nope = _mm(c_kv, wk, DTYPE).astype(DTYPE)
            v = _mm(c_kv, wv, DTYPE).astype(DTYPE)
            return attn_kernels.masked_prefix_attention(
                qn, qr, k_nope, k_rope, v, mask, G, q_offset,
                cfg.softmax_scale, impl=impl)

        o = lax.map(group, (by_group(w["wkvb_k"], dn), by_group(w["wkvb_v"],
                                                               dv),
                            by_group(q_nope, dn), q_rope))   # [H/G, Tq, G*dv]
        return o.transpose(1, 0, 2).reshape(Tq, H * dv)


def attention_gate(o, u, w, cfg: MoeMlaConfig):
    """Head h of o [T, H*dv] times sigmoid(u W_g)[h]."""
    if not cfg.attention_gate_type:
        return o
    T, H = o.shape[0], cfg.num_attention_heads
    with jax.named_scope("lm_attn_gate"):
        g = jax.nn.sigmoid(_mm(u, w["wgate"], DTYPE))
        return (o.reshape(T, H, -1).astype(jnp.float32)
                * g[:, :, None]).reshape(T, -1).astype(DTYPE)


def attention_out(x, o, w, cfg: MoeMlaConfig):
    """The residual stream after attention: x + o W_o."""
    with jax.named_scope(_scope(cfg, "proj")):
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


EXPERT_LEAVES = ("eg", "eu", "ed")
FFN_LEAVES = ("ffn_norm", "router", "router_bias", "sg", "su", "sd")


def moe_layer_weights(params, index: int, cfg: Optional[MoeMlaConfig] = None):
    """Expert layer `index`'s weights out of the stacks (not the flat
    experts): its FFN leaves, and the attention leaves of its kind."""
    moe = params["moe"]
    if "swa" not in moe:
        return {k: v[index] for k, v in moe.items() if k not in EXPERT_LEAVES}
    kinds = cfg.kinds[cfg.first_k_dense_replace:]
    nth = kinds[:index].count(kinds[index])
    attn = moe["swa"] if kinds[index] == SLIDING else {
        k: v for k, v in moe.items()
        if k not in EXPERT_LEAVES + FFN_LEAVES + ("swa",)}
    return dict({k: v[nth] for k, v in attn.items()},
                **{k: moe[k][index] for k in FFN_LEAVES})


def lane_pad(latent):
    """Latent rows padded to whole lanes, as the cache holds them."""
    width = latent.shape[-1]
    return jnp.pad(latent, ((0, 0), (0, -width % attn_kernels.LANES)))


def forward(params, tokens, cfg: MoeMlaConfig, impl: str = "xla"):
    """Logits [S, vocabulary slice] of ONE whole sequence at positions
    0..S-1, no cache: every layer over the sequence's own rows. What the
    tests hold against the reference; the engine's step (serve/lm_engine.py)
    is built from the same functions."""
    S = tokens.shape[0]
    tables = {kind: rope_tables(jnp.arange(S), of_kind(cfg, kind))
              for kind in set(cfg.kinds)}
    x = embed(params, tokens)

    def attend(x, w, kind):
        k, (cos, sin) = of_kind(cfg, kind), tables[kind]
        if not (k.index_topk or k.attention_gate_type):
            q_nope, q_rope, latent = mla_project(x, w, k, cos, sin)
            return attention_out(x, mla_prefill(q_nope, q_rope, latent, w, k,
                                                0, impl, first_valid=0), w, k)
        with jax.named_scope(_scope(k, "proj")):
            u, c_q = mla_inputs(x, w, k)
        q_nope, q_rope, latent = mla_project(x, w, k, cos, sin, (u, c_q))
        if k.index_topk:
            q_i, k_i, w_i = dsa_project(u, c_q, w, k, cos, sin)
            ids, valid = dsa_select(dsa_index(q_i, w_i, k_i, 0, impl),
                                    jnp.arange(S) + 1, k)
            o = gathered_attend(q_nope, q_rope, lane_pad(latent), ids, valid,
                                w, k, "lm_dsa_prefill")
        else:
            o = mla_prefill(q_nope, q_rope, latent, w, k, 0, impl,
                            first_valid=0)
        return attention_out(x, attention_gate(o, u, w, k), w, k)

    x = dense_mlp(attend(x, params["dense"], cfg.kinds[0]), params["dense"],
                  cfg)
    moe = params["moe"]
    for i in range(cfg.moe_layers):
        w = moe_layer_weights(params, i, cfg)
        x, _ = moe_mlp(attend(x, w, cfg.kinds[i + cfg.first_k_dense_replace]),
                       w, moe["eg"], moe["eu"], moe["ed"],
                       i * cfg.experts_held, cfg, impl)
    return head(params, x, cfg)[0]
