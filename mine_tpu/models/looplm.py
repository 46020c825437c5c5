"""A looped language model (the Ouro family): one stack of L transformer
layers whose weights serve `total_ut_steps` passes, with a final norm and an
exit gate after every pass.

With x = E[tokens], for pass t = 1..T and layer l = 1..L:

    a = x + N2_l(Attn_l(N1_l(x)))      Attn: q, k, v = x Wq, x Wk, x Wv split
    x = a + N4_l(MLP_l(N3_l(a)))             into heads, RoPE on q and k,
                                              softmax(q k^T / sqrt(D) + causal) v,
                                              heads joined, times Wo; no biases
    MLP(u) = (silu(u Wg) * (u Wu)) Wd
    after layer L:  h_t = N_f(x);  x <- h_t;  g_t = h_t . w_g + b_g

and `per_pass(h_t, g_t)` (the head and the loss terms, train/lm_loss.py) is
called inside the pass, so that no pass's hidden states outlive it.

The loop is a `lax.scan` over passes around a `lax.scan` over the stacked
layer axis. The weights are closed over by the outer scan, so the gradient of
a weight is the sum over the passes' applications of it. Every layer
application is rematerialised from its input (`jax.checkpoint`): what is kept
for the backward is one [B, S, hidden] activation an application.

Precision, as `training.dtype: bfloat16` means for the MINE stacks:
parameters float32; matmuls and attention take `dtype` operands and accumulate
in float32; the residual stream is `dtype`; norms, softmax, RoPE and the gate
are computed in float32.

Named scopes `lm_embed`, `lm_attention`, `lm_mlp` (and `lm_head_loss`, opened
by the loss) name the step's device operations by layer
(telemetry/programs.py).

Pure functions over a parameter tree; no module state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

from mine_tpu.kernels import on_tpu_backend
from mine_tpu.kernels.attention import flash_attention, plain_attention

INIT_STD = 0.02   # normal(0, 0.02) on every matrix, the family's initializer


@dataclasses.dataclass(frozen=True)
class LoopLMConfig:
    """The `lm.*` keys, which mirror the source `config.json` key for key."""
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    num_hidden_layers: int
    total_ut_steps: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False


def looplm_config_from_dict(config: Dict[str, Any]) -> LoopLMConfig:
    cfg = LoopLMConfig(
        hidden_size=int(config["lm.hidden_size"]),
        num_attention_heads=int(config["lm.num_attention_heads"]),
        num_key_value_heads=int(config["lm.num_key_value_heads"]),
        head_dim=int(config["lm.head_dim"]),
        intermediate_size=int(config["lm.intermediate_size"]),
        num_hidden_layers=int(config["lm.num_hidden_layers"]),
        total_ut_steps=int(config["lm.total_ut_steps"]),
        vocab_size=int(config["lm.vocab_size"]),
        rms_norm_eps=float(config["lm.rms_norm_eps"]),
        rope_theta=float(config["lm.rope_theta"]),
        hidden_act=str(config["lm.hidden_act"]),
        tie_word_embeddings=bool(config["lm.tie_word_embeddings"]))
    # what this model code does not implement fails at construction, not as
    # a silently different model
    if cfg.num_key_value_heads != cfg.num_attention_heads:
        raise ValueError("grouped key-value heads are not implemented: "
                         "lm.num_key_value_heads must equal "
                         "lm.num_attention_heads")
    if cfg.num_attention_heads * cfg.head_dim != cfg.hidden_size:
        raise ValueError("lm.num_attention_heads * lm.head_dim must equal "
                         "lm.hidden_size")
    if cfg.hidden_act != "silu" or cfg.tie_word_embeddings:
        raise ValueError("lm.hidden_act must be silu and the head untied")
    return cfg


def init_params(key: jax.Array, cfg: LoopLMConfig) -> Dict[str, Any]:
    """{"lm": ...}: one top-level group, so one learning rate (`lr.lm_lr`)."""
    H, I, L, V = (cfg.hidden_size, cfg.intermediate_size,
                  cfg.num_hidden_layers, cfg.vocab_size)
    names = (("embed", (V, H)), ("head", (H, V)), ("wq", (L, H, H)),
             ("wk", (L, H, H)), ("wv", (L, H, H)), ("wo", (L, H, H)),
             ("wg", (L, H, I)), ("wu", (L, H, I)), ("wd", (L, I, H)))
    keys = jax.random.split(key, len(names))
    mats = {name: INIT_STD * jax.random.normal(k, shape, jnp.float32)
            for (name, shape), k in zip(names, keys)}
    layers = {k: mats[k] for k in ("wq", "wk", "wv", "wo", "wg", "wu", "wd")}
    for n in ("norm1", "norm2", "norm3", "norm4"):   # the sandwich
        layers[n] = jnp.ones((L, H), jnp.float32)
    return {"lm": {
        "embed": mats["embed"], "layers": layers,
        "final_norm": jnp.ones((H,), jnp.float32),
        # one linear map to a scalar: hidden + 1 parameters
        "exit_gate": {"w": INIT_STD * jax.random.normal(
            jax.random.fold_in(key, len(names)), (H,), jnp.float32),
                      "b": jnp.zeros((), jnp.float32)},
        "head": mats["head"]}}


def rms_norm(x, scale, eps, dtype):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(dtype)


def rope_tables(seq_len: int, head_dim: int, theta: float):
    """cos, sin [S, D] float32 for positions 0..S-1 (rotate-half)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, heads: int):
    """[B, S, H*D] -> the same, each head rotated (float32 inside)."""
    B, S, HD = x.shape
    d = HD // heads
    x32 = x.astype(jnp.float32).reshape(B, S, heads, d)
    half = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], axis=-1)
    out = x32 * cos[None, :, None, :] + half * sin[None, :, None, :]
    return out.reshape(B, S, HD).astype(x.dtype)


def _mm(x, w, dtype):
    return jnp.dot(x, w.astype(dtype), preferred_element_type=jnp.float32)


def layer(x, w, cos, sin, cfg: LoopLMConfig, dtype, attention: Callable):
    """One application of one layer: `w` is that layer's slice of the stack."""
    heads, eps = cfg.num_attention_heads, cfg.rms_norm_eps
    with jax.named_scope("lm_attention"):
        u = rms_norm(x, w["norm1"], eps, dtype)
        q = apply_rope(_mm(u, w["wq"], dtype).astype(dtype), cos, sin, heads)
        k = apply_rope(_mm(u, w["wk"], dtype).astype(dtype), cos, sin, heads)
        v = _mm(u, w["wv"], dtype).astype(dtype)
        o = _mm(attention(q, k, v, heads), w["wo"], dtype)
        a = (x.astype(jnp.float32)
             + rms_norm(o, w["norm2"], eps, jnp.float32)).astype(dtype)
    with jax.named_scope("lm_mlp"):
        u = rms_norm(a, w["norm3"], eps, dtype)
        act = (jax.nn.silu(_mm(u, w["wg"], dtype))
               * _mm(u, w["wu"], dtype)).astype(dtype)
        m = _mm(act, w["wd"], dtype)
        return (a.astype(jnp.float32)
                + rms_norm(m, w["norm4"], eps, jnp.float32)).astype(dtype)


def default_attention() -> Callable:
    """The Pallas kernel on the TPU; off it the same function in XLA."""
    return flash_attention if on_tpu_backend() else plain_attention


def embed(lm, tokens, dtype):
    with jax.named_scope("lm_embed"):
        return jnp.take(lm["embed"], tokens, axis=0).astype(dtype)


def make_pass(lm, seq_len: int, cfg: LoopLMConfig, dtype, per_pass: Callable,
              attention: Callable = None) -> Callable:
    """One pass over the stack as a scan body: x [B, S, hidden] ->
    (h_t, per_pass(h_t, g_t))."""
    attention = attention or default_attention()
    cos, sin = rope_tables(seq_len, cfg.head_dim, cfg.rope_theta)
    apply_layer = jax.checkpoint(
        lambda x, w: layer(x, w, cos, sin, cfg, dtype, attention))

    def one_pass(x, _):
        x, _ = lax.scan(lambda x, w: (apply_layer(x, w), None), x,
                        lm["layers"])
        with jax.named_scope("lm_head_loss"):
            h = rms_norm(x, lm["final_norm"], cfg.rms_norm_eps, dtype)
            gate = jnp.sum(h.astype(jnp.float32) * lm["exit_gate"]["w"],
                           axis=-1) + lm["exit_gate"]["b"]
        return h, per_pass(h, gate)

    return one_pass


def run_loop(lm, tokens, cfg: LoopLMConfig, dtype, per_pass: Callable,
             attention: Callable = None):
    """All passes over `tokens` [B, S]. `per_pass(h_t, g_t)` -> a pytree of
    what the caller keeps of pass t (h_t [B, S, hidden] in `dtype`, g_t
    [B, S] float32); returns those stacked on a leading pass axis."""
    one_pass = make_pass(lm, tokens.shape[1], cfg, dtype, per_pass, attention)
    _, outs = lax.scan(one_pass, embed(lm, tokens, dtype), None,
                       length=cfg.total_ut_steps)
    return outs
