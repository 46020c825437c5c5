"""Operations and bytes of the token model's serve step (models/moe_mla.py),
from its shapes: the model FLOPs of a step (for `mfu.serve`) and the floors
of its three kernels' scopes (for `mla_prefill_roofline.serve`,
`mla_decode_roofline.serve`, `moe_experts_roofline.serve`).

They price what the ALGORITHM needs, whatever implements it:

  * model FLOPs of a step: every real token (padding is not work) through the
    projections of 7 layers (W_kvb counted once a token, as a forward pass
    over the sequence would; a chunk that up-projects its whole cached
    prefix again is recomputing), the dense MLP, the router, the shared
    expert, 6 * h * I_moe a (token, expert) pair held here, attention at
    2 * (nope + rope + v) a head and (query, key) pair in both forms (the
    absorbed form spends more to read less: not counted), the head on the
    rows that are sampled.
  * prefill scope: the chunk's (query, key) pairs at 2 * (192 + 128) a head,
    and W_kvb over the chunk's OWN tokens; it reads the sequence's latent
    rows, the queries, and writes the output, once.
  * decode scope: each sequence's latent rows are read ONCE for all heads
    (what the absorbed form buys); its operations are the absorbed form's,
    2 * ((rank + rope) + rank) a head and pair, plus the two absorbing
    products a token.
  * expert scope: 6 * h * I_moe a held pair; each expert a step touches has
    its three matrices read once; a pair's row is read and written once.

A floor is the larger of operations over the bfloat16 peak and bytes over
the HBM bandwidth (benchmark/peaks.json). `shapes` is the driver's
`{"kind": "lm_serve", ...}`; a `step` is the fields of one `serve.lm.step`
span: tokens, decode, prefill, prefill_start, decode_context, expert_pairs,
experts_touched, sampled_rows.
"""

from __future__ import annotations

BF16 = 2


def _attention_widths(s):
    return (s["heads"], s["qk_nope_head_dim"], s["qk_rope_head_dim"],
            s["v_head_dim"], s["kv_lora_rank"])


def projection_flops_per_token(s) -> float:
    """The attention sub-layer's matmuls against weights, one layer."""
    H, dn, dr, dv, r = _attention_widths(s)
    h, qr = s["hidden"], s["q_lora_rank"]
    return 2.0 * (h * qr + qr * H * (dn + dr) + h * (r + dr)
                  + r * H * (dn + dv) + H * dv * h)


def prefill_pairs(step) -> float:
    n, start = step["prefill"], step["prefill_start"]
    return n * start + n * (n + 1) / 2.0


def step_model_flops(s, step) -> float:
    H, dn, dr, dv, _ = _attention_widths(s)
    h, L, Lm = s["hidden"], s["layers"], s["moe_layers"]
    tokens = step["tokens"]
    per_token = (L * projection_flops_per_token(s)
                 + (L - Lm) * 6.0 * h * s["dense_intermediate"]
                 + Lm * (2.0 * h * s["n_routed_experts"]
                         + 6.0 * h * s["moe_intermediate"]))
    pairs = prefill_pairs(step) + step["decode_context"]
    return (tokens * per_token
            + step["expert_pairs"] * 6.0 * h * s["moe_intermediate"]
            + L * pairs * H * 2.0 * (dn + dr + dv)
            + step["sampled_rows"] * 2.0 * h * s["vocab"])


def _floor(ops, nbytes, peaks) -> float:
    return max(ops / (peaks["peak_tflops_bf16"] * 1e12),
               nbytes / (peaks["hbm_gbps"] * 1e9))


def mla_prefill_floor_s(s, step, peaks) -> float:
    H, dn, dr, dv, r = _attention_widths(s)
    n = step["prefill"]
    if not n:
        return 0.0
    ops = (prefill_pairs(step) * H * 2.0 * (dn + dr + dv)
           + n * 2.0 * r * H * (dn + dv))
    nbytes = BF16 * ((step["prefill_start"] + n) * (r + dr)
                     + n * H * (dn + dr) + n * H * dv)
    return s["layers"] * _floor(ops, nbytes, peaks)


def mla_decode_floor_s(s, step, peaks) -> float:
    H, dn, dr, dv, r = _attention_widths(s)
    d, pairs = step["decode"], step["decode_context"]
    if not d:
        return 0.0
    ops = pairs * H * 2.0 * ((r + dr) + r) + d * H * 2.0 * (dn * r + r * dv)
    nbytes = BF16 * (pairs * (r + dr) + d * H * ((r + dr) + r))
    return s["layers"] * _floor(ops, nbytes, peaks)


def moe_experts_floor_s(s, step, peaks) -> float:
    """`expert_pairs` and `experts_touched` are sums over the expert layers,
    so the floor is too."""
    h, i = s["hidden"], s["moe_intermediate"]
    pairs = step["expert_pairs"]
    if not pairs:
        return 0.0
    ops = pairs * 6.0 * h * i
    nbytes = BF16 * (step["experts_touched"] * 3 * h * i + pairs * 2 * h)
    return _floor(ops, nbytes, peaks)
