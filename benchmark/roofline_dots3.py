"""Operations and bytes of the serve step of a token model that selects its
keys and slides a window (models/moe_mla.py under `lm.index_*`, `lm.swa_*`;
configuration dots3_note_ep16_d9), from its shapes: the model FLOPs of a
step (for `mfu_hybrid.serve`) and the floors of the new scopes (for
`dsa_index_roofline.serve`, `dsa_attend_roofline.serve`,
`swa_roofline.serve`). The expert layer's floor is
benchmark/roofline_moe_mla.py's: the same code runs it.

They price what the ALGORITHM needs, whatever implements it:

  * model FLOPs of a step: every real token (padding is not work) through
    each layer's projections at ITS kind's widths (W_kvb once a token), the
    indexer's three projections and the gate's, the dense MLP, the router,
    the shared expert, 6 * h * I_moe a (token, expert) pair held here; the
    indexer's (query, key) pairs at 2 * d_I an index head (model work: the
    selection needs every score); attention over the SELECTED pairs on full
    layers and over the pairs INSIDE THE WINDOW on sliding ones, at
    2 * (nope + rope + v) a head (the absorbed form spends more to read
    less: not counted); the head on the rows that are sampled. Padding,
    recomputation and the selection's own passes over the scores are not
    counted.
  * indexer scope (`lm_dsa_index` + `lm_dsa_select`): 2 * d_I * n_I an
    indexed pair; it reads every sequence's index keys once a step and the
    rows' index queries; the score matrix need never leave the chip, so its
    bytes are not in the floor. Compute binds.
  * selected attention (`lm_dsa_prefill` + `lm_dsa_decode`): a selected
    pair in latent space, 2 * ((rank + rope) + rank) a head, plus the two
    absorbing products a token; a selected row is read once for all heads:
    (rank + rope) * 2 bytes a selected pair. At 241 FLOP a gathered byte
    the two sides of the floor meet.
  * window attention (`lm_swa_prefill` + `lm_swa_decode`): a pair inside the
    window at 2 * (nope + rope + v) a head and W_kvb over the chunk's own
    tokens; a sequence's window rows read once.

A floor is the larger of operations over the bfloat16 peak and bytes over
the HBM bandwidth (benchmark/peaks.json). `shapes` is the driver's
`{"kind": "lm_serve", "attention": "selected+window", ...}`; a `step` is the
fields of one `serve.lm.step` span: tokens, decode, prefill, prefill_start,
decode_context, expert_pairs, experts_touched, sampled_rows, index_pairs,
selected_pairs, window_pairs, dense_rows.
"""

from __future__ import annotations

BF16 = 2


def is_cell(shapes) -> bool:
    return (shapes.get("kind") == "lm_serve"
            and shapes.get("attention") == "selected+window")


def _widths(w):
    return (w["heads"], w["qk_nope_head_dim"], w["qk_rope_head_dim"],
            w["v_head_dim"], w["kv_lora_rank"], w["q_lora_rank"])


def projection_flops_per_token(s, w) -> float:
    """One attention sub-layer's matmuls against weights at the widths `w`
    (the shapes themselves for a full layer, `shapes["swa"]` for a sliding
    one), its gate among them."""
    H, dn, dr, dv, r, qr = _widths(w)
    h = s["hidden"]
    return 2.0 * (h * qr + qr * H * (dn + dr) + h * (r + dr)
                  + r * H * (dn + dv) + H * dv * h
                  + (h * H if w.get("gate") else 0))


def indexer_flops_per_token(s) -> float:
    return 2.0 * (s["q_lora_rank"] * s["index_n_heads"] * s["index_head_dim"]
                  + s["hidden"] * (s["index_head_dim"] + s["index_n_heads"]))


def step_model_flops(s, step) -> float:
    h, L, Lm = s["hidden"], s["layers"], s["moe_layers"]
    Lf, Ls, swa = s["full_layers"], s["sliding_layers"], s["swa"]
    H, dn, dr, dv, _, _ = _widths(s)
    Hs, dns, drs, dvs, _, _ = _widths(swa)
    per_token = (Lf * (projection_flops_per_token(s, s)
                       + indexer_flops_per_token(s))
                 + Ls * projection_flops_per_token(s, swa)
                 + (L - Lm) * 6.0 * h * s["dense_intermediate"]
                 + Lm * (2.0 * h * s["n_routed_experts"]
                         + 6.0 * h * s["moe_intermediate"]))
    return (step["tokens"] * per_token
            + step["expert_pairs"] * 6.0 * h * s["moe_intermediate"]
            + Lf * step["index_pairs"] * s["index_n_heads"] * 2.0
            * s["index_head_dim"]
            + Lf * step["selected_pairs"] * H * 2.0 * (dn + dr + dv)
            + Ls * step["window_pairs"] * Hs * 2.0 * (dns + drs + dvs)
            + step["sampled_rows"] * 2.0 * h * s["vocab"])


def _floor(ops, nbytes, peaks) -> float:
    return max(ops / (peaks["peak_tflops_bf16"] * 1e12),
               nbytes / (peaks["hbm_gbps"] * 1e9))


def _keys_read(step) -> float:
    """Cached positions the step's sequences span: a chunk's prefix and
    itself, each decode row's context."""
    chunk = step["prefill_start"] + step["prefill"] if step["prefill"] else 0
    return chunk + step["decode_context"]


def dsa_index_floor_s(s, step, peaks) -> float:
    nI, dI = s["index_n_heads"], s["index_head_dim"]
    ops = step["index_pairs"] * nI * 2.0 * dI
    nbytes = BF16 * (_keys_read(step) * dI + step["tokens"] * nI * dI)
    return s["full_layers"] * _floor(ops, nbytes, peaks)


def dsa_attend_floor_s(s, step, peaks) -> float:
    H, dn, dr, dv, r, _ = _widths(s)
    pairs = step["selected_pairs"]
    ops = (pairs * H * 2.0 * ((r + dr) + r)
           + step["tokens"] * H * 2.0 * (dn * r + r * dv))
    nbytes = BF16 * (pairs * (r + dr)
                     + step["tokens"] * H * ((r + dr) + r))
    return s["full_layers"] * _floor(ops, nbytes, peaks)


def swa_floor_s(s, step, peaks) -> float:
    swa = s["swa"]
    H, dn, dr, dv, r, _ = _widths(swa)
    pairs, n = step["window_pairs"], step["prefill"]
    ops = pairs * H * 2.0 * (dn + dr + dv) + n * 2.0 * r * H * (dn + dv)
    chunk = min(step["prefill_start"], swa["window"] - 1) + n if n else 0
    rows = chunk + min(step["decode_context"],
                       step["decode"] * swa["window"])
    nbytes = BF16 * (rows * (r + dr) + step["tokens"] * H * (dn + dr + dv))
    return s["sliding_layers"] * _floor(ops, nbytes, peaks)


def scope_share(obs, layers, floor_fn):
    """Percent: the floors of the traced steps (summed, scaled to the
    executions the trace holds whole) over the device time of the scopes
    `layers`. None, and never a raise, where the cell, the trace, the
    spans' fields or the scopes are not there."""
    from benchmark import lm_serve_spans
    if not is_cell(obs.get("shapes", {})):
        return None
    found = lm_serve_spans.device_by_layer(obs)
    steps = lm_serve_spans.traced_steps(obs)
    if not found or not steps or "selected_pairs" not in steps[0]:
        return None
    seconds = sum(found["layers"].get(name, 0.0) for name in layers)
    if not seconds:
        return None
    floor = sum(floor_fn(obs["shapes"], s, obs["peaks"]) for s in steps)
    return 100.0 * floor * found["executions"] / len(steps) / seconds
