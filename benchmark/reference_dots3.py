"""Plain reference of dots3-note-prev's language model (`model_type:
dots3_note`): latent attention (MLA) in two kinds of layer, full layers under
a DeepSeek-V3.2-style lightning indexer (learned sparse attention) and
sliding-window layers with widths of their own, a headwise sigmoid gate on
every attention output, one leading dense SwiGLU layer, then layers of
sigmoid-routed experts beside a shared expert. Straightforward `jax.numpy`,
float32, `jax.default_matmul_precision("highest")`; no cache, no kernels, no
batching over requests; imports nothing of the program.

One sequence at a time: `tokens` [S] at positions 0..S-1. With x the
residual stream [S, hidden], N an RMSNorm (eps `rms_norm_eps`) and
`layer_types[l]` the kind of layer l:

    x <- x + Attn_kind(N(x));  x <- x + FFN(N(x));  logits = N_f(x) W_head

  full     (`num_attention_heads` H, nope / rope / v, `q_lora_rank`,
           `kv_lora_rank`, `rope_theta`, no rope scaling, softmax scale
           (nope + rope)^-0.5)
           u = N(x); c_q = a_q N(u W_qa); q = c_q W_qb -> H x (nope | rope)
           u W_kva -> (c_kv | k_rope); c_kv = a_kv N(c_kv); one k_rope for
           all heads; RoPE(q_rope), RoPE(k_rope)
           indexer: q_I = c_q W_Iq -> [n_I, d_I]; k_I = LayerNorm(u W_Ik)
           -> [d_I]; RoPE on the first rope dimensions of both; w = u W_Iw;
           I[t, s] = n_I^-0.5 d_I^-0.5 sum_j w[t, j] relu(q_I[t, j] . k_I[s])
           for s <= t; S_t = the `index_topk` positions of largest I[t, .]
           (all of them while t + 1 <= index_topk)
           o[t] = softmax over s in S_t of (q_nope.k_nope + q_rope.k_rope)
           * scale, times v; k_nope | v = c_kv W_kvb
           g = sigmoid(u W_g) -> [H]; head h times g_h; heads joined, W_o
  sliding  the same with the `swa_*` widths and `swa_rope_theta`; token t
           attends s in [t - (sliding_window_size - 1), t]; no indexer
  rescale  (`apply_mla_qkv_lora_rescale`) a_q = (hidden / q rank)^0.5,
           a_kv = (hidden / kv rank)^0.5, each kind's own ranks; else 1
  FFN 0    (silu(u W_g) * (u W_u)) W_d
  FFN l    sigma = sigmoid(u W_r) over ALL routed experts; the choice is the
           top k of sigma + b; the weights sigma_e / sum_chosen(sigma) *
           scale; y = sum over the chosen experts HELD HERE of w_e E_e(u) +
           Shared(u)

The weights arrive in the program's parameter tree (`layer_weights` names
the leaves) in whatever dtype they are stored; every function upcasts what it
is handed to float32, so a caller may hand over one layer at a time.

Departures from the published description, each for a reason:
  * Only the experts `held = (offset, count)` are applied (the chip's share
    of an expert-parallel deployment; `held = (0, n_routed_experts)` is the
    uncut layer); the router still scores and chooses over all. An expert is
    applied to the rows that chose it, gathered, and added back.
  * The vocabulary is the slice the embedding and the head are handed.
  * W_qb and W_kvb are stored as two matrices each (`wqb_nope` / `wqb_rope`,
    `wkvb_k` / `wkvb_v`): the same columns grouped by kind.
  * RoPE pairs dimension i with i + d/2 (halves), as the configuration's
    `assumed` states.
  * A full layer's softmax over S_t is taken in latent space, over the
    GATHERED rows [c_kv | k_rope] of S_t: q_nope . (c_kv W_kvb^K) = (q_nope
    W_kvb^K^T) . c_kv, the same number in another order of products, so that
    a selected row is gathered once for all heads and a request of 40,960
    tokens takes seconds. tests/test_dots3.py holds it against
    `reference_moe_mla.attention`, which up-projects, where S_t is every key.
  * The program's own S_t and choice of experts are TAKEN for the rows it
    returned, wherever they are sets of the right size of distinct valid
    members (`check_selected`, `choose`): with weights from a seed the
    rescaled latents give attention logits of standard deviation ~2, and a
    bfloat16 residual stream is 5-20% from the float32 one by layer 5
    (PERF.md section 6, PR 37: the same program in float32 is 4e-7 from this
    reference), so a selection is no longer a top-k of THIS forward's
    scores. That the selection is exact is held where it can be: the
    driver checks S_t against the scores the program itself returned, and
    those scores against `same_index` on the program's own inputs. How far
    the program's sets stand from this forward's own is its drift
    (`index_margin`, `router_drift`): at the FIRST full layer, whose input
    is the embedding's rows and so exact, S_t is held to within
    `index.drift_first` of this forward's own index_topk-th score, and the
    indexer's q_I and w to this forward's own (`index.q_first`,
    `index.w_first`): a wrong W_Iq or W_Iw or a q_I without RoPE fails
    there; at the deeper layers the drift is reported and held to no limit.
  * Blocked over query rows and heads: nothing larger than [S, hidden],
    [QUERY_BLOCK, S] or [GATHER_BLOCK, index_topk, latent width] is ever
    held (a head's attention output goes through its rows of W_o at once).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
ROUTER_TIE_TOL = 1e-2    # as reference_moe_mla: bfloat16 operands on a logit
INDEX_NORM_EPS = 1e-6
QUERY_BLOCK = 1024       # query rows whose [rows, S] scores are held at once
GATHER_BLOCK = 128       # query rows whose gathered rows are held at once
EXPERT_PAD = 2048        # an expert's rows are padded to a multiple of this
_MASKED = -1e30

# ---- tolerances of the serving check, each with its reason ---------------
# Relative L2 error of a vector (the reference in the denominator) unless
# said otherwise. The program computes with bfloat16 weights and matmul
# operands, float32 accumulation, a bfloat16 residual stream and bfloat16
# caches; the reference is float32 throughout. Each limit lies between two
# readings taken on the chip at the published widths (PERF.md section 6,
# PR 37): the largest the program read over its seeds, and what the planted
# fault named beside it reads (tools/lm_serve_fault_readings.py).
TOLERANCES = {
    # logits over the vocabulary slice at the prompt's last position and at
    # each of the first decode steps, through 9 layers and three kinds of
    # cache. With weights from a seed this model's attention logits have a
    # standard deviation of ~2 (the rescaled latents), so every layer
    # multiplies the bfloat16 residual stream's distance from the float32
    # one: the program reads 0.035-0.066 and 0.041-0.074 here (16 requests
    # of 13k-36k tokens) where kimi_k2.5_ep32_d7 reads 0.013; the same
    # program in float32 is 4e-7 from this reference (tests/test_dots3.py;
    # PERF.md section 6, PR 37). They hold the path as a whole: a chunk's
    # window of 256 reads 0.22 at the prompt's last position, 1,024 selected
    # keys 0.73 / 0.86, no gate 0.95 / 1.0, no rescale 1.2 / 1.3; each
    # precision is held by the limit of its own block below.
    "logits.prefill_last": 0.12,
    "logits.decode": 0.2,
    # one block alone, the reference's float32 on the program's own input:
    # what is left is accumulation order, so a result or an operand rounded
    # to a lower precision shows whole
    "same.head": 1e-4,       # program 0
    "same.router": 1e-4,     # program 2.1e-8
    # the indexer's scores alone: the reference's float32 sum over the index
    # heads of the program's own q_I, w and the index keys read from the
    # cache, over the document's positions: program 1.2e-7; scores rounded
    # to bfloat16 1.66e-3 (and nothing else moves under that fault)
    "same.index": 1e-4,
    # rows the caches hold against the reference's float32 rows of its own
    # forward (the median over the document's rows of a row's relative
    # error). The FIRST full layer's latent rows and index keys: one
    # rounding to the cache's dtype: program 0.00232-0.00236; float8_e4m3
    # rows 0.0264 / 0.0265; no rescale 0.676 on the latent rows.
    "cache.latent_first": 8e-3,
    "cache.index_first": 8e-3,
    # The LAST full layer's and the last sliding layer's: the layers' drift
    # before them too: program 0.045-0.059 / 0.045-0.058 / 0.072-0.091;
    # float8 rows 0.151 / 0.149 / 0.212, a window of 256 0.162 / 0.161 /
    # 0.222, 1,024 keys 0.75 / 0.74 / 0.79. (Nearer the faults' readings
    # than the program's: a fresh seed must not fail, and each of these
    # faults fails a limit of its own besides.)
    "cache.latent_last": 0.12,
    "cache.index_last": 0.12,
    "cache.window_last": 0.17,
    # each layer's attention output (o W_o of the returned rows) against the
    # reference's, on the program's own S_t, the worst layer of a kind:
    # program 0.063-0.194 and 0.121-0.153 (drift again); a window of 256
    # 0.344 on the sliding layers, no gate 2.3 / 2.9, no rescale 0.97 / 1.0,
    # 1,024 keys 1.05 / 1.04
    "attn.full": 0.45,
    "attn.sliding": 0.27,
    # S_t of the returned rows against the scores the program returned with
    # it (which `same.index` holds): how far the lowest selected key stands
    # below the highest key left out, in units of the row's spread: an exact
    # top-k reads 0 or less (program 0.0); and rows whose S_t is no set of
    # min(index_topk, t + 1) distinct visible positions (program 0; 1,024
    # selected keys: all 51 of a request's)
    "index.margin": 1e-6,
    "index.bad_rows": 0.5,
    # the FIRST full layer (layer 0: its input is the embedding's rows, the
    # same on both sides) against THIS forward's own indexer, which the
    # limits above do not reach (they hold the program's scores to its own
    # q_I and w). The returned rows' q_I (after RoPE) and w (scaled): two
    # products, a norm and a rounding to bfloat16 from an exact input:
    # program 0.00284-0.00289 and 0.00159-0.00170 (ten requests of 12.7k-
    # 29.7k tokens); a q_I without RoPE 0.76-0.80, negated weights 2.0.
    "index.q_first": 1e-2,
    "index.w_first": 1e-2,
    # and S_t's `selection_margin` against this forward's own scores: how
    # far the lowest selected key stands below the highest left out, in
    # units of the row's spread, the worst returned row: program 0.0197-
    # 0.0283 (bfloat16 index queries and keys against float32 ones move the
    # scores by a few thousandths, and the 2,048th of 12k-30k scores has
    # neighbours that close); a q_I without RoPE 5.9-6.3, negated weights
    # 8.4-9.3. (Set at 0.05 before the first reading; 0.1 leaves a fresh
    # seed some four times the program's largest.) The deeper layers read
    # 0.89-2.14 by the residual stream's drift and are reported, not
    # limited: `drift` in the driver's result.
    "index.drift_first": 0.1,
    # the program's choices of experts that are no top-k of the scores it
    # returned with them (which `same.router` holds): none allowed
    "router.bad_choices": 0.5,
}


def config_from_flat(config: dict) -> dict:
    """`config_from` of a flat key space that holds the source's keys as
    `lm.<key>`."""
    return config_from({k[3:]: v for k, v in config.items()
                        if k.startswith("lm.")})


def config_from(lm: dict) -> dict:
    """The numbers the equations need, from the source config.json's keys
    (a plain dict: the configuration file's top level, or `lm.*` stripped)."""
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "layer_types", "index_n_heads",
            "index_head_dim", "index_topk", "attention_gate_type",
            "apply_mla_qkv_lora_rescale", "sliding_window_size",
            "swa_num_attention_heads", "swa_q_lora_rank", "swa_kv_lora_rank",
            "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
            "swa_rope_theta", "swa_attention_gate_type", "n_routed_experts",
            "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
            "rms_norm_eps", "first_k_dense_replace", "num_hidden_layers")
    cfg = {k: lm[k] for k in keys}
    cfg["layer_types"] = tuple(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    return cfg


def kind_widths(cfg: dict, kind: str) -> dict:
    """The widths one kind of layer runs at."""
    p = "swa_" if kind == SLIDING else ""
    out = {"heads": cfg[p + "num_attention_heads"],
           "q_rank": cfg[p + "q_lora_rank"],
           "kv_rank": cfg[p + "kv_lora_rank"],
           "nope": cfg[p + "qk_nope_head_dim"],
           "rope": cfg[p + "qk_rope_head_dim"], "v": cfg[p + "v_head_dim"],
           "theta": float(cfg[p + "rope_theta"]),
           "gate": cfg[p + "attention_gate_type"],
           "window": cfg["sliding_window_size"] if kind == SLIDING else 0,
           "topk": 0 if kind == SLIDING else int(cfg["index_topk"] or 0)}
    rescale = cfg["apply_mla_qkv_lora_rescale"]
    out["a_q"] = (cfg["hidden_size"] / out["q_rank"]) ** 0.5 if rescale else 1
    out["a_kv"] = (cfg["hidden_size"] / out["kv_rank"]) ** 0.5 if rescale \
        else 1
    out["scale"] = (out["nope"] + out["rope"]) ** -0.5
    return out


# ---------------- pieces ----------------

def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(F32)


def layer_norm(x, scale, bias, eps):
    x = x.astype(F32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(F32)
            + bias.astype(F32))


def rope(x, positions, theta: float):
    """x [..., S, d] rotated at `positions` [S] (halves pairing), plain."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions.astype(F32)[:, None] * jnp.asarray(inv.astype(np.float32))
    ang = jnp.concatenate([ang, ang], axis=-1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def projections(x, w, cfg, kw, positions):
    """Everything of one attention sub-layer that is a row's own and small:
    u, the gate, c_q, the cache's rows [c_kv | k_rope], and the indexer's
    k_I and w (scaled) where the layer has one (its q_I, 64 x 128 a row, is
    made a block of rows at a time: `index_queries`)."""
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, w["attn_norm"], eps)
    c_q = kw["a_q"] * rms_norm(u @ w["wqa"].astype(F32), w["q_norm"], eps)
    kv = u @ w["wkva"].astype(F32)
    c_kv = kw["a_kv"] * rms_norm(kv[:, :kw["kv_rank"]], w["kv_norm"], eps)
    latent = jnp.concatenate(
        [c_kv, rope(kv[:, kw["kv_rank"]:], positions, kw["theta"])], axis=-1)
    out = {"u": u, "c_q": c_q, "latent": latent}
    if kw["gate"]:
        out["gate"] = jax.nn.sigmoid(u @ w["wgate"].astype(F32))
    if kw["topk"]:
        nI, dI, dr = cfg["index_n_heads"], cfg["index_head_dim"], kw["rope"]
        k = layer_norm(u @ w["wik"].astype(F32), w["ik_norm"], w["ik_bias"],
                       INDEX_NORM_EPS)
        out["index_k"] = jnp.concatenate(
            [rope(k[:, :dr], positions, kw["theta"]), k[:, dr:]], axis=-1)
        out["index_w"] = (u @ w["wiw"].astype(F32)) * (nI ** -0.5
                                                       * dI ** -0.5)
    return out


def index_queries(c_q, w, cfg, kw, positions):
    """q_I [n_I, n, d_I] of the rows whose c_q [n, q rank] and positions
    are given."""
    nI, dI, dr = cfg["index_n_heads"], cfg["index_head_dim"], kw["rope"]
    q = (c_q @ w["wiq"].astype(F32)).reshape(-1, nI, dI).transpose(1, 0, 2)
    return jnp.concatenate(
        [rope(q[..., :dr], positions, kw["theta"]), q[..., dr:]], axis=-1)


def index_scores(index_q, index_w, index_k, rows):
    """I[t, s] of the query rows `rows` [n] (positions) against every key:
    index_q [n_I, n, d_I], index_w [n, n_I], index_k [S, d_I] -> [n, S],
    `_MASKED` where s > t. One index head at a time."""
    def add_head(acc, head):
        q, w = head                                        # [n, d_I], [n]
        return acc + w[:, None] * jax.nn.relu(q @ index_k.T), None

    acc, _ = jax.lax.scan(
        add_head, jnp.zeros((rows.shape[0], index_k.shape[0]), F32),
        (index_q, index_w.T))
    seen = jnp.arange(index_k.shape[0])[None, :] <= rows[:, None]
    return jnp.where(seen, acc, _MASKED)


def top_positions(scores, k: int):
    """(positions [n, k'], valid [n, k']) of the k' = min(k, S) largest
    scores a row; a row that sees fewer has the rest invalid."""
    vals, ids = jax.lax.top_k(scores, min(k, scores.shape[1]))
    return ids, vals > _MASKED / 2


def selection_margin(scores, members) -> float:
    """How far the lowest member stands below the highest visible key left
    out of `members`, in units of the scores' spread: <= 0 for a top-k."""
    rest = np.ones(len(scores), bool)
    rest[members] = False
    if not rest.any():
        return 0.0
    return float((scores[rest].max() - scores[members].min())
                 / max(float(scores.std()), 1e-30))


def check_selected(scores, t: int, k: int, theirs):
    """One row's S_t on the host: `scores` [t + 1] the reference's own,
    `theirs` the program's (padded with -1), which is taken where it is a
    set of min(k, t + 1) distinct visible positions.
    -> (ids [want] or None, {"bad_rows", "margin"}: its drift from a top-k
    of the reference's own scores)"""
    theirs = np.asarray(theirs).astype(int)
    theirs = theirs[theirs >= 0]
    want = min(k, t + 1)
    if (len(theirs) != want or len(set(theirs.tolist())) != want
            or theirs.max() > t):
        return None, {"bad_rows": 1, "margin": 0.0}
    return theirs, {"bad_rows": 0,
                    "margin": selection_margin(scores, theirs)}


def sparse_attention(q_nope, q_rope, latent, ids, valid, w, kw):
    """Latent-space softmax of each query over ITS gathered rows: q_nope
    [n, H, nope], q_rope [n, H, rope], latent [S, rank + rope], ids / valid
    [n, K] -> [n, H * v]. GATHER_BLOCK queries at a time."""
    H, r = kw["heads"], kw["kv_rank"]
    wk = w["wkvb_k"].astype(F32).reshape(r, H, kw["nope"])
    wv = w["wkvb_v"].astype(F32).reshape(r, H, kw["v"])

    def block(args):
        qn, qr, ids, valid = args
        rows = latent[ids]                                   # [b, K, width]
        q_lat = jnp.einsum("bhd,chd->bhc", qn, wk)
        s = (jnp.einsum("bhc,bkc->bhk", q_lat, rows[..., :r])
             + jnp.einsum("bhd,bkd->bhk", qr, rows[..., r:])) * kw["scale"]
        p = jax.nn.softmax(jnp.where(valid[:, None, :], s, -jnp.inf), axis=-1)
        o_lat = jnp.einsum("bhk,bkc->bhc", p, rows[..., :r])
        return jnp.einsum("bhc,chd->bhd", o_lat, wv).reshape(
            qn.shape[0], H * kw["v"])

    n = q_nope.shape[0]
    pad = -n % GATHER_BLOCK
    blocked = lambda a: jnp.pad(  # noqa: E731
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, GATHER_BLOCK) + a.shape[1:])
    # a padded query sees its first key, so that its softmax is finite
    valid = jnp.pad(valid, ((0, pad), (0, 0))).at[n:, 0].set(True)
    out = jax.lax.map(block, (blocked(q_nope), blocked(q_rope),
                              blocked(ids), valid.reshape(
                                  (-1, GATHER_BLOCK) + valid.shape[1:])))
    return out.reshape(-1, out.shape[-1])[:n]


def window_attention(c_q, latent, gate, w, kw, positions):
    """A sliding layer over the whole sequence, up-projected, one head at a
    time and QUERY_BLOCK rows at a time against the slice of keys the
    block's windows reach; each head's output, times its gate [S, H] where
    there is one, goes through its rows of W_o at once -> [S, hidden]."""
    H, r, W = kw["heads"], kw["kv_rank"], kw["window"]
    S = c_q.shape[0]
    B = min(QUERY_BLOCK, S)
    pad = -S % B
    back = W - 1                     # keys before a block's first row
    c_kv = jnp.pad(latent[:, :r], ((back, pad), (0, 0)))
    k_rope = jnp.pad(latent[:, r:], ((back, pad), (0, 0)))
    c_qp = jnp.pad(c_q, ((0, pad), (0, 0)))
    pos = jnp.pad(positions, (0, pad))
    row = jnp.arange(B)[:, None] + back      # a block's rows among its keys
    col = jnp.arange(B + back)[None, :]

    def one_head(out, head):
        wqn, wqr, wkn, wv, wo, g = head
        k_nope, v = c_kv @ wkn, c_kv @ wv

        def one_block(b):
            lo = b * B
            cq = jax.lax.dynamic_slice_in_dim(c_qp, lo, B)
            qn = cq @ wqn
            qr = rope(cq @ wqr, jax.lax.dynamic_slice_in_dim(pos, lo, B),
                      kw["theta"])
            take = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                a, lo, B + back)
            s = (qn @ take(k_nope).T + qr @ take(k_rope).T) * kw["scale"]
            seen = (col <= row) & (col > row - W) & (col + lo >= back)
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf),
                                  axis=-1) @ take(v)

        o = jax.lax.map(one_block, jnp.arange((S + pad) // B)).reshape(
            S + pad, kw["v"])[:S]
        return out + (o * g[:, None]) @ wo, None

    split = lambda m, d: m.astype(F32).reshape(  # noqa: E731
        m.shape[0], H, d).transpose(1, 0, 2)
    gates = jnp.ones((H, S), F32) if gate is None else gate.T
    out, _ = jax.lax.scan(
        one_head, jnp.zeros((S, w["wo"].shape[1]), F32),
        (split(w["wqb_nope"], kw["nope"]), split(w["wqb_rope"], kw["rope"]),
         split(w["wkvb_k"], kw["nope"]), split(w["wkvb_v"], kw["v"]),
         w["wo"].astype(F32).reshape(H, kw["v"], -1), gates))
    return out


def swiglu(u, wg, wu, wd):
    """(silu(u W_g) * (u W_u)) W_d, a block of rows at a time."""
    wg, wu, wd = wg.astype(F32), wu.astype(F32), wd.astype(F32)
    B = 4096
    return jnp.concatenate([
        (jax.nn.silu(u[lo:lo + B] @ wg) * (u[lo:lo + B] @ wu)) @ wd
        for lo in range(0, u.shape[0], B)], axis=0)


def choose(biased, k: int, program_choice=None):
    """The top k of sigma + b a row, on the host. The program's choice of a
    row (`program_choice` {row: ids}) is taken where it is k distinct
    experts. -> (chosen [S, k], rows where it is no top k of the
    reference's own scores even up to ROUTER_TIE_TOL: drift, bad choices)"""
    b = np.asarray(biased)
    chosen = np.argsort(-b, axis=-1, kind="stable")[:, :k]
    drift, bad = 0, 0
    for row, theirs in (program_choice or {}).items():
        theirs = np.asarray(theirs).astype(int)
        if (len(set(theirs.tolist())) != k or theirs.min() < 0
                or theirs.max() >= b.shape[1]):
            bad += 1
            continue
        rest = np.ones(b.shape[1], bool)
        rest[theirs] = False
        drift += int(b[row, theirs].min() < b[row, rest].max()
                     - ROUTER_TIE_TOL)
        chosen[row] = theirs
    return chosen, drift, bad


def expert_weights(sigma, chosen, cfg):
    picked = np.take_along_axis(np.asarray(sigma), chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    return picked * cfg["routed_scaling_factor"]


# ---------------- the parameter tree, a layer at a time ----------------

FFN_LEAVES = ("ffn_norm", "router", "router_bias", "sg", "su", "sd")
EXPERT_LEAVES = ("eg", "eu", "ed")


def num_layers(params) -> int:
    return 1 + params["moe"]["router"].shape[0]


@jax.jit
def take_rows(a, rows):
    return a[rows]


@functools.partial(jax.jit, static_argnums=(1, 2))
def layer_weights(params, index: int, kinds):
    """Layer `index`'s weights out of the program's tree: layer 0 is
    `params["dense"]`; layer l >= 1 has slice l - 1 of the FFN leaves
    stacked in `params["moe"]`, rows (l-1)*held .. l*held of the flat `eg` /
    `eu` / `ed`, and slice n of its kind's attention leaves, n the layers of
    that kind among the expert layers before it: the full layers' leaves lie
    in `params["moe"]`, the sliding layers' in `params["moe"]["swa"]`."""
    if index == 0:
        return dict(params["dense"])
    moe, i = params["moe"], index - 1
    held = moe["eg"].shape[0] // moe["router"].shape[0]
    nth = list(kinds[1:index]).count(kinds[index])
    attn = moe["swa"] if kinds[index] == SLIDING else {
        k: v for k, v in moe.items()
        if k not in FFN_LEAVES + EXPERT_LEAVES + ("swa",)}
    w = {k: v[nth] for k, v in attn.items()}
    w.update({k: moe[k][i] for k in FFN_LEAVES})
    w.update({k: moe[k][i * held:(i + 1) * held] for k in EXPERT_LEAVES})
    return w


_JITTED = {}


def _jitted(cfg, kind):
    key = (repr(sorted((k, repr(v)) for k, v in cfg.items())), kind)
    if key in _JITTED:
        return _JITTED[key]
    kw = kind_widths(cfg, kind)
    H = kw["heads"]
    hi = jax.default_matmul_precision("highest")

    def project(x, w, positions):
        with hi:
            return projections(x, w, cfg, kw, positions)

    def scores(pr, w, rows, positions):
        with hi:
            q = index_queries(pr["c_q"][rows], w, cfg, kw, positions[rows])
            sc = index_scores(q, pr["index_w"][rows], pr["index_k"], rows)
            return (sc,) + top_positions(sc, kw["topk"])

    def queries(pr, w, rows, positions):
        """The indexer's own inputs of `rows`: (q_I [n, n_I * d_I], w [n,
        n_I] with the score's scale)."""
        with hi:
            q = index_queries(pr["c_q"][rows], w, cfg, kw, positions[rows])
            return (q.transpose(1, 0, 2).reshape(rows.shape[0], -1),
                    pr["index_w"][rows])

    def same_scores(index_q, index_w, index_k, rows):
        with hi:
            return index_scores(index_q.astype(F32), index_w.astype(F32),
                                index_k.astype(F32), rows)

    def attend_selected(pr, w, rows, ids, valid, positions):
        """The rows' gated attention output through W_o: [n, hidden]."""
        with hi:
            n = rows.shape[0]
            c_q = pr["c_q"][rows]
            q_nope = (c_q @ w["wqb_nope"].astype(F32)).reshape(n, H,
                                                               kw["nope"])
            q_rope = (c_q @ w["wqb_rope"].astype(F32)).reshape(n, H,
                                                               kw["rope"])
            q_rope = rope(q_rope.transpose(1, 0, 2), positions[rows],
                          kw["theta"]).transpose(1, 0, 2)
            o = sparse_attention(q_nope, q_rope, pr["latent"], ids, valid,
                                 w, kw)
            if kw["gate"]:
                o = (o.reshape(n, H, kw["v"])
                     * pr["gate"][rows][:, :, None]).reshape(n, -1)
            return o @ w["wo"].astype(F32)

    def attend_window(pr, w, positions):
        with hi:
            return window_attention(pr["c_q"], pr["latent"],
                                    pr.get("gate"), w, kw, positions)

    def finish(x, a, w, keep):
        """x + a (the gated attention output through W_o; its rows `keep`
        are handed back), the FFN's input, and the router's scores or the
        dense layer's result."""
        with hi:
            kept = a[keep]
            x = x + a
            u = rms_norm(x, w["ffn_norm"], cfg["rms_norm_eps"])
            if "router" in w:
                sigma = jax.nn.sigmoid(u @ w["router"].astype(F32))
                return x, kept, u, (sigma,
                                    sigma + w["router_bias"].astype(F32))
            return x + swiglu(u, w["wg"], w["wu"], w["wd"]), kept, u, None

    def shared(x, u, w):
        with hi:
            return x + swiglu(u, w["sg"], w["su"], w["sd"])

    def one_expert(x, u, rows, weights, wg, wu, wd):
        """x + w_e E_e(u) on `rows` (padded rows carry weight 0)."""
        with hi:
            y = swiglu(u[rows], wg, wu, wd) * weights[:, None]
            return x.at[rows].add(y)

    # the residual stream is updated in place where the backend can (a
    # request of 40,960 tokens is 0.84 GB a copy, beside 9 GB of weights)
    inplace = {} if jax.default_backend() == "cpu" else {
        "finish": (0, 1), "shared": (0,), "one_expert": (0,)}
    fns = {k: jax.jit(f, donate_argnums=inplace.get(k, ()))
           for k, f in dict(
               project=project, scores=scores, queries=queries,
               same_scores=same_scores,
               attend_selected=attend_selected, attend_window=attend_window,
               finish=finish, shared=shared, one_expert=one_expert).items()}
    fns["kw"] = kw
    _JITTED[key] = fns
    return fns


def layer(x, w, cfg, kind, positions, held, program_choice=None,
          program_selected=None, keep_rows=()):
    """One layer on the float32 residual stream x [S, hidden]; `w` (of
    `layer_weights`) in whatever dtype it is stored. `program_choice` {row:
    expert ids} and `program_selected` {row: positions}: the program's
    router choice and S_t of some rows (see `choose`, `select`);
    `keep_rows`: the rows whose index scores (with the indexer's own q_I
    and w) and attention output are kept in `info`. -> (x', info)"""
    fns = _jitted(cfg, kind)
    kw, S = fns["kw"], x.shape[0]
    pr = fns["project"](x, w, positions)
    info = {"latent": pr["latent"], "index_bad_rows": 0, "index_margin": 0.0}
    if kw["topk"]:
        info["index_k"] = pr["index_k"]
        info["index_scores"] = {}
        if keep_rows:
            info["index_q"], info["index_w"] = (np.asarray(a) for a in fns[
                "queries"](pr, w, jnp.asarray(keep_rows, jnp.int32),
                           positions))
        outs = []
        theirs = program_selected or {}
        for lo in range(0, S, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, S)
            rows = jnp.arange(lo, hi)
            sc, ids, valid = fns["scores"](pr, w, rows, positions)
            held_rows = sorted(t for t in set(theirs) | set(keep_rows)
                               if lo <= t < hi)
            if held_rows:
                local = np.asarray(held_rows) - lo
                sc_rows = np.asarray(take_rows(sc, jnp.asarray(local)))
                ids, valid = np.array(ids), np.array(valid)
                for i, t in zip(local, held_rows):
                    row = sc_rows[held_rows.index(t), :t + 1]
                    if t in keep_rows:
                        info["index_scores"][t] = row
                    if t not in theirs:
                        continue
                    taken, sel = check_selected(row, t, kw["topk"], theirs[t])
                    info["index_bad_rows"] += sel["bad_rows"]
                    info["index_margin"] = max(info["index_margin"],
                                               sel["margin"])
                    if taken is not None:
                        ids[i, :len(taken)] = taken
                        valid[i] = np.arange(ids.shape[1]) < len(taken)
            outs.append(fns["attend_selected"](
                pr, w, rows, jnp.asarray(ids), jnp.asarray(valid), positions))
        o = jnp.concatenate(outs, axis=0)
    else:
        o = fns["attend_window"](pr, w, positions)
    del pr
    x, kept, u, routed = fns["finish"](
        x, o, w, jnp.asarray(list(keep_rows), jnp.int32))
    del o
    info["ffn_input"] = u
    info["attn_out"] = dict(zip(keep_rows, np.asarray(kept)))
    if routed is not None:
        sigma, biased = routed
        chosen, drift, bad = choose(biased, cfg["num_experts_per_tok"],
                                    program_choice)
        weights = expert_weights(sigma, chosen, cfg)
        x = fns["shared"](x, u, w)
        offset, count = held
        for e in range(count):
            rows, slot = np.nonzero(chosen == offset + e)
            if not len(rows):
                continue
            pad = -len(rows) % EXPERT_PAD
            x = fns["one_expert"](
                x, u, jnp.asarray(np.pad(rows, (0, pad))),
                jnp.asarray(np.pad(weights[rows, slot], (0, pad)), F32),
                w["eg"][e], w["eu"][e], w["ed"][e])
        info.update(sigma=sigma, chosen=chosen, router_drift=drift,
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


def same_index(cfg, index_q, index_w, index_k, rows):
    """The indexer's scores alone on the program's own q_I [n, n_I, d_I], w
    [n, n_I] (scaled) and index keys [S, d_I] (read from the cache), the
    queries at positions `rows` -> [n, S]."""
    return _jitted(cfg, FULL)["same_scores"](
        jnp.asarray(index_q).transpose(1, 0, 2), jnp.asarray(index_w),
        jnp.asarray(index_k), jnp.asarray(rows))


def forward(params, tokens, cfg, held, program_choices=None,
            program_selected=None, keep_rows=()):
    """Logits [S, vocabulary slice] of one sequence, and per-layer info.
    `program_choices` / `program_selected` {layer index: {row: ids}}."""
    positions = jnp.arange(tokens.shape[0])
    x = embed(params, tokens)
    infos = []
    for index in range(num_layers(params)):
        x, info = layer(
            x, layer_weights(params, index, cfg["layer_types"]), cfg,
            cfg["layer_types"][index], positions, held,
            (program_choices or {}).get(index),
            (program_selected or {}).get(index), keep_rows)
        infos.append(info)
    return head(params, x, cfg), infos


def rel_err(got, want) -> float:
    g = np.asarray(got, np.float64).ravel()
    w = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))


def median_row_err(got, want) -> float:
    """The median over the rows of a row's relative error."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.median(np.linalg.norm(got - want, axis=-1)
                           / np.maximum(np.linalg.norm(want, axis=-1),
                                        1e-30)))
