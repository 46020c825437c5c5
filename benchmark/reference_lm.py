"""Plain reference of the looped language model's training loss: the
published equations in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")` (on a TPU a float32 matmul else
runs as one bfloat16 pass). Python loops over passes and layers, one full
[S, S] softmax a head, no scan, no kernel, no rematerialisation, no chunking.
Imports nothing from the program.

    x = E[tokens];  for pass t = 1..T, layer l = 1..L:
        a = x + N2_l(Attn_l(N1_l(x)));   x = a + N4_l(MLP_l(N3_l(a)))
    after layer L:  h_t = N_f(x);  x <- h_t;  g_t = h_t . w_g + b_g
    logits_t = h_t W_head;  lambda_t = sigmoid(g_t)
    q_1 = lambda_1;  q_t = lambda_t prod_{j<t}(1 - lambda_j);  q_T = prod_{j<T}(1 - lambda_j)
    loss = mean over unmasked tokens of [ sum_t q_t CE(logits_t, label) - beta H(q) ]

The parameter tree is the program's ({"embed", "layers": stacked on a leading
layer axis, "final_norm", "exit_gate": {"w", "b"}, "head"}); a configuration
is a plain dict of the source config.json's keys.

Departures from the published description, each where it happens:
  * the exit distribution's entropy clamps q at 1e-30 inside the logarithm
    (0 log 0 = 0);
  * the loss is averaged over the slots the batch's mask marks, and a row's
    labels are given (the packer shifts them), not derived from the tokens;
  * attention over a packed row is plain causal attention, no document mask
    (the cell's traffic states it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EXIT_ENTROPY_BETA = 0.1


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, theta):
    """x [S, heads, D], positions 0..S-1, rotate-half."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + rotated * sin


def attention(x, w, cfg):
    """One sequence x [S, hidden] through one layer's attention."""
    S = x.shape[0]
    heads, D = cfg["num_attention_heads"], cfg["head_dim"]
    q = rope((x @ w["wq"]).reshape(S, heads, D), cfg["rope_theta"])
    k = rope((x @ w["wk"]).reshape(S, heads, D), cfg["rope_theta"])
    v = (x @ w["wv"]).reshape(S, heads, D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    out = []
    for h in range(heads):
        s = q[:, h] @ k[:, h].T / jnp.sqrt(jnp.float32(D))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        out.append(p @ v[:, h])
    return jnp.concatenate(out, axis=-1) @ w["wo"]


def mlp(x, w):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def layer_apply(x, w, cfg):
    """One application of one layer (its weights `w`) to one sequence."""
    eps = cfg["rms_norm_eps"]
    a = x + rms_norm(attention(rms_norm(x, w["norm1"], eps), w, cfg),
                     w["norm2"], eps)
    return a + rms_norm(mlp(rms_norm(a, w["norm3"], eps), w), w["norm4"], eps)


def pass_end(x, final_norm, gate, cfg):
    """After layer L: h_t = N_f(x), which also feeds the next pass, and the
    exit gate g_t."""
    h = rms_norm(x, final_norm, cfg["rms_norm_eps"])
    return h, h @ gate["w"] + gate["b"]


def cross_entropy(h, head, labels):
    logits = h @ head
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]


def exit_distribution(gates):
    """gates: list of T arrays -> q, list of T arrays, sum_t q_t = 1."""
    lam = [jax.nn.sigmoid(g) for g in gates]
    q, stay = [], jnp.ones_like(lam[0])
    for t in range(len(lam) - 1):
        q.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return q + [stay]


def token_sums(ce, gates, mask, beta=EXIT_ENTROPY_BETA):
    """From a sequence's per-pass CE and gates (lists of T arrays [S]), the
    masked SUMS {"loss", "ce" [T], "q" [T], "entropy", "tokens"}; divide by
    the batch's tokens for the means."""
    q = exit_distribution(gates)
    entropy = -sum(qt * jnp.log(jnp.maximum(qt, 1e-30)) for qt in q)
    per_token = sum(qt * c for qt, c in zip(q, ce)) - beta * entropy
    total = lambda x: jnp.sum(x * mask)   # noqa: E731
    return {"loss": total(per_token),
            "ce": jnp.stack([total(c) for c in ce]),
            "q": jnp.stack([total(qt) for qt in q]),
            "entropy": total(entropy), "tokens": jnp.sum(mask)}


def sequence_sums(lm, tokens, labels, mask, cfg, beta=EXIT_ENTROPY_BETA):
    """One sequence of token ids [S] through every pass -> `token_sums`."""
    with jax.default_matmul_precision("highest"):
        x = lm["embed"][tokens]
        ce, gates = [], []
        for _ in range(cfg["total_ut_steps"]):
            for l in range(cfg["num_hidden_layers"]):
                x = layer_apply(x, jax.tree_util.tree_map(
                    lambda a: a[l], lm["layers"]), cfg)
            x, g = pass_end(x, lm["final_norm"], lm["exit_gate"], cfg)
            ce.append(cross_entropy(x, lm["head"], labels))
            gates.append(g)
        return token_sums(ce, gates, mask, beta)


def _means(sums):
    tokens = sum(s["tokens"] for s in sums)
    mean = lambda k: sum(s[k] for s in sums) / tokens   # noqa: E731
    return mean("loss"), {"ce_ut": mean("ce"), "exit_q_mean": mean("q"),
                          "exit_entropy": mean("entropy"), "tokens": tokens}


def loss_and_terms(lm, batch, cfg, beta=EXIT_ENTROPY_BETA):
    """The batch's loss and its terms as means over the unmasked tokens."""
    return _means([sequence_sums(
        lm, batch["tokens"][b], batch["labels"][b],
        batch["mask"][b].astype(jnp.float32), cfg, beta)
        for b in range(batch["tokens"].shape[0])])


def loss_and_grads(lm, batch, cfg, beta=EXIT_ENTROPY_BETA):
    """(loss, terms, gradients of the loss by every parameter)."""
    (loss, terms), grads = jax.value_and_grad(
        lambda p: loss_and_terms(p, batch, cfg, beta), has_aux=True)(lm)
    return loss, terms, grads


# ---------------- the optimizer's step ----------------
#
# AdamW with the gradients' global norm clipped first, as the family trains
# (the source config.json states no optimizer: assumed, like beta). The rate
# and the decay are the caller's.

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
CLIP_GLOBAL_NORM = 1.0


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree_util.tree_leaves(tree)))


def clip_scale(norm, max_norm=CLIP_GLOBAL_NORM):
    """What every gradient is multiplied by before Adam sees it."""
    return jnp.where(norm < max_norm, 1.0, max_norm / norm)


def decayed(name: str) -> bool:
    """The decoupled decay is for the matrices (and the gate's vector): not
    for a norm's scale (norm1..norm4, final_norm), not for the gate's bias."""
    return not (name.startswith("norm") or name in ("final_norm", "b"))


def adamw_step(p, g, m, v, t, lr, weight_decay, decays: bool):
    """Step t (from 1) of one leaf: parameters p, the clipped gradient g,
    the moments m and v -> (new p, new m, new v)."""
    m = ADAM_B1 * m + (1 - ADAM_B1) * g
    v = ADAM_B2 * v + (1 - ADAM_B2) * g * g
    update = (m / (1 - ADAM_B1 ** t)) / (
        jnp.sqrt(v / (1 - ADAM_B2 ** t)) + ADAM_EPS)
    if decays:
        update = update + weight_decay * p
    return p - lr * update, m, v


# ---------------- the same numbers, one block at a time ----------------
#
# At the published widths and 4,096 tokens, `loss_and_grads` keeps every
# layer application's [heads, S, S] probabilities for its backward (1.07 GB
# each, 32 of them) and does not fit a 16 GB chip. The function below gives
# the same loss, terms and gradients, and what the means are taken over (each
# token's cross entropy and gate, one token's logits), by running the same
# block functions one layer application at a time and chaining their VJPs by
# hand: forward keeps each application's input, backward re-runs one
# application under `jax.vjp` and adds its weights' gradients to the running
# sums. Nothing of the mathematics changes; tests compare the two forms at a
# small size.

def _jit(fn, **kw):
    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return jax.jit(traced, **kw)


def _layer_of(layers, l):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False), layers)


def block_functions(cfg, beta=EXIT_ENTROPY_BETA):
    """The jitted blocks `blockwise_loss_and_grads` chains, by name."""
    def layer_back(x, layers, l, ct, sums):
        _, vjp = jax.vjp(lambda x, w: layer_apply(x, w, cfg), x,
                         _layer_of(layers, l))
        ct_x, ct_w = vjp(ct)
        return ct_x, jax.tree_util.tree_map(lambda a, g: a.at[l].add(g),
                                            sums, ct_w)

    def head(x, final_norm, gate, head_w, labels):
        h, g = pass_end(x, final_norm, gate, cfg)
        return h, cross_entropy(h, head_w, labels), g

    def head_back(x, weights, labels, ct_h, ct_ce, ct_g, sums):
        _, vjp = jax.vjp(lambda x, w: head(x, w["final_norm"], w["exit_gate"],
                                           w["head"], labels), x, weights)
        ct_x, ct_w = vjp((ct_h, ct_ce, ct_g))
        return ct_x, jax.tree_util.tree_map(jnp.add, sums, ct_w)

    def sums_and_cts(ce, gates, mask, n):
        sums, vjp = jax.vjp(lambda c, g: token_sums(list(c), list(g), mask,
                                                    beta), ce, gates)
        seed = jax.tree_util.tree_map(jnp.zeros_like, sums)
        seed["loss"] = jnp.ones_like(sums["loss"]) / n   # d mean / d sum
        return sums, vjp(seed)

    return {
        "fwd": _jit(lambda x, layers, l: layer_apply(
            x, _layer_of(layers, l), cfg)),
        "layer_back": _jit(layer_back, donate_argnums=(4,)),
        "head": _jit(head),
        "head_back": _jit(head_back, donate_argnums=(6,)),
        "sums_and_cts": _jit(sums_and_cts),
        "embed_rows": _jit(lambda embed, tokens: embed[tokens]),
        # the embedding's gradient is the scatter of d loss / d x0 by token
        "embed_back": _jit(lambda acc, tokens, ct: acc.at[tokens].add(ct),
                           donate_argnums=(0,)),
        "row_logits": _jit(lambda h, head_w, pos: h[pos] @ head_w),
        "zeros": _jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))}


def blockwise_loss_and_grads(lm, batch, cfg, row_at=(0, -1),
                             beta=EXIT_ENTROPY_BETA):
    """(loss, terms, per_token, grads): `loss_and_grads`'s three, and
    per_token = {"ce" [T, B, S], "gates" [T, B, S], "logits_row" [vocab]: the
    last pass's logits of token `row_at` = (row, position)}. `batch` holds
    host arrays."""
    L, T = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    f = block_functions(cfg, beta)
    n = float(batch["mask"].sum())
    top = {k: lm[k] for k in ("final_norm", "exit_gate", "head")}
    g_layers, g_top, g_embed = (f["zeros"](lm["layers"]), f["zeros"](top),
                                f["zeros"](lm["embed"]))
    all_sums, all_ce, all_gates, logits_row = [], [], [], None
    for b in range(batch["tokens"].shape[0]):
        tokens, labels = batch["tokens"][b], batch["labels"][b]
        mask = batch["mask"][b].astype("float32")
        x = f["embed_rows"](lm["embed"], tokens)
        inputs, ends, ce, gates = [], [], [], []
        for _ in range(T):
            for l in range(L):
                inputs.append(x)
                x = f["fwd"](x, lm["layers"], l)
            ends.append(x)
            x, c, g = f["head"](x, lm["final_norm"], lm["exit_gate"],
                                lm["head"], labels)
            ce.append(c)
            gates.append(g)
        if b == row_at[0]:
            logits_row = f["row_logits"](x, lm["head"],
                                         row_at[1] % len(tokens))
        ce, gates = jnp.stack(ce), jnp.stack(gates)
        sums, (ct_ce, ct_g) = f["sums_and_cts"](ce, gates, mask, n)
        all_sums.append(sums)
        all_ce.append(ce)
        all_gates.append(gates)
        ct = jnp.zeros_like(x)    # nothing reads the last pass's h but the head
        for t in reversed(range(T)):
            ct, g_top = f["head_back"](ends[t], top, labels, ct, ct_ce[t],
                                       ct_g[t], g_top)
            for l in reversed(range(L)):
                ct, g_layers = f["layer_back"](inputs[t * L + l],
                                               lm["layers"], l, ct, g_layers)
        g_embed = f["embed_back"](g_embed, tokens, ct)
    loss, terms = _means(all_sums)
    per_token = {"ce": jnp.stack(all_ce, axis=1),
                 "gates": jnp.stack(all_gates, axis=1),
                 "logits_row": logits_row}
    return loss, terms, per_token, dict(g_top, layers=g_layers, embed=g_embed)
