"""Traffic kind `lm_serve_sparse_closed_loop`: the closed loop, scripts and
window of `lm_serve_closed_loop` over a token model that selects its keys and
slides a window (models/moe_mla.py under `lm.layer_types`, `lm.index_*`,
`lm.swa_*`), held against benchmark/reference_dots3.py.

Everything up to the check is `lm_serve_closed_loop`'s, loaded as a module
of this driver's own (nothing of the accepted driver's is edited, and the
cell it serves sees none of this): `setup`, the workers, `measure` with its
counters and its result. Three of its names are given this driver's:
`reference_check`, `_shapes` and `Workers` (`_rebind` says at once if a
later edit of that driver no longer reads them by these names).

Which requests the reference is held against. A request here lasts about a
window (a new document of 40k tokens is 20 chunk steps), so a worker rarely
comes to its NEXT request before the window closes, and `flag_upcoming`'s
picks would be served in the drain, with the slots emptying. The flagged
requests are therefore picked, by the accepted driver's own rule (the
shortest question on a resident document and the shortest new document
within `reference_max_tokens`), among the FIRST requests of the scripts:
the ones `start` sends, all 16 workers live, `warmup_seconds` before the
window opens. Their compared rows (the prompt's last position and the first
decode steps) come from steps that run beside every other worker's chunks
and decode rows; whether those steps lay inside the window is written into
each request's result (`sent_s`, `compared_s`: seconds from the window's
opening; `in_window`). `correct` so covers the chunk programs on block
tables up to `reference_max_tokens` (here the DENSE form of a chunk's
selected attention: the gathered form's chunk programs, on tables over
65,536 tokens, run in set-up and in the window and are held by tier-1
alone) and the decode rows (gathered) beside them.

The check (after the window). The flagged requests carry `detail_steps`;
with their tokens the server returns, from the steps that served them, the
float32 logits at the prompt's last position and at each of the first
decode steps, the hidden rows, every expert layer's router input, scores
and choice,
every layer's attention output, and of every full layer the indexer's
queries, weights, scores over the context and S_t. Before the caches are
released, the first and the last full layer's latent rows and index keys of
the request's document, and the last sliding layer's window rows at the
document's end, are read from the caches themselves (`cached_rows`). The
reference runs one full forward over document + question + the server's own
sampled ids, a layer at a time with that layer's weights upcast, and the
numbers are held to its `TOLERANCES` by `compare`.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark import harness

base = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "lm_serve_closed_loop.py"), "driver_lm_serve_sparse_base")
REFERENCE_PAD = base.REFERENCE_PAD
FULL, SLIDING = "full_attention", "sliding_attention"

measure, teardown = base.measure, base.teardown


class Workers(base.Workers):
    """The closed loop, in which the requests the reference is held against
    are among the first that `start` sends (the module's docstring)."""

    check = (2, 12288)    # (reference_requests, reference_max_tokens): setup

    def start(self):
        self.picks = super().flag_upcoming(*self.check)
        self.opened = self.stopped = None
        super().start()

    def flag_upcoming(self, how_many, max_tokens):
        """`measure` calls this as the window opens: nothing more is
        flagged; the moment is kept."""
        self.opened = time.perf_counter()
        return self.picks

    def stop(self):
        if self.stopped is None:
            self.stopped = time.perf_counter()
        super().stop()

    def when(self, result, steps):
        """Where a flagged request's compared rows lie against the window:
        seconds from its opening at which the request was sent and at which
        its first and its last compared token were delivered; `in_window`
        where all of them lie between the opening and `stop` (which
        `measure` calls as the window closes, after the trace is joined)."""
        times = np.asarray(result.token_times)[:steps + 1]
        return {"sent_s": round(result.submitted - self.opened, 2),
                "compared_s": [round(float(times[0]) - self.opened, 2),
                               round(float(times[-1]) - self.opened, 2)],
                "in_window": bool(self.opened <= times[0]
                                  and times[-1] <= self.stopped)}


def setup(cell, seed, devices, spans):
    wl = cell.workload
    Workers.check = (int(wl.get("reference_requests", 2)),
                     int(wl.get("reference_max_tokens", 12288)))
    return base.setup(cell, seed, devices, spans)


def _shapes(cfg):
    """What benchmark/roofline_dots3.py prices a step from (and, under the
    names they know, the accepted readers of the expert layer)."""
    from mine_tpu.models import moe_mla
    swa = moe_mla.of_kind(cfg, SLIDING)
    kinds = list(cfg.kinds)
    return {"kind": "lm_serve", "attention": "selected+window",
            "hidden": cfg.hidden_size, "layer_kinds": kinds,
            "heads": cfg.num_attention_heads, "q_lora_rank": cfg.q_lora_rank,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "index_n_heads": cfg.index_n_heads,
            "index_head_dim": cfg.index_head_dim,
            "index_topk": cfg.index_topk, "gate": bool(
                cfg.attention_gate_type),
            "swa": {"heads": swa.num_attention_heads,
                    "q_lora_rank": swa.q_lora_rank,
                    "kv_lora_rank": swa.kv_lora_rank,
                    "qk_nope_head_dim": swa.qk_nope_head_dim,
                    "qk_rope_head_dim": swa.qk_rope_head_dim,
                    "v_head_dim": swa.v_head_dim, "window": swa.window,
                    "gate": bool(swa.attention_gate_type)},
            "dense_intermediate": cfg.intermediate_size,
            "moe_intermediate": cfg.moe_intermediate_size,
            "layers": cfg.num_hidden_layers, "moe_layers": cfg.moe_layers,
            "full_layers": kinds.count(FULL),
            "sliding_layers": kinds.count(SLIDING),
            "n_routed_experts": cfg.n_routed_experts,
            "experts_held": cfg.experts_held, "vocab": cfg.vocab_held}


# ---------------- the reference check ----------------

def cached_rows(cache, doc_id):
    """What the caches hold of a resident document after the window, float32:
    the first and the last full layer's latent rows and index keys over its
    whole pages, and the last sliding layer's rows of the window pages it
    keeps (with the position of the first of them); None where the document
    is no longer resident."""
    doc = cache.documents.get(doc_id)
    if doc is None or not doc.ready or not doc.pages:
        return None
    ps = cache.page_size
    rows_of = lambda pages: (np.asarray(pages)[:, None] * ps  # noqa: E731
                             + np.arange(ps)[None, :]).reshape(-1)
    at, last = rows_of(doc.pages), cache.layers - 1
    out = {"latent_first": cache.rows[0, at, :cache.width],
           "latent_last": cache.rows[last, at, :cache.width]}
    if cache.index_rows is not None:
        out.update(index_first=cache.index_rows[0, at],
                   index_last=cache.index_rows[last, at])
    if cache.window_rows is not None and doc.window is not None:
        out["window_last"] = cache.window_rows[
            cache.window_layers - 1, rows_of(doc.window.pages),
            :cache.window_width]
        out["window_start"] = doc.window.first * ps
    return {k: v if isinstance(v, int) else np.asarray(v, np.float32)
            for k, v in out.items()}


def reference_numbers(params, record, positions, config, held):
    """The reference's side: one full forward over document + question + the
    server's own sampled ids, a layer at a time; at `positions` the logits,
    every layer's attention output and the indexer's own scores, and what
    each block gives on the program's own inputs."""
    import jax.numpy as jnp

    from benchmark import reference_dots3 as R
    cfg = R.config_from_flat(config)
    kinds = cfg["layer_types"]
    full = [i for i, k in enumerate(kinds) if k == FULL]
    req, res = record["request"], record["result"]
    seq = np.concatenate([req.document, req.question,
                          np.asarray(res.tokens, np.int32)])
    seq = seq[:positions[-1] + 1]
    padded = -(-len(seq) // REFERENCE_PAD) * REFERENCE_PAD
    tokens = jnp.asarray(np.pad(seq, (0, padded - len(seq))))
    rows = {d["position"]: d for d in res.detail}
    pos = jnp.arange(padded)
    x = R.embed(params, tokens)
    out = {"router_drift": 0, "bad_choices": 0, "index_bad_rows": 0,
           "index_margin": 0.0, "attn_out": [], "latent": {}, "index_k": {}}
    keep = tuple(positions)
    for index in range(R.num_layers(params)):
        choice = selected = None
        if index >= 1:
            choice = {p: rows[p]["chosen"][index - 1] for p in positions}
        if kinds[index] == FULL and cfg["index_topk"]:
            n = full.index(index)
            selected = {p: rows[p]["selected"][n] for p in positions}
        x, info = R.layer(x, R.layer_weights(params, index, kinds), cfg,
                          kinds[index], pos, held, choice, selected, keep)
        out["router_drift"] += info.get("router_drift", 0)
        out["bad_choices"] += info.get("bad_choices", 0)
        out["index_bad_rows"] += info["index_bad_rows"]
        out["index_margin"] = max(out["index_margin"], info["index_margin"])
        if full and index == full[0] and "index_q" in info:
            out["index_first"] = {"q": info["index_q"], "w": info["index_w"],
                                  "margin": info["index_margin"]}
        out["attn_out"].append(np.stack([info["attn_out"][p]
                                         for p in positions]))
        if index in (full[0], full[-1]) or index == max(
                (i for i, k in enumerate(kinds) if k == SLIDING), default=-1):
            out["latent"][index] = np.asarray(info["latent"][:len(seq)])
            if "index_k" in info:
                out["index_k"][index] = np.asarray(
                    info["index_k"][:len(seq)])
    at = jnp.asarray(positions)
    out["logits"] = np.asarray(R.head(params, x[at], cfg))
    hidden = jnp.stack([jnp.asarray(rows[p]["hidden"]) for p in positions])
    out["same_head"] = np.asarray(R.same_head(hidden, params["head"]))
    out["same_router"] = np.stack([np.asarray(R.same_router(
        jnp.stack([jnp.asarray(rows[p]["router_input"][i])
                   for p in positions]), params["moe"]["router"][i]))
        for i in range(R.num_layers(params) - 1)], axis=1)
    out.update(kinds=kinds, full=full, cfg=cfg)
    return out


def same_index(got, want, which: int, key: str):
    """The indexer alone, full layer `which` (0: the first, -1: the last):
    the reference's scores of the program's own index queries and weights
    against the index keys the cache holds of the document, beside the
    program's scores over the same positions. -> (mine, reference's)"""
    from benchmark import reference_dots3 as R
    cfg, keys = want["cfg"], got["cached"][key]
    positions, rows = got["positions"], got["rows"]
    n = which % len(want["full"])
    q = np.stack([rows[p]["index_q"][n] for p in positions]).reshape(
        len(positions), cfg["index_n_heads"], cfg["index_head_dim"])
    w = np.stack([rows[p]["index_w"][n] for p in positions])
    ref = np.asarray(R.same_index(cfg, q, w, keys, np.asarray(positions)))
    mine = np.stack([rows[p]["index_scores"][n][:len(keys)]
                     for p in positions])
    return mine, ref


def own_exactness(got, want):
    """The program's sets against the scores it returned WITH them (which
    `same.index` and `same.router` hold to the reference's float32): the
    worst row's `selection_margin` of S_t, and the choices of experts that
    are no top k of the program's own sigma (the bias of weights from a
    seed is zero)."""
    from benchmark import reference_dots3 as R
    margin, bad = 0.0, 0
    for p in got["positions"]:
        row = got["rows"][p]
        for n in range(len(want["full"])):
            picked = row["selected"][n]
            picked = picked[picked >= 0]
            if len(picked):
                margin = max(margin, R.selection_margin(
                    row["index_scores"][n][:p + 1], picked))
        for sigma, chosen in zip(row["sigma"], row["chosen"]):
            rest = np.ones(len(sigma), bool)
            rest[chosen] = False
            bad += int(sigma[chosen].min() < sigma[rest].max())
    return margin, bad


def compare(got, want):
    """{"errors": {name: error}, "ok": all within TOLERANCES}."""
    from benchmark import reference_dots3 as R
    positions, rows = got["positions"], got["rows"]
    kinds, full = want["kinds"], want["full"]
    margin, bad_choices = own_exactness(got, want)
    mine = np.stack([rows[p]["logits"] for p in positions])
    per_pos = [R.rel_err(mine[i], want["logits"][i])
               for i in range(len(positions))]
    attn = [R.rel_err(np.stack([rows[p]["attn_out"][i] for p in positions]),
                      want["attn_out"][i]) for i in range(len(kinds))]
    errors = {
        "logits.prefill_last": per_pos[0],
        "logits.decode": max(per_pos[1:]) if len(per_pos) > 1 else 0.0,
        "same.head": R.rel_err(mine, want["same_head"]),
        "same.router": R.rel_err(
            np.stack([rows[p]["sigma"] for p in positions]),
            want["same_router"]),
        "attn.full": max(e for e, k in zip(attn, kinds) if k == FULL),
        "attn.sliding": max([e for e, k in zip(attn, kinds) if k == SLIDING]
                            or [0.0]),
        "index.margin": margin,
        "index.bad_rows": float(want["index_bad_rows"]),
        "router.bad_choices": float(bad_choices + want["bad_choices"]),
    }
    first = want.get("index_first")
    if first is not None:     # layer 0's indexer against the forward's own
        errors.update({
            "index.q_first": R.rel_err(np.stack(
                [rows[p]["index_q"][0] for p in positions]), first["q"]),
            "index.w_first": R.rel_err(np.stack(
                [rows[p]["index_w"][0] for p in positions]), first["w"]),
            "index.drift_first": first["margin"]})
    cached = got.get("cached")
    if cached is not None:
        sliding = max(i for i, k in enumerate(kinds) if k == SLIDING)
        n = len(cached["latent_first"])
        errors.update({
            "cache.latent_first": R.median_row_err(
                cached["latent_first"], want["latent"][full[0]][:n]),
            "cache.latent_last": R.median_row_err(
                cached["latent_last"], want["latent"][full[-1]][:n]),
            "cache.index_first": R.median_row_err(
                cached["index_first"], want["index_k"][full[0]][:n]),
            "cache.index_last": R.median_row_err(
                cached["index_last"], want["index_k"][full[-1]][:n])})
        if "window_last" in cached:
            lo = cached["window_start"]
            errors["cache.window_last"] = R.median_row_err(
                cached["window_last"], want["latent"][sliding][
                    lo:lo + len(cached["window_last"])])
        # the seen part of each row: a masked score is no number to compare
        pairs = [same_index(got, want, which, key) for which, key in (
            (0, "index_first"), (-1, "index_last"))]
        seen = np.arange(n)[None, :] <= np.asarray(positions)[:, None]
        errors["same.index"] = max(R.rel_err(m[seen], r[seen])
                                   for m, r in pairs)
    over = [k for k, v in errors.items() if not v <= R.TOLERANCES[k]]
    finite = bool(np.isfinite(mine).all() and mine.std(axis=-1).min() > 0)
    return {"errors": {k: float("%.3g" % v) for k, v in errors.items()},
            "ok": not over, "over": over, "logits_ok": finite,
            # the program's sets against THIS forward's own scores, every
            # layer: drift of a bfloat16 residual stream, held to no limit
            # beyond the first full layer (`index.drift_first`)
            "drift": {"index_margin": float("%.3g" % want["index_margin"]),
                      "router_rows": int(want["router_drift"])},
            "positions": len(positions),
            "document_resident": cached is not None}


def reference_check(ctx, records, picks):
    """The flagged requests, each against the reference, after the caches
    are released, so that the reference fits."""
    steps = int(ctx["cell"].workload.get("reference_decode_steps", 16))
    server, cfg = ctx["server"], ctx["server"].engine.cfg
    flagged = [r for r in records if r["request"] is not None
               and r["result"].detail]
    if not flagged:
        return {"ok": False, "why": "no flagged request was served: %s"
                % (picks,)}
    t0 = time.perf_counter()
    cached = [cached_rows(server.engine.cache, rec["request"].doc_id)
              for rec in flagged]
    server.engine.cache.set_arrays(None)           # release the caches
    import jax
    harness.say("caches released: %.2f GB in use on the device" % (
        (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0) / 1e9))
    params = server.engine.params
    held = (cfg.expert_offset, cfg.experts_held)
    out, ok = [], True
    for rec, rows in zip(flagged, cached):
        got = dict(base.program_numbers(rec, steps), cached=rows)
        want = reference_numbers(params, rec, got["positions"],
                                 ctx["config"], held)
        result = compare(got, want)
        result.update(kind=rec["kind"], tokens=rec["result"].prompt_tokens,
                      cached=rec["result"].cached_tokens,
                      **ctx["workers"].when(rec["result"], steps))
        ok = ok and result["ok"]
        out.append(result)
    return {"ok": ok, "logits_ok": all(r["logits_ok"] for r in out),
            "seconds": round(time.perf_counter() - t0, 1),
            "in_window": [r["in_window"] for r in out],
            "errors": [r["errors"] for r in out], "requests": out}


def _rebind():
    """Give the loaded copy of the accepted driver this driver's three
    names, and fail at import where its functions no longer read them."""
    for fn, names in ((base.measure, ("reference_check", "_shapes")),
                      (base.setup, ("Workers",))):
        gone = [n for n in names if n not in fn.__code__.co_names
                or not hasattr(base, n)]
        if gone:
            raise harness.BenchError(
                "lm_serve_closed_loop.%s no longer reads %s by name: "
                "lm_serve_sparse_closed_loop cannot stand in for them"
                % (fn.__name__, gone))
    base.reference_check, base._shapes = reference_check, _shapes
    base.Workers = Workers


_rebind()
