"""Traffic kind `lm_serve_closed_loop`: the token server under a closed loop of
workers that ask questions about documents, and the check of what it served
against the plain reference.

The program's own objects, built as serve_cli.py builds them:
`mine_tpu.serve.lm_scheduler.build_server` (weights from the seed in one
jitted program, `LatentCache`, `LMEngine` with every bucket warmed,
`StepScheduler`, `LMServer`); requests go in through `LMServer.submit`.

One item of `serve_views_per_s` is ONE ANSWER TOKEN delivered to its worker
inside the window (the benchmark has one serve metric; requests a second,
prompt tokens a second and the time to a first token are in `details`).

The traffic (all of it data in the traffic file). `--seed` draws the weights
and every token id. The scripts' SHAPE (the resident documents' lengths and
every request's kind, lengths and popularity rank) is one plain draw of the
file's distributions under its `script_seed`, the same in every run, so that
every seed is given the same work: drawn from `--seed`, which new documents
a window of 20 s held moved its answer tokens by 35% from seed to seed
(PERF.md section 6, PR 35).
  workers            closed loop, no think time, each with a script; a
                     worker's next request is sent the moment the last token
                     of the one before is delivered (from the completion's
                     callback, so that the order of arrivals is the order of
                     completions and not a race of threads)
  resident_documents documents prefilled in set-up through the same chunked
                     path; `document_tokens` {median, sigma, min, max}
  new_document_probability  a request brings a NEW document with this
                     probability; otherwise it asks about the r-th newest
                     document the driver knows of, r Zipf(`zipf_exponent`)
                     over `resident_documents`
  question_tokens, answer_tokens  {median, sigma, min, max}, lognormal
  token_zipf_exponent  ids Zipf over the vocabulary slice
  warmup_seconds     the loop runs this long before the window opens
  script_seed        draws the scripts' shape (above)
  reference_requests how many served requests are held against
                     benchmark/reference_moe_mla.py after the window
  reference_decode_steps, reference_max_tokens

The check (after the window, after the cache is released): among the
requests that the workers were about to send when the window opened, the
shortest question on a resident document and the shortest new document
(else the second shortest question) carry `detail_steps`; the server
returns, with their tokens, the float32 logits at the prompt's last position
and at each of the first decode steps, the hidden rows under the head and
every expert layer's router input, scores and choice, all from the timed
steps; the last layer's rows of the request's document are read from the
cache itself before it is released (`document_rows`). The reference runs one
full forward over document + question + the server's own sampled ids, a
layer at a time with that layer's weights upcast, and the numbers are held
to its `TOLERANCES` by `compare`.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from benchmark import harness

DRAIN_TIMEOUT_S = 120.0
STEP_SPAN = "serve.lm.step"
ID_POOL = 1 << 21          # ids drawn once a run; documents are windows of it
REFERENCE_PAD = 2048       # the reference's sequences are padded to this


# ---------------- traffic from the seed ----------------

def lognormal(rng, spec, n):
    """n lengths drawn lognormal(median, sigma), clipped to [min, max]."""
    return np.clip(np.exp(rng.normal(math.log(spec["median"]), spec["sigma"],
                                     size=n)),
                   spec["min"], spec["max"]).round().astype(int)


class Traffic:
    """Every worker's script, and the documents the driver knows of (newest
    first). A script entry is resolved to a request when it is sent. The ids
    are the seed's, the shape the traffic file's `script_seed`'s."""

    def __init__(self, wl, seed, vocab, script_len=256):
        from mine_tpu.data.tokens import zipf_ids
        content = np.random.RandomState(harness.mix_seed(seed, "ids"))
        self.pool = zipf_ids(content, ID_POOL, vocab,
                             float(wl.get("token_zipf_exponent", 1.0)))
        self.content = content
        rng = np.random.RandomState(harness.mix_seed(
            int(wl["script_seed"]), "script"))
        n_res = int(wl["resident_documents"])
        self.documents = []         # [(doc_id, tokens)], newest first
        for k, n in enumerate(lognormal(rng, wl["document_tokens"], n_res)):
            self.documents.insert(0, ("resident%03d" % k, self.ids(int(n))))
        self.resident = list(self.documents)
        self.max_known = n_res
        workers = int(wl["workers"])
        shape = (workers, script_len)
        new = rng.random_sample(shape) < float(wl["new_document_probability"])
        new_lens = lognormal(rng, wl["document_tokens"], shape)
        questions = lognormal(rng, wl["question_tokens"], shape)
        answers = lognormal(rng, wl["answer_tokens"], shape)
        weights = 1.0 / np.arange(1, n_res + 1) ** float(
            wl.get("zipf_exponent", 1.0))
        ranks = rng.choice(n_res, size=shape, p=weights / weights.sum())
        self.scripts = [[{
            "question": int(questions[w, i]), "answer": int(answers[w, i]),
            "rank": int(ranks[w, i]),
            "new": int(new_lens[w, i]) if new[w, i] else None,
            "ids_at": content.randint(0, ID_POOL // 2, size=2)}
            for i in range(script_len)] for w in range(workers)]
        self._lock = threading.Lock()
        self._new = 0

    def ids(self, n, start=None):
        if start is None:
            start = self.content.randint(0, ID_POOL // 2)
        return self.pool[int(start):int(start) + n]

    def resolve(self, entry, detail_steps=0):
        """The request of one script entry, against the documents known at
        this moment. -> (LMRequest, kind)"""
        from mine_tpu.serve.lm_scheduler import LMRequest
        with self._lock:
            if entry["new"] is not None:
                self._new += 1
                doc = ("new%05d" % self._new,
                       self.ids(entry["new"], entry["ids_at"][0]))
                self.documents.insert(0, doc)
                del self.documents[self.max_known:]
                kind = "new_document"
            else:
                doc = self.documents[min(entry["rank"],
                                         len(self.documents) - 1)]
                kind = "question"
            question = self.ids(entry["question"], entry["ids_at"][1])
        return LMRequest(question=question, max_tokens=entry["answer"],
                         doc_id=doc[0], document=doc[1],
                         detail_steps=detail_steps), kind

    def tokens_of(self, entry):
        """Prompt + answer tokens of an entry as it would resolve now."""
        with self._lock:
            doc = (entry["new"] if entry["new"] is not None else len(
                self.documents[min(entry["rank"],
                                   len(self.documents) - 1)][1]))
        return doc + entry["question"] + entry["answer"]


class Workers:
    """The closed loop. A worker's next request is sent from the callback of
    the one before (on the server's thread, at the moment its last token is
    delivered): arrivals follow completions in the server's own order, so
    two runs of one structure compose the same steps."""

    def __init__(self, server, traffic, detail_steps):
        self.server, self.traffic = server, traffic
        self.detail_steps = detail_steps
        self.next_index = [0] * len(traffic.scripts)
        self.flagged = {}   # (worker, index) -> its request, with detail
        self.records = []   # one dict a completed request
        self.errors = []
        self.in_flight = 0
        self._lock = threading.RLock()
        self._stopped = False
        self._idle = threading.Event()

    def start(self):
        for w in range(len(self.traffic.scripts)):
            self._send(w)

    def _send(self, w):
        script = self.traffic.scripts[w]
        with self._lock:
            if self._stopped:
                if not self.in_flight:
                    self._idle.set()
                return
            i = self.next_index[w]
            self.next_index[w] = i + 1
            ready = self.flagged.get((w, i))
            request, kind = ready or self.traffic.resolve(
                script[i % len(script)])
            self.in_flight += 1
        future = self.server.submit(request)
        future.add_done_callback(
            lambda f: self._done(w, i, kind, request if ready else None, f))

    def _done(self, w, i, kind, request, future):
        with self._lock:
            self.in_flight -= 1
            if future.exception() is not None:
                self.errors.append(repr(future.exception()))
            else:
                self.records.append({"worker": w, "index": i, "kind": kind,
                                     "request": request,
                                     "result": future.result()})
        self._send(w)

    def flag_upcoming(self, how_many, max_tokens):
        """Mark, among the requests the workers send next, the shortest
        question on a known document and the shortest new document (else
        more questions), as the ones the reference will be held against."""
        with self._lock:
            upcoming = []
            for w, script in enumerate(self.traffic.scripts):
                i = self.next_index[w]
                entry = script[i % len(script)]
                upcoming.append((self.traffic.tokens_of(entry),
                                 entry["new"] is not None, w, i))
            upcoming.sort()
            short = [u for u in upcoming if u[0] <= max_tokens] or upcoming[:1]
            questions = [u for u in short if not u[1]]
            news = [u for u in short if u[1]]
            picks = (questions[:1] + news[:1] + questions[1:])[:how_many]
            for _, _, w, i in picks:
                # resolved now, so that its size is the size that was picked
                script = self.traffic.scripts[w]
                self.flagged[(w, i)] = self.traffic.resolve(
                    script[i % len(script)], self.detail_steps)
            return picks

    def stop(self):
        with self._lock:
            self._stopped = True
            if not self.in_flight:
                self._idle.set()

    def join(self, timeout):
        """The requests in flight at `stop` run to their ends."""
        return self._idle.wait(timeout)


# ---------------- set-up ----------------

def setup(cell, seed, devices, spans):
    import jax

    try:
        config = cell.program_config()
        from mine_tpu.models import moe_mla
        from mine_tpu.serve.lm_scheduler import LMRequest, build_server
    except (OSError, KeyError, ImportError) as e:
        # a program from before this family: no YAML, no keys, no module
        raise harness.BenchError("this program cannot run %s: %r"
                                 % (cell.name, e))
    if cell.chips != 1:
        raise harness.BenchError("the token server runs on one chip")
    wl = cell.workload
    cfg = moe_mla.moe_mla_config_from_dict(config)
    traffic = Traffic(wl, seed, cfg.vocab_held)
    t0 = time.perf_counter()
    server = build_server(config, seed=harness.mix_seed(seed, "weights"))
    engine = server.engine
    jax.block_until_ready(engine.cache.rows)
    harness.say("server built in %.1fs: %d step programs %s, cache %s" % (
        time.perf_counter() - t0, len(engine.buckets()), engine.buckets(),
        engine.cache.stats()))
    temp_bytes = _largest_temp_bytes(engine)
    t0 = time.perf_counter()
    futures = [server.submit(LMRequest(
        question=traffic.ids(1), max_tokens=1, doc_id=doc_id, document=doc))
        for doc_id, doc in reversed(traffic.resident)]
    for f in futures:
        f.result(timeout=600)
    resident = sum(len(d) for _, d in traffic.documents)
    harness.say("%d resident documents, %d tokens, prefilled in %.1fs "
                "(%.0f tokens/s); cache %s" % (
                    len(futures), resident, time.perf_counter() - t0,
                    resident / (time.perf_counter() - t0),
                    engine.cache.stats()))
    workers = Workers(server, traffic,
                      int(wl.get("reference_decode_steps", 16)))
    workers.start()
    time.sleep(float(wl["warmup_seconds"]))   # the workers fall out of step
    return {"cell": cell, "config": config, "server": server,
            "traffic": traffic, "workers": workers, "seed": seed,
            "spans": spans, "temp_bytes": temp_bytes, "model": moe_mla}


def _largest_temp_bytes(engine) -> int:
    """Scratch of the largest step program beside its arguments, by the
    compiler's memory analysis (the allocator's peak does not count it)."""
    try:
        analysis = engine.memory_analysis()
        peak = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)
        temp = (peak - int(analysis.argument_size_in_bytes) if peak
                else int(analysis.temp_size_in_bytes))
        harness.say("largest step program %s: %.3f GB of arguments, %.3f GB "
                    "beside them at its peak" % (
                        engine.buckets()[-1],
                        analysis.argument_size_in_bytes / 1e9, temp / 1e9))
        return max(temp, 0)
    except Exception as e:  # noqa: BLE001 - a missing analysis is not a fault
        harness.say("no memory analysis of the step program: %r" % (e,))
        return 0


# ---------------- the window ----------------

def _registry(server):
    from mine_tpu import telemetry
    server.log_gauges()
    return telemetry.REGISTRY.snapshot("serve.lm.")


def _step_records(t0, t1):
    """The program's `serve.lm.step` spans that lie inside [t0, t1]
    (time.perf_counter seconds): each span's fields, with its interval."""
    from mine_tpu import telemetry
    out = []
    for r in telemetry.spans.records(STEP_SPAN):
        if (r.t0_ns >= t0 * 1e9 and r.t1_ns <= t1 * 1e9
                and "tokens" in r.fields):
            out.append(dict(r.fields, ms=r.ms))
    return out


def measure(ctx, seconds, tracer, watch):
    wl, spans, workers = ctx["cell"].workload, ctx["spans"], ctx["workers"]
    server = ctx["server"]
    picks = workers.flag_upcoming(int(wl.get("reference_requests", 2)),
                                  int(wl.get("reference_max_tokens", 12288)))
    reg0 = _registry(server)
    spans.recording = True
    wall0 = time.time()
    t0 = time.perf_counter()
    if tracer is not None:
        tracer.start_after(0.3 * seconds)
    with spans.span("window.sleep"):
        time.sleep(seconds)
    t1 = time.perf_counter()
    wall1 = time.time()
    reg1 = _registry(server)
    spans.recording = False
    window_s = t1 - t0
    if tracer is not None:
        tracer.join()

    # ---- after the window: let the requests in flight end, read, check ----
    workers.stop()
    drained = workers.join(DRAIN_TIMEOUT_S)
    records = list(workers.records)
    gaps, ttft, latency, tokens_in = [], [], [], 0
    requests_in, prompt_in = 0, 0
    for rec in records:
        res = rec["result"]
        times = np.asarray(res.token_times)
        inside = (times >= t0) & (times <= t1)
        tokens_in += int(inside.sum())
        if t0 <= times[-1] <= t1:
            requests_in += 1
            latency.append((times[-1] - res.submitted) * 1e3)
            prompt_in += res.prompt_tokens - res.cached_tokens
        if t0 <= times[0] <= t1:
            ttft.append((times[0] - res.submitted) * 1e3)
        gaps.extend((np.diff(times)[inside[1:]] * 1e3).tolist())
    steps = _step_records(t0, t1)
    in_window = watch.between(wall0, wall1)
    dropped = reg1.get("serve.lm.dropped_tokens", 0) - reg0.get(
        "serve.lm.dropped_tokens", 0)
    server.close()
    ref = reference_check(ctx, records, picks)
    checks = {
        "no_failed_request": not workers.errors and drained
        and server.error is None,
        "no_compile_in_window": not in_window,
        "no_token_dropped": dropped == 0,
        "matches_reference": ref["ok"],
        "logits_finite_not_constant": ref.get("logits_ok", False),
    }
    harness.say("requests completed in the window %d (%d in all), answer "
                "tokens delivered in it %d, steps %d; failed %d %s; compile "
                "requests in window: %s" % (
                    requests_in, len(records), tokens_in, len(steps),
                    len(workers.errors), workers.errors[:2], in_window))
    harness.say("reference: %s" % ref)
    harness.say("checks: %s" % checks)
    traced = None
    if tracer is not None and tracer.span is not None:
        traced = _step_records(*tracer.span)
    counters = {
        "answer_tokens": tokens_in, "requests": requests_in,
        "steps": len(steps), "window_steps": steps, "traced_steps": traced,
        # a request's latency: submission to its last answer token
        "latency_p50_ms": harness.percentile(latency, 50) if latency else None,
        "latency_p95_ms": harness.percentile(latency, 95) if latency else None,
        "ttft_p50_ms": harness.percentile(ttft, 50) if ttft else None,
        "token_gap_p50_ms": harness.percentile(gaps, 50) if gaps else None,
        "token_gap_p95_ms": harness.percentile(gaps, 95) if gaps else None}
    return {
        "window_start": wall0, "window_s": window_s,
        "attempted": len(records) + len(workers.errors),
        "failed": len(workers.errors),
        "correct": all(checks.values()), "checks": checks,
        "end_to_end": {"serve_views_per_s": tokens_in / window_s},
        "counters": counters,
        "registry": {"start": reg0, "end": reg1},
        "shapes": _shapes(ctx["server"].engine.cfg),
        "temp_bytes": ctx["temp_bytes"],
        "details": {
            "requests_per_s": "%.3f" % (requests_in / window_s),
            "prompt_tokens_per_s": "%.1f prefilled (not read from resident "
                                   "pages)" % (prompt_in / window_s),
            "ttft_ms": "p50 %.1f p95 %.1f over %d first tokens" % (
                harness.percentile(ttft, 50), harness.percentile(ttft, 95),
                len(ttft)) if ttft else "none",
            "reference_errors": ref.get("errors")},
    }


def _shapes(cfg):
    """What benchmark/roofline_moe_mla.py prices a step from."""
    return {"kind": "lm_serve", "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads, "q_lora_rank": cfg.q_lora_rank,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "dense_intermediate": cfg.intermediate_size,
            "moe_intermediate": cfg.moe_intermediate_size,
            "layers": cfg.num_hidden_layers, "moe_layers": cfg.moe_layers,
            "n_routed_experts": cfg.n_routed_experts,
            "experts_held": cfg.experts_held, "vocab": cfg.vocab_held}


# ---------------- the reference check ----------------

def program_numbers(record, steps):
    """The program's side of `compare` for one served request: what its
    steps returned at the prompt's last position and the first decode steps,
    by position."""
    res = record["result"]
    last = res.prompt_tokens - 1
    rows = {d["position"]: d for d in res.detail
            if last <= d["position"] <= last + steps}
    return {"positions": sorted(rows), "rows": rows, "prompt_last": last}


def reference_numbers(params, record, positions, config, held):
    """The reference's side: one full forward over document + question + the
    server's own sampled ids, a layer at a time; at `positions` the logits,
    and what each block gives on the program's own inputs."""
    import jax.numpy as jnp

    from benchmark import reference_moe_mla as R
    cfg = R.config_from_flat(config)
    req, res = record["request"], record["result"]
    seq = np.concatenate([req.document, req.question,
                          np.asarray(res.tokens, np.int32)])
    seq = seq[:positions[-1] + 1]
    padded = -(-len(seq) // REFERENCE_PAD) * REFERENCE_PAD
    tokens = jnp.asarray(np.pad(seq, (0, padded - len(seq))))
    rows = {d["position"]: d for d in res.detail}
    pos = jnp.arange(padded)
    x = R.embed(params, tokens)
    ties = bad = 0
    latent0 = latent_last = None
    for index in range(R.num_layers(params)):
        choice = None
        if index >= 1:
            choice = {p: rows[p]["chosen"][index - 1] for p in positions}
        x, info = R.layer(x, R.layer_weights(params, index), cfg, pos, held,
                          choice)
        ties += info.get("router_ties", 0)
        bad += info.get("bad_choices", 0)
        if index == 0:
            latent0 = np.asarray(info["latent"][:len(seq)])
        if index == R.num_layers(params) - 1:
            latent_last = np.asarray(info["latent"][:len(seq)])
    at = jnp.asarray(positions)
    logits = np.asarray(R.head(params, x[at], cfg))
    hidden = jnp.stack([jnp.asarray(rows[p]["hidden"]) for p in positions])
    same_head = np.asarray(R.same_head(hidden, params["head"]))
    same_router = np.stack([np.asarray(R.same_router(
        jnp.stack([jnp.asarray(rows[p]["router_input"][i])
                   for p in positions]), params["moe"]["router"][i]))
        for i in range(R.num_layers(params) - 1)], axis=1)
    return {"logits": logits, "same_head": same_head,
            "same_router": same_router, "latent0": latent0,
            "latent_last": latent_last,
            "router_ties": ties, "bad_choices": bad}


def compare(got, want):
    """{"errors": {name: relative error}, "ok": all within TOLERANCES}."""
    from benchmark import reference_moe_mla as R
    positions, rows = got["positions"], got["rows"]
    mine = np.stack([rows[p]["logits"] for p in positions])
    per_pos = [R.rel_err(mine[i], want["logits"][i])
               for i in range(len(positions))]
    errors = {
        "logits.prefill_last": per_pos[0],
        "logits.decode": max(per_pos[1:]) if len(per_pos) > 1 else 0.0,
        "same.head": R.rel_err(mine, want["same_head"]),
        "same.router": R.rel_err(
            np.stack([rows[p]["sigma"] for p in positions]),
            want["same_router"]),
        "router.bad_choices": float(want["bad_choices"]),
    }
    errors["cache.layer0"] = R.rel_err(
        np.stack([rows[p]["cached_latent0"] for p in positions]),
        want["latent0"][positions])
    if got.get("cached_last") is not None:
        # the median over the document's rows: at a position the steps did
        # not return, the reference routes by its own scores, and a tie
        # that falls the other way there is that one row's, not the cache's
        mine_last = got["cached_last"]
        ref_last = want["latent_last"][:len(mine_last)]
        errors["cache.last"] = float(np.median(
            np.linalg.norm(mine_last - ref_last, axis=-1)
            / np.linalg.norm(ref_last, axis=-1)))
    over = [k for k, v in errors.items() if not v <= R.TOLERANCES[k]]
    finite = bool(np.isfinite(mine).all() and mine.std(axis=-1).min() > 0)
    return {"errors": {k: float("%.3g" % v) for k, v in errors.items()},
            "ok": not over, "over": over, "logits_ok": finite,
            "router_ties": int(want["router_ties"]),
            "positions": len(positions),
            "document_resident": got.get("cached_last") is not None}


def document_rows(cache, doc_id):
    """The LAST layer's rows of a resident document's whole pages as the
    cache holds them after the window (written by an earlier step, read by
    every request on the document since), float32 [tokens, width]; None
    where the document is no longer resident."""
    doc = cache.documents.get(doc_id)
    if doc is None or not doc.ready or not doc.pages:
        return None
    at = (np.asarray(doc.pages)[:, None] * cache.page_size
          + np.arange(cache.page_size)[None, :]).reshape(-1)
    return np.asarray(cache.rows[cache.layers - 1, at, :cache.width],
                      np.float32)


def reference_check(ctx, records, picks):
    """The flagged requests, each against the reference, after the cache
    is released, so that the reference fits."""
    steps = int(ctx["cell"].workload.get("reference_decode_steps", 16))
    server, cfg = ctx["server"], ctx["server"].engine.cfg
    flagged = [r for r in records if r["request"] is not None
               and r["result"].detail]
    if not flagged:
        return {"ok": False, "why": "no flagged request was served: %s"
                % (picks,)}
    t0 = time.perf_counter()
    cached_last = [document_rows(server.engine.cache, rec["request"].doc_id)
                   for rec in flagged]
    server.engine.cache.rows = None                # release the cache
    params = server.engine.params
    held = (cfg.expert_offset, cfg.experts_held)
    out, ok = [], True
    for rec, rows_last in zip(flagged, cached_last):
        got = dict(program_numbers(rec, steps), cached_last=rows_last)
        want = reference_numbers(params, rec, got["positions"],
                                 ctx["config"], held)
        result = compare(got, want)
        result.update(kind=rec["kind"], tokens=rec["result"].prompt_tokens,
                      cached=rec["result"].cached_tokens)
        ok = ok and result["ok"]
        out.append(result)
    return {"ok": ok, "logits_ok": all(r["logits_ok"] for r in out),
            "seconds": round(time.perf_counter() - t0, 1),
            "errors": [r["errors"] for r in out], "requests": out}


def teardown(ctx):
    ctx["workers"].stop()
    if not ctx["server"].close(timeout=DRAIN_TIMEOUT_S):
        raise harness.BenchError("the server's thread did not stop")
