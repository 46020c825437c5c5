"""Continuous batching of a token model at STEP granularity.

`MicroBatcher.flush` (serve/batcher.py) serves one request in one device
call. Here a request lives for hundreds of steps, and every step's token
budget (`serve.lm.max_step_tokens`) is divided: first every running
sequence's next decode token, so that no long prompt ever starves a
sequence that is answering; then one chunk of the OLDEST waiting prompt, as
large as what is left of the budget (and of the engine's largest chunk
bucket). First come, first served: a prompt is chunked to its end before the
next one starts, and at most `serve.lm.max_running` sequences are admitted.

A request is a document (optional, shared by its id), a question and a
number of answer tokens. Admission finds or reserves the document's pages in
the latent cache (serve/latent_cache.py), pins the document, and takes pages
of the request's own for the tokens past the document's last whole page, the
question and the answer. Where the model has sliding-window layers the
request also draws on the window pool: admission sets aside the most window
pages it can hold at once (`_window_need`), every step takes the pages its
tokens are written to and gives back those that lie wholly behind the window
(`_window_step`), and a new document keeps the pages that cover its last
window before its last page boundary, so that a question on it can start.
A request that cannot have its pages yet stays at
the head of the queue; everything behind it waits (no overtaking). Sampling
is greedy on the device; a request ends at its `max_tokens` (with weights
from a seed no id means "end").

The server issues step n + 1 BEFORE it reads step n (`LMServer.step`): what
step n + 1 holds follows from counts alone (a request ends at its
`max_tokens`, a prompt at its length), and a token that step n is still
sampling is handed to step n + 1 on the device (`LMEngine.dispatch`'s
feedback row). `plan` therefore books a step when it is issued (`scheduled`,
`pos`), and `commit` files its tokens when they are read.

`StepScheduler` is the policy, on the host and engine-free (plan / commit);
`LMServer` runs it against an `LMEngine` on one thread and hands out
futures; `build_server` wires both from the config as serve_cli.py and the
benchmark's driver do.

Spans: `serve.lm.step` (from a step's issue to its reading: tokens, decode,
prefill, prefill_start, decode_context, sampled_rows, bucket, pages,
expert_pairs, experts_touched; under an indexer or a window also
index_pairs, selected_pairs, window_pairs, dense_rows:
`StepPlan.shape_fields`),
`serve.lm.schedule`, `serve.lm.prefill_done` (a request's first token: its
time to first token). Counters `serve.lm.tokens_out`, `.prompt_tokens`,
`.prompt_tokens_cached`, `.step_tokens`, `.step_budget`, `.requests_done`;
gauges `serve.lm.expert_tokens.<e>` (at log cadence), `.running`, `.waiting`.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import threading
import time
from typing import Any, Deque, Dict, Hashable, List, Optional

import numpy as np

from mine_tpu import telemetry
from mine_tpu.serve.latent_cache import (Document, LatentCache,
                                         WindowTable)
from mine_tpu.serve.lm_engine import LMEngine, StepInput, StepOutput

LOG_EVERY_STEPS = 50   # the cadence of the expert-load gauges


@dataclasses.dataclass
class LMRequest:
    question: np.ndarray                    # token ids
    max_tokens: int
    doc_id: Optional[Hashable] = None       # a shared document's id
    document: Optional[np.ndarray] = None   # its token ids (always sent)
    # keep the detail (logits, hidden row, routing) of the prompt rows a
    # step returns and of this many decode steps; 0: none is read back
    detail_steps: int = 0


@dataclasses.dataclass
class LMResult:
    tokens: List[int]
    token_times: List[float]          # time.perf_counter() at each delivery
    submitted: float
    prompt_tokens: int
    cached_tokens: int                # of them, read from resident pages
    # detail_steps: for each position whose row a step returned (the last
    # `prompt_logits` of every chunk, the first decode steps), its
    # float32 logits, the hidden row under the head, each expert layer's
    # router input, scores and choice, layer 0's row as the cache holds it
    detail: List[Dict[str, Any]]


class Sequence:
    """An admitted request: the logical prompt [document | question], its
    block table [document's shared pages | own pages], where its prefill
    stands, and what it has generated."""

    def __init__(self, request: LMRequest, future, submitted: float):
        doc = (np.asarray(request.document, np.int32)
               if request.document is not None else np.zeros(0, np.int32))
        self.request, self.future, self.submitted = request, future, submitted
        self.prompt = np.concatenate(
            [doc, np.asarray(request.question, np.int32)])
        self.doc_tokens = len(doc)
        self.document: Optional[Document] = None
        self.own_pages: List[int] = []
        self.table: List[int] = []
        self.window: Optional[WindowTable] = None   # sliding layers' pages
        self.window_budget = 0        # set aside for it and not yet taken
        self.pos = 0                  # next prompt position to prefill
        self.cached = 0               # prompt tokens found resident
        self.tokens: List[int] = []   # delivered
        self.scheduled = 0            # tokens that issued steps will sample
        self.feedback_row = -1        # where the step in flight samples one
        self.times: List[float] = []
        self.detail: List[Dict[str, np.ndarray]] = []

    @property
    def prefilled(self) -> bool:
        return self.pos >= len(self.prompt)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.request.max_tokens

    @property
    def wants_detail(self) -> bool:
        return self.scheduled <= self.request.detail_steps > 0

    @property
    def length(self) -> int:
        """Prompt and delivered tokens."""
        return len(self.prompt) + len(self.tokens)


@dataclasses.dataclass
class StepPlan:
    """One step as it was booked: `decode` (sequence, its input token or
    None with the feedback row, the token's position), one prompt's chunk,
    and the sequences whose rows' detail is kept."""
    decode: List[Any]
    chunk: Optional[Sequence]
    chunk_start: int = 0
    chunk_tokens: int = 0
    completes: bool = False
    detail: Any = ()
    index_topk: int = 0      # the model's: what `shape_fields` counts by
    window: int = 0

    @property
    def tokens(self) -> int:
        return len(self.decode) + self.chunk_tokens

    def engine_input(self) -> StepInput:
        chunk = None
        if self.chunk is not None:
            s = self.chunk
            chunk = (s.prompt[self.chunk_start:self.chunk_start
                              + self.chunk_tokens], self.chunk_start, s.table,
                     s.window)
        return StepInput(
            chunk=chunk, want_logits=bool(self.detail),
            decode=[(token, position, s.table, row, s.window)
                    for s, token, row, position in self.decode])

    def shape_fields(self) -> Dict[str, int]:
        """What the step's span says of its shape (the benchmark prices a
        step's work from these)."""
        fields = {"tokens": self.tokens, "decode": len(self.decode),
                  "prefill": self.chunk_tokens,
                  "prefill_start": self.chunk_start,
                  "decode_context": sum(p + 1 for _, _, _, p in self.decode),
                  "sampled_rows": len(self.decode) + int(self.completes)}
        if self.index_topk or self.window:
            # keys each row sees (a full layer's (query, key) pairs: what
            # the indexer scores), of them selected, and inside the window
            sees = np.concatenate([
                self.chunk_start + 1 + np.arange(self.chunk_tokens),
                np.asarray([p + 1 for _, _, _, p in self.decode], np.int64)])
            fields["index_pairs"] = int(sees.sum())
            if self.index_topk:
                fields["selected_pairs"] = int(
                    np.minimum(sees, self.index_topk).sum())
                fields["dense_rows"] = int((sees <= self.index_topk).sum())
            if self.window:
                fields["window_pairs"] = int(
                    np.minimum(sees, self.window).sum())
        return fields


class StepScheduler:
    def __init__(self, cache: LatentCache, max_step_tokens: int,
                 max_running: int, max_chunk: int, max_context: int,
                 index_topk: int = 0):
        self.cache = cache
        self.index_topk = int(index_topk)
        self.max_step_tokens = int(max_step_tokens)
        self.max_running = int(max_running)
        self.max_chunk = int(max_chunk)
        self.max_context = int(max_context)
        self.waiting: Deque[Sequence] = collections.deque()
        self.running: List[Sequence] = []     # admitted, in arrival order
        self.prefilling: Optional[Sequence] = None
        self._lock = threading.Lock()
        self._c = {k: telemetry.counter("serve.lm." + k) for k in (
            "tokens_out", "prompt_tokens", "prompt_tokens_cached",
            "step_tokens", "step_budget", "requests_done")}

    def submit(self, request: LMRequest, future=None) -> Sequence:
        seq = Sequence(request, future, time.perf_counter())
        if len(seq.prompt) == 0 or request.max_tokens < 1:
            raise ValueError("a request needs a prompt and max_tokens >= 1")
        if len(seq.prompt) + request.max_tokens > self.max_context:
            raise ValueError("prompt + max_tokens = %d exceeds the longest "
                             "context %d" % (len(seq.prompt)
                                             + request.max_tokens,
                                             self.max_context))
        with self._lock:
            self.waiting.append(seq)
        return seq

    def idle(self) -> bool:
        with self._lock:
            return not self.waiting and not self.running

    # ---- admission ----

    def _admit(self, seq: Sequence) -> bool:
        """Pages for the head of the queue; False (nothing changed) where
        they cannot be had yet."""
        cache, ps = self.cache, self.cache.page_size
        doc, reserved = None, False
        if seq.request.doc_id is not None and seq.doc_tokens >= ps:
            doc = cache.lookup(seq.request.doc_id)
            if doc is None:
                doc = cache.reserve_document(seq.request.doc_id,
                                             seq.doc_tokens)
                if doc is None:
                    return False
                reserved = True
            doc.readers += 1    # pinned before the next allocation evicts
        shared = 0 if doc is None else doc.tokens
        own = cache.allocate(cache.pages_for(
            len(seq.prompt) + seq.request.max_tokens - shared))
        need = self._window_need(seq, doc)
        if own is None or not cache.reserve_window(need):
            if own is not None:
                cache.release(own)
            if doc is not None:
                doc.readers -= 1
                if reserved:
                    cache.drop_document(doc.doc_id)
            return False
        seq.document, seq.own_pages = doc, own
        seq.table = ([] if doc is None else list(doc.pages)) + own
        if doc is not None and doc.ready:
            seq.pos = seq.cached = doc.tokens
        if cache.window:
            seq.window, seq.window_budget = WindowTable(), need
            if seq.cached and doc.window is not None:
                seq.window = WindowTable(doc.window.first, doc.window.pages,
                                         doc.window.pages)
        self._c["prompt_tokens"].inc(len(seq.prompt))
        self._c["prompt_tokens_cached"].inc(seq.cached)
        return True

    def _release(self, seq: Sequence) -> None:
        self.cache.release(seq.own_pages)
        if seq.window is not None:
            self.cache.give_window(seq.window.owned(), reserve=False)
            self.cache.unreserve_window(seq.window_budget)
        if seq.document is not None:
            seq.document.readers -= 1
        self.running.remove(seq)

    # ---- the window pool (sliding layers) ----

    def _window_keep(self, doc_tokens: int):
        """(first, end) page numbers of the window pages a document keeps:
        those that hold its last window before its last page boundary."""
        ps = self.cache.page_size
        return (max(doc_tokens - (self.cache.window - 1), 0) // ps,
                doc_tokens // ps)

    def _window_need(self, seq: Sequence, doc: Optional[Document]) -> int:
        """The most window pages the sequence holds at once: a chunk's rows
        and the window before them, never more than its own length spans;
        and what its document will keep, where it writes one."""
        if not self.cache.window:
            return 0
        pages_for, back = self.cache.pages_for, self.cache.window - 1
        start = doc.tokens if doc is not None and doc.ready else 0
        total = len(seq.prompt) + seq.request.max_tokens - max(start - back,
                                                               0)
        need = min(pages_for(total), pages_for(back + self.max_chunk)) + 1
        if doc is not None and not doc.ready:
            first, end = self._window_keep(doc.tokens)
            need += end - first
        return need

    def _window_step(self, seq: Sequence, first_read: int, last_written: int):
        """Before a step that reads the sequence's window rows from position
        `first_read` on and writes up to `last_written`: give back the pages
        wholly behind the window, take those the new rows need."""
        cache, table, ps = self.cache, seq.window, self.cache.page_size
        drop = min(max(first_read, 0) // ps - table.first, len(table.pages))
        if drop > 0:
            gone = table.drop(drop)
            cache.give_window(gone, reserve=True)
            seq.window_budget += len(gone)
        if not table.pages:
            table.first = max(table.first, max(first_read, 0) // ps)
        more = last_written // ps + 1 - (table.first + len(table.pages))
        if more > 0:
            table.pages.extend(cache.take_window(more))
            seq.window_budget -= more

    def _window_hand_over(self, seq: Sequence, doc: Document) -> None:
        """The document is whole: the window pages at its end are its own
        from here on (the sequence reads on, and no longer gives them back)."""
        first, end = self._window_keep(doc.tokens)
        at = first - seq.window.first
        doc.window = WindowTable(first, seq.window.pages[at:at + end - first])
        seq.window.shared |= set(doc.window.pages)

    # ---- one step ----

    def plan(self) -> Optional[StepPlan]:
        """The next step, booked: positions and counts advance now, tokens
        arrive with `commit`."""
        decode = []
        for s in self.running:
            if s.prefilled and s.scheduled < s.request.max_tokens:
                in_flight = s.scheduled > len(s.tokens)
                decode.append((s, None if in_flight else s.tokens[-1],
                               s.feedback_row,
                               len(s.prompt) + s.scheduled - 1))
        if self.prefilling is None and len(self.running) < self.max_running:
            with self._lock:
                head = self.waiting[0] if self.waiting else None
            if head is not None and self._admit(head):
                with self._lock:
                    self.waiting.popleft()
                self.running.append(head)
                self.prefilling = head
        budget = min(self.max_step_tokens - len(decode), self.max_chunk)
        plan = StepPlan(decode=decode, chunk=None, index_topk=self.index_topk,
                        window=self.cache.window)
        if self.prefilling is not None and budget > 0:
            s = self.prefilling
            plan.chunk, plan.chunk_start = s, s.pos
            plan.chunk_tokens = min(budget, len(s.prompt) - s.pos)
        if not plan.tokens:
            return None
        plan.detail = {id(s) for s in [d[0] for d in decode] + (
            [plan.chunk] if plan.chunk is not None else []) if s.wants_detail}
        back = self.cache.window - 1
        for s, _, _, position in decode:
            s.scheduled += 1
            if s.window is not None:
                self._window_step(s, position - back, position)
        if plan.chunk is not None:
            s = plan.chunk
            if s.window is not None:
                self._window_step(s, s.pos - back,
                                  s.pos + plan.chunk_tokens - 1)
            s.pos += plan.chunk_tokens
            doc = s.document
            if doc is not None and not doc.ready and s.pos >= doc.tokens:
                doc.ready = True    # a later step reads what this one writes
                if s.window is not None:
                    self._window_hand_over(s, doc)
            if s.prefilled:
                plan.completes = True
                self.prefilling = None
                s.scheduled = 1
        self._c["step_tokens"].inc(plan.tokens)
        self._c["step_budget"].inc(self.max_step_tokens)
        return plan

    def issued(self, plan: StepPlan, pending) -> None:
        """Where the issued step samples each sequence's next token."""
        for i, (s, _, _, _) in enumerate(plan.decode):
            s.feedback_row = i
        if plan.completes:
            plan.chunk.feedback_row = pending.chunk_row

    def commit(self, plan: StepPlan, out: StepOutput) -> List[Sequence]:
        """What the step produced, filed: tokens are delivered, finished
        sequences give their pages back."""
        now = time.perf_counter()
        for i, (s, _, _, _) in enumerate(plan.decode):
            self._deliver(plan, s, int(out.decode_tokens[i]), now, out, i)
        if plan.chunk is not None:
            s = plan.chunk
            # the chunk's last `chunk_rows` rows are the step's last rows
            k = out.chunk_rows
            first = (0 if out.detail is None
                     else len(out.detail["logits"]) - k)
            end = plan.chunk_start + plan.chunk_tokens
            for r in range(k - 1 if plan.completes else k):
                self._keep(plan, s, end - k + r, out, first + r)
            if plan.completes:
                telemetry.spans.record(
                    "serve.lm.prefill_done", int(s.submitted * 1e9),
                    int(now * 1e9), prompt=len(s.prompt), cached=s.cached)
                self._deliver(plan, s, int(out.chunk_tokens[-1]), now, out,
                              first + k - 1)
        finished = [s for s in self.running if s.done]
        for s in finished:
            self._release(s)
            self._c["requests_done"].inc()
            if s.future is not None:
                s.future.set_result(LMResult(
                    tokens=s.tokens, token_times=s.times,
                    submitted=s.submitted, prompt_tokens=len(s.prompt),
                    cached_tokens=s.cached, detail=s.detail))
        return finished

    def _deliver(self, plan: StepPlan, s: Sequence, token: int, now: float,
                 out: StepOutput, row: int) -> None:
        """`token` was sampled from the step's row `row`, which stood at the
        sequence's last position."""
        self._keep(plan, s, s.length - 1, out, row)
        s.tokens.append(token)
        s.times.append(now)
        self._c["tokens_out"].inc()

    @staticmethod
    def _keep(plan: StepPlan, s: Sequence, position: int, out: StepOutput,
              row: int) -> None:
        if out.detail is None or id(s) not in plan.detail:
            return
        # a row of what the step returned: by row, or by layer and row
        by_row = ("logits", "hidden", "cached_latent0")
        s.detail.append(dict(
            {k: v[row] if k in by_row else v[:, row]
             for k, v in out.detail.items()}, position=position))


class LMServer:
    """One thread that plans, runs and commits steps while there is work;
    `submit` returns a future of an `LMResult`."""

    THREAD_NAME = "mine-tpu-lm-server"

    def __init__(self, engine: LMEngine, scheduler: StepScheduler,
                 start: bool = True):
        self.engine, self.scheduler = engine, scheduler
        self._wake = threading.Condition()
        self._stop = False
        self._pending = None     # (plan, the engine's handle) of a step issued
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=self.THREAD_NAME)
        self._gauges = (telemetry.gauge("serve.lm.running"),
                        telemetry.gauge("serve.lm.waiting"))
        if start:
            self._thread.start()

    def submit(self, request: LMRequest) -> "concurrent.futures.Future":
        future: "concurrent.futures.Future" = concurrent.futures.Future()
        if self.error is not None:
            future.set_exception(self.error)
            return future
        self.scheduler.submit(request, future)
        with self._wake:
            self._wake.notify()
        return future

    def step(self) -> bool:
        """Issue the next step, if there is one to issue, THEN read and file
        the one before it. False when there was neither."""
        sched, before = self.scheduler, self._pending
        with telemetry.span("serve.lm.schedule") as sp:
            plan = sched.plan()
            sp.histogram = plan is not None
        self._pending = None
        if plan is not None:
            pending = self.engine.dispatch(plan.engine_input())
            sched.issued(plan, pending)
            self._pending = (plan, pending)
        if before is not None:
            plan, pending = before
            out = self.engine.collect(pending)
            sched.commit(plan, out)
            telemetry.spans.record(
                "serve.lm.step", pending.t0_ns, time.perf_counter_ns(),
                bucket=out.bucket[0], pages=out.bucket[1],
                expert_pairs=out.held_pairs,
                experts_touched=int((out.expert_rows > 0).sum()),
                **plan.shape_fields())
            if self.engine.steps % LOG_EVERY_STEPS == 0:
                self.log_gauges()
        return self._pending is not None or before is not None

    def log_gauges(self) -> None:
        for e, rows in enumerate(self.engine.expert_rows_total().sum(axis=0)):
            telemetry.gauge("serve.lm.expert_tokens.%d" % e).set(float(rows))
        self._gauges[0].set(len(self.scheduler.running))
        self._gauges[1].set(len(self.scheduler.waiting))

    def _loop(self) -> None:
        try:
            while True:
                with self._wake:
                    while (not self._stop and self._pending is None
                           and self.scheduler.idle()):
                        self._wake.wait(timeout=0.5)
                    if self._stop:
                        return
                if not self.step():
                    # the head of the queue waits for pages that only a
                    # finishing sequence frees; none is running: cannot be
                    time.sleep(0.001)
        except BaseException as e:  # noqa: BLE001 - handed to every waiter
            self.error = e
            sched = self.scheduler
            for s in list(sched.running) + list(sched.waiting):
                if s.future is not None and not s.future.done():
                    s.future.set_exception(e)

    def close(self, timeout: float = 60.0) -> bool:
        with self._wake:
            self._stop = True
            self._wake.notify()
        if self._thread.is_alive():
            self._thread.join(timeout)
        self.log_gauges()
        return not self._thread.is_alive()


def build_server(config: Dict[str, Any], seed: int = 0, start: bool = True,
                 prompt_logits: int = 1) -> LMServer:
    """The token model's server from the config (`lm.*`, `serve.lm.*`):
    weights from `seed` in one jitted program, the latent
    cache (rows in the residual stream's dtype), the engine with every
    bucket warmed, the scheduler. `prompt_logits` (the rows at a chunk's end
    whose logits a step returns) is more than 1 only in tests."""
    import jax

    from mine_tpu.config import lm_serve_config_from_dict
    from mine_tpu.models import moe_mla
    cfg = moe_mla.moe_mla_config_from_dict(config)
    serve = lm_serve_config_from_dict(config)
    params = jax.jit(lambda s: moe_mla.init_params(
        jax.random.key(s, impl="rbg"), cfg))(seed)
    swa = moe_mla.of_kind(cfg, moe_mla.SLIDING)
    sliding = cfg.layers_of(moe_mla.SLIDING)
    cache = LatentCache(
        cfg.layers_of(moe_mla.FULL), serve.cache_tokens, serve.page_size,
        cfg.latent_width, moe_mla.DTYPE,
        index_width=cfg.index_head_dim if cfg.index_topk else 0,
        window_layers=sliding, window_tokens=serve.window_cache_tokens,
        window_width=swa.latent_width if sliding else 0,
        window=swa.window if sliding else 0)
    engine = LMEngine(cfg, params, cache, max_running=serve.max_running,
                      chunk_buckets=serve.chunk_buckets,
                      page_buckets=[-(-c // serve.page_size)
                                    for c in serve.context_buckets],
                      prompt_logits=prompt_logits)
    engine.warmup()
    scheduler = StepScheduler(cache, serve.max_step_tokens, serve.max_running,
                              engine.max_chunk, engine.max_context,
                              index_topk=cfg.index_topk)
    return LMServer(engine, scheduler, start=start)
