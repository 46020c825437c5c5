"""The token server (serve/lm_scheduler.py, lm_engine.py, latent_cache.py)
at a small size on the CPU: chunked prefill, questions against a cached
document and decode through the pages against the reference's one full
forward; eviction; the scheduler's policy; serve_cli.py's front door."""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_moe_mla as R  # noqa: E402
from mine_tpu.models import moe_mla  # noqa: E402
from mine_tpu.serve.latent_cache import LatentCache  # noqa: E402
from mine_tpu.serve.lm_engine import StepOutput  # noqa: E402
from mine_tpu.serve.lm_scheduler import (LMRequest, StepScheduler,  # noqa
                                         build_server)
from test_moe_mla import TINY, YAML, reference_config, tiny_config  # noqa

SERVE = {"serve.lm.max_step_tokens": 20, "serve.lm.max_running": 4,
         "serve.lm.page_size": 8, "serve.lm.cache_tokens": 256,
         "serve.lm.chunk_buckets": [16], "serve.lm.context_buckets": [64, 96]}


@pytest.fixture(scope="module")
def server():
    """One tiny server for the module, its operands in float32 (so that it
    and the reference differ by accumulation order alone); stepped by hand."""
    saved = moe_mla.DTYPE
    moe_mla.DTYPE = jnp.float32
    try:
        srv = build_server(tiny_config(**SERVE), seed=3, start=False,
                           prompt_logits=16)
        yield srv
    finally:
        moe_mla.DTYPE = saved


def run(srv, requests):
    """Submit and step to completion on this thread. -> results"""
    futures = [srv.submit(r) for r in requests]
    for _ in range(10000):
        if not srv.step():
            break
    assert all(f.done() for f in futures)
    return [f.result() for f in futures]


def reference_logits(srv, request, result):
    config = tiny_config(**SERVE)
    seq = np.concatenate([request.document, request.question,
                          np.asarray(result.tokens, np.int32)])
    logits, _ = R.forward(srv.engine.params, jnp.asarray(seq),
                          reference_config(config), (4, 4))
    return np.asarray(logits)


def check_against_reference(srv, request, result, first_position=0):
    """Every position whose row a step returned, against the reference."""
    want = reference_logits(srv, request, result)
    seen = sorted(d["position"] for d in result.detail)
    last = result.prompt_tokens + len(result.tokens) - 2
    assert seen == list(range(first_position, last + 1)), seen
    for d in result.detail:
        assert R.rel_err(d["logits"], want[d["position"]]) < 2e-5
    # greedy: each token is the argmax of the row before it
    for i, token in enumerate(result.tokens):
        assert token == int(np.argmax(want[result.prompt_tokens - 1 + i]))


# ---- (iv) prefill in chunks, a question on the cached document, decode ----

def test_chunked_prefill_cached_question_and_decode_match_one_full_forward(
        server):
    rng = np.random.RandomState(0)
    doc_a, doc_b = rng.randint(0, 128, 37), rng.randint(0, 128, 22)
    first = LMRequest(question=rng.randint(0, 128, 5), max_tokens=6,
                      doc_id="a", document=doc_a, detail_steps=99)
    other = LMRequest(question=rng.randint(0, 128, 3), max_tokens=4,
                      doc_id="b", document=doc_b, detail_steps=99)
    res_first, res_other = run(server, [first, other])
    assert res_first.cached_tokens == 0 and res_first.prompt_tokens == 42
    check_against_reference(server, first, res_first)
    check_against_reference(server, other, res_other)
    # a second request on document a reads its four whole pages, prefills
    # the five tokens past them and its own question
    second = LMRequest(question=rng.randint(0, 128, 7), max_tokens=5,
                       doc_id="a", document=doc_a, detail_steps=99)
    (res_second,) = run(server, [second])
    assert res_second.cached_tokens == 32
    check_against_reference(server, second, res_second, first_position=32)
    cache = server.engine.cache
    assert cache.documents["a"].readers == 0 and cache.documents["a"].ready
    # own pages came back: only the two documents' pages are held
    assert cache.pages_used == 4 + 2


# ---- (v) eviction -----------------------------------------------------------

def test_an_evicted_document_is_prefilled_again_to_the_same_logits(server):
    rng = np.random.RandomState(1)
    doc = rng.randint(0, 128, 30)
    ask = lambda: LMRequest(question=np.asarray([7, 8, 9]),  # noqa: E731
                            max_tokens=3, doc_id="c", document=doc,
                            detail_steps=99)
    (before,) = run(server, [ask()])
    cache = server.engine.cache
    evictions = cache._evictions.value
    cache.evict("c")
    assert "c" not in cache.documents
    assert cache._evictions.value == evictions + 1
    (after,) = run(server, [ask()])
    assert after.cached_tokens == 0 and after.tokens == before.tokens
    for a, b in zip(before.detail, after.detail):
        assert a["position"] == b["position"]
        np.testing.assert_allclose(a["logits"], b["logits"], rtol=1e-5,
                                   atol=1e-6)


def test_a_document_being_read_is_never_evicted():
    cache = LatentCache(layers=1, tokens=80, page_size=8, width=40,
                        dtype="float32")
    assert cache.row_width == 128 and cache.num_pages == 11
    old = cache.reserve_document("old", 32)       # 4 pages
    read = cache.reserve_document("read", 24)     # 3 pages
    read.readers = 1
    cache.lookup("old")                           # now the most recent
    assert cache.pages_free == 3
    # 5 pages: the idle document goes, the one being read stays
    pages = cache.allocate(5)
    assert pages is not None and "old" not in cache.documents
    assert "read" in cache.documents and cache.pages_free == 2
    assert cache.allocate(3) is None              # only `read` is left
    with pytest.raises(RuntimeError):
        cache.evict("read")
    read.readers = 0
    assert cache.allocate(3) is not None and not cache.documents
    del old


def test_eviction_is_least_recently_used_first():
    cache = LatentCache(layers=1, tokens=64, page_size=8, width=40)
    for name in "xyz":
        cache.reserve_document(name, 16)
    cache.lookup("x")
    assert cache.allocate(3) is not None     # 2 free + the oldest idle: y
    assert list(cache.documents) == ["z", "x"]


# ---- (vi) the scheduler's policy --------------------------------------------

class FakeEngine:
    """Answers a plan with token 1 everywhere."""

    def out(self, plan):
        return StepOutput(
            decode_tokens=np.ones(len(plan.decode), np.int32),
            chunk_tokens=np.ones(1, np.int32), chunk_rows=1, bucket=(0, 0),
            expert_rows=np.zeros((1, 1), np.int64), held_pairs=0)


def test_scheduler_budget_order_and_decode_never_starved():
    cache = LatentCache(layers=1, tokens=4096, page_size=8, width=40)
    sched = StepScheduler(cache, max_step_tokens=16, max_running=3,
                          max_chunk=16, max_context=1024)
    rng = np.random.RandomState(2)
    make = lambda n, out: LMRequest(  # noqa: E731
        question=rng.randint(0, 9, n), max_tokens=out)
    short = sched.submit(make(4, 50))
    long = sched.submit(make(100, 2))
    third = sched.submit(make(6, 2))
    fourth = sched.submit(make(6, 2))
    fake, plans = FakeEngine(), []
    for _ in range(200):
        plan = sched.plan()
        if plan is None:
            break
        plans.append((plan.tokens, len(plan.decode), plan.chunk,
                      plan.chunk_tokens, [d[0] for d in plan.decode]))
        sched.commit(plan, fake.out(plan))
    # the token budget holds in every step
    assert max(p[0] for p in plans) <= 16
    # first come, first served: prompts are chunked to their end in order
    order = []
    for _, _, chunk, _, _ in plans:
        if chunk is not None and (not order or order[-1] is not chunk):
            order.append(chunk)
    assert order == [short, long, third, fourth]
    # the long prompt's chunks fill what the decode tokens leave
    assert [p[3] for p in plans if p[2] is long] == [15] * 6 + [10]
    # while it is chunked, the running sequence decodes in EVERY step
    assert all(short in p[4] for p in plans if p[2] is long)
    # at most max_running admitted: the fourth waits for a finished one
    assert max(len(set(p[4]) | ({p[2]} if p[2] is not None else set()))
               for p in plans) <= 3
    assert len(short.tokens) == 50 and len(long.tokens) == 2
    assert cache.pages_used == 0 and sched.idle()


def test_scheduler_refuses_what_cannot_fit():
    cache = LatentCache(layers=1, tokens=64, page_size=8, width=40)
    sched = StepScheduler(cache, 16, 2, 16, max_context=32)
    with pytest.raises(ValueError, match="longest context"):
        sched.submit(LMRequest(question=np.zeros(30, np.int32),
                               max_tokens=8))
    with pytest.raises(ValueError, match="max_tokens"):
        sched.submit(LMRequest(question=np.zeros(3, np.int32), max_tokens=0))


def test_lm_serve_config_is_validated():
    from mine_tpu.config import lm_serve_config_from_dict
    ok = lm_serve_config_from_dict(tiny_config(**SERVE))
    assert ok.chunk_buckets == (16,) and ok.context_buckets == (64, 96)
    for key, value in (("serve.lm.cache_tokens", 100),
                       ("serve.lm.chunk_buckets", [64]),
                       ("serve.lm.context_buckets", [60])):
        with pytest.raises(ValueError, match=key):
            lm_serve_config_from_dict(tiny_config(**dict(SERVE,
                                                         **{key: value})))


def test_spans_and_counters_of_a_served_request(server):
    from mine_tpu import telemetry
    before = telemetry.REGISTRY.snapshot("serve.lm.")
    n_spans = len(telemetry.spans.records("serve.lm.step"))
    rng = np.random.RandomState(4)
    run(server, [LMRequest(question=rng.randint(0, 128, 20), max_tokens=4)])
    after = telemetry.REGISTRY.snapshot("serve.lm.")
    delta = lambda k: after["serve.lm." + k] - before.get(  # noqa: E731
        "serve.lm." + k, 0)
    assert delta("tokens_out") == 4 and delta("prompt_tokens") == 20
    assert delta("dropped_tokens") == 0 and delta("requests_done") == 1
    assert delta("step_tokens") == 20 + 3 and delta("step_budget") == 5 * 20
    steps = [s for s in telemetry.spans.records("serve.lm.step")[n_spans:]
             if "tokens" in s.fields]
    assert [s.fields["prefill"] for s in steps] == [16, 4, 0, 0, 0]
    assert [s.fields["decode"] for s in steps] == [0, 0, 1, 1, 1]
    assert steps[1].fields["sampled_rows"] == 1
    for name in ("schedule", "dispatch", "device_wait", "readback"):
        assert after["serve.lm.%s_ms" % name]["count"] > before.get(
            "serve.lm.%s_ms" % name, {"count": 0})["count"]
    assert telemetry.spans.records("serve.lm.prefill_done")
    # every bucket's program is registered under its own name
    from mine_tpu.serve.lm_engine import program_name
    assert all(telemetry.programs.registered(program_name(b))
               for b in server.engine.buckets())


def test_next_step_is_issued_before_the_last_is_read(server, monkeypatch):
    """One step of lookahead: a decode row whose token the step in flight is
    still sampling is handed over on the device (token None, a feedback
    row), and the step before is read only after the next was issued."""
    engine, order, inputs = server.engine, [], []
    dispatch, collect = engine.dispatch, engine.collect
    monkeypatch.setattr(engine, "dispatch", lambda step: (
        order.append("issue"), inputs.append(step), dispatch(step))[-1])
    monkeypatch.setattr(engine, "collect", lambda pending: (
        order.append("read"), collect(pending))[-1])
    rng = np.random.RandomState(6)
    request = LMRequest(question=rng.randint(0, 128, 10), max_tokens=5,
                        detail_steps=99)
    (result,) = run(server, [request])
    # 1 chunk step + 4 decode steps; every read follows the next issue
    assert order == ["issue"] + ["issue", "read"] * 4 + ["read"]
    decode = [step.decode[0] for step in inputs[1:]]
    assert [d[0] for d in decode] == [None] * 4        # never from the host
    assert decode[0][3] == engine.logit_rows(16) - 1   # the chunk's last row
    assert [d[3] for d in decode[1:]] == [0, 0, 0]     # then decode row 0
    assert [d[1] for d in decode] == [10, 11, 12, 13]  # positions
    request.document = np.zeros(0, np.int32)
    check_against_reference(server, request, result)


# ---- the front door ---------------------------------------------------------

def test_serve_cli_answers_a_request_for_the_token_model(tmp_path,
                                                         monkeypatch):
    import serve_cli
    rng = np.random.RandomState(5)
    doc = rng.randint(0, 128, 40).tolist()
    requests = [{"doc_id": "d", "document": doc, "max_tokens": 3,
                 "question": rng.randint(0, 128, 4).tolist()}
                for _ in range(2)]
    data = tmp_path / "requests.jsonl"
    data.write_text("\n".join(json.dumps(r) for r in requests))
    out = tmp_path / "out"
    monkeypatch.setattr(sys, "argv", [
        "serve_cli.py", "--config_path", YAML, "--data_path", str(data),
        "--output_dir", str(out), "--seed", "1", "--extra_config",
        json.dumps(dict(TINY, **SERVE))])
    serve_cli.main()
    answers = [json.loads(line) for line in
               (out / "answers.jsonl").read_text().splitlines()]
    assert [len(a["tokens"]) for a in answers] == [3, 3]
    assert answers[0]["cached_tokens"] == 0
    assert answers[1]["cached_tokens"] == 40    # five whole pages
    log = (out / "serve.log").read_text()
    assert "lm serve stats: requests=2 tokens_out=6" in log
    assert "dropped_tokens=0" in log


# ---- one family, two members: the first one's programs are untouched --------

KIMI_STEP_PROGRAMS_SHA256 = (
    "4a1af8147def43b6b9ccab4a5846a9947f77bb00235a099a7baeea82d4fb3961")


def test_all_full_ungated_layers_lower_to_the_step_before_layer_kinds(
        monkeypatch):
    """PR 37 gave the family layer kinds, an indexer, a gate and a rescale.
    With none of them (this YAML) the step programs at this file's sizes
    lower to the text they lowered to before, byte for byte: the hash is of
    the parent commit's (5b72aba) three programs, taken with the same
    lines."""
    import hashlib

    import jax
    # (this module's server fixture runs the model in float32)
    monkeypatch.setattr(moe_mla, "DTYPE", jnp.bfloat16)
    srv = build_server(tiny_config(**SERVE), seed=3, start=False)
    engine = srv.engine
    # (conftest.py raises the matmul precision for the numerics tests; the
    # hash was taken at JAX's default, as the CLIs run)
    with jax.default_matmul_precision(None):
        text = "\n".join(
            "### %s\n%s" % (b, engine._program(b).lower(
                *engine._shapes(b)).as_text()) for b in engine.buckets())
    assert hashlib.sha256(text.encode()).hexdigest() == (
        KIMI_STEP_PROGRAMS_SHA256)
