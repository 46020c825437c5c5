"""The token model's serving engine: one jitted STEP program a bucket, in which
a chunk of one prompt and every running sequence's next decode token share
the projections, the router and the experts, while attention is taken per
sequence: the chunk in the up-projected form against its cached prefix, the
decode tokens in the absorbed (latent-space) form through their block tables
(models/moe_mla.py, kernels/attention.py).

Where the model has them (`lm.layer_types`, `lm.index_topk`), a layer's kind
decides its attention and its cache: a full layer under an indexer scores
every cached index key, selects exactly (`moe_mla.dsa_threshold`) and attends
the selection alone: a chunk densely under the selection's mask
(up-projected) while that form's transients fit `DENSE_SELECTED_BYTES`, a
chunk on a longer block table and every decode row over the gathered rows
(latent space); a sliding layer attends its window: the chunk over its own
rows and the window before them, a decode row over its gathered window,
both out of the window pool (`LatentCache.window_rows`), whose rows the
host names a step at a time (`_ints`: where a row is written, the window
before the chunk, a decode row's window). The expert layers run as one
scan over whole periods of the layer pattern; with every layer full, dense
and ungated the step is the one program it was before there were kinds.

A step's rows are [chunk rows (Tc) | decode rows (max_running)]; a bucket is
(Tc, P): Tc of `chunk_buckets` (0: decode only), P pages of `page_buckets`
in the CHUNK's block table (its cached prefix and itself, rounded up: what
the up-projected form pays for). The decode rows' block tables always span
the longest sequence: the paged kernel's work follows each sequence's own
length. Every bucket is compiled by `warmup()`; a step never compiles.
Padded rows carry token 0 at a valid position, write their latent rows to
page 0 and are kept out of the experts.

What a step returns, for its `logit_rows` (every decode row, and the last
`prompt_logits` rows of the chunk): the greedy sample over the vocabulary
slice (taken on the device), and, for whoever asks (`LMRequest.want_logits`;
the benchmark's check), the float32 logits, the normed hidden rows under the
head, each expert layer's router input, scores and choice, and layer 0's
latent rows as the cache holds them (and, under an indexer or a window,
every layer's attention output and the full layers' index queries, weights,
scores and S: `detail_names`). Also every
held expert's rows (the load) and the (token, expert) pairs held here: no
pair is ever dropped, and the difference of the two is the counter that
says so.

A step is issued (`dispatch`) and read (`collect`) apart, so that the server
can issue step n + 1 before it has read step n: a decode row whose token
step n is still sampling takes it ON THE DEVICE from step n's `sampled`
array (`feedback`), and the host is out of the device's way.

Spans `serve.lm.dispatch`, `serve.lm.device_wait`, `serve.lm.readback`;
every bucket's program is registered with `telemetry.programs` under
`program_name(bucket)`.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from mine_tpu import telemetry
from mine_tpu.models import moe_mla
from mine_tpu.serve.latent_cache import LatentCache

STEP_PROGRAM = "_lm_serve_step_impl"
# what a step returns of its logit rows to whoever asks (`detail_steps`)
DETAIL = ("logits", "hidden", "router_input", "sigma", "chosen",
          "cached_latent0")
# and where the model selects keys or slides a window (`detail_names`): every
# layer's attention output, the full layers' index queries, weights, scores
# over the context and S (positions, -1 where a row sees fewer)
DETAIL_ATTN = ("attn_out",)
DETAIL_INDEX = ("index_q", "index_w", "index_scores", "selected")


def detail_names(cfg) -> Tuple[str, ...]:
    sliding = moe_mla.SLIDING in cfg.kinds
    return (DETAIL + (DETAIL_ATTN if cfg.index_topk or sliding else ())
            + (DETAIL_INDEX if cfg.index_topk else ()))


@dataclasses.dataclass
class StepInput:
    """One step as the scheduler composed it. `chunk`: (tokens [n],
    first position, block table, window table) of one prompt's next chunk,
    or None; `decode`: (token, position, block table, feedback row, window
    table) of each running sequence: the token on the host, or None and the
    row of the step before (decode row i: i; a chunk's last token:
    `Pending.chunk_row`) that is sampling it. A window table
    (`latent_cache.WindowTable`) only where the model has sliding layers."""
    chunk: Optional[Tuple[np.ndarray, int, List[int], Any]]
    decode: List[Tuple[Optional[int], int, List[int], int, Any]]
    want_logits: bool = False


@dataclasses.dataclass
class Pending:
    """A step that was issued and not yet read."""
    step: StepInput
    bucket: Tuple[int, int]
    sampled: Any
    aux: Dict[str, Any]
    chunk_row: int          # the row of `sampled` of the chunk's last token
    t0_ns: int


@dataclasses.dataclass
class StepOutput:
    decode_tokens: np.ndarray            # [len(decode)] sampled ids
    chunk_tokens: Optional[np.ndarray]   # samples at the chunk's last rows
    chunk_rows: int                      # how many of them are real rows
    bucket: Tuple[int, int]
    expert_rows: np.ndarray              # [expert layers, held]
    held_pairs: int
    detail: Optional[Dict[str, np.ndarray]] = None   # want_logits


# A chunk's attention over its selection is DENSE under the selection's mask
# (`moe_mla.masked_attend`) while what that form alone holds stays within
# this many bytes (`selected_dense_bytes`), and runs over the GATHERED rows
# beyond. Readings at dots3_note_ep16_d9's widths, 2,048 rows a chunk, a v5e
# (PERF.md section 6, PR 37). A block table of 65,536 tokens (0.67 GB by the
# count below): a step 345 ms dense, 598 ms gathered, on the chip. One of
# 133,120 tokens (1.36 GB): the dense program asks for 3.75 GiB beside
# 12.04 GiB of arguments, 15.79 of the 15.75 GiB a v5e has, and is refused
# (compiled for a described v5e; with 8 heads a group it is taken with 0.27
# GiB to spare, which no run could count on); the gathered one 2.61 GiB.
DENSE_SELECTED_BYTES = 1 << 30


def selected_dense_bytes(cfg, chunk_rows: int, tokens: int) -> int:
    """What the dense form holds that the gathered form does not, for a
    chunk of `chunk_rows` on a block table of `tokens`: one head group's
    up-projected keys and values over the table, and the int8 mask."""
    group = min(moe_mla.DENSE_HEADS, cfg.num_attention_heads)
    values = group * (cfg.qk_nope_head_dim + cfg.v_head_dim)
    return tokens * (values * np.dtype(moe_mla.DTYPE).itemsize + chunk_rows)


def _lin(i, a: int, b: int):
    """i * a + b of a traced i and two Python numbers, without the
    operations that do nothing."""
    out = i if a == 1 else i * a
    return out if b == 0 else out + b


def _step_impl(params, cache_rows, ints, feedback, *, cfg, chunk_rows, pages,
               decode_pages, running, logit_rows, page_size, impl,
               dense_selected=False):
    """See the module docstring. `cache_rows`: `LatentCache.arrays()`;
    `ints`: the step's integers packed into one array (`_pack`); `feedback`:
    the step before's `sampled`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    FULL, SLIDING = moe_mla.FULL, moe_mla.SLIDING
    alone = not isinstance(cache_rows, dict)    # one kind of row, bare
    if alone:
        cache_rows = {"latent": cache_rows}
    Tc, P, Pd, D, R = chunk_rows, pages, decode_pages, running, logit_rows
    T = Tc + D
    W = cfg.sliding_window_size if SLIDING in cfg.kinds else 0
    back = W - 1 if Tc else 0        # a chunk's window rows before itself
    at = iter(np.cumsum([0, T, T, T, T, T, P, D * Pd, D, R, 1]
                        + ([T, back, D * W] if W else [])))
    take = lambda n: lax.dynamic_slice(ints, (int(next(at)),), (n,))  # noqa
    tokens, positions, slots, valid = take(T), take(T), take(T), take(T)
    source = take(T)    # >= 0: the row of `feedback` that holds the token
    tokens = jnp.where(source >= 0, feedback[jnp.maximum(source, 0)], tokens)
    chunk_table, dec_tables, dec_lens = take(P), take(D * Pd), take(D)
    rows_out, chunk_offset = take(R), take(1)[0]
    dec_tables = dec_tables.reshape(D, Pd)
    valid = valid > 0
    if W:   # the window pool's rows: written, before the chunk, a decode row
        window_slots, window_back = take(T), take(back)
        window_dec = take(D * W).reshape(D, W)
    ropes = {kind: moe_mla.rope_tables(positions, moe_mla.of_kind(cfg, kind))
             for kind in dict.fromkeys(cfg.kinds)}
    rich = bool(cfg.index_topk or W)    # the detail the newer kinds return

    def attend_dense(q_nope, q_rope, cache, w, k, layer):
        outs = []
        if Tc:
            ctx_rows = (chunk_table[:, None] * page_size
                        + jnp.arange(page_size)[None, :]).reshape(-1)
            ctx = cache[layer, ctx_rows][:, :k.latent_width]
            outs.append(moe_mla.mla_prefill(q_nope[:Tc], q_rope[:Tc], ctx, w,
                                            k, chunk_offset, impl))
        outs.append(moe_mla.mla_decode(q_nope[Tc:], q_rope[Tc:], cache,
                                       layer, dec_tables, dec_lens, w, k,
                                       page_size, impl))
        return outs

    def attend_selected(q_nope, q_rope, caches, w, k, layer, inputs, cos, sin):
        """A full layer under the indexer: scores, S, the gathered rows."""
        q_i, k_i, w_i = moe_mla.dsa_project(*inputs, w, k, cos, sin)
        index = caches["index"].at[layer, slots].set(
            k_i.astype(caches["index"].dtype))
        cache = caches["latent"]
        flat = cache.reshape(-1, cache.shape[-1])
        base = layer * cache.shape[1]
        outs, scores, chosen = [], [], []
        if Tc:
            ctx_rows = (chunk_table[:, None] * page_size
                        + jnp.arange(page_size)[None, :]).reshape(-1)
            sc = moe_mla.dsa_index(q_i[:Tc], w_i[:Tc], index[layer, ctx_rows],
                                   chunk_offset, impl)
            sees = jnp.minimum(chunk_offset + 1 + jnp.arange(Tc),
                               P * page_size)
            tau, bound = moe_mla.dsa_threshold(sc, sees, k)
            mine = rows_out[D:]
            if dense_selected:
                # a short context: dense under the selection's mask; only
                # the returned rows' S is written out as positions
                outs.append(moe_mla.masked_attend(
                    q_nope[:Tc], q_rope[:Tc],
                    cache[layer, ctx_rows][:, :k.latent_width],
                    moe_mla.dsa_mask(sc, tau, bound), w, k, chunk_offset,
                    impl))
                ids, ok = moe_mla.dsa_positions(
                    sc[mine], sees[mine], tau[mine], bound[mine], k)
                chosen.append(jnp.where(ok, ids, -1))
            else:
                ids, ok, at_rows = moe_mla.dsa_positions(
                    sc, sees, tau, bound, k, chunk_table, page_size)
                outs.append(moe_mla.gathered_attend(
                    q_nope[:Tc], q_rope[:Tc], flat, base + at_rows, ok, w, k,
                    "lm_dsa_prefill"))
                chosen.append(jnp.where(ok, ids, -1)[mine])
            scores.append(jnp.pad(sc[mine], ((0, 0), (0, (Pd - P)
                                                      * page_size))))
        sc = moe_mla.dsa_index_paged(q_i[Tc:], w_i[Tc:], index, layer,
                                     dec_tables, dec_lens, page_size)
        tau, bound = moe_mla.dsa_threshold(sc, dec_lens, k)
        ids, ok, at_rows = moe_mla.dsa_positions(
            sc, dec_lens, tau, bound, k, dec_tables, page_size)
        outs.append(moe_mla.gathered_attend(
            q_nope[Tc:], q_rope[Tc:], flat, base + at_rows, ok, w, k,
            "lm_dsa_decode"))
        K = ids.shape[1]
        chosen = [jnp.where(ok, ids, -1)] + [
            jnp.pad(c, ((0, 0), (0, K - c.shape[1])), constant_values=-1)
            for c in chosen]
        detail = {"index_q": q_i[rows_out].reshape(R, -1),
                  "index_w": w_i[rows_out],
                  "index_scores": jnp.concatenate([sc] + scores, axis=0),
                  "selected": jnp.concatenate(chosen, axis=0)}
        return outs, dict(caches, index=index), detail

    def attend_window(q_nope, q_rope, latent, caches, w, k, layer):
        """A sliding layer: the chunk over its own rows and the window
        before it, a decode row over its gathered window."""
        win = caches["window"]
        row = jnp.pad(latent, ((0, 0), (0, win.shape[-1] - k.latent_width)))
        win = win.at[layer, window_slots].set(row.astype(win.dtype))
        flat = win.reshape(-1, win.shape[-1])
        base = layer * win.shape[1]
        outs = []
        if Tc:
            before = flat[base + window_back][:, :k.latent_width]
            ctx = jnp.concatenate([before.astype(latent.dtype), latent[:Tc]],
                                  axis=0)
            outs.append(moe_mla.mla_prefill(
                q_nope[:Tc], q_rope[:Tc], ctx, w, k, back, impl,
                first_valid=jnp.maximum(back - chunk_offset, 0)))
        seen = (dec_lens[:, None] - W + jnp.arange(W)[None, :]) >= 0
        outs.append(moe_mla.gathered_attend(
            q_nope[Tc:], q_rope[Tc:], flat, base + window_dec, seen, w, k,
            "lm_swa_decode"))
        return outs, dict(caches, window=win)

    def attend(x, caches, w, kind, layer):
        """One attention sub-layer of `kind`, its rows in layer `layer` of
        its kind's cache. -> (x', caches', detail)"""
        k, (cos, sin) = moe_mla.of_kind(cfg, kind), ropes[kind]
        inputs = None
        if k.index_topk or k.attention_gate_type:
            with jax.named_scope(moe_mla._scope(k, "proj")):
                inputs = moe_mla.mla_inputs(x, w, k)
        q_nope, q_rope, latent = moe_mla.mla_project(x, w, k, cos, sin,
                                                     inputs)
        detail = {}
        if kind == SLIDING:
            outs, caches = attend_window(q_nope, q_rope, latent, caches, w,
                                         k, layer)
        else:
            cache = caches["latent"]
            latent = jnp.pad(latent, ((0, 0), (
                0, cache.shape[-1] - k.latent_width))).astype(cache.dtype)
            caches = dict(caches, latent=cache.at[layer, slots].set(latent))
            if k.index_topk:
                outs, caches, detail = attend_selected(
                    q_nope, q_rope, caches, w, k, layer, inputs, cos, sin)
            else:
                outs = attend_dense(q_nope, q_rope, caches["latent"], w, k,
                                    layer)
        o = jnp.concatenate(outs, axis=0)
        if k.attention_gate_type:
            o = moe_mla.attention_gate(o, inputs[0], w, k)
        after = moe_mla.attention_out(x, o, w, k)
        if rich:
            detail["attn_out"] = (after.astype(jnp.float32)
                                  - x.astype(jnp.float32))[rows_out]
        return after, caches, detail

    first = cfg.first_k_dense_replace
    x = moe_mla.embed(params, tokens)
    x, cache_rows, detail0 = attend(x, cache_rows, params["dense"],
                                    cfg.kinds[0], 0)
    # layer 0's rows of the returned positions, as the cache now holds them
    cached0 = cache_rows["latent"][0, slots[rows_out], :cfg.latent_width]
    x = moe_mla.dense_mlp(x, params["dense"], cfg)
    moe = params["moe"]
    period = cfg.period
    n = {kind: period.count(kind) for kind in (FULL, SLIDING)}
    before = {kind: cfg.kinds[:first].count(kind) for kind in (FULL, SLIDING)}
    stacked = {k: v for k, v in moe.items()
               if k not in moe_mla.EXPERT_LEAVES + ("swa",)}
    if len(period) == 1:
        stacks = {"layers": stacked}     # a layer's leaves, whole
    else:
        split = lambda tree, m: jax.tree_util.tree_map(  # noqa: E731
            lambda a: a.reshape((-1, m) + a.shape[1:]), tree)
        stacks = {"ffn": split({k: stacked[k] for k in moe_mla.FFN_LEAVES},
                               len(period)),
                  FULL: split({k: v for k, v in stacked.items()
                               if k not in moe_mla.FFN_LEAVES}, n[FULL]),
                  SLIDING: split(moe.get("swa", {}), max(n[SLIDING], 1))}

    def body(carry, xs):
        x, cache_rows = carry
        ws, index = xs
        kept, seen = [], {FULL: 0, SLIDING: 0}
        for j, kind in enumerate(period):
            if len(period) == 1:
                w = ws["layers"]
            else:
                pick = lambda tree, i: jax.tree_util.tree_map(  # noqa: E731
                    lambda a: a[i], tree)
                w = dict(pick(ws[kind], seen[kind]), **pick(ws["ffn"], j))
            x, cache_rows, detail = attend(
                x, cache_rows, w, kind,
                _lin(index, n[kind], before[kind] + seen[kind]))
            seen[kind] += 1
            x, info = moe_mla.moe_mlp(
                x, w, moe["eg"], moe["eu"], moe["ed"],
                _lin(index, len(period) * cfg.experts_held,
                     j * cfg.experts_held), cfg, impl, valid)
            kept.append(dict(
                detail, router_input=info["router_input"][rows_out],
                sigma=info["sigma"][rows_out],
                chosen=info["chosen"][rows_out],
                expert_rows=info["expert_rows"],
                held_pairs=info["held_pairs"]))
        if len(period) == 1:
            return (x, cache_rows), kept[0]
        # a period's layers: what every layer has stacked, what only the
        # full layers have stacked over them
        every = [k for k in kept[0] if all(k in d for d in kept)]
        some = [k for k in kept[0] if k not in every]
        out = {k: jnp.stack([d[k] for d in kept]) for k in every}
        out.update({k: jnp.stack([d[k] for d in kept if k in d])
                    for k in some})
        return (x, cache_rows), out

    (x, cache_rows), kept = lax.scan(
        body, (x, cache_rows),
        (stacks, jnp.arange(cfg.moe_layers // len(period))))
    if len(period) > 1:     # [periods, layers of a period, ...] -> [layers]
        kept = {k: v.reshape((-1,) + v.shape[2:]) for k, v in kept.items()}
    if rich:                # layer 0's in front
        kept.update({k: jnp.concatenate([v[None], kept[k]], axis=0)
                     for k, v in detail0.items()})
    logits, hidden = moe_mla.head(params, x[rows_out], cfg)
    with jax.named_scope("lm_head"):
        sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # what the host reads every step, in one array; `sampled` at a fixed
    # length, so that the next step's program takes any bucket's
    counts = jnp.concatenate([sampled, kept["expert_rows"].reshape(-1),
                              kept["held_pairs"].reshape(-1)])
    sampled = jnp.pad(sampled, (0, feedback.shape[0] - R))
    if alone:
        cache_rows = cache_rows["latent"]
    return cache_rows, sampled, counts, dict(
        kept, logits=logits, hidden=hidden,
        cached_latent0=cached0.astype(jnp.float32))


def program_name(bucket) -> str:
    return "%s_c%d_p%d" % (STEP_PROGRAM, bucket[0], bucket[1])


def _pack(arrays: Sequence[np.ndarray]) -> np.ndarray:
    return np.concatenate([np.asarray(a, np.int32).ravel() for a in arrays])


class LMEngine:
    def __init__(self, cfg: moe_mla.MoeMlaConfig, params, cache: LatentCache,
                 max_running: int, chunk_buckets: Sequence[int],
                 page_buckets: Sequence[int], prompt_logits: int = 1,
                 impl: Optional[str] = None):
        from mine_tpu.kernels import on_tpu_backend
        self.cfg, self.params, self.cache = cfg, params, cache
        self.max_running = int(max_running)
        self.chunk_buckets = tuple(sorted(set(int(c) for c in chunk_buckets)
                                          | {0}))
        self.page_buckets = tuple(sorted(int(p) for p in page_buckets))
        self.prompt_logits = int(prompt_logits)
        self.detail_names = detail_names(cfg)
        self.impl = impl or ("pallas" if on_tpu_backend() else "xla")
        self._programs: Dict[Tuple[int, int], Any] = {}
        self.steps = 0
        self._feedback = None     # the last issued step's `sampled`
        self._dropped = telemetry.counter("serve.lm.dropped_tokens")
        self._expert_rows = np.zeros((cfg.moe_layers, cfg.experts_held),
                                     np.int64)

    # ---- buckets and programs ----

    @property
    def max_chunk(self) -> int:
        return self.chunk_buckets[-1]

    @property
    def max_context(self) -> int:
        return self.page_buckets[-1] * self.cache.page_size

    def buckets(self) -> List[Tuple[int, int]]:
        """(chunk rows, pages of the chunk's block table); decode only: one
        program, (0, the longest)."""
        return [(0, self.page_buckets[-1])] + [
            (c, p) for c in self.chunk_buckets[1:] for p in self.page_buckets]

    def bucket_of(self, chunk_tokens: int, chunk_context: int):
        """The smallest bucket that holds a chunk of `chunk_tokens` rows
        whose sequence is `chunk_context` tokens long with it, the padded
        rows still inside the block table."""
        if not chunk_tokens:
            return 0, self.page_buckets[-1]
        for c in self.chunk_buckets[1:]:
            if c >= chunk_tokens:
                break
        else:
            raise ValueError("a chunk of %d tokens exceeds the largest "
                             "bucket %d" % (chunk_tokens, self.max_chunk))
        need = self.cache.pages_for(chunk_context + (c - chunk_tokens))
        for p in self.page_buckets:
            if p >= need:
                return c, p
        raise ValueError("a context of %d tokens exceeds the largest bucket"
                         % chunk_context)

    def logit_rows(self, chunk_rows: int) -> int:
        return self.max_running + (min(self.prompt_logits, chunk_rows)
                                   if chunk_rows else 0)

    @property
    def feedback_rows(self) -> int:
        return self.logit_rows(self.max_chunk)

    def _feedback_array(self):
        import jax.numpy as jnp
        if self._feedback is None:
            self._feedback = jnp.zeros((self.feedback_rows,), jnp.int32)
        return self._feedback

    def _shapes(self, bucket):
        import jax
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (self.params, self.cache.arrays(),
             self._ints(bucket, StepInput(None, [])), self._feedback_array()))

    def _program(self, bucket):
        import jax
        if bucket not in self._programs:
            static = dict(
                cfg=self.cfg, chunk_rows=bucket[0], pages=bucket[1],
                decode_pages=self.page_buckets[-1], running=self.max_running,
                logit_rows=self.logit_rows(bucket[0]),
                page_size=self.cache.page_size, impl=self.impl)
            if self.cfg.index_topk and bucket[0]:
                static["dense_selected"] = selected_dense_bytes(
                    self.cfg, bucket[0], bucket[1] * self.cache.page_size
                ) <= DENSE_SELECTED_BYTES

            def _lm_serve_step_impl(params, cache_rows, ints, feedback):
                return _step_impl(params, cache_rows, ints, feedback,
                                  **static)

            # a program a bucket, each under its own name: a device trace
            # names operations by instruction, and every bucket has its own
            name = program_name(bucket)
            _lm_serve_step_impl.__name__ = name
            jitted = self._programs[bucket] = jax.jit(
                _lm_serve_step_impl, donate_argnums=(1,))
            shapes = self._shapes(bucket)
            telemetry.programs.register(
                name, lambda: jitted.lower(*shapes).compile().as_text())
        return self._programs[bucket]

    def warmup(self) -> None:
        """Run every bucket once on an empty step (its rows all padding)."""
        for bucket in self.buckets():
            self.collect(self._issue(bucket, StepInput(None, [])))
        self.steps = 0

    def memory_analysis(self, bucket=None):
        """The compiler's memory analysis of one bucket's program (the
        largest by default)."""
        bucket = bucket or self.buckets()[-1]
        return self._program(bucket).lower(
            *self._shapes(bucket)).compile().memory_analysis()

    # ---- one step ----

    def _ints(self, bucket, step: StepInput) -> np.ndarray:
        Tc, P = bucket
        D, ps, Pd = (self.max_running, self.cache.page_size,
                     self.page_buckets[-1])
        T, R = Tc + D, self.logit_rows(Tc)
        tokens = np.zeros(T, np.int32)
        positions = np.zeros(T, np.int32)
        slots = np.arange(T, dtype=np.int32) % ps         # page 0
        valid = np.zeros(T, np.int32)
        source = np.full(T, -1, np.int32)
        chunk_table = np.zeros(P, np.int32)
        dec_tables = np.zeros((D, Pd), np.int32)
        dec_lens = np.zeros(D, np.int32)
        rows_out = np.zeros(R, np.int32)
        W = self.cache.window
        window_slots = np.arange(T, dtype=np.int32) % ps  # page 0
        window_back = np.zeros(W - 1 if W and Tc else 0, np.int32)
        window_dec = np.zeros((D, W), np.int32)

        def window_rows(table, at):
            """The window pool's rows of positions `at`; page 0 where the
            sequence holds none."""
            first, held = table.first, np.asarray(table.pages + [0], np.int32)
            i = at // ps - first
            i = np.where((at >= 0) & (i >= 0) & (i < len(held) - 1), i, -1)
            return held[i] * ps + at % ps

        offset = 0
        if step.chunk is not None:
            ids, start, table = step.chunk[:3]
            n = len(ids)
            tokens[:n] = ids
            # padded rows stand at the positions that follow: finite work
            positions[:Tc] = start + np.arange(Tc)
            at = start + np.arange(n)
            table_arr = np.asarray(table, np.int32)
            slots[:n] = table_arr[at // ps] * ps + at % ps
            valid[:n] = 1
            chunk_table[:min(len(table), P)] = table_arr[:P]
            offset = start
            K = R - D
            rows_out[D:] = np.clip(n - K + np.arange(K), 0, None)
            if W:
                window_slots[:n] = window_rows(step.chunk[3], at)
                window_back[:] = window_rows(
                    step.chunk[3], start - (W - 1) + np.arange(W - 1))
        for i, (token, position, table, row, *rest) in enumerate(step.decode):
            if token is None:
                source[Tc + i] = row
            else:
                tokens[Tc + i] = token
            positions[Tc + i] = position
            slots[Tc + i] = table[position // ps] * ps + position % ps
            valid[Tc + i] = 1
            dec_tables[i, :min(len(table), Pd)] = table[:Pd]
            dec_lens[i] = position + 1
            if W:
                at = position - (W - 1) + np.arange(W)
                window_dec[i] = window_rows(rest[0], at)
                window_slots[Tc + i] = window_dec[i, -1]
        rows_out[:D] = Tc + np.arange(D)
        return _pack([tokens, positions, slots, valid, source, chunk_table,
                      dec_tables, dec_lens, rows_out, [offset]]
                     + ([window_slots, window_back, window_dec] if W else []))

    def dispatch(self, step: StepInput) -> Pending:
        """Issue one step; returns at once."""
        n_chunk = 0 if step.chunk is None else len(step.chunk[0])
        return self._issue(self.bucket_of(
            n_chunk, step.chunk[1] + n_chunk if n_chunk else 0), step)

    def _issue(self, bucket, step: StepInput) -> Pending:
        t0 = time.perf_counter_ns()
        with telemetry.span("serve.lm.dispatch"):
            ints = self._ints(bucket, step)
            arrays, sampled, counts, aux = self._program(bucket)(
                self.params, self.cache.arrays(), ints,
                self._feedback_array())
            self.cache.set_arrays(arrays)
        self._feedback = sampled
        return Pending(step=step, bucket=bucket, sampled=sampled,
                       aux=dict(aux, counts=counts),
                       chunk_row=self.logit_rows(bucket[0]) - 1, t0_ns=t0)

    def collect(self, pending: Pending) -> StepOutput:
        """Wait for an issued step and read what the host needs of it."""
        import jax
        step, aux = pending.step, pending.aux
        with telemetry.span("serve.lm.device_wait"):
            jax.block_until_ready(aux["counts"])
        with telemetry.host_readback("serve.lm.readback"):
            fetch = {"counts": aux["counts"]}
            if step.want_logits:
                fetch.update({k: aux[k] for k in self.detail_names})
            got = jax.device_get(fetch)
        self.steps += 1
        D, R = self.max_running, self.logit_rows(pending.bucket[0])
        sampled, rest = got["counts"][:R], got["counts"][R:]
        held = self.cfg.moe_layers * self.cfg.experts_held
        rows = rest[:held].reshape(self.cfg.moe_layers, -1).astype(np.int64)
        pairs = int(rest[held:].sum())
        self._expert_rows += rows
        self._dropped.inc(pairs - int(rows.sum()))
        n_chunk = 0 if step.chunk is None else len(step.chunk[0])
        return StepOutput(
            decode_tokens=sampled[:len(step.decode)],
            chunk_tokens=sampled[D:] if R > D else None,
            chunk_rows=min(R - D, n_chunk), bucket=pending.bucket,
            expert_rows=rows, held_pairs=pairs,
            detail=({k: got[k] for k in self.detail_names}
                    if step.want_logits else None))

    def expert_rows_total(self) -> np.ndarray:
        """Rows each held expert has computed since the start, [expert
        layers, held] (read at log cadence into `serve.lm.expert_tokens.*`)."""
        return self._expert_rows.copy()
