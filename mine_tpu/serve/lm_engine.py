"""The token model's serving engine: one jitted STEP program a bucket, in which
a chunk of one prompt and every running sequence's next decode token share
the projections, the router and the experts, while attention is taken per
sequence: the chunk in the up-projected form against its cached prefix, the
decode tokens in the absorbed (latent-space) form through their block tables
(models/moe_mla.py, kernels/attention.py).

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
latent rows as the cache holds them. Also every
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


@dataclasses.dataclass
class StepInput:
    """One step as the scheduler composed it. `chunk`: (tokens [n],
    first position, block table) of one prompt's next chunk, or None;
    `decode`: (token, position, block table, feedback row) of each running
    sequence: the token on the host, or None and the row of the step before
    (decode row i: i; a chunk's last token: `Pending.chunk_row`) that is
    sampling it."""
    chunk: Optional[Tuple[np.ndarray, int, List[int]]]
    decode: List[Tuple[Optional[int], int, List[int], int]]
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


def _step_impl(params, cache_rows, ints, feedback, *, cfg, chunk_rows, pages,
               decode_pages, running, logit_rows, page_size, impl):
    """See the module docstring. `ints`: the step's integers packed into one
    array (`_pack`); `feedback`: the step before's `sampled`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    Tc, P, Pd, D, R = chunk_rows, pages, decode_pages, running, logit_rows
    T = Tc + D
    at = iter(np.cumsum([0, T, T, T, T, T, P, D * Pd, D, R, 1]))
    take = lambda n: lax.dynamic_slice(ints, (int(next(at)),), (n,))  # noqa
    tokens, positions, slots, valid = take(T), take(T), take(T), take(T)
    source = take(T)    # >= 0: the row of `feedback` that holds the token
    tokens = jnp.where(source >= 0, feedback[jnp.maximum(source, 0)], tokens)
    chunk_table, dec_tables, dec_lens = take(P), take(D * Pd), take(D)
    rows_out, chunk_offset = take(R), take(1)[0]
    dec_tables = dec_tables.reshape(D, Pd)
    valid = valid > 0
    cos, sin = moe_mla.rope_tables(positions, cfg)
    pad = cache_rows.shape[-1] - cfg.latent_width

    def attend(x, cache_rows, w, layer):
        q_nope, q_rope, latent = moe_mla.mla_project(x, w, cfg, cos, sin)
        latent = jnp.pad(latent, ((0, 0), (0, pad))).astype(cache_rows.dtype)
        cache_rows = cache_rows.at[layer, slots].set(latent)
        outs = []
        if Tc:
            ctx_rows = (chunk_table[:, None] * page_size
                        + jnp.arange(page_size)[None, :]).reshape(-1)
            ctx = cache_rows[layer, ctx_rows][:, :cfg.latent_width]
            outs.append(moe_mla.mla_prefill(q_nope[:Tc], q_rope[:Tc], ctx, w,
                                            cfg, chunk_offset, impl))
        outs.append(moe_mla.mla_decode(q_nope[Tc:], q_rope[Tc:], cache_rows,
                                       layer, dec_tables, dec_lens, w, cfg,
                                       page_size, impl))
        return moe_mla.attention_out(x, jnp.concatenate(outs, axis=0),
                                     w), cache_rows

    x = moe_mla.embed(params, tokens)
    x, cache_rows = attend(x, cache_rows, params["dense"], 0)
    # layer 0's rows of the returned positions, as the cache now holds them
    cached0 = cache_rows[0, slots[rows_out], :cfg.latent_width]
    x = moe_mla.dense_mlp(x, params["dense"], cfg)
    moe = params["moe"]
    stacked = {k: v for k, v in moe.items() if k not in ("eg", "eu", "ed")}

    def body(carry, xs):
        x, cache_rows = carry
        w, index = xs
        x, cache_rows = attend(x, cache_rows, w, index + 1)
        x, info = moe_mla.moe_mlp(x, w, moe["eg"], moe["eu"], moe["ed"],
                                  index * cfg.experts_held, cfg, impl, valid)
        kept = {"router_input": info["router_input"][rows_out],
                "sigma": info["sigma"][rows_out],
                "chosen": info["chosen"][rows_out],
                "expert_rows": info["expert_rows"],
                "held_pairs": info["held_pairs"]}
        return (x, cache_rows), kept

    (x, cache_rows), kept = lax.scan(
        body, (x, cache_rows), (stacked, jnp.arange(cfg.moe_layers)))
    logits, hidden = moe_mla.head(params, x[rows_out], cfg)
    with jax.named_scope("lm_head"):
        sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # what the host reads every step, in one array; `sampled` at a fixed
    # length, so that the next step's program takes any bucket's
    counts = jnp.concatenate([sampled, kept["expert_rows"].reshape(-1),
                              kept["held_pairs"].reshape(-1)])
    sampled = jnp.pad(sampled, (0, feedback.shape[0] - R))
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
            (self.params, self.cache.rows,
             self._ints(bucket, StepInput(None, [])), self._feedback_array()))

    def _program(self, bucket):
        import jax
        if bucket not in self._programs:
            static = dict(
                cfg=self.cfg, chunk_rows=bucket[0], pages=bucket[1],
                decode_pages=self.page_buckets[-1], running=self.max_running,
                logit_rows=self.logit_rows(bucket[0]),
                page_size=self.cache.page_size, impl=self.impl)

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
        offset = 0
        if step.chunk is not None:
            ids, start, table = step.chunk
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
        for i, (token, position, table, row) in enumerate(step.decode):
            if token is None:
                source[Tc + i] = row
            else:
                tokens[Tc + i] = token
            positions[Tc + i] = position
            slots[Tc + i] = table[position // ps] * ps + position % ps
            valid[Tc + i] = 1
            dec_tables[i, :min(len(table), Pd)] = table[:Pd]
            dec_lens[i] = position + 1
        rows_out[:D] = Tc + np.arange(D)
        return _pack([tokens, positions, slots, valid, source, chunk_table,
                      dec_tables, dec_lens, rows_out, [offset]])

    def dispatch(self, step: StepInput) -> Pending:
        """Issue one step; returns at once."""
        n_chunk = 0 if step.chunk is None else len(step.chunk[0])
        return self._issue(self.bucket_of(
            n_chunk, step.chunk[1] + n_chunk if n_chunk else 0), step)

    def _issue(self, bucket, step: StepInput) -> Pending:
        t0 = time.perf_counter_ns()
        with telemetry.span("serve.lm.dispatch"):
            ints = self._ints(bucket, step)
            self.cache.rows, sampled, counts, aux = self._program(bucket)(
                self.params, self.cache.rows, ints, self._feedback_array())
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
                fetch.update({k: aux[k] for k in DETAIL})
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
            detail={k: got[k] for k in DETAIL} if step.want_logits else None)

    def expert_rows_total(self) -> np.ndarray:
        """Rows each held expert has computed since the start, [expert
        layers, held] (read at log cadence into `serve.lm.expert_tokens.*`)."""
        return self._expert_rows.copy()
