"""Blocked causal attention (flash attention), forward and backward, in Pallas.

One application of plain causal softmax attention over a packed row,
softmax(q k^T / sqrt(D) + causal) v, without ever holding an [S, S] score
matrix: unfused, one application's scores at S = 4096 and 16 heads are
1.07 GB a sequence. Adapted from the idea of the TPU flash attention that
ships with JAX (`jax.experimental.pallas.ops.tpu.flash_attention`), cut down
to what the looped language model needs: causal, no bias, no segment ids,
as many key-value heads as query heads, square blocks.

Layout: q, k, v and the output are [B, S, H*D], the layout the projections
produce, and a head is a lane-aligned column block of width D, so nothing is
transposed on the way in or out (on the chip D must be a multiple of 128;
interpret mode takes any D). The forward also returns the row log-sum-exp,
lane-replicated as [B, H, S, 128] float32, which the backward reads.

Grid (batch, head, q block, kv block), the last axis sequential. A block
above the diagonal is skipped: its body does not run, and its index map
points at the block the row needs anyway, so nothing is fetched for it.
The backward is two kernels (dK/dV with the q blocks innermost, dQ with the
kv blocks innermost), each recomputing the probabilities from q, k and the
log-sum-exp; delta = rowsum(dO * O) is formed inside them.

Precision: the inputs' dtype feeds the MXU (bfloat16 under
`training.dtype: bfloat16`), every product accumulates in float32, and the
running max, the normaliser and the accumulators are float32.

`name=` on the three calls (`flash_attention_fwd`, `flash_attention_bwd_dkv`,
`flash_attention_bwd_dq`) is the instruction's name in a device trace.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_NEG = -1e30   # finite: a masked score never makes inf - inf


def block_size(seq_len: int) -> int:
    """The square block of a sequence: the largest of 512, 256, 128 that
    divides it, else the whole sequence."""
    for b in (512, 256, 128):
        if seq_len % b == 0:
            return b
    return seq_len


def _dot(a, b, contract):
    return lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                           preferred_element_type=jnp.float32)


def _causal(block):
    row = lax.broadcasted_iota(jnp.int32, (block, block), 0)
    col = lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return col <= row


# ---------------- forward ----------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, block):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(diagonal):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = _dot(q, k, ((1,), (1,))) * scale                 # [bq, bk]
        if diagonal:
            s = jnp.where(_causal(block), s, _NEG)
        m_prev, l_prev = m_sc[...], l_sc[...]                # [bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha[:, :1] + _dot(
            p.astype(v.dtype), v, ((1,), (0,)))
        m_sc[...] = m_new

    @pl.when(j < i)
    def _():
        step(False)

    @pl.when(j == i)   # the diagonal is the row's last block
    def _():
        step(True)
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l)


def _head_spec(block, d, index):
    """A [block, D] tile of a [B, S, H*D] array: the head is a column block."""
    return pl.BlockSpec((None, block, d), index)


def _fwd(q, k, v, heads, interpret):
    B, S, HD = q.shape
    d = HD // heads
    block = block_size(S)
    n = S // block
    qo = _head_spec(block, d, lambda b, h, i, j: (b, i, h))
    kv = _head_spec(block, d, lambda b, h, i, j: (b, jnp.minimum(j, i), h))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d), block=block),
        grid=(B, heads, n, n),
        in_specs=[qo, kv, kv],
        out_specs=[qo, pl.BlockSpec((None, None, block, LANES),
                                    lambda b, h, i, j: (b, h, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, S, HD), q.dtype),
                   jax.ShapeDtypeStruct((B, heads, S, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, LANES), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)


# ---------------- backward ----------------

def _probabilities(q, k, lse, do, o, v, scale, block, diagonal):
    """p and ds of one (q block, kv block) pair, float32 [bq, bk]."""
    s = _dot(q, k, ((1,), (1,))) * scale
    p = jnp.exp(s - lse[:, :1])
    if diagonal:
        p = jnp.where(_causal(block), p, 0.0)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=1,
                    keepdims=True)
    dp = _dot(do, v, ((1,), (1,)))
    return p, p * (dp - delta) * scale


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc, *, scale, block, n):
    j, i = pl.program_id(2), pl.program_id(3)   # kv block, q block

    @pl.when(i == 0)
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def step(diagonal):
        q, do = q_ref[...], do_ref[...]
        p, ds = _probabilities(q, k_ref[...], lse_ref[...], do, o_ref[...],
                               v_ref[...], scale, block, diagonal)
        dv_sc[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dk_sc[...] += _dot(ds.astype(q.dtype), q, ((0,), (0,)))

    @pl.when(i == j)
    def _():
        step(True)

    @pl.when(i > j)
    def _():
        step(False)

    @pl.when(i == n - 1)
    def _():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dq_sc,
                   *, scale, block):
    i, j = pl.program_id(2), pl.program_id(3)   # q block, kv block

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    def step(diagonal):
        k = k_ref[...]
        _, ds = _probabilities(q_ref[...], k, lse_ref[...], do_ref[...],
                               o_ref[...], v_ref[...], scale, block, diagonal)
        dq_sc[...] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    @pl.when(j < i)
    def _():
        step(False)

    @pl.when(j == i)
    def _():
        step(True)
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _bwd(heads, interpret, residuals, do):
    q, k, v, o, lse = residuals
    B, S, HD = q.shape
    d = HD // heads
    block = block_size(S)
    n = S // block
    scale = 1.0 / math.sqrt(d)
    semantics = pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))

    # dK, dV: kv block j outer, q blocks i >= j inner
    row = lambda b, h, j, i: (b, jnp.maximum(i, j), h)       # noqa: E731
    col = lambda b, h, j, i: (b, j, h)                       # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block=block, n=n),
        grid=(B, heads, n, n),
        in_specs=[_head_spec(block, d, row), _head_spec(block, d, col),
                  _head_spec(block, d, col), _head_spec(block, d, row),
                  _head_spec(block, d, row),
                  pl.BlockSpec((None, None, block, LANES),
                               lambda b, h, j, i: (b, h, jnp.maximum(i, j),
                                                   0))],
        out_specs=[_head_spec(block, d, col), _head_spec(block, d, col)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=semantics,
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(q, k, v, o, do, lse)

    # dQ: q block i outer, kv blocks j <= i inner
    row = lambda b, h, i, j: (b, i, h)                       # noqa: E731
    col = lambda b, h, i, j: (b, jnp.minimum(j, i), h)       # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block=block),
        grid=(B, heads, n, n),
        in_specs=[_head_spec(block, d, row), _head_spec(block, d, col),
                  _head_spec(block, d, col), _head_spec(block, d, row),
                  _head_spec(block, d, row),
                  pl.BlockSpec((None, None, block, LANES),
                               lambda b, h, i, j: (b, h, i, 0))],
        out_specs=_head_spec(block, d, row),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=semantics,
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(q, k, v, o, do, lse)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, heads, interpret):
    return _fwd(q, k, v, heads, interpret)[0]


def _flash_fwd(q, k, v, heads, interpret):
    o, lse = _fwd(q, k, v, heads, interpret)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(q, k, v, heads: int, interpret: bool = False):
    """Causal attention of [B, S, H*D] q, k, v (one dtype) -> [B, S, H*D]."""
    return _flash(q, k, v, heads, interpret)


def plain_attention(q, k, v, heads: int):
    """The same function as one [S, S] softmax a head, in XLA: what runs off
    the TPU (interpret mode is orders of magnitude slower than XLA there)
    and what the kernel's tests compare with."""
    B, S, HD = q.shape
    d = HD // heads
    split = lambda x: x.reshape(B, S, heads, d)              # noqa: E731
    s = jnp.einsum("bqhd,bkhd->bhqk", split(q), split(k),
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), split(v),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, HD).astype(q.dtype)


# ======================================================================
# Latent attention for serving (models/moe_mla.py): forward only.
#
# `prefix_attention`: a prompt chunk's queries against a longer run of keys,
# at UNEQUAL widths: a query/key head is 128 + 64 wide (a `nope` part of its
# own and a rotary part whose key is shared by all heads), a value head 128.
# 192 is no multiple of the 128 lanes, so the two parts arrive as two arrays
# and the scores are the sum of two products; q row r stands at position
# `q_offset + r` and sees keys 0..q_offset + r. `q_offset` is a runtime
# scalar (scalar prefetch): one compiled program serves every prefix.
#
# `paged_latent_attention`: one query token a sequence, in latent space,
# against that sequence's pages of the latent cache: every head's query
# [H, rank + rope] reads each page [page, rank + rope] ONCE; the page's
# first `rank` columns are also the values. Block tables and lengths are
# scalar-prefetched; a page past a sequence's length is neither fetched nor
# computed.
#
# `masked_prefix_attention`: `prefix_attention` under a SELECTION: row r sees
# the keys its row of `mask` [Tq, Tk] (int8) marks, which lie at or before
# `q_offset + r`. What a short context takes under learned sparse attention
# (dense up-projected attention, masked: the selected rows need no gather).
#
# `window_attention`: the same under a sliding window W, a head's query and
# key in one piece (a sliding layer's head is 192 + 64 = 256 wide): row r
# sees keys in [r - (W - 1), r] that stand at or after `first_valid` (a
# runtime scalar: where the sequence starts inside the keys), and key blocks
# wholly behind a q block's window are neither fetched nor computed.
#
# `index_scores`: the lightning indexer of learned sparse attention,
# I[r, s] = sum_j w[r, j] relu(q[r, j] . k[s]) over the index heads j, one
# key for all heads, causal with a runtime offset; float32 [Tq, Tk] out. The
# [Tq, heads, Tk] products never leave the chip's fast memory.
#
# `gathered_latent_attention`: one query against ITS OWN gathered rows of a
# latent cache (the rows a selection or a window names), in latent space;
# plain XLA everywhere (the gather is the work; see PERF.md section 6,
# PR 37), a block of queries at a time through `in_blocks`.
#
# `impl`: "pallas" on the chip, "interpret" (the same kernels interpreted),
# "xla" the same functions in plain XLA (off the chip, and the tests' other
# side).
# ======================================================================

MASKED = _NEG   # what a masked index score reads


def plain_prefix_attention(q_nope, q_rope, k_nope, k_rope, v, heads, q_offset,
                           scale):
    """q_nope [Tq, H*dn], q_rope [H, Tq, dr], k_nope [Tk, H*dn], k_rope
    [Tk, dr] (one for all heads), v [Tk, H*dv] -> [Tq, H*dv]."""
    Tq, Tk = q_nope.shape[0], k_nope.shape[0]
    split = lambda x: x.reshape(x.shape[0], heads, -1)           # noqa: E731
    s = jnp.einsum("qhd,khd->hqk", split(q_nope), split(k_nope),
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("hqd,kd->hqk", q_rope, k_rope,
                       preferred_element_type=jnp.float32)
    s = s * scale
    seen = (jnp.arange(Tk)[None, :] <= q_offset + jnp.arange(Tq)[:, None])
    p = jax.nn.softmax(jnp.where(seen[None], s, _NEG), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), split(v),
                   preferred_element_type=jnp.float32)
    return o.reshape(Tq, -1).astype(q_nope.dtype)


def _prefix_kernel(off_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                   m_sc, l_sc, acc_sc, *, scale, bq, bk, nk):
    i, j = pl.program_id(1), pl.program_id(2)
    offset = off_ref[0]
    # the last key block any row of this q block sees
    last = jnp.minimum((offset + (i + 1) * bq - 1) // bk, nk - 1)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(masked):
        v = v_ref[...]
        s = (_dot(qn_ref[...], kn_ref[...], ((1,), (1,)))
             + _dot(qr_ref[...], kr_ref[...], ((1,), (1,)))) * scale
        if masked:
            row = offset + i * bq + lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            col = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(col <= row, s, _NEG)
        m_prev, l_prev = m_sc[...], l_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha[:, :1] + _dot(
            p.astype(v.dtype), v, ((1,), (0,)))
        m_sc[...] = m_new

    # every row of the q block sees the whole key block
    whole = (j + 1) * bk - 1 <= offset + i * bq

    @pl.when(whole)
    def _():
        step(False)

    @pl.when(jnp.logical_and(jnp.logical_not(whole), j <= last))
    def _():
        step(True)

    @pl.when(j == last)
    def _():
        o_ref[...] = (acc_sc[...] / l_sc[...][:, :1]).astype(o_ref.dtype)


def _softmax_step(s, seen, v, m_sc, l_sc, acc_sc):
    """One key block of the running softmax under a mask: scores s [bq, bk]
    (scaled), seen [bq, bk], values v [bk, dv]; a row may see nothing of a
    block its q block needs."""
    s = jnp.where(seen, s, _NEG)
    m_prev, l_prev = m_sc[...], l_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(seen, jnp.exp(s - m_new[:, :1]), 0.0)
    l_sc[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha[:, :1] + _dot(
        p.astype(v.dtype), v, ((1,), (0,)))
    m_sc[...] = m_new


def _masked_kernel(off_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, mask_ref,
                   o_ref, m_sc, l_sc, acc_sc, *, scale, bq, bk, nk):
    """`_prefix_kernel` with a row's keys named by a mask (which holds the
    causal order too: a key after its query is never marked)."""
    i, j = pl.program_id(1), pl.program_id(2)
    last = jnp.minimum((off_ref[0] + (i + 1) * bq - 1) // bk, nk - 1)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(j <= last)
    def _():
        v = v_ref[...]
        seen = mask_ref[...] != 0
        s = (_dot(qn_ref[...], kn_ref[...], ((1,), (1,)))
             + _dot(qr_ref[...], kr_ref[...], ((1,), (1,)))) * scale
        _softmax_step(s, seen, v, m_sc, l_sc, acc_sc)

    @pl.when(j == last)
    def _():
        l = l_sc[...][:, :1]
        o_ref[...] = (acc_sc[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def masked_prefix_attention(q_nope, q_rope, k_nope, k_rope, v, mask,
                            heads: int, q_offset, scale: float,
                            impl: str = "pallas", blocks=None):
    """`prefix_attention` over the keys `mask` [Tq, Tk] (int8, nonzero:
    attend) marks; every marked key lies at or before its query (row r at
    `q_offset + r`), so key blocks past a q block's last row are skipped."""
    Tq, Tk = q_nope.shape[0], k_nope.shape[0]
    if impl == "xla":
        split = lambda x: x.reshape(x.shape[0], heads, -1)       # noqa: E731
        s = (jnp.einsum("qhd,khd->hqk", split(q_nope), split(k_nope),
                        preferred_element_type=jnp.float32)
             + jnp.einsum("hqd,kd->hqk", q_rope, k_rope,
                          preferred_element_type=jnp.float32)) * scale
        seen = (mask != 0)[None]
        p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, _NEG), axis=-1),
                      0.0)
        o = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), split(v),
                       preferred_element_type=jnp.float32)
        return o.reshape(Tq, -1).astype(q_nope.dtype)
    dn, dv, dr = q_nope.shape[1] // heads, v.shape[1] // heads, k_rope.shape[1]
    bq, bk = blocks or prefix_blocks(Tq, Tk)
    nq, nk = Tq // bq, Tk // bk

    def kv_block(h, i, j, off):
        return jnp.minimum(j, jnp.minimum((off[0] + (i + 1) * bq - 1) // bk,
                                          nk - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(heads, nq, nk),
        in_specs=[
            pl.BlockSpec((bq, dn), lambda h, i, j, off: (i, h)),
            pl.BlockSpec((None, bq, dr), lambda h, i, j, off: (h, i, 0)),
            pl.BlockSpec((bk, dn),
                         lambda h, i, j, off: (kv_block(h, i, j, off), h)),
            pl.BlockSpec((bk, dr),
                         lambda h, i, j, off: (kv_block(h, i, j, off), 0)),
            pl.BlockSpec((bk, dv),
                         lambda h, i, j, off: (kv_block(h, i, j, off), h)),
            pl.BlockSpec((bq, bk),
                         lambda h, i, j, off: (i, kv_block(h, i, j, off)))],
        out_specs=pl.BlockSpec((bq, dv), lambda h, i, j, off: (i, h)),
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_masked_kernel, scale=scale, bq=bq, bk=bk, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tq, heads * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        name="mla_masked_attention_fwd",
        interpret=(impl == "interpret"),
    )(jnp.asarray(q_offset, jnp.int32).reshape(1), q_nope, q_rope, k_nope,
      k_rope, v, mask)


def _window_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
                   *, scale, bq, bk, nk, window):
    """`_prefix_kernel` under a sliding window, a head's query and key in
    one piece: `off_ref` = (q offset, first valid key)."""
    i, j = pl.program_id(1), pl.program_id(2)
    offset, first_valid = off_ref[0], off_ref[1]
    first, last = _window_blocks(offset, first_valid, i, bq, bk, nk, window)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(jnp.logical_and(j >= first, j <= last))
    def _():
        v = v_ref[...]
        s = _dot(q_ref[...], k_ref[...], ((1,), (1,))) * scale
        row = offset + i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        seen = (col <= row) & (col > row - window) & (col >= first_valid)
        _softmax_step(s, seen, v, m_sc, l_sc, acc_sc)

    @pl.when(j == last)
    def _():
        o_ref[...] = (acc_sc[...] / l_sc[...][:, :1]).astype(o_ref.dtype)


def _window_blocks(offset, first_valid, i, bq, bk, nk, window):
    """(first, last) key block that q block i's window touches."""
    lowest = jnp.maximum(offset + i * bq - (window - 1), first_valid)
    first = jnp.maximum(lowest, 0) // bk
    last = jnp.minimum((offset + (i + 1) * bq - 1) // bk, nk - 1)
    return jnp.minimum(first, last), last


def prefix_blocks(Tq: int, Tk: int):
    """(q block, key block): 1024 x 1024 where the lengths allow it. On the
    v5e the kernel is bound by the vector unit's work per score, and the
    per-block work on the running max, the normaliser and the accumulator
    is amortised over a larger block: 2,048 queries against a 30k prefix
    take 34.3 ms at 512 x 512 and 21.5 ms at 1024 x 1024 (chip run, PR 35)."""
    pick = lambda n: next((b for b in (1024, 512, 256, 128)  # noqa: E731
                           if n % b == 0), n)
    return pick(Tq), pick(Tk)


def prefix_attention(q_nope, q_rope, k_nope, k_rope, v, heads: int, q_offset,
                     scale: float, impl: str = "pallas",
                     blocks=None):
    """Causal attention of a chunk's queries at positions `q_offset`.. over
    keys 0..Tk-1 (see the section's comment for the layouts)."""
    if impl == "xla":
        return plain_prefix_attention(q_nope, q_rope, k_nope, k_rope, v,
                                      heads, q_offset, scale)
    Tq, Tk = q_nope.shape[0], k_nope.shape[0]
    dn, dv, dr = q_nope.shape[1] // heads, v.shape[1] // heads, k_rope.shape[1]
    bq, bk = blocks or prefix_blocks(Tq, Tk)
    nq, nk = Tq // bq, Tk // bk

    def kv_block(h, i, j, off):
        return jnp.minimum(j, jnp.minimum((off[0] + (i + 1) * bq - 1) // bk,
                                          nk - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(heads, nq, nk),
        in_specs=[
            pl.BlockSpec((bq, dn), lambda h, i, j, off: (i, h)),
            pl.BlockSpec((None, bq, dr), lambda h, i, j, off: (h, i, 0)),
            pl.BlockSpec((bk, dn),
                         lambda h, i, j, off: (kv_block(h, i, j, off), h)),
            pl.BlockSpec((bk, dr),
                         lambda h, i, j, off: (kv_block(h, i, j, off), 0)),
            pl.BlockSpec((bk, dv),
                         lambda h, i, j, off: (kv_block(h, i, j, off), h))],
        out_specs=pl.BlockSpec((bq, dv), lambda h, i, j, off: (i, h)),
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_prefix_kernel, scale=scale, bq=bq, bk=bk, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tq, heads * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        name="mla_prefix_attention_fwd",
        interpret=(impl == "interpret"),
    )(jnp.asarray(q_offset, jnp.int32).reshape(1), q_nope, q_rope, k_nope,
      k_rope, v)


def plain_window_attention(q, k, v, heads, q_offset, scale, window,
                           first_valid):
    Tq, Tk = q.shape[0], k.shape[0]
    split = lambda x: x.reshape(x.shape[0], heads, -1)           # noqa: E731
    s = jnp.einsum("qhd,khd->hqk", split(q), split(k),
                   preferred_element_type=jnp.float32) * scale
    col, row = jnp.arange(Tk)[None, :], q_offset + jnp.arange(Tq)[:, None]
    seen = (col <= row) & (col > row - window) & (col >= first_valid)
    p = jax.nn.softmax(jnp.where(seen[None], s, _NEG), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), split(v),
                   preferred_element_type=jnp.float32)
    return o.reshape(Tq, -1).astype(q.dtype)


def window_attention(q, k, v, heads: int, q_offset, scale: float,
                     window: int, first_valid=0, impl: str = "pallas",
                     blocks=None):
    """Sliding-window attention of a chunk's queries q [Tq, H*d] at key
    indices `q_offset`.. over keys k [Tk, H*d], v [Tk, H*dv]: row r sees
    the keys in [r - (window - 1), r] at or after `first_valid` (see the
    section's comment). On the chip d and dv are multiples of the lanes."""
    if impl == "xla":
        return plain_window_attention(q, k, v, heads, q_offset, scale,
                                      window, first_valid)
    Tq, Tk = q.shape[0], k.shape[0]
    d, dv = q.shape[1] // heads, v.shape[1] // heads
    bq, bk = blocks or prefix_blocks(Tq, Tk)
    nq, nk = Tq // bq, Tk // bk

    def kv_block(h, i, j, off):
        first, last = _window_blocks(off[0], off[1], i, bq, bk, nk, window)
        return jnp.clip(j, first, last)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(heads, nq, nk),
        in_specs=[
            pl.BlockSpec((bq, d), lambda h, i, j, off: (i, h)),
            pl.BlockSpec((bk, d),
                         lambda h, i, j, off: (kv_block(h, i, j, off), h)),
            pl.BlockSpec((bk, dv),
                         lambda h, i, j, off: (kv_block(h, i, j, off), h))],
        out_specs=pl.BlockSpec((bq, dv), lambda h, i, j, off: (i, h)),
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)])
    scalars = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(first_valid, jnp.int32)])
    return pl.pallas_call(
        functools.partial(_window_kernel, scale=scale, bq=bq, bk=bk, nk=nk,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tq, heads * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        name="mla_window_attention_fwd",
        interpret=(impl == "interpret"),
    )(scalars, q, k, v)


# ---------------- the indexer's scores ----------------

def plain_index_scores(q, w, k, q_offset):
    """q [Tq, J, d], w [Tq, J] float32, k [Tk, d] -> I [Tq, Tk] float32,
    MASKED where the key stands after the query (row r at `q_offset + r`)."""
    s = jnp.einsum("qjd,kd->qjk", q, k.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    s = jnp.einsum("qjk,qj->qk", jax.nn.relu(s), w,
                   precision=lax.Precision.HIGHEST)
    seen = (jnp.arange(k.shape[0])[None, :]
            <= q_offset + jnp.arange(q.shape[0])[:, None])
    return jnp.where(seen, s, MASKED)


def _index_kernel(off_ref, q_ref, w_ref, k_ref, o_ref, *, bq, bk, heads):
    i, j = pl.program_id(0), pl.program_id(1)
    offset = off_ref[0]
    seen_any = j * bk <= offset + (i + 1) * bq - 1

    @pl.when(seen_any)
    def _():
        k, w = k_ref[...], w_ref[...]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            s = _dot(q_ref[h], k, ((1,), (1,)))              # [bq, bk]
            acc = acc + jnp.maximum(s, 0.0) * w[:, h:h + 1]
        row = offset + i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        o_ref[...] = jnp.where(col <= row, acc, MASKED)

    @pl.when(jnp.logical_not(seen_any))
    def _():
        o_ref[...] = jnp.full((bq, bk), MASKED, jnp.float32)


def index_blocks(Tq: int, Tk: int):
    pick = lambda n, sizes: next((b for b in sizes if n % b == 0), n)  # noqa
    return pick(Tq, (256, 128)), pick(Tk, (512, 256, 128))


def index_scores(q, w, k, q_offset, impl: str = "pallas", blocks=None):
    """The indexer's scores of a chunk's rows (see the section's comment):
    q [Tq, J, d], w [Tq, J] float32, k [Tk, d] -> [Tq, Tk] float32."""
    if impl == "xla":
        return plain_index_scores(q, w, k, q_offset)
    Tq, J, d = q.shape
    Tk = k.shape[0]
    bq, bk = blocks or index_blocks(Tq, Tk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((J, bq, d), lambda i, j, off: (0, i, 0)),
            pl.BlockSpec((bq, J), lambda i, j, off: (i, 0)),
            # a key block no row of the q block sees repeats the last one
            pl.BlockSpec((bk, d), lambda i, j, off: (jnp.minimum(
                j, (off[0] + (i + 1) * bq - 1) // bk), 0))],
        out_specs=pl.BlockSpec((bq, bk), lambda i, j, off: (i, j)))
    return pl.pallas_call(
        functools.partial(_index_kernel, bq=bq, bk=bk, heads=J),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tq, Tk), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "arbitrary")),
        name="dsa_index_scores",
        interpret=(impl == "interpret"),
    )(jnp.asarray(q_offset, jnp.int32).reshape(1), q.transpose(1, 0, 2),
      w.astype(jnp.float32), k.astype(q.dtype))


# ---------------- attention over gathered rows ----------------

GATHER_BLOCK = 64    # queries whose gathered rows are held at once


def gathered_latent_attention(q, rows, ids, valid, rank: int, scale: float):
    """q [B, H, width]; rows [N, width]; ids [B, K] rows of each query;
    valid [B, K] -> o_lat [B, H, rank] float32: the softmax over each
    query's valid gathered rows, whose first `rank` columns are the values.
    A caller with many queries hands them over through `in_blocks`."""
    got = rows.at[ids].get(mode="promise_in_bounds").astype(q.dtype)
    s = jnp.einsum("bhd,bkd->bhk", q, got,
                   preferred_element_type=jnp.float32) * scale
    seen = valid[:, None, :]
    p = jax.nn.softmax(jnp.where(seen, s, _NEG), axis=-1)
    p = jnp.where(seen, p, 0.0)
    # over the whole rows (a slice of them would be a copy of them)
    return jnp.einsum("bhk,bkd->bhd", p.astype(q.dtype), got,
                      preferred_element_type=jnp.float32)[:, :, :rank]


def in_blocks(fn, *arrays, block: int = GATHER_BLOCK):
    """fn (-> an array or a tuple of them) over the leading axis of
    `arrays`, `block` rows at a time (one call where they are no more):
    what bounds a gather's memory."""
    R = arrays[0].shape[0]
    if R <= block:
        return fn(*arrays)
    pad = -R % block
    blocked = lambda a: jnp.pad(  # noqa: E731
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, block) + a.shape[1:])
    out = lax.map(lambda args: fn(*args), tuple(blocked(a) for a in arrays))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((-1,) + o.shape[2:])[:R], out)


def plain_paged_latent_attention(q, cache, layer, tables, lengths, rank,
                                 page_size, scale):
    """q [B, H, rank + dr]; cache [L, rows, rank + dr]; tables [B, P] page
    ids; lengths [B] -> o_lat [B, H, rank] float32 (zeros where length 0)."""
    B, P = tables.shape
    rows = (tables[:, :, None] * page_size
            + jnp.arange(page_size)[None, None, :]).reshape(B, P * page_size)
    kv = cache[layer, rows].astype(q.dtype)                 # [B, ctx, width]
    s = jnp.einsum("bhd,bkd->bhk", q, kv,
                   preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(P * page_size)[None, :] < lengths[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, :], s, _NEG), axis=-1)
    p = jnp.where(seen[:, None, :], p, 0.0)
    return jnp.einsum("bhk,bkd->bhd", p.astype(q.dtype), kv[:, :, :rank],
                      preferred_element_type=jnp.float32)


PAGES_A_STEP = 4   # pages of one sequence a grid step reads (a grid step
                   # costs ~0.35 us whether its body runs or not: at one page
                   # a step 32 sequences x 136 pages cost 1.4 ms a layer)


def _paged_kernel(layer_ref, tables_ref, lengths_ref, q_ref, *refs, scale,
                  rank, page_size, group):
    page_refs, o_ref = refs[:group], refs[group]
    m_sc, l_sc, acc_sc = refs[group + 1:]
    b, j = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[b]
    last = jnp.maximum((length + page_size * group - 1)
                       // (page_size * group) - 1, 0)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    for g, page_ref in enumerate(page_refs):
        start = (j * group + g) * page_size

        @pl.when(start < length)
        def _(page_ref=page_ref, start=start):
            q, page = q_ref[...], page_ref[...]
            s = _dot(q, page, ((1,), (1,))) * scale           # [H, page]
            col = start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < length, s, _NEG)
            m_prev, l_prev = m_sc[...], l_sc[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_sc[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[...] = acc_sc[...] * alpha[:, :1] + _dot(
                p.astype(page.dtype), page[:, :rank], ((1,), (0,)))
            m_sc[...] = m_new

    @pl.when(j == last)
    def _():
        l = l_sc[...][:, :1]
        o_ref[...] = (acc_sc[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def paged_latent_attention(q, cache, layer, tables, lengths, rank: int,
                           page_size: int, scale: float,
                           impl: str = "pallas"):
    """Decode attention in latent space through the block tables (see the
    section's comment). -> o_lat [B, H, rank] float32."""
    if impl == "xla":
        return plain_paged_latent_attention(q, cache, layer, tables, lengths,
                                            rank, page_size, scale)
    B, H, width = q.shape
    P = tables.shape[1]
    group = PAGES_A_STEP if P % PAGES_A_STEP == 0 else 1

    def page_of(g):
        def index(b, j, layer, tables, lengths):
            # a page past the sequence's last repeats it: nothing is fetched
            last = jnp.maximum((lengths[b] + page_size - 1) // page_size - 1,
                               0)
            return (layer[0], tables[b, jnp.minimum(j * group + g, last)], 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, P // group),
        in_specs=[pl.BlockSpec((None, H, width),
                               lambda b, j, *_: (b, 0, 0))] + [
            pl.BlockSpec((None, page_size, width), page_of(g))
            for g in range(group)],
        out_specs=pl.BlockSpec((None, H, rank), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, LANES), jnp.float32),
                        pltpu.VMEM((H, LANES), jnp.float32),
                        pltpu.VMEM((H, rank), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, rank=rank,
                          page_size=page_size, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "arbitrary")),
        name="mla_paged_latent_attention",
        interpret=(impl == "interpret"),
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
      lengths.astype(jnp.int32), q, *([cache] * group))
