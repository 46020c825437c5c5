"""The looped language model's training loss: the expected next-token cross
entropy over the exit distribution, less an entropy bonus.

After pass t the model gives logits_t = h_t W_head and a gate g_t. With
lambda_t = sigmoid(g_t), a token leaves after pass t with probability

    q_1 = lambda_1;  q_t = lambda_t prod_{j<t}(1 - lambda_j), 1 < t < T;
    q_T = prod_{j<T}(1 - lambda_j)                      (sum_t q_t = 1)

and  loss = mean over unmasked tokens of [ sum_t q_t CE_t - beta H(q) ].
With T = 1, q = 1 and the loss is plain cross entropy.

The head and the cross entropy run over token chunks, each rematerialised in
the backward, so that no [tokens, vocabulary] array of a whole pass is ever
live: one pass's float32 logits at 8,192 tokens and 49,152 ids are 1.6 GB.
The head matmul takes `dtype` operands and accumulates in float32; the
log-sum-exp, the exit distribution and the loss are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EXIT_ENTROPY_BETA = 0.1   # the family's paper; stated under `assumed`
CHUNK_TOKENS = 1024       # 1024 x 49152 float32 logits = 201 MB a chunk


def token_chunk(tokens: int) -> int:
    """The largest chunk of at most CHUNK_TOKENS that divides `tokens`."""
    chunk = min(tokens, CHUNK_TOKENS)
    while tokens % chunk:
        chunk -= 1
    return chunk


def head_logits(hx, head, dtype):
    """hx [..., hidden] (`dtype`) times the float32 head: float32 logits."""
    return jnp.dot(hx, head.astype(dtype), preferred_element_type=jnp.float32)


def chunked_cross_entropy(h, head, labels, dtype, chunk: int = 0):
    """CE of every token: h [B, S, hidden] (`dtype`), head [hidden, vocab]
    float32, labels [B, S] int32 -> [B, S] float32. `chunk` tokens at a time
    (0: `token_chunk`); a chunk of all the tokens is the unchunked form."""
    B, S, H = h.shape
    n = B * S
    chunk = chunk or token_chunk(n)

    @jax.checkpoint
    def one(hx, lx):
        logits = head_logits(hx, head, dtype)
        picked = jnp.take_along_axis(logits, lx[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    with jax.named_scope("lm_head_loss"):
        ce = lax.map(lambda hl: one(*hl),
                     (h.reshape(n // chunk, chunk, H),
                      labels.reshape(n // chunk, chunk)))
    return ce.reshape(B, S)


def exit_distribution(gates):
    """gates [T, ...] float32 -> q [T, ...], the probability of leaving
    after each pass."""
    lam = jax.nn.sigmoid(gates.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)           # prod_{j<=t}
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay], axis=0)
    return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]], axis=0)


def looplm_loss(ce, gates, mask, beta: float = EXIT_ENTROPY_BETA):
    """ce, gates [T, B, S] float32, mask [B, S] -> (loss, step metrics)."""
    with jax.named_scope("lm_head_loss"):
        mask = mask.astype(jnp.float32)
        tokens = jnp.sum(mask)
        mean = lambda x: jnp.sum(x * mask, axis=(-2, -1)) / tokens  # noqa: E731
        q = exit_distribution(gates)
        entropy = -jnp.sum(q * jnp.log(jnp.maximum(q, 1e-30)), axis=0)
        loss = mean(jnp.sum(q * ce, axis=0) - beta * entropy)
        return loss, {"loss": loss, "ce_ut": mean(ce), "exit_q_mean": mean(q),
                      "exit_entropy": mean(entropy), "tokens": tokens}
