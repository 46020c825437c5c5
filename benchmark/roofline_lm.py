"""Operations and bytes of the looped language model's train step, from its
shapes: the model FLOPs of one step (for `mfu.train`) and the floor of the
causal-attention kernels (for `attention_roofline.train`).

XLA's `cost_analysis()` does not see inside a `tpu_custom_call`, so the
attention calls are priced here by hand, from what the ALGORITHM needs,
whatever implements it: causal attention of S tokens over H heads of width D
needs the lower triangle of q k^T and of p v, 2 * 2 * (S^2 / 2) * D
multiply-adds' worth of operations a head, and reads q, k, v and writes the
output once. The backward needs five such triangles (dV = p^T dO, dP = dO
v^T, dQ = dS k, dK = dS^T q, and the recomputed q k^T), 2.5 times the
forward, and reads q, k, v, o, dO and writes dQ, dK, dV. A kernel that
recomputes more (the shipped backward forms q k^T and dO v^T twice, once in
each of its two calls) spends more than the floor and reads lower, never
over 100%.

Model FLOPs of one step (recomputed operations do not count, so the
rematerialised layer forwards and the head's recomputed chunks are left
out): 6 * tokens * (passes * L * P_layer + passes * P_head) for the matmuls
against weights (P_layer = 4 h^2 + 3 h i, P_head = h * vocab; the embedding
is a gather), plus the attention triangles forward and backward.

`shapes` is the driver's `{"kind": "lm_train", ...}`.
"""

from __future__ import annotations

BF16 = 2


def attention_call(shapes, peaks, backward: bool) -> dict:
    """All heads and rows of ONE layer application's attention."""
    rows, S = shapes["rows_per_step"], shapes["seq_len"]
    H, D = shapes["heads"], shapes["head_dim"]
    triangles = 5 if backward else 2
    ops = rows * H * triangles * 2.0 * (S * S / 2.0) * D
    arrays = 8 if backward else 4          # q k v o dO dQ dK dV | q k v o
    nbytes = rows * S * H * D * BF16 * arrays
    t_ops = ops / (peaks["peak_tflops_bf16"] * 1e12)
    t_bytes = nbytes / (peaks["hbm_gbps"] * 1e9)
    return {"ops": ops, "bytes": nbytes, "floor_s": max(t_ops, t_bytes),
            "bound": "compute" if t_ops > t_bytes else "memory"}


def attention_step_floor_s(shapes, peaks) -> float:
    """The least time one step's attention takes: every layer application
    (passes * layers of them) forward and backward. The rematerialised
    forward is recomputation and is not part of the floor."""
    applications = shapes["passes"] * shapes["layers"]
    return applications * (attention_call(shapes, peaks, False)["floor_s"]
                           + attention_call(shapes, peaks, True)["floor_s"])


def model_flops_per_step(shapes) -> float:
    tokens = shapes["rows_per_step"] * shapes["seq_len"]
    h, i = shapes["hidden"], shapes["intermediate"]
    p_layer = 4 * h * h + 3 * h * i
    p_head = h * shapes["vocab"]
    weights = 6.0 * tokens * shapes["passes"] * (shapes["layers"] * p_layer
                                                 + p_head)
    # forward 2 triangles + backward 5, each 2 * (S^2 / 2) * D a head
    attention = (shapes["passes"] * shapes["layers"] * shapes["rows_per_step"]
                 * shapes["heads"] * 7 * 2.0
                 * (shapes["seq_len"] ** 2 / 2.0) * shapes["head_dim"])
    return weights + attention
