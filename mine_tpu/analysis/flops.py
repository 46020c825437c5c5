"""Jaxpr walkers for contraction budgets: dot counts, dot FLOPs, blur dots.

The FLOP-budget pass (passes.py) and tests/test_fused_loss.py share this
single implementation; the numeric gates live in
tools/analysis_baseline.json instead of inline test constants.

All walkers recurse into sub-jaxprs found in eqn params (pjit bodies, cond
branches, scan/while carries, custom_vjp calls), so counting a jitted
function's jaxpr and counting its unjitted body agree.
"""

from __future__ import annotations

import numpy as np


def _jaxpr_of(j):
    """Accept a ClosedJaxpr, a Jaxpr, or anything carrying `.jaxpr`."""
    inner = getattr(j, "jaxpr", j)
    # ClosedJaxpr.jaxpr is a Jaxpr; a Jaxpr has .eqns directly
    return getattr(inner, "jaxpr", inner)


def iter_eqns(jaxpr):
    """Yield every eqn in `jaxpr` and, recursively, in any sub-jaxpr held
    by an eqn's params (the walker idiom shared by all passes)."""
    jaxpr = _jaxpr_of(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from iter_eqns(inner)


def count_dots(jaxpr) -> int:
    """Number of dot_general eqns in the program (static count: a dot
    inside a scan body counts once — the budget tracks program structure;
    `dot_flops` weights trip counts)."""
    return sum(1 for eqn in iter_eqns(jaxpr)
               if eqn.primitive.name == "dot_general")


def dot_flops(jaxpr, mult: int = 1) -> int:
    """Sum dot_general FLOPs (2 * batch * lhs_free * rhs_free * contract),
    recursing into sub-jaxprs; scan bodies multiply by the trip count."""
    jaxpr = _jaxpr_of(jaxpr)
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            batch = int(np.prod([lhs[i] for i in lb], initial=1))
            contract = int(np.prod([lhs[i] for i in lc], initial=1))
            lfree = int(np.prod([lhs[i] for i in range(len(lhs))
                                 if i not in tuple(lc) + tuple(lb)],
                                initial=1))
            rfree = int(np.prod([rhs[i] for i in range(len(rhs))
                                 if i not in tuple(rc) + tuple(rb)],
                                initial=1))
            total += 2 * mult * batch * contract * lfree * rfree
            continue
        m = mult
        if eqn.primitive.name == "scan":
            m = mult * int(eqn.params["length"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += dot_flops(inner, m)
    return total


def count_blur_dots(jaxpr, sizes=(64, 32, 16, 8)) -> int:
    """dot_generals attributable to SSIM blurs: a Toeplitz blur einsum is
    the only contraction in the loss graph whose operand is a square 2-D
    matrix sized like a pyramid level (everything else contracts [B,3,3]
    intrinsics-style batches or non-square grids)."""
    n = 0
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        for var in eqn.invars:
            shape = var.aval.shape
            if (len(shape) == 2 and shape[0] == shape[1]
                    and shape[0] in sizes):
                n += 1
                break
    return n
