#!/usr/bin/env python
"""What the token server's reference check reads when the program computes
in the nearest precision below the one its configuration states: the second
reading each limit of benchmark/reference_moe_mla.py `TOLERANCES` is set
against (PERF.md section 6, PR 35). On the chip, at the cell's timed sizes:

  chiprun -- python tools/lm_serve_fault_readings.py <seed> [fault ...]

The cell `kimi_serve_docqa` through benchmark/run.py itself (same driver,
same traffic, a short window), with faults patched in from here before the
server is built (the program has no knob for any):
  cache_fp8      the latent rows of every layer are rounded through
                 float8_e4m3fn on their way into the cache (a float8
                 cache)                                -> cache.layer0
  cache_fp8_late the same on the expert layers (1..) alone, which
                 cache.layer0 cannot see               -> cache.last
  router_bf16    the router's scores and top-k in bfloat16 -> same.router
  head_bf16      the logits leave the head as bfloat16     -> same.head
With no fault named, the first, third and fourth at once: each is read by a
limit of its own (a block alone on the program's own inputs), so one run
shows all three. A run's `correct` must come out false, by those limits; a
fault named alone shows what `logits.*` reads under it alone.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAULTS = ("cache_fp8", "router_bf16", "head_bf16")
ALONE = ("cache_fp8_late",)


def plant(faults):
    import jax
    import jax.numpy as jnp

    from mine_tpu.models import moe_mla
    if "cache_fp8" in faults or "cache_fp8_late" in faults:
        project = moe_mla.mla_project
        every_layer = "cache_fp8" in faults

        def project_fp8(x, w, *args, **kwargs):
            q_nope, q_rope, latent = project(x, w, *args, **kwargs)
            if every_layer or "router" in w:    # an expert layer's weights
                latent = jax.lax.reduce_precision(
                    latent.astype(jnp.float32), 4, 3).astype(latent.dtype)
            return q_nope, q_rope, latent
        moe_mla.mla_project = project_fp8
    if "router_bf16" in faults:
        moe_mla.ROUTER_DTYPE = jnp.bfloat16
    if "head_bf16" in faults:
        head = moe_mla.head

        def head_bf16(*args, **kwargs):
            logits, hidden = head(*args, **kwargs)
            # (a convert down and up again is "excess precision" the
            # compiler may drop; reduce_precision it keeps)
            return jax.lax.reduce_precision(logits, 8, 7), hidden
        moe_mla.head = head_bf16


def main():
    seed = int(sys.argv[1])
    faults = tuple(sys.argv[2:]) or FAULTS
    assert set(faults) <= set(FAULTS + ALONE), faults
    from benchmark import run
    plant(faults)
    print("faults planted: %s" % (faults,), flush=True)
    return run.run(["--workload", "kimi_serve_docqa", "--seed", str(seed),
                    "--seconds", "6", "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
