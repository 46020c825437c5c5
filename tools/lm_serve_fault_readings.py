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

The faults of `dots3_serve_longdoc` (benchmark/reference_dots3.py
`TOLERANCES`; PERF.md section 6, PR 37), each named ALONE, a process a fault:
  index_bf16     the indexer's scores rounded to bfloat16  -> same.index
  topk_1024      the selection keeps 1,024 keys            -> index.bad_rows
  window_256     a chunk's rows attend a window of 256     -> attn.sliding
  rows_fp8       every cache row (latent, index key, window) through
                 float8_e4m3fn                             -> cache.*
  no_gate        the headwise gate left out                -> attn.*
  no_rescale     the latents' rescale left out             -> attn.*, cache.*
  index_no_rope  the index queries without RoPE (the index keys keep it)
                                          -> index.q_first, index.drift_first
  index_w_negated  the index heads' weights negated: S_t is the 2,048 LOWEST
                 scores, exactly, and every number the program returns is
                 consistent with it       -> index.w_first, index.drift_first
They run the cell's configuration at its published widths through
benchmark/run.py with its traffic cut to what the check needs (3 resident
documents of 12.3k-13k tokens, one chunk bucket, one context bucket: two step
programs to compile a fault where the cell has nine).
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAULTS = ("cache_fp8", "router_bf16", "head_bf16")
ALONE = ("cache_fp8_late",)
SPARSE = ("index_bf16", "topk_1024", "window_256", "rows_fp8", "no_gate",
          "no_rescale", "index_no_rope", "index_w_negated")
# the sparse cell's traffic and buckets, cut to what the check needs
SPARSE_TRAFFIC = {
    "resident_documents": 3, "workers": 4,
    # (the longest prompt, padded to a whole chunk, stays inside the bucket)
    "document_tokens": {"median": 12600, "sigma": 0.02, "min": 12288,
                        "max": 13000},
    "config_overrides": {"serve.lm.chunk_buckets": [2048],
                         "serve.lm.context_buckets": [16384],
                         "serve.lm.cache_tokens": 131072}}


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


def plant_sparse(fault):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from mine_tpu.kernels import attention
    from mine_tpu.models import moe_mla
    fp8 = lambda a: jax.lax.reduce_precision(  # noqa: E731
        a.astype(jnp.float32), 4, 3).astype(a.dtype)
    if fault == "index_bf16":
        for module, name in ((attention, "index_scores"),
                             (moe_mla, "dsa_index_paged")):
            def rounded(*args, _fn=getattr(module, name), **kwargs):
                return jax.lax.reduce_precision(_fn(*args, **kwargs), 8, 7)
            setattr(module, name, rounded)
    elif fault == "topk_1024":
        threshold, positions = moe_mla.dsa_threshold, moe_mla.dsa_positions
        half = lambda cfg: dataclasses.replace(cfg,  # noqa: E731
                                               index_topk=1024)
        moe_mla.dsa_threshold = lambda scores, seen, cfg: threshold(
            scores, seen, half(cfg))
        moe_mla.dsa_positions = (
            lambda scores, seen, tau, bound, cfg, *args: positions(
                scores, seen, tau, bound, half(cfg), *args))
    elif fault == "window_256":
        attend = attention.window_attention
        attention.window_attention = (
            lambda q, k, v, heads, q_offset, scale, window, *args, **kwargs:
            attend(q, k, v, heads, q_offset, scale, min(window, 256), *args,
                   **kwargs))
    elif fault == "rows_fp8":
        project, index = moe_mla.mla_project, moe_mla.dsa_project

        def project_fp8(*args, **kwargs):
            q_nope, q_rope, latent = project(*args, **kwargs)
            return q_nope, q_rope, fp8(latent)

        def index_fp8(*args, **kwargs):
            q, k, w = index(*args, **kwargs)
            return q, fp8(k), w
        moe_mla.mla_project, moe_mla.dsa_project = project_fp8, index_fp8
    elif fault in ("index_no_rope", "index_w_negated"):
        index = moe_mla.dsa_project

        def wrong(u, c_q, w, cfg, cos, sin):
            q, k, w_i = index(u, c_q, w, cfg, cos, sin)
            if fault == "index_w_negated":
                return q, k, -w_i
            return index(u, c_q, w, cfg, jnp.ones_like(cos),
                         jnp.zeros_like(sin))[0], k, w_i
        moe_mla.dsa_project = wrong
    elif fault == "no_gate":
        moe_mla.attention_gate = lambda o, u, w, cfg: o
    elif fault == "no_rescale":
        moe_mla.MoeMlaConfig.lora_scales = property(lambda self: (1.0, 1.0))


def cut_traffic():
    """The sparse cell with SPARSE_TRAFFIC laid over its traffic file."""
    from benchmark import harness
    init = harness.Cell.__init__

    def cut(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self.workload = dict(self.workload, **SPARSE_TRAFFIC)
    harness.Cell.__init__ = cut


def main():
    seed = int(sys.argv[1])
    faults = tuple(sys.argv[2:]) or FAULTS
    assert set(faults) <= set(FAULTS + ALONE + SPARSE + ("none",)), faults
    from benchmark import run
    workload = "kimi_serve_docqa"
    if set(faults) & set(SPARSE + ("none",)):   # `none`: the cut cell alone
        assert len(faults) == 1, "a sparse fault is planted alone"
        workload = "dots3_serve_longdoc"
        cut_traffic()
        plant_sparse(faults[0])
    else:
        plant(faults)
    print("faults planted: %s" % (faults,), flush=True)
    return run.run(["--workload", workload, "--seed", str(seed),
                    "--seconds", "6", "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
