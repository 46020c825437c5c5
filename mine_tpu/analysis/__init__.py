"""Static program analysis: jaxpr/StableHLO lints + runtime sanitizers.

The hot paths of this repo are stock-op XLA programs, so the regressions
that hurt are silent program-level ones — dtype upcasts, recompile churn,
accidental host syncs, dropped donation, and (host-side) lock-order bugs in
the serve threads. This package turns the one-off checks that used to live
in `tools/dtype_audit.py` and per-test dot-count asserts into a pass
framework with checked-in baselines and a loud CI gate
(`tools/audit.py --gate`, run by `tools/verify_tier1.sh`):

  flops.py      jaxpr walkers: dot_general counts / FLOPs / blur-einsum
                counts (the shared source of truth the tests assert with)
  dtype.py      StableHLO bf16->f32 upcast collection + report (the old
                tools/dtype_audit.py internals; the CLI is now a shim)
  locks.py      rank-ordered lock/condition wrappers + the global
                acquisition order for the serve/telemetry threads, plus
                thread-leak helpers (stdlib-only; no jax, no mine_tpu)
  programs.py   the registry of core jitted programs at canonical CPU
                shapes (train step, fused loss fwd/bwd, two warp
                backends, serve render single-device + mesh, eval_encode)
  framework.py  AuditPass / PassResult / run_audit + baseline file IO
                (tools/analysis_baseline.json)
  passes.py     the six registered passes, each with a seeded-violation
                selftest proving it actually detects its failure mode

Imports are lazy (PEP 562): `mine_tpu.analysis.locks` must be importable
from telemetry/serve modules without dragging in `programs` (which imports
the train and serve stacks and would create an import cycle).
"""

_SUBMODULES = ("dtype", "flops", "framework", "locks", "passes", "programs")


def __getattr__(name):
    if name in _SUBMODULES:
        import importlib
        return importlib.import_module(f"mine_tpu.analysis.{name}")
    raise AttributeError(f"module 'mine_tpu.analysis' has no attribute "
                         f"{name!r}")


__all__ = list(_SUBMODULES)
