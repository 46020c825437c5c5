"""Guards the quick tier's coverage against silent drift.

conftest.QUICK maps suites to one cheap representative test each; a rename
or deletion of a listed test would silently shrink the tier (`pytest -m
quick` has no way to notice an entry that matched nothing). This test makes
that drift loud without collecting the whole suite.
"""

import os
import re

from tests.conftest import QUICK

HERE = os.path.dirname(os.path.abspath(__file__))


def test_quick_entries_point_at_existing_tests():
    for entry in sorted(QUICK):
        fname, _, func = entry.partition("::")
        base_func = func.split("[", 1)[0]
        path = os.path.join(HERE, fname)
        assert os.path.exists(path), f"QUICK names missing file: {entry}"
        with open(path) as f:
            src = f.read()
        assert re.search(rf"^def {re.escape(base_func)}\(", src, re.M), \
            f"QUICK names missing test function: {entry}"


def test_quick_tier_covers_most_suites():
    """Every test file should have a quick representative unless listed as a
    documented exception (suites whose every member compiles a full train
    step and would blow the <2 min budget)."""
    heavy_exempt = {
        "test_eval_cli.py",       # one end-to-end convert->eval CLI test
        "test_parity_eval.py",    # one end-to-end parity-table test
        "test_torch_parity.py",   # full-model torch parity (minutes)
        "test_train_loop.py",     # every test runs the TrainLoop
        "test_train_variants.py", # every test jits a full train step
        "test_plane_sharding.py", # mesh train-step compiles
        "test_multiprocess.py",   # env-gated 2-process job
        "test_tpu_compile.py",    # libtpu compiles, ~80 s for the file
        "test_serve_trace_e2e.py",  # every test is slow-marked (two fleets,
                                    # 2x32 traced requests)
    }
    files = {f for f in os.listdir(HERE)
             if f.startswith("test_") and f.endswith(".py")}
    covered = {e.partition("::")[0] for e in QUICK}
    missing = files - covered - heavy_exempt
    assert not missing, f"suites without a quick representative: {missing}"
