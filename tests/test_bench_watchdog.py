"""Unit tests for bench.py's subprocess watchdog protocol.

The watchdog is what stands between a single `python bench.py` invocation
and a variant child that hangs, dies or never gets a device (see bench.py
docstring). Fake children simulate each mode so the triage logic — success
/ recorded error / crash / init-hang / body-hang — is pinned by tests, not
just by smoke runs against the real chip.
"""

import json
import os
import sys
import tempfile

import bench

PY = sys.executable

# children must write result.json ATOMICALLY (tmp + replace), exactly like
# bench.write_result — the parent polls for the file's existence
_WRITE = ("import json as _j, os as _o\n"
          "def _write(p):\n"
          "    _j.dump(p, open(_o.path.join('OUTDIR', 'r.tmp'), 'w'))\n"
          "    _o.replace(_o.path.join('OUTDIR', 'r.tmp'),"
          " _o.path.join('OUTDIR', 'result.json'))\n")


def _run(child_code, init_timeout=5.0, body_timeout=5.0, tmp_path=None):
    import shutil
    outdir = tempfile.mkdtemp(prefix="wdtest_",
                              dir=str(tmp_path) if tmp_path else None)
    try:
        payload, err, wedged = bench.run_child_watchdog(
            [PY, "-c", (_WRITE + child_code).replace("OUTDIR", outdir)],
            outdir, init_timeout, body_timeout)
    finally:
        shutil.rmtree(outdir, ignore_errors=True)
    return payload, err, wedged


def test_success():
    payload, err, wedged = _run(
        "import os\n"
        "open(os.path.join('OUTDIR', 'INIT_OK'), 'w').close()\n"
        "_write({'ips': 12.5})\n")
    assert err is None and not wedged
    assert payload == {"ips": 12.5}


def test_child_recorded_error_before_init():
    payload, err, wedged = _run(
        "_write({'error': 'no backend'})\n")
    assert payload is None and not wedged
    assert err == "no backend"


def test_child_crash_before_init_is_not_a_wedge():
    payload, err, wedged = _run("import os; os._exit(9)")
    assert payload is None and not wedged
    assert "died before device init" in err


def test_init_hang_flags_wedge():
    payload, err, wedged = _run(
        "import time\ntime.sleep(60)", init_timeout=1.5)
    assert payload is None and wedged
    assert "init timeout" in err


def test_body_hang_is_not_a_wedge():
    # a hang AFTER init is a variant-specific failure: the sweep continues
    # and the NEXT child's init probe decides whether the chip is wedged
    payload, err, wedged = _run(
        "import os, time\n"
        "open(os.path.join('OUTDIR', 'INIT_OK'), 'w').close()\n"
        "time.sleep(60)\n", body_timeout=1.5)
    assert payload is None and not wedged
    assert "timeout" in err


def test_child_crash_mid_run():
    payload, err, wedged = _run(
        "import os\n"
        "open(os.path.join('OUTDIR', 'INIT_OK'), 'w').close()\n"
        "os._exit(11)\n")
    assert payload is None and not wedged
    assert "died mid-run" in err


def test_result_error_after_init():
    payload, err, wedged = _run(
        "import os\n"
        "open(os.path.join('OUTDIR', 'INIT_OK'), 'w').close()\n"
        "_write({'error': 'RESOURCE_EXHAUSTED: vmem'})\n")
    assert payload is None and not wedged
    assert err.startswith("RESOURCE_EXHAUSTED")


def test_atomic_result_write_helper(tmp_path):
    outdir = str(tmp_path)
    bench.write_result(outdir, {"ips": 1.0})
    with open(os.path.join(outdir, "result.json")) as f:
        assert json.load(f) == {"ips": 1.0}
    assert not os.path.exists(os.path.join(outdir, "result.json.tmp"))


def test_physics_audit_rejects_above_peak_readings():
    """The round-2 incident as a regression: 226.3 img/s at 4.526
    TFLOP/step and B=4 implies 256 TFLOP/s > the 197 TFLOP/s peak."""
    v5e = "TPU v5 lite"
    err = bench.audit_reading(226.3, 4.526, 4, v5e)
    assert err is not None and err.startswith("suspect")
    # a physically plausible reading passes (70 img/s => 79 TFLOP/s)
    assert bench.audit_reading(70.0, 4.526, 4, v5e) is None
    # no cost-analysis figure -> nothing to audit against
    assert bench.audit_reading(226.3, None, 4, v5e) is None
    # a device with no published peak is an error, never priced as a v5e
    import pytest
    with pytest.raises(KeyError, match="no published peaks"):
        bench.audit_reading(70.0, 4.526, 4, "cpu")
