"""The program auditor (mine_tpu/analysis/ + tools/audit.py).

Four layers of coverage:
  * lock-order monitor mechanics (OrderedLock/ordered_condition, the
    violation recorder, the thread-leak policy)
  * the pass framework's primitives (flop counting, baseline IO, report)
  * each pass's DETECTION, via its seeded-violation selftest — proving the
    gate can actually fail (a lint that never fires is worse than none)
  * the two expensive real-program audits ISSUE names: donation on the
    actual jitted train step, recompile churn on the serve engine across
    every cache quant mode
"""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu.analysis import costmodel
from mine_tpu.analysis import flops as flops_mod
from mine_tpu.analysis import locks
from mine_tpu.analysis import passes as passes_mod
from mine_tpu.analysis.framework import (BASELINE_SCHEMA, PassResult,
                                         format_report, load_baseline,
                                         run_audit, save_baseline)
from mine_tpu.telemetry import hostsync


# ---------------------------------------------------------------------------
# lock-order monitor
# ---------------------------------------------------------------------------

def test_lock_order_monitor_records_inversion():
    locks.violations(clear=True)
    hi = locks.OrderedLock("t.hi", rank=20)
    lo = locks.OrderedLock("t.lo", rank=10)
    with hi:
        with lo:  # rank 10 acquired while holding rank 20: inversion
            pass
    v = locks.violations(clear=True)
    assert len(v) == 1
    assert v[0]["acquiring"] == "t.lo"
    assert v[0]["held"] == [("t.hi", 20)]


def test_lock_order_ascending_is_clean():
    locks.violations(clear=True)
    lo = locks.OrderedLock("t.lo", rank=10)
    hi = locks.OrderedLock("t.hi", rank=20)
    with lo:
        with hi:
            pass
    # sequential (non-nested) use in any order is clean too
    with hi:
        pass
    with lo:
        pass
    assert locks.violations(clear=True) == []


def test_equal_rank_nesting_is_a_violation():
    """Two metric locks (peers at one rank) must never nest — that is an
    undeclared ordering the rank table cannot arbitrate."""
    locks.violations(clear=True)
    a = locks.OrderedLock("t.a", rank=55)
    b = locks.OrderedLock("t.b", rank=55)
    with a:
        with b:
            pass
    v = locks.violations(clear=True)
    assert len(v) == 1 and v[0]["acquiring"] == "t.b"


def test_held_stack_is_thread_local():
    locks.violations(clear=True)
    hi = locks.OrderedLock("t.hi", rank=20)
    lo = locks.OrderedLock("t.lo", rank=10)
    err = []

    def other():
        try:
            with lo:  # this thread holds nothing: no violation
                pass
        except Exception as e:  # pragma: no cover
            err.append(e)

    with hi:
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert not err
    assert locks.violations(clear=True) == []


def test_unknown_name_without_rank_raises():
    with pytest.raises(KeyError):
        locks.OrderedLock("not.in.the.table")


def test_registered_names_resolve_ranks():
    for name, rank in locks.LOCK_RANKS.items():
        assert locks.ordered_lock(name).rank == rank


def test_ordered_condition_wait_notify():
    """Condition(lock=OrderedLock) must behave like a plain Condition —
    the batcher's cv is exactly this. Includes the _is_owned probe path
    (a failed non-blocking acquire must not touch the held-stack)."""
    locks.violations(clear=True)
    cv = locks.ordered_condition("t.cv", rank=10)
    ready = []

    def waiter():
        with cv:
            while not ready:
                cv.wait(timeout=5)

    t = threading.Thread(target=waiter)
    t.start()
    with cv:
        ready.append(1)
        cv.notify()
    t.join(timeout=5)
    assert not t.is_alive()
    assert locks.violations(clear=True) == []


def test_leaked_threads_flags_owned_daemon_and_nondaemon():
    stop = threading.Event()

    def linger():
        stop.wait(10)

    owned = threading.Thread(target=linger, daemon=True,
                             name="mine-tpu-serve-batcher-test")
    plain_daemon = threading.Thread(target=linger, daemon=True,
                                    name="innocent-daemon")
    owned.start()
    plain_daemon.start()
    try:
        leaked = locks.leaked_threads()
        names = {t.name for t in leaked}
        assert "mine-tpu-serve-batcher-test" in names  # owned prefix match
        assert "innocent-daemon" not in names  # non-owned daemons exempt
        baseline = set(threading.enumerate())
        assert locks.leaked_threads(baseline=baseline) == []
    finally:
        stop.set()
        owned.join(timeout=5)
        plain_daemon.join(timeout=5)


# ---------------------------------------------------------------------------
# flop counting
# ---------------------------------------------------------------------------

def test_count_dots_and_flops_plain_matmul():
    j = jax.make_jaxpr(lambda a, b: a @ b)(
        jnp.zeros((4, 8), jnp.float32), jnp.zeros((8, 2), jnp.float32))
    assert flops_mod.count_dots(j) == 1
    assert flops_mod.dot_flops(j) == 2 * 4 * 2 * 8


def test_dot_flops_scan_multiplies_by_trip_count():
    def scanned(a, b):
        def body(c, _):
            return c @ b, ()
        out, _ = jax.lax.scan(body, a, None, length=5)
        return out

    j = jax.make_jaxpr(scanned)(
        jnp.zeros((4, 8), jnp.float32), jnp.zeros((8, 8), jnp.float32))
    assert flops_mod.dot_flops(j) == 5 * 2 * 4 * 8 * 8


def test_count_blur_dots_square_pyramid_operands_only():
    def f(m, x):
        a = jnp.einsum("ij,bcjk->bcik", m, x)     # square 64: counted
        return a @ jnp.swapaxes(x, -1, -2)        # non-pyramid: not
    j = jax.make_jaxpr(f)(jnp.zeros((64, 64), jnp.float32),
                          jnp.zeros((2, 3, 64, 64), jnp.float32))
    # the second dot's operands are 4-D [2,3,64,64]: only the Toeplitz-style
    # square 2-D operand matches the blur signature
    assert flops_mod.count_blur_dots(j) == 1


# ---------------------------------------------------------------------------
# framework: baseline IO + report
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_schema_gate(tmp_path):
    path = str(tmp_path / "b.json")
    missing = load_baseline(path)
    assert missing["programs"] == {} and missing["schema"] == BASELINE_SCHEMA
    assert missing["cost"] == {}
    missing["programs"]["p"] = {"dots": 3}
    missing["cost"]["p"] = {"flops": 128, "peak_hbm_bytes": 224}
    save_baseline(missing, path)
    again = load_baseline(path)
    assert again["programs"]["p"] == {"dots": 3}
    assert again["cost"]["p"] == {"flops": 128, "peak_hbm_bytes": 224}
    with open(path, "w") as f:
        json.dump({"schema": "other"}, f)
    with pytest.raises(ValueError, match="schema"):
        load_baseline(path)


def test_baseline_without_cost_section_gets_empty_one(tmp_path):
    """A pre-PR-12 baseline file (no 'cost' key) loads with an empty cost
    section instead of KeyError-ing every CostBudgetPass lookup."""
    path = str(tmp_path / "old.json")
    with open(path, "w") as f:
        json.dump({"schema": BASELINE_SCHEMA, "programs": {},
                   "budgets": {}}, f)
    assert load_baseline(path)["cost"] == {}


def test_checked_in_baseline_covers_all_programs():
    """Every registered program has a budget entry — a new program without
    one fails the gate with 'run --update-baseline', and this test makes
    the omission visible without running the audit."""
    from mine_tpu.analysis.programs import program_names
    baseline = load_baseline()
    missing = set(program_names()) - set(baseline["programs"])
    assert not missing, f"programs without a baseline entry: {missing}"
    for key in ("fused_loss.blur_dots", "fused_loss.blur_dots_reference"):
        assert key in baseline["budgets"]
    # cost side of the ledger: every program pinned, every key present
    missing_cost = set(program_names()) - set(baseline["cost"])
    assert not missing_cost, (
        f"programs without a cost baseline entry: {missing_cost}")
    for name, entry in baseline["cost"].items():
        assert set(entry) == set(costmodel.COST_KEYS), (
            f"{name}: cost keys drifted from COST_KEYS — regenerate with "
            f"tools/audit.py --update-baseline")


def test_format_report_counts_failures():
    results = [PassResult("p1", "a", ok=True, details="fine"),
               PassResult("p2", "b", ok=False, details="broken")]
    text = format_report(results)
    assert "[  ok]" in text and "[FAIL]" in text
    assert "2 checks, 1 failed" in text


def test_run_audit_survives_crashing_pass():
    class Boom(passes_mod.AuditPass):
        name = "boom"

        def run(self, program):
            raise RuntimeError("kaput")

    class P:
        name = "prog"

    results = run_audit([P()], [Boom()])
    assert len(results) == 1 and not results[0].ok
    assert "kaput" in results[0].details


# ---------------------------------------------------------------------------
# each pass detects its seeded violation (the --selftest contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pass_name", [
    "dtype_upcast", "dot_budget", "cost_budget", "recompile_churn",
    "transfer_guard", "donation", "concurrency", "aot_staleness"])
def test_pass_selftest_detects_seeded_violation(pass_name):
    p = passes_mod.pass_by_name(pass_name)
    r = p.selftest()
    assert r.ok is False, (
        f"{pass_name} selftest came back ok — the pass is blind to the "
        f"violation it exists to catch: {r.details}")
    assert r.details  # a failure must explain itself


def test_dtype_pass_passes_on_justified_and_nonconv_upcasts():
    p = passes_mod.DtypeUpcastPass()
    clean = """
%0 = stablehlo.convert %a : (tensor<2x64xbf16>) -> tensor<2x64xf32> loc(#loc1)
%1 = stablehlo.convert %b : (tensor<8xbf16>) -> tensor<8xf32> loc(#loc2)
#loc1 = loc("jit(step)/encoder/resnet/bn1/batch_norm/convert"(#loc9))
#loc2 = loc("jit(step)/adam/convert_element_type"(#loc9))
"""
    r = p._check_text("fixture", clean)
    assert r.ok, r.details


def test_transfer_guard_pass_clean_on_staged_args():
    p = passes_mod.TransferGuardPass()
    f = jax.jit(lambda x: x * 2)
    staged = jnp.ones((4,), jnp.float32)
    r = p._check_workload("fixture", lambda: f(staged))
    assert r.ok, r.details


def test_host_readback_counts_and_allows():
    hostsync.reset()
    with jax.transfer_guard("disallow"):
        with hostsync.host_readback("test.reason"):
            # declared: the h2d that would otherwise be disallowed
            jnp.asarray(np.ones((2,), np.float32)).block_until_ready()
    assert hostsync.readback_counts() == {"test.reason": 1}
    hostsync.reset()
    assert hostsync.readback_counts() == {}


# ---------------------------------------------------------------------------
# the real-program audits ISSUE names (heavy: compiles the tiny train step)
# ---------------------------------------------------------------------------

def test_donation_audit_on_real_train_step():
    """The jitted SynthesisTrainer train step's donated state buffers are
    actually consumed — a dropped donation would double peak memory at the
    flagship shape, invisible at test shapes without this check."""
    from mine_tpu.analysis.programs import get_program
    prog = get_program("train_step")
    assert prog.donate_argnums  # state is donated by construction
    r = passes_mod.DonationPass().run(prog)
    assert r.ok, r.details
    assert r.data["leaves"] > 0


@pytest.mark.parametrize("quant", ["float32", "bf16", "int8"])
def test_recompile_churn_serve_engine_all_quant_modes(quant):
    """Re-dispatching the serve render with freshly materialized inputs
    must hit the jit cache in every plane-cache quant mode — int8's
    scales operand and bf16's cast path each churn differently."""
    from mine_tpu.analysis.programs import serve_render_program
    prog = serve_render_program(quant=quant)
    r = passes_mod.RecompileChurnPass().run(prog)
    assert r.ok, r.details


def test_transfer_guard_on_serve_workload():
    """The engine's full hot path (dispatch + declared output readback)
    is clean under transfer_guard(disallow)."""
    from mine_tpu.analysis.programs import serve_render_program
    prog = serve_render_program(quant="int8")
    r = passes_mod.TransferGuardPass().run(prog)
    assert r.ok, r.details


def test_concurrency_pass_clean_on_live_workload():
    """The live threaded serve workload (3 submitters x 8 requests +
    ops-endpoint traffic) crosses every instrumented lock without an
    order violation or a leaked thread."""
    r = passes_mod.ConcurrencyPass().run_global()
    assert r.ok, r.details


# ---------------------------------------------------------------------------
# compiled cost/memory model (analysis/costmodel.py, the cost_budget pass)
# ---------------------------------------------------------------------------

def test_compiled_cost_tiny_matmul_keys_and_bound():
    m, k, n = 8, 16, 4
    cost = costmodel.compiled_cost(
        jax.jit(lambda a, b: a @ b),
        (jnp.zeros((m, k), jnp.float32), jnp.zeros((k, n), jnp.float32)))
    assert set(cost) == set(costmodel.COST_KEYS)
    assert cost["flops"] == 2 * m * k * n
    assert all(v >= 0 for v in cost.values())
    # no donation here, so alias=0 and peak is exactly arg+out+temp
    assert cost["alias_bytes"] == 0
    assert cost["peak_hbm_bytes"] >= (cost["argument_bytes"]
                                      + cost["output_bytes"])


def test_roofline_picks_the_binding_resource():
    # 1 TFLOP at 1 byte: compute-bound; expected time = flops / peak
    c = costmodel.roofline({"flops": 10**12, "bytes_accessed": 1},
                           peak_tflops=1.0, hbm_gbps=1000.0)
    assert c["bound"] == "compute"
    assert c["expected_ms"] == pytest.approx(1000.0)
    # 1 flop over 1 GB: memory-bound; expected time = bytes / bandwidth
    m = costmodel.roofline({"flops": 1, "bytes_accessed": 10**9},
                           peak_tflops=1.0, hbm_gbps=1.0)
    assert m["bound"] == "memory"
    assert m["expected_ms"] == pytest.approx(1000.0)
    assert m["expected_ms"] == max(m["compute_ms"], m["memory_ms"])


@pytest.mark.slow
def test_cost_peak_hbm_bound_on_real_train_step():
    """On the real donated train step, peak HBM must still cover the live
    argument+output working set — the donation alias discount can never
    push the model below what the arrays themselves occupy. Also pins the
    measurement against the checked-in baseline (same CPU determinism the
    gate relies on). Slow tier: ~35s AOT compile the in-window audit
    --gate cost_budget pass already performs and exact-gates."""
    from mine_tpu.analysis.programs import get_program
    prog = get_program("train_step")
    cost = costmodel.measure_program(prog)
    assert cost["peak_hbm_bytes"] >= (cost["argument_bytes"]
                                      + cost["output_bytes"])
    assert cost["alias_bytes"] > 0  # state donation actually aliases
    expected = load_baseline()["cost"]["train_step"]
    assert cost == expected, (
        "compiled train_step cost drifted from tools/analysis_baseline.json"
        " — rerun tools/audit.py --update-baseline and review the diff")
