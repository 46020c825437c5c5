"""Staged pipeline train step (mine_tpu/parallel/pipeline.py) and its
planner (mine_tpu/analysis/planner.py): the numerics contract the module
docstring pins — pipeline-off leaves the fused step bitwise-untouched,
1 stage x 1 microbatch matches the fused step to house tolerances, M
microbatches match a hand-accumulated per-microbatch reference — plus the
cost-model planner's exact peak-HBM sums, the pipeline_plan audit pass,
the st1 stage_ms telemetry round-trip, and per-stage GSPMD parity on the
8-device CPU mesh (localizing the known fused-step divergence)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu.analysis import planner
from mine_tpu.config import (CONFIG_DIR, load_config,
                             pipeline_config_from_dict)
from mine_tpu.data.synthetic import make_batch
from mine_tpu.parallel.pipeline import (STAGE_MS_KEYS, STAGE_NAMES,
                                        PipelineExecutor, stage_assignment)
from mine_tpu.telemetry import stepline
from mine_tpu.train.step import SynthesisTrainer, sample_disparity


def tiny_config(**overrides):
    cfg = load_config(os.path.join(CONFIG_DIR, "params_default.yaml"))
    cfg.update({
        "data.name": "llff",
        "data.img_h": 64, "data.img_w": 64,
        "data.per_gpu_batch_size": 2,
        "mpi.num_bins_coarse": 4,
        "mpi.disparity_start": 1.0, "mpi.disparity_end": 0.2,
        "model.num_layers": 18,
        "lr.backbone_lr": 1e-3, "lr.decoder_lr": 1e-3,
        "lr.decay_steps": [1000],
        "loss.smoothness_lambda_v1": 0.0,
        "loss.smoothness_lambda_v2": 0.0,
        "training.dtype": "float32",
    })
    cfg.update(overrides)
    return cfg


def to_jnp(batch):
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _leaf_close(a, b, rtol=2e-3, atol=0.0, err_msg=""):
    """Scaled infinity-norm closeness per leaf: max|a-b| <= rtol*max|b|
    + atol. Element-wise allclose is the wrong bar for gradient trees —
    near-zero entries carry huge relative error at float32 even when the
    trees agree to 1e-4 in norm; atol floors leaves (e.g. a bias gradient
    of 1e-7 magnitude) that are pure noise at float32."""
    for pa, pb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        na, nb = np.asarray(pa), np.asarray(pb)
        scale = float(np.abs(nb).max()) + 1e-12
        diff = float(np.abs(na - nb).max())
        assert diff <= rtol * scale + atol, (err_msg, diff, scale)


# ------------------------------------------------------------------ unit

def test_stage_assignment_contiguous():
    assert stage_assignment(1) == [0, 0, 0, 0]
    assert stage_assignment(2) == [0, 0, 1, 1]
    # array_split semantics: earlier groups take the extra program
    assert stage_assignment(3) == [0, 0, 1, 2]
    assert stage_assignment(4) == [0, 1, 2, 3]
    with pytest.raises(ValueError):
        stage_assignment(0)
    with pytest.raises(ValueError):
        stage_assignment(5)


def test_pipeline_config_validation():
    assert pipeline_config_from_dict({}).enabled is False
    cfg = pipeline_config_from_dict({"training.pipeline.enabled": True,
                                     "training.pipeline.microbatches": 4,
                                     "training.pipeline.stages": 2,
                                     "training.pipeline.hbm_budget_gb": 16})
    assert (cfg.enabled, cfg.microbatches, cfg.stages,
            cfg.hbm_budget_gb) == (True, 4, 2, 16.0)
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_config_from_dict({"training.pipeline.microbatches": 0})
    with pytest.raises(ValueError, match="stages"):
        pipeline_config_from_dict({"training.pipeline.stages": 5})
    with pytest.raises(ValueError, match="hbm_budget_gb"):
        pipeline_config_from_dict({"training.pipeline.hbm_budget_gb": -1})


# ------------------------------------------------- construction-time guards

def test_executor_rejects_fine_bins():
    cfg = tiny_config(**{"training.pipeline.enabled": True,
                         "mpi.num_bins_fine": 2})
    with pytest.raises(ValueError, match="num_bins_fine"):
        SynthesisTrainer(cfg, steps_per_epoch=10)


def test_executor_stages_require_mesh():
    cfg = tiny_config(**{"training.pipeline.enabled": True,
                         "training.pipeline.stages": 2})
    with pytest.raises(ValueError, match="mesh"):
        SynthesisTrainer(cfg, steps_per_epoch=10)


# ------------------------------------------------------------ parity bars

@pytest.fixture(scope="module")
def pipe_trainer():
    cfg = tiny_config(**{"training.pipeline.enabled": True,
                         "training.pipeline.microbatches": 1})
    trainer = SynthesisTrainer(cfg, steps_per_epoch=10)
    assert trainer._pipeline is not None
    return trainer


@pytest.fixture(scope="module")
def fused_trainer():
    return SynthesisTrainer(tiny_config(), steps_per_epoch=10)


@pytest.fixture(scope="module")
def batch2():
    return to_jnp(make_batch(2, 64, 64, num_points=16))


def test_pipeline_off_default_routes_fused_bitwise(fused_trainer, batch2):
    """enabled=False (the default) constructs no executor, and an explicit
    enabled=False config produces the bit-identical update — the fused
    step's trace is already pinned by the audit baselines; this pins the
    routing."""
    assert fused_trainer._pipeline is None
    t_explicit = SynthesisTrainer(
        tiny_config(**{"training.pipeline.enabled": False,
                       "training.pipeline.microbatches": 4}),
        steps_per_epoch=10)
    assert t_explicit._pipeline is None
    s0 = fused_trainer.init_state(batch_size=2, seed=3)
    s1 = t_explicit.init_state(batch_size=2, seed=3)
    (sa, ma) = fused_trainer.train_step(s0, batch2)
    (sb, mb) = t_explicit.train_step(s1, batch2)
    for a, b in zip(jax.tree_util.tree_leaves(sa.params),
                    jax.tree_util.tree_leaves(sb.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ma["loss"]),
                                  np.asarray(mb["loss"]))


def test_staged_1x1_matches_fused(pipe_trainer, fused_trainer, batch2):
    """1 stage x 1 microbatch: the staged schedule is the fused step cut at
    its seams. Metrics and BN statistics must match the fused step to house
    float tolerances. The reference is the fused function JITTED, as the
    trainer runs it: under jax 0.9 the same function run eagerly, op by
    op, differs from its own jitted form by up to 71% of a leaf's scale
    (decoder.conv_up1.bn.bias; 1% on loss_ssim_src), which is what failed
    the 25% bar this test used to hold against an eager reference — the
    staged path was never the side that moved. Against the jitted step
    every staged gradient leaf sits within a fifth of 2e-3 * scale + 1e-6
    (measured), so the bar is 2% + 1e-5: composing the staged functions under
    one value_and_grad reproduces the fused gradient BITWISE (the cut is
    exact), the executor runs each stage as its own XLA program, and
    XLA-CPU's threaded reductions make the cross-program noise vary run
    to run. The M-microbatch test below pins the schedule's bookkeeping
    bitwise. Gradient-level via the keep_grads hook: Adam flips update
    signs on near-zero gradients, so param deltas can't pin accumulation
    numerics."""
    ex = pipe_trainer._pipeline
    state_p = pipe_trainer.init_state(batch_size=2, seed=3)
    state_f = fused_trainer.init_state(batch_size=2, seed=3)

    ex.keep_grads = True
    try:
        state_p2, m_pipe = pipe_trainer.train_step(state_p, batch2)
        g_pipe = ex.last_grads
    finally:
        ex.keep_grads = False
        ex.last_grads = None

    key = jax.random.fold_in(state_f.rng, state_f.step)
    g_ref, m_ref, stats_ref = jax.jit(fused_trainer._grads_and_metrics)(
        state_f, batch2, key)

    _leaf_close(g_pipe["backbone"], g_ref["backbone"], rtol=2e-2,
                atol=1e-5, err_msg="backbone")
    _leaf_close(g_pipe["decoder"], g_ref["decoder"], rtol=2e-2,
                atol=1e-5, err_msg="decoder")
    # every fused metric the staged path also computes (the staged update
    # adds the same layer/guard keys via the shared _apply_update body).
    # rtol 1e-2, not the mesh-parity 2e-3: the same cross-program noise
    # amplification shifts warp-boundary pixels (observed ~4e-3 on the
    # smaller ssim terms), and XLA-CPU's threaded reductions make the
    # noise nondeterministic run to run, so the bar carries margin
    for k, v in m_ref.items():
        np.testing.assert_allclose(float(m_pipe[k]), float(v), rtol=1e-2,
                                   atol=1e-6, err_msg=k)
    _leaf_close(state_p2.batch_stats, stats_ref, rtol=1e-2, atol=1e-6,
                err_msg="batch_stats")
    assert int(state_p2.step) == 1


def test_microbatched_matches_hand_accumulated(pipe_trainer, batch2):
    """M=2: the executor's fill/drain bookkeeping — batch slicing, the RNG
    derivation (full-batch disparity draw, shared dropout key), sequential
    ghost-BN stats threading, reversed-drain gradient accumulation, the
    1/M mean — reproduced by hand from the executor's OWN jitted stage
    programs in the same call order. Same compiled programs + same inputs
    + same accumulation order = bitwise-equal gradients and stats; any
    bookkeeping drift in step() shows up exactly, with no cross-program
    float noise to hide behind."""
    t = pipe_trainer
    ex = t._pipeline
    saved_cfg = ex.cfg
    ex.cfg = dataclasses.replace(ex.cfg, microbatches=2)
    ex.keep_grads = True
    try:
        state = t.init_state(batch_size=2, seed=7)
        state2, m_pipe = t.train_step(state, batch2)
        g_pipe = ex.last_grads
    finally:
        ex.cfg = saved_cfg
        ex.keep_grads = False
        ex.last_grads = None

    # hand-rolled fill/drain over the executor's jitted programs
    key = jax.random.fold_in(state.rng, state.step)
    d_key, _f_key, drop_key = jax.random.split(key, 3)
    B, M = 2, 2
    b = B // M
    disparity = sample_disparity(d_key, B, t.cfg)
    sb = state.batch_stats["backbone"]
    sd = state.batch_stats["decoder"]
    fwd = []
    for m in range(M):
        lo, hi = m * b, (m + 1) * b
        mb = {k: v[lo:hi] for k, v in batch2.items()}
        disp = disparity[lo:hi]
        sb_in, sd_in = sb, sd
        feats, sb = ex._enc_fwd(state.params["backbone"], sb_in,
                                mb["src_img"], drop_key)
        mpi, sd = ex._dec_fwd(state.params["decoder"], sd_in, feats, disp,
                              drop_key)
        rendered = ex._rend_fwd(mpi, disp, mb)
        fwd.append((mb, disp, sb_in, sd_in, feats, mpi, rendered))
    add = lambda x, y: jax.tree_util.tree_map(jnp.add, x, y)
    g_b = g_d = None
    loss_sum = 0.0
    for m in reversed(range(M)):
        mb, disp, sb_in, sd_in, feats, mpi, rendered = fwd[m]
        _, metrics, g_rend = ex._loss_vg(rendered, mb)
        loss_sum += float(metrics["loss"])
        g_mpi = ex._rend_bwd(mpi, disp, mb, g_rend)
        g_pd, g_feats = ex._dec_bwd(state.params["decoder"], sd_in, feats,
                                    disp, drop_key, g_mpi)
        g_pb = ex._enc_bwd(state.params["backbone"], sb_in, mb["src_img"],
                           drop_key, g_feats)
        g_b = g_pb if g_b is None else add(g_b, g_pb)
        g_d = g_pd if g_d is None else add(g_d, g_pd)
    inv = 1.0 / M
    scale = lambda tr: jax.tree_util.tree_map(lambda x: x * inv, tr)
    g_ref = {"backbone": scale(g_b), "decoder": scale(g_d)}

    for grp in ("backbone", "decoder"):
        for a, r in zip(jax.tree_util.tree_leaves(g_pipe[grp]),
                        jax.tree_util.tree_leaves(g_ref[grp])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(r),
                                          err_msg=grp)
    np.testing.assert_allclose(float(m_pipe["loss"]), loss_sum / M,
                               rtol=1e-6, err_msg="mean loss")
    # ghost BN: final stats are the last microbatch's threaded update
    for a, r in zip(jax.tree_util.tree_leaves(state2.batch_stats),
                    jax.tree_util.tree_leaves({"backbone": sb,
                                               "decoder": sd})):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


def test_executor_microbatches_must_divide_batch(pipe_trainer, batch2):
    ex = pipe_trainer._pipeline
    saved_cfg = ex.cfg
    ex.cfg = dataclasses.replace(ex.cfg, microbatches=3)
    try:
        state = pipe_trainer.init_state(batch_size=2, seed=0)
        with pytest.raises(ValueError, match="microbatches"):
            pipe_trainer.train_step(state, batch2)
    finally:
        ex.cfg = saved_cfg


# ------------------------------------------------------- stage_ms telemetry

def test_step_emits_stage_ms_and_stepline_roundtrip(pipe_trainer, batch2):
    """With time_stages on, the executor leaves a per-stage wall breakdown
    whose keys are exactly STAGE_MS_KEYS; the st1 line appends them after
    data_errors and the ONE shared parser recovers them. Without extras the
    line is byte-identical to the pre-pipeline schema (append-only rule)."""
    state = pipe_trainer.init_state(batch_size=2, seed=0)
    assert pipe_trainer._pipeline.time_stages
    pipe_trainer.train_step(state, batch2)
    ms = pipe_trainer._pipeline.last_stage_ms
    assert set(ms) == set(STAGE_MS_KEYS)
    assert all(v >= 0.0 for v in ms.values())

    times = {"step_ms": 10.0, "host_wait_ms": 1.0, "device_ms": 8.5,
             "h2d_ms": 0.5}
    base = stepline.format_step_line(times, 0)
    assert base == ("time: schema=st1 step_ms=10.0 host_wait_ms=1.0 "
                    "device_ms=8.5 h2d_ms=0.5 data_errors=0")
    line = stepline.format_step_line(times, 0, extra=ms)
    assert line.startswith(base)  # append-only
    rec = stepline.parse_line(line)
    for k in STAGE_MS_KEYS:
        np.testing.assert_allclose(rec[k[:-3]], round(ms[k], 1), atol=0.051)
    agg = stepline.parse_lines([line, base])
    assert len(agg["step"]) == 2
    assert len(agg["stage_encode"]) == 1  # only the pipeline line has it


# --------------------------------------------------------------- planner

def _row(peak_hbm, flops=10 ** 12):
    # bytes tiny -> roofline is compute-bound -> expected_ms tracks flops
    return {"flops": flops, "bytes_accessed": 10 ** 3,
            "argument_bytes": 10 ** 2, "output_bytes": 10 ** 2,
            "temp_bytes": 10 ** 2, "alias_bytes": 0,
            "peak_hbm_bytes": peak_hbm}


def test_planner_single_stage_when_budget_ample():
    table = {p: _row(10 ** 6) for p in planner.PIPE_PROGRAMS}
    plan = planner.plan_stages(table, hbm_budget_bytes=10 ** 9)
    assert plan["stages"] == 1
    assert plan["cuts"] == [list(planner.PIPE_PROGRAMS)]
    assert plan["microbatches"] == 1
    assert plan["per_stage"][0]["peak_hbm_bytes"] == 4 * 10 ** 6


def test_planner_cuts_under_budget():
    # equal peaks of 6: 1 stage needs 24; at budget 12 only [enc+dec |
    # render+loss] fits among the 2-stage partitions
    table = {p: _row(6) for p in planner.PIPE_PROGRAMS}
    plan = planner.plan_stages(table, hbm_budget_bytes=12)
    assert plan["stages"] == 2
    assert plan["cuts"] == [["pipe_encode", "pipe_decode"],
                            ["pipe_render", "pipe_loss"]]
    assert [s["peak_hbm_bytes"] for s in plan["per_stage"]] == [12, 12]
    assert plan["microbatches"] == 4  # bubble (2-1)/(4+1) = 20%
    assert plan["hbm_budget_bytes"] == 12


def test_planner_min_bottleneck_among_feasible():
    # peaks of 1 with budget 3: every 2-stage partition fits; flops make
    # pipe_loss 5x the others, so the min-bottleneck cut isolates it late
    table = {p: _row(1, flops=10 ** 12) for p in planner.PIPE_PROGRAMS}
    table["pipe_loss"] = _row(1, flops=5 * 10 ** 12)
    plan = planner.plan_stages(table, hbm_budget_bytes=3)
    assert plan["stages"] == 2
    assert plan["cuts"] == [["pipe_encode", "pipe_decode", "pipe_render"],
                            ["pipe_loss"]]
    assert plan["bottleneck_ms"] <= plan["total_ms"]


def test_planner_infeasible_raises():
    table = {p: _row(100) for p in planner.PIPE_PROGRAMS}
    with pytest.raises(planner.PlanInfeasibleError, match="no contiguous"):
        planner.plan_stages(table, hbm_budget_bytes=99)


def test_planner_missing_rows_keyerror():
    table = {"pipe_encode": _row(1)}
    with pytest.raises(KeyError, match="pipe_decode"):
        planner.plan_stages(table, hbm_budget_bytes=10 ** 9)


def test_propose_microbatches_bubble_bound():
    assert planner.propose_microbatches(1) == 1
    for s in (2, 3, 4):
        m = planner.propose_microbatches(s)
        assert (s - 1) / (m + s - 1) <= planner.MAX_BUBBLE_FRAC
        assert (s - 1) / ((m - 1) + s - 1) > planner.MAX_BUBBLE_FRAC


def test_planner_peak_hbm_exact_vs_cost_model():
    """Acceptance bar: the plan's per-stage peak-HBM figures are EXACT
    integer sums of the live cost model's per-program rows (XLA's own
    post-fusion analysis on this CPU build — no estimation layer between
    the planner and the compiler)."""
    from mine_tpu.analysis import costmodel
    from mine_tpu.analysis.programs import get_program

    table = {name: costmodel.measure_program(get_program(name))
             for name in planner.PIPE_PROGRAMS}
    budget = sum(int(r["peak_hbm_bytes"]) for r in table.values()) + 1
    plan = planner.plan_stages(table, hbm_budget_bytes=budget)
    assert plan["stages"] == 1  # ample budget -> fused wins
    for st in plan["per_stage"]:
        assert st["peak_hbm_bytes"] == sum(
            int(table[p]["peak_hbm_bytes"]) for p in st["programs"])
    # and a budget squeezed under the 1-stage sum forces a real cut whose
    # stage peaks still sum exactly from the same rows
    squeezed = max(int(r["peak_hbm_bytes"]) for r in table.values())
    try:
        plan2 = planner.plan_stages(table, hbm_budget_bytes=2 * squeezed)
    except planner.PlanInfeasibleError:
        return  # rows too lopsided to cut under 2x-max — exactness held
    for st in plan2["per_stage"]:
        assert st["peak_hbm_bytes"] == sum(
            int(table[p]["peak_hbm_bytes"]) for p in st["programs"])


# ------------------------------------------------------------- audit pass

def test_pipeline_plan_pass_selftest_fails_on_seeded_violation():
    from mine_tpu.analysis.passes import PipelinePlanPass
    res = PipelinePlanPass({}, budget_gb=16.0).selftest()
    assert res.ok is False
    assert "partition" in res.details or "budget" in res.details


def test_pipeline_plan_pass_missing_rows_fail():
    from mine_tpu.analysis.passes import PipelinePlanPass
    res = PipelinePlanPass({"cost": {"train_step": {}}},
                           budget_gb=16.0).run_global()
    assert res.ok is False
    assert "no cost baseline entry" in res.details
    assert "pipe_encode" in res.details


def test_pipeline_plan_pass_green_on_feasible_rows():
    from mine_tpu.analysis.passes import PipelinePlanPass
    rows = {p: _row(10 ** 6) for p in planner.PIPE_PROGRAMS}
    res = PipelinePlanPass({"cost": rows}, budget_gb=16.0).run_global()
    assert res.ok is True
    assert "1 stage(s)" in res.details


# ------------------------- per-stage GSPMD parity on the 8-device mesh
# Satellite of the ROADMAP "Mesh-vs-single numeric divergence at 8 CPU
# devices" item: the fused train step diverges nondeterministically on any
# 8-device CPU mesh (tests/test_train.py xfails). Running each staged
# sub-program standalone against the same 8-device sharding localizes the
# drift. Empirically ALL FOUR stages hold 2e-3 parity (stable over
# repeated runs on this jax build), so none carries an xfail: the
# divergence lives in the full-graph partition (cross-stage fusion /
# collective placement), not in any one stage's ops. If a stage regresses
# on a jax upgrade, mark THAT parametrization xfail(strict=False) and
# leave the rest enforcing.

def _mesh_stage_fixture():
    from mine_tpu.parallel.mesh import make_mesh

    cfg = tiny_config(**{"data.per_gpu_batch_size": 4})
    t = SynthesisTrainer(cfg, steps_per_epoch=10)
    state = t.init_state(batch_size=4, seed=0)
    batch = to_jnp(make_batch(4, 64, 64, num_points=16))
    key = jax.random.PRNGKey(0)
    disp = jnp.tile(jnp.linspace(1.0, 0.2, t.cfg.num_bins_coarse)[None],
                    (4, 1))
    feats, _ = t.stage_encode(state.params["backbone"],
                              state.batch_stats["backbone"],
                              batch["src_img"], key)
    mpi, _ = t.stage_decode(state.params["decoder"],
                            state.batch_stats["decoder"], feats, disp, key)
    rendered = t.stage_render(mpi, disp, batch)
    mesh = make_mesh(data=4, plane=2)
    return t, state, batch, key, disp, feats, mpi, rendered, mesh


@pytest.fixture(scope="module")
def mesh_stages():
    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"
    return _mesh_stage_fixture()


def _repl(tree, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.device_put(tree, NamedSharding(mesh, P()))


def _batch_shard(tree, mesh):
    """Per-leaf batch sharding, mirroring the executor's _put_batch: shard
    dim 0 over 'data' when it divides, replicate the rest (rank-0 leaves
    like a loss scalar can't take a data spec)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rows = mesh.shape["data"]

    def put(leaf):
        arr = jnp.asarray(leaf)
        spec = P("data") if arr.ndim >= 1 and arr.shape[0] % rows == 0 \
            else P()
        return jax.device_put(arr, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(put, tree)


@pytest.mark.parametrize("stage", STAGE_NAMES)
def test_stage_gspmd_parity_8dev(mesh_stages, stage):
    t, state, batch, key, disp, feats, mpi, rendered, mesh = mesh_stages
    if stage == "encode":
        ref, _ = t.stage_encode(state.params["backbone"],
                                state.batch_stats["backbone"],
                                batch["src_img"], key)
        got, _ = jax.jit(t.stage_encode)(
            _repl(state.params["backbone"], mesh),
            _repl(state.batch_stats["backbone"], mesh),
            _batch_shard(batch["src_img"], mesh), key)
    elif stage == "decode":
        ref, _ = t.stage_decode(state.params["decoder"],
                                state.batch_stats["decoder"], feats, disp,
                                key)
        got, _ = jax.jit(t.stage_decode)(
            _repl(state.params["decoder"], mesh),
            _repl(state.batch_stats["decoder"], mesh),
            _batch_shard(feats, mesh), _batch_shard(disp, mesh), key)
    elif stage == "render":
        ref = rendered
        got = jax.jit(lambda m, d, b: t.stage_render(m, d, b, mesh=mesh))(
            _batch_shard(mpi, mesh), _batch_shard(disp, mesh),
            _batch_shard(batch, mesh))
    else:  # loss
        ref = t.stage_loss(rendered, batch)
        got = jax.jit(t.stage_loss)(_batch_shard(rendered, mesh),
                                    _batch_shard(batch, mesh))
    _leaf_close(got, ref, rtol=2e-3, err_msg=stage)
