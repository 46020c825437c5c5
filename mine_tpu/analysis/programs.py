"""Registry of the core jitted programs at canonical (tiny, CPU) shapes.

Every pass in passes.py runs over these Programs: the train step, the fused
loss forward and backward, the two warp backends, the serve render engine
(single-device and mesh), and the eval encode. Shapes are the smallest ones
that exercise the real program structure (the same 64x64 / 4-plane /
resnet18 family the test suite's tiny_setup uses), so the full audit gate
runs on the CPU container in minutes.

A Program owns one jitted callable plus an `args_fn` that materializes
FRESH canonical arguments on every call — donation passes consume buffers,
and the recompile-churn pass needs two independently-constructed but
aval-identical argument sets. Arguments are rebuilt from cached HOST copies
(numpy trees), so repeated materialization costs a device_put, not a model
re-init.

Builders are lazy and cached: importing this module imports the train and
serve stacks, but nothing is traced or compiled until a pass asks.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from mine_tpu.analysis import dtype as _dtype
from mine_tpu.config import SERVE_WARP_BACKENDS

# canonical tiny-trainer shape (tools/dtype_audit.py --small): 64x64,
# 4 coarse planes, resnet18, batch 1
TINY = dict(height=64, width=64, planes=4, layers=18, batch=1)

# serve-engine canonical shape: R cached entries of S planes at HxW,
# P poses. S=2 divides the mesh "model" axis; H=W=16 keeps compiles sub-s.
SERVE = dict(R=1, S=2, H=16, W=16, P=2)

# one warp program for each backend a config may name
WARP_IMPLS = SERVE_WARP_BACKENDS


@dataclasses.dataclass
class Program:
    """One audited program: a jitted callable + canonical argument factory.

    tags:
      "train" / "serve" / "warp" / "loss"  subsystem, for --programs filters
      "mesh"      runs on a multi-device CPU mesh
      "pallas"    body contains pallas_call (interpret mode on CPU)
    donate_argnums: positions whose buffers the program donates (the
      donation pass audits exactly these).
    workload: optional host-side hot path (no arguments) for the transfer
      sanitizer — e.g. the serve engine's full _call including its output
      readback; defaults to dispatching the jitted callable.
    """

    name: str
    jit_fn: Callable
    args_fn: Callable[[], Tuple]
    tags: Tuple[str, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    workload: Optional[Callable[[], None]] = None
    _jaxpr: Optional[object] = dataclasses.field(default=None, repr=False)
    _hlo: Optional[str] = dataclasses.field(default=None, repr=False)

    def jaxpr(self):
        if self._jaxpr is None:
            self._jaxpr = jax.make_jaxpr(self.jit_fn)(*self.args_fn())
        return self._jaxpr

    def stablehlo(self) -> str:
        if self._hlo is None:
            lowered = self.jit_fn.lower(*self.args_fn())
            self._hlo = _dtype.stablehlo_text(lowered)
        return self._hlo

    def run(self):
        return self.jit_fn(*self.args_fn())

    def cache_size(self) -> Optional[int]:
        fn = getattr(self.jit_fn, "_cache_size", None)
        return fn() if fn is not None else None


def _host_tree(tree):
    """Pytree -> numpy host copies (device-independent canonical form)."""
    return jax.tree_util.tree_map(np.asarray, tree)


def _device_tree(tree):
    """Host tree -> fresh device buffers, preserving dtypes exactly."""
    return jax.tree_util.tree_map(jnp.asarray, tree)


# ------------------------------------------------------------ tiny trainer

@functools.lru_cache(maxsize=2)
def _tiny_trainer(dtype: str = "bfloat16"):
    """The shared 64x64/4-plane/resnet18 trainer behind the train, loss and
    eval programs. bf16 by default so the dtype-upcast pass audits the
    mixed-precision program the bench runs, not an f32 stand-in."""
    from mine_tpu.config import CONFIG_DIR, load_config
    from mine_tpu.data.synthetic import make_batch
    from mine_tpu.train.step import SynthesisTrainer

    t = TINY
    config = load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"))
    config.update({
        "data.img_h": t["height"], "data.img_w": t["width"],
        "mpi.num_bins_coarse": t["planes"],
        "model.num_layers": t["layers"],
        "data.per_gpu_batch_size": t["batch"],
        "training.dtype": dtype,
        # audit the portable program, not a TPU-only lowering
        "training.warp_backend": "xla",
        "training.composite_backend": "xla",
        # audit the telemetry-enabled step: the transfer_guard pass staying
        # green here is the proof that per-layer stats add no host syncs
        "training.layer_stats": True,
    })
    trainer = SynthesisTrainer(config, steps_per_epoch=10_000)
    state_host = _host_tree(trainer.init_state(batch_size=t["batch"]))
    batch_host = {k: np.asarray(v) for k, v in
                  make_batch(t["batch"], t["height"], t["width"],
                             num_points=64).items()}
    return trainer, state_host, batch_host


def _build_train_step() -> Program:
    trainer, state_host, batch_host = _tiny_trainer()

    def args_fn():
        return _device_tree(state_host), _device_tree(batch_host)

    # mirrors the donate_argnums the trainer's constructor chose
    donate = (0, 1) if bool(
        trainer.config.get("training.donate_batch", False)) else (0,)
    return Program(name="train_step", jit_fn=trainer._train_step,
                   args_fn=args_fn, tags=("train",),
                   donate_argnums=donate)


def _build_eval_encode() -> Program:
    trainer, state_host, batch_host = _tiny_trainer()
    S = TINY["planes"]
    disparity = np.tile(np.linspace(1.0, 0.2, S, dtype=np.float32)[None],
                        (TINY["batch"], 1))

    def args_fn():
        return (_device_tree(state_host),
                jnp.asarray(batch_host["src_img"]),
                jnp.asarray(disparity))

    return Program(name="eval_encode", jit_fn=trainer._eval_encode,
                   args_fn=args_fn, tags=("train",))


# ------------------------------------------------------------- fused loss

@functools.lru_cache(maxsize=1)
def _loss_fixture():
    from mine_tpu.data.synthetic import make_batch
    from mine_tpu.train import loss as loss_mod

    trainer, _, _ = _tiny_trainer()
    cfg = trainer.cfg
    B, S, side = TINY["batch"], TINY["planes"], TINY["height"]
    batch_host = {k: np.asarray(v) for k, v in
                  make_batch(B, side, side, num_points=64).items()}
    mpi_host = [np.zeros((B, S, 4, side // 2 ** s, side // 2 ** s),
                         np.float32) for s in range(4)]
    disp_host = np.tile(np.linspace(1.0, 0.2, S, dtype=np.float32)[None],
                        (B, 1))

    def total(m, d, bt):
        return loss_mod.compute_losses(m, d, bt, cfg)[0]

    return total, mpi_host, disp_host, batch_host


def _loss_args_fn():
    _, mpi_host, disp_host, batch_host = _loss_fixture()
    return (_device_tree(mpi_host), jnp.asarray(disp_host),
            _device_tree(batch_host))


def _build_fused_loss_fwd() -> Program:
    total, _, _, _ = _loss_fixture()
    return Program(name="fused_loss_fwd", jit_fn=jax.jit(total),
                   args_fn=_loss_args_fn, tags=("loss",))


def _build_fused_loss_bwd() -> Program:
    total, _, _, _ = _loss_fixture()
    return Program(name="fused_loss_bwd",
                   jit_fn=jax.jit(jax.grad(total)),
                   args_fn=_loss_args_fn, tags=("loss",))


# ------------------------------------------------------- pipeline stages

@functools.lru_cache(maxsize=1)
def _stage_fixture():
    """Canonical inputs for the four pipeline stage programs (train/step.py
    stage_encode/stage_decode/stage_render/stage_loss): the boundary
    activations are materialized ONCE by running the real stage chain on
    the tiny trainer, then cached as host trees — so pipe_decode is audited
    on genuine encoder features, pipe_loss on genuine rendered pytrees.
    These are the programs the pipeline executor jits per stage; their
    cost rows feed tools/pipeline_plan.py."""
    trainer, state_host, batch_host = _tiny_trainer()
    B, S = TINY["batch"], TINY["planes"]
    state = _device_tree(state_host)
    batch = _device_tree(batch_host)
    disp_host = np.tile(np.linspace(1.0, 0.2, S, dtype=np.float32)[None],
                        (B, 1))
    key = jax.random.PRNGKey(0)
    feats, _ = trainer.stage_encode(state.params["backbone"],
                                    state.batch_stats["backbone"],
                                    batch["src_img"], key)
    mpi, _ = trainer.stage_decode(state.params["decoder"],
                                  state.batch_stats["decoder"],
                                  feats, jnp.asarray(disp_host), key)
    rendered = trainer.stage_render(mpi, jnp.asarray(disp_host), batch)
    return (trainer, state_host, batch_host, disp_host,
            _host_tree(feats), _host_tree(mpi), _host_tree(rendered))


def _build_pipe_encode() -> Program:
    trainer, state_host, batch_host, _, _, _, _ = _stage_fixture()

    def args_fn():
        state = _device_tree(state_host)
        return (state.params["backbone"], state.batch_stats["backbone"],
                jnp.asarray(batch_host["src_img"]), jax.random.PRNGKey(0))

    return Program(name="pipe_encode", jit_fn=jax.jit(trainer.stage_encode),
                   args_fn=args_fn, tags=("train", "pipeline"))


def _build_pipe_decode() -> Program:
    trainer, state_host, _, disp_host, feats_host, _, _ = _stage_fixture()

    def args_fn():
        state = _device_tree(state_host)
        return (state.params["decoder"], state.batch_stats["decoder"],
                _device_tree(feats_host), jnp.asarray(disp_host),
                jax.random.PRNGKey(0))

    return Program(name="pipe_decode", jit_fn=jax.jit(trainer.stage_decode),
                   args_fn=args_fn, tags=("train", "pipeline"))


def _build_pipe_render() -> Program:
    trainer, _, batch_host, disp_host, _, mpi_host, _ = _stage_fixture()

    def args_fn():
        return (_device_tree(mpi_host), jnp.asarray(disp_host),
                _device_tree(batch_host))

    return Program(name="pipe_render", jit_fn=jax.jit(trainer.stage_render),
                   args_fn=args_fn, tags=("train", "pipeline"))


def _build_pipe_loss() -> Program:
    trainer, _, batch_host, _, _, _, rendered_host = _stage_fixture()

    def args_fn():
        return (_device_tree(rendered_host), _device_tree(batch_host))

    return Program(name="pipe_loss", jit_fn=jax.jit(trainer.stage_loss),
                   args_fn=args_fn, tags=("train", "pipeline"))


# ------------------------------------------------------------- warp backends

def _build_warp(impl: str) -> Program:
    from mine_tpu import geometry
    from mine_tpu.ops.warp import homography_warp

    Bp, C, H, W, band = 4, 4, 32, 32, 8
    rng = np.random.RandomState(0)
    src = rng.uniform(-1, 1, (Bp, C, H, W)).astype(np.float32)
    d_src = np.linspace(1.0, 0.25, Bp).astype(np.float32)
    G = np.tile(np.eye(4, dtype=np.float32), (Bp, 1, 1))
    G[:, 0, 3] = np.linspace(0.0, 0.02, Bp)
    K = np.tile(np.asarray([[W, 0.0, W / 2], [0.0, H, H / 2],
                            [0.0, 0.0, 1.0]], np.float32), (Bp, 1, 1))
    K_inv = np.asarray(geometry.inverse_intrinsics(jnp.asarray(K)))
    grid = np.asarray(geometry.cached_pixel_grid(H, W))

    def warp(src, d_src, G, K_inv, K, grid):
        return homography_warp(src, d_src, G, K_inv, K, grid,
                               impl=impl, band=band)

    def args_fn():
        return tuple(jnp.asarray(a) for a in
                     (src, d_src, G, K_inv, K, grid))

    tags: Tuple[str, ...] = ("warp",)
    if impl.startswith("pallas"):
        tags += ("pallas",)
    return Program(name=f"warp_{impl}", jit_fn=jax.jit(warp),
                   args_fn=args_fn, tags=tags)


# ------------------------------------------------------------- serve render

def _serve_scene(quant: str):
    """Canonical cached-entry pytree for the serve render program."""
    from mine_tpu.serve.cache import quantize_planes

    s = SERVE
    rng = np.random.RandomState(7)
    planes = rng.uniform(0.0, 1.0,
                         (s["R"], s["S"], 4, s["H"], s["W"])).astype(
                             np.float32)
    q, scales = [], []
    for r in range(s["R"]):
        qr, sr = quantize_planes(planes[r], quant)
        q.append(np.asarray(qr))
        if sr is not None:
            scales.append(np.asarray(sr))
    planes_q = np.stack(q)
    scales_q = np.stack(scales) if scales else None
    disp = np.tile(np.linspace(1.0, 0.2, s["S"], dtype=np.float32)[None],
                   (s["R"], 1))
    K = np.tile(np.asarray([[s["W"], 0.0, s["W"] / 2],
                            [0.0, s["H"], s["H"] / 2],
                            [0.0, 0.0, 1.0]], np.float32),
                (s["R"], 1, 1))
    idx = np.zeros((s["P"],), np.int32)
    G = np.tile(np.eye(4, dtype=np.float32), (s["P"], 1, 1))
    G[:, 0, 3] = np.linspace(0.0, 0.01, s["P"])
    return planes_q, scales_q, disp, K, idx, G


def serve_render_program(quant: str = "bf16",
                         mesh: Optional[Tuple[int, int]] = None,
                         name: Optional[str] = None) -> Program:
    """Build the serve render Program for one cache quant mode ("float32",
    "bf16", "int8"), optionally over a (mesh_batch, mesh_model) CPU mesh.
    Exposed so tests can sweep quant modes; the registry registers the
    default-quant single-device and 2x2 mesh variants."""
    from mine_tpu import geometry
    from mine_tpu.serve.engine import RenderEngine
    from mine_tpu.serve.shardmap import MeshRenderEngine

    if mesh is None:
        engine = RenderEngine(max_bucket=SERVE["P"])
        out_shardings = None
        name = name or f"serve_render[{quant}]"
        tags: Tuple[str, ...] = ("serve",)
    else:
        engine = MeshRenderEngine(mesh_batch=mesh[0], mesh_model=mesh[1],
                                  max_bucket=SERVE["P"])
        out_shardings = engine._shardings["out"]
        name = name or f"serve_render_mesh[{quant},{mesh[0]}x{mesh[1]}]"
        tags = ("serve", "mesh")
    planes, scales, disp, K, idx, G = _serve_scene(quant)
    K_inv = np.asarray(geometry.inverse_intrinsics(jnp.asarray(K)))

    def render(planes, scales, disp, K, K_inv, idx, G):
        return engine._render_impl(planes, scales, disp, K, K_inv, idx, G,
                                   "xla")

    jit_fn = (jax.jit(render) if out_shardings is None else
              jax.jit(render, out_shardings=(out_shardings, out_shardings)))

    def args_fn():
        raw = (jnp.asarray(planes),
               None if scales is None else jnp.asarray(scales),
               jnp.asarray(disp), jnp.asarray(K), jnp.asarray(K_inv),
               jnp.asarray(idx), jnp.asarray(G))
        # the mesh engine commits operands under NamedShardings — the
        # placement is part of the audited program's canonical inputs
        return engine._place(*raw)

    def workload():
        # the host hot path, including the output readback the engine
        # declares via host_readback — what the transfer sanitizer runs
        rgb, depth = jit_fn(*args_fn())
        from mine_tpu.telemetry.hostsync import host_readback
        with host_readback("analysis.serve_render"):
            np.asarray(rgb), np.asarray(depth)

    return Program(name=name, jit_fn=jit_fn, args_fn=args_fn, tags=tags,
                   workload=workload)


# --------------------------------------------------------------- registry

_BUILDERS: Dict[str, Callable[[], Program]] = {}
_CACHE: Dict[str, Program] = {}


def _register(name: str, builder: Callable[[], Program]) -> None:
    _BUILDERS[name] = builder


_register("train_step", _build_train_step)
_register("fused_loss_fwd", _build_fused_loss_fwd)
_register("fused_loss_bwd", _build_fused_loss_bwd)
for _impl in WARP_IMPLS:
    _register(f"warp_{_impl}", functools.partial(_build_warp, _impl))
_register("serve_render",
          functools.partial(serve_render_program, "bf16", None,
                            "serve_render"))
_register("serve_render_mesh",
          functools.partial(serve_render_program, "bf16", (2, 2),
                            "serve_render_mesh"))
_register("eval_encode", _build_eval_encode)
# the staged train step's four sub-programs (parallel/pipeline.py): their
# cost rows are the planner's input (tools/pipeline_plan.py) and their dot
# budgets pin each stage's trace independently of the fused step's
_register("pipe_encode", _build_pipe_encode)
_register("pipe_decode", _build_pipe_decode)
_register("pipe_render", _build_pipe_render)
_register("pipe_loss", _build_pipe_loss)


def program_names() -> List[str]:
    return list(_BUILDERS)


def get_program(name: str) -> Program:
    if name not in _BUILDERS:
        raise KeyError(f"unknown program {name!r}; "
                       f"known: {', '.join(_BUILDERS)}")
    if name not in _CACHE:
        _CACHE[name] = _BUILDERS[name]()
    return _CACHE[name]


def get_programs(names=None) -> List[Program]:
    return [get_program(n) for n in (names or program_names())]
