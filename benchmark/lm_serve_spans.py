"""What the readers of the token server's per-layer metrics share (PR 35).

The server (`mine_tpu/serve/lm_scheduler.py`) records one `serve.lm.step`
span a step, whose fields say what the step held (tokens, decode, prefill,
prefill_start, decode_context, expert_pairs, experts_touched, sampled_rows,
bucket, pages); the driver hands over those of the window
(`counters["window_steps"]`) and of the profiler's window
(`counters["traced_steps"]`), and two snapshots of every `serve.lm.*`
registry name. A step's device operations are named by layer through
`mine_tpu.telemetry.programs`: every bucket is a program of its own
(`_lm_serve_step_impl_c<rows>_p<pages>`), so an operation is classified by
the map of the program whose execution encloses it.

Every function returns None, and never raises, where the program has no such
span, counter or map: a program from before PR 35 reports none of these.
"""

from __future__ import annotations

import bisect
import re

from benchmark import harness, program_spans

PROGRAM = re.compile(r"_lm_serve_step_impl_c\d+_p\d+")
MLA = ("mla_proj", "mla_prefill", "mla_decode")
MOE = ("moe_router", "moe_experts", "moe_shared")


def is_cell(obs) -> bool:
    return obs["shapes"].get("kind") == "lm_serve"


def window_steps(obs):
    steps = obs["counters"].get("window_steps") if is_cell(obs) else None
    return steps or None


def traced_steps(obs):
    steps = obs["counters"].get("traced_steps") if is_cell(obs) else None
    return steps or None


def counter_delta(obs, name: str):
    a = obs["registry"].get("start", {}).get(name)
    b = obs["registry"].get("end", {}).get(name)
    if not isinstance(b, (int, float)):
        return None
    return b - (a if isinstance(a, (int, float)) else 0)


def device_by_layer(obs):
    """{"executions": n, "busy_s": seconds in which an operation ran inside
    a step program's executions, "layers": {layer or None: self seconds}}
    over the step programs' executions that lie whole inside the traced
    window, on the first device; cached in `obs`."""
    if "_lm_serve_device" in obs:
        return obs["_lm_serve_device"]
    out = None
    try:
        out = _device_by_layer(obs)
    except Exception as e:  # noqa: BLE001 - a reader never fails the run
        harness.say("no per-layer split of the serve step: %r" % (e,))
    obs["_lm_serve_device"] = out
    return out


def _device_by_layer(obs):
    trace = obs["trace"]
    if trace is None or not is_cell(obs):
        return None
    dev = trace["devices"][0]
    runs = sorted((s, e, PROGRAM.search(name).group(0))
                  for s, e, name in dev["modules"] if PROGRAM.search(name))
    if not runs:
        return None
    classify = {}
    for program in {r[2] for r in runs}:
        classify[program] = program_spans._classifier(program)
        if classify[program] is None:
            return None
    starts = [r[0] for r in runs]
    layers, busy = {}, 0.0
    for op in dev["ops"]:
        i = bisect.bisect_right(starts, op["start_ns"]) - 1
        if i < 0 or op["end_ns"] > runs[i][1]:
            continue
        layer = classify[runs[i][2]](op)
        layers[layer] = layers.get(layer, 0.0) + op["self_s"]
        busy += op["self_s"]
    found = {"executions": len(runs), "busy_s": busy, "layers": layers}
    rest = layers.get(None, 0.0)
    harness.say("serve step on the device: %d executions in the traced "
                "window, %.3f s busy, by layer %s; under no lm_* scope "
                "%.2f%% of it" % (
                    len(runs), busy,
                    {str(k): round(v, 4) for k, v in sorted(
                        layers.items(), key=lambda kv: -kv[1])},
                    100.0 * rest / busy if busy else 0.0))
    return found


def layer_ms_per_step(obs, names):
    """Self time of the operations of the layers `names`, ms a step."""
    found = device_by_layer(obs)
    if not found:
        return None
    return 1e3 * sum(found["layers"].get(n, 0.0) for n in names) / found[
        "executions"]


def roofline_share(obs, layer: str, floor_fn):
    """Percent: the floors of the traced steps (`floor_fn(shapes, step,
    peaks)`, summed, scaled to the executions the trace holds whole) over
    the layer's device time."""
    found, steps = device_by_layer(obs), traced_steps(obs)
    if not found or not steps or not found["layers"].get(layer):
        return None
    floor = sum(floor_fn(obs["shapes"], s, obs["peaks"]) for s in steps)
    floor *= found["executions"] / len(steps)
    return 100.0 * floor / found["layers"][layer]
