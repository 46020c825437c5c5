"""Token server over selected and windowed attention: model FLOP/s
utilization of the whole serve path: the model FLOPs of the traced steps
(benchmark/roofline_dots3.py: real tokens only, each layer at its kind's
widths, the indexer's pairs, attention over the SELECTED pairs on full
layers and the pairs inside the window on sliding ones; padding,
recomputation and the selection's passes do not count) over the traced
window times the chip's bfloat16 peak. The share of the whole step that a
later claim in this cell is bounded by; the host's gaps count against it.
(`mfu.serve` prices dense attention in identical layers: not this cell's.)"""
from benchmark import lm_serve_spans, roofline_dots3

LAYER = "lm step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    steps = lm_serve_spans.traced_steps(obs)
    trace = obs["trace"]
    if (not steps or trace is None or not trace["window_s"]
            or not roofline_dots3.is_cell(obs["shapes"])
            or "selected_pairs" not in steps[0]):
        return None
    flops = sum(roofline_dots3.step_model_flops(obs["shapes"], s)
                for s in steps)
    return 100.0 * flops / (trace["window_s"]
                            * obs["peaks"]["peak_tflops_bf16"] * 1e12)
