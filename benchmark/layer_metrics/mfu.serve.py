"""Token server: model FLOP/s utilization of the whole serve path: the model
FLOPs of the traced steps (benchmark/roofline_moe_mla.py: real tokens only,
W_kvb once a token, attention at the up-projected form's cost; padding and
recomputation do not count) over the traced window times the chip's
bfloat16 peak. The share of the whole step that a later claim in this cell
is bounded by; the host's gaps count against it."""
from benchmark import lm_serve_spans, roofline_moe_mla

LAYER = "lm step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    steps = lm_serve_spans.traced_steps(obs)
    trace = obs["trace"]
    if not steps or trace is None or not trace["window_s"]:
        return None
    flops = sum(roofline_moe_mla.step_model_flops(obs["shapes"], s)
                for s in steps)
    return 100.0 * flops / (trace["window_s"]
                            * obs["peaks"]["peak_tflops_bf16"] * 1e12)
