"""Expert layers: the busiest held expert's rows over the mean of the held
experts', over the window (gauges `serve.lm.expert_tokens.<e>`, summed over
the expert layers). 1 is an even load; ids are Zipf, so it is not."""
LAYER = "lm step"
UNIT = "x"
SOURCE = "program_counter"
MOVES = "serve_views_per_s"
PREFIX = "serve.lm.expert_tokens."


def read(obs):
    a, b = obs["registry"].get("start", {}), obs["registry"].get("end", {})
    rows = [b[k] - a.get(k, 0.0) for k in b if k.startswith(PREFIX)
            and isinstance(b[k], (int, float))]
    if not rows or sum(rows) <= 0:
        return None
    return max(rows) / (sum(rows) / len(rows))
