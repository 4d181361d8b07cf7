"""Model FLOPs of the tokens the traced steps processed (prompt and
output; projections at the configuration's stated weights, attention
over the valid context, logits of the sampled positions) over the traced
window times the chip's bf16 peak."""


def read(run):
    steps = run.traced_steps()
    if not steps or run.trace is None or run.peaks is None:
        return None
    flops = sum(s.flops for s in steps)
    return 100.0 * flops / (run.trace.window_s
                            * run.peaks["bf16_flops_per_s"])
