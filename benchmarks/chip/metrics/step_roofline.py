"""The least time the traced steps need, each the larger of its FLOPs
over the bf16 peak and its bytes (stated weights once, valid KV, the
unembedding when it samples) over HBM bandwidth, as a share of the time
the device was busy in the traced window."""


def read(run):
    steps = run.traced_steps()
    if not steps or run.trace is None or run.peaks is None \
            or run.trace.busy_s <= 0:
        return None
    pk = run.peaks
    least = sum(max(s.flops / pk["bf16_flops_per_s"],
                    s.bytes / pk["hbm_bytes_per_s"]) for s in steps)
    return 100.0 * least / run.trace.busy_s
