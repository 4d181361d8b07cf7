"""The least time of the expert layer's grouped matmuls in the traced
steps as a share of their device time (the trace's ragged-dot ops).
Per traced step the least time is the larger of its FLOPs (valid tokens
x layers x pairs per token and layer x 6 x hidden x expert width) over
the bf16 peak and its bytes (every held expert's weights once per layer
at ``dense_bits``, and each pair's row in and out in bf16) over HBM
bandwidth.  Pairs per token and layer are the run's own counters,
``moe_pairs_here / moe_tokens``.  Nothing where the program keeps no
such counters or the trace holds no such op."""

#: the grouped-matmul ops of a v5e trace (``jax.lax.ragged_dot``)
OPS = r"^ragged-dot"


def read(run):
    stats, dims, pk = run.served.stats, run.dims, run.peaks
    steps = run.traced_steps()
    if not steps or run.trace is None or pk is None \
            or not stats.get("moe_tokens") or not hasattr(dims, "held"):
        return None
    device_s = run.trace.ops_matching(OPS)
    if device_s <= 0:
        return None
    per_token = stats["moe_pairs_here"] / stats["moe_tokens"]
    weights = dims.layers * len(dims.held) * 3 * dims.d * dims.d_expert \
        * dims.weight_bits / 8
    least = 0.0
    for s in steps:
        pairs = s.tokens * dims.layers * per_token
        flops = pairs * 6 * dims.d * dims.d_expert
        moved = weights + pairs * 2 * dims.d * 2
        least += max(flops / pk["bf16_flops_per_s"],
                     moved / pk["hbm_bytes_per_s"])
    return 100.0 * least / device_s
