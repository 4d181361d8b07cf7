"""Rows one held expert multiplies in one layer of one model call, in
the traced steps: their valid tokens times the pairs the program
computed here per valid token and layer (its counters over the run,
``moe_pairs_here / moe_tokens``), over the traced steps times the
experts held here.  The counters alone (``moe_pairs_here /
(moe_layer_calls x held)``) would mostly read the steady start's
prefill, which is set-up.  Nothing where the program keeps no such
counters."""


def read(run):
    stats = run.served.stats
    held = getattr(run.dims, "held", None)
    steps = run.traced_steps()
    if not steps or not stats.get("moe_tokens") or not held:
        return None
    per_token = stats["moe_pairs_here"] / stats["moe_tokens"]
    return per_token * sum(s.tokens for s in steps) \
        / (len(steps) * len(held))
