"""95th percentile (nearest rank) of the gaps between consecutive output
tokens of a request, over every gap that ends in the window, all
requests together."""
from chipbench.stats import percentile


def read(run):
    w0, w1 = run.served.window
    gaps = [b - a for log in run.served.logs.values()
            for a, b in zip(log.times, log.times[1:]) if w0 < b <= w1]
    return 1000 * percentile(gaps, 95) if gaps else None
