"""The KV page pool's high-water mark over the run (the session's
``pages_peak`` counter) as a share of the pool's usable pages."""


def read(run):
    peak = run.served.stats.get("pages_peak")
    if peak is None:
        return None
    usable = run.config["serving"]["kv_pool_pages"] - 1   # page 0: padding
    return 100.0 * peak / usable
