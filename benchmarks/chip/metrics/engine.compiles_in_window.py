"""Backend compiles (or persistent-cache loads) inside the window: the
entries of the program's process-wide compile log
(``repro.obs.compile_events``, stamped on ``time.perf_counter`` like the
window) that fall inside ``served.window``.  Nothing where the program
keeps no such log."""


def read(run):
    try:
        from repro.obs import compile_events
    except ImportError:
        return None
    w0, w1 = run.served.window
    return float(sum(1 for t, name, _ in list(compile_events)
                     if w0 <= t <= w1 and name.startswith("compile:")))
