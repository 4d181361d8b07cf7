"""Output tokens emitted in the window over the window's seconds."""


def read(run):
    w0, w1 = run.served.window
    n = sum(1 for log in run.served.logs.values() for t in log.times
            if w0 < t <= w1)
    return n / (w1 - w0)
