"""Share of the window the host spent making batches and placing them on the
devices (the harness's ``batch`` and ``device_put`` spans)."""


def read(run):
    t0, t1 = run.window
    host = sum(sum(run.spans.durations(n, t0, t1))
               for n in ("batch", "device_put"))
    return 100.0 * host / (t1 - t0)
