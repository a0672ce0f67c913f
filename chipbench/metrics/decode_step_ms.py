"""Mean host time of the window's step() calls that decoded and ran no
prefill chunk."""


def read(run):
    d = [s.t1 - s.t0 for s in run.window.steps
         if s.decode_ctx and not s.chunks]
    return 1e3 * sum(d) / len(d) if d else None
