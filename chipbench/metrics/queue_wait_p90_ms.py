"""90th percentile of due -> slot assigned (or the window's close), over
the requests due in the window, seen from the harness after each step."""
from chipbench.readers import percentile_ms


def read(run):
    w = run.window
    waits = [(r.slotted if r.slotted >= 0 else w.t1) - r.due
             for r in w.requests.values()]
    return percentile_ms(waits, 90)
