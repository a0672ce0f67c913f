"""p90, over the requests due in the serving window, of the engine's own
queue phase (``engine.request.queue``: submit to first admission), cut at
the close."""
from chipbench.spans import engine_queue_p90_ms


def read(run):
    return engine_queue_p90_ms(run.window, run.rec) if run.rec else None
