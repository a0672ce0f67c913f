"""p90, over the requests due in the serving window, of the engine's
prefill phase (``engine.request.prefill``: admission to first token), cut
at the close."""
from chipbench.spans import prefill_p90_ms


def read(run):
    return prefill_p90_ms(run.window, run.rec) if run.rec else None
