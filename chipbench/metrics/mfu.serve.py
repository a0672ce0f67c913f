"""Model FLOPs of the window's prompt and output tokens (2 N per token
through the layers, the head for each decoded token and each prompt's
last, attention at each token's context) over the window and the chip's
peak."""
from chipbench import flops


def read(run):
    w = run.window
    f = sum(flops.serve_step_flops(run.spec, s.decode_ctx, s.chunks)
            for s in w.steps)
    return 100.0 * f / w.seconds / run.peak.flops if f else None
