"""Training tokens/s of the window times the model FLOPs per token (6 N
plus causal attention, recompute excluded) over the chip's peak."""
from chipbench import flops


def read(run):
    w = run.window
    if not w["steps"]:
        return None
    rate = w["tokens"] / w["seconds"]
    return 100.0 * rate * flops.train_flops_per_token(
        run.spec, w["seq_len"]) / run.peak.flops
