"""Decode-ready slots per decode launch as a share of the batch slots,
over the window's steps that decoded."""


def read(run):
    w = run.window
    n = [len(s.decode_ctx) for s in w.steps if s.decode_ctx]
    return 100.0 * sum(n) / (len(n) * w.batch_slots) if n else None
