"""Mean ``trainer.data`` span (the next batch and its bucket pin) over the
window's steps."""
from chipbench.spans import data_wait_ms


def read(run):
    return data_wait_ms(run.rec, run.t_open, run.t_close) if run.rec \
        else None
