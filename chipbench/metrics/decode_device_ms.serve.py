"""Mean device time of one execution of the decode program
(``decode_step_paged``) in the traced window."""
from chipbench.spans import executions, mean_ms


def read(run):
    return mean_ms(executions(run.trace, "decode_step_paged")) \
        if run.trace else None
