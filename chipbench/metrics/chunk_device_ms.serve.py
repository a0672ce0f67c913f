"""Mean device time of one execution of the chunked-prefill program
(``prefill_paged_chunk``) in the traced window."""
from chipbench.spans import executions, mean_ms


def read(run):
    return mean_ms(executions(run.trace, "prefill_paged_chunk")) \
        if run.trace else None
