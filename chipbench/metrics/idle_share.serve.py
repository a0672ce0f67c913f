"""1 - device busy time / traced window, serving."""
from chipbench.readers import idle_share


def read(run):
    return idle_share(run)
