"""Paged attention (decode launches and prefill chunks) against its
roofline, over the traced steps."""
from chipbench.readers import paged_attention_roofline


def read(run):
    return paged_attention_roofline(run)
