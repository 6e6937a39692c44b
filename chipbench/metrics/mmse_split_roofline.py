"""Roofline share of the split re/im MMSE kernel (see ``roofline.py``)."""
from chipbench.metrics import roofline


def read(record, trace):
    return roofline.share("mmse_split", record, trace)
