"""Roofline share of the tiled Cholesky solve (see ``roofline.py``)."""
from chipbench.metrics import roofline


def read(record, trace):
    return roofline.share("chol_tiled", record, trace)
