"""Roofline share of the fused Eq. 7-9 selection kernel."""
from metrics._roofline import share


def read(run):
    return share(run, "select_topk", ("select_topk",))
