"""Roofline share of the packed gossip mix kernel."""
from metrics._roofline import share


def read(run):
    return share(run, "gossip_mix", ("gossip_mix",))
