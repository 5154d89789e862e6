"""90th percentile of the time to first token of the online requests due
in the window, from the time each was due (host clock, client side)."""
from harness import measures


def read(run):
    return measures.percentile(measures.ttfts(run, "online"), 90)
