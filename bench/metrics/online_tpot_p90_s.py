"""90th percentile, over online requests due in the window with at least
two tokens inside it, of each request's client-seen time per output token:
(last token - first token) / (tokens - 1)."""
from harness import measures


def read(run):
    return measures.percentile(measures.tpots(run, "online"), 90)
