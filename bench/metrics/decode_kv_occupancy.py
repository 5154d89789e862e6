"""Share of the KV pages the decode steps fetched (bucket x the batch's page
count padded to a power of two) that the real rows' block tables hold, per
decode iteration, over the traced span's ``ooco.engine.launch`` spans."""
from harness import spans


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    return spans.decode_kv_occupancy(run.trace)
