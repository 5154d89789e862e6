"""Share of the decode rows the engine dispatched that were real rows, not
padding to the decode bucket, per decode iteration, over the traced span's
``ooco.engine.launch`` spans that carry decode rows."""
from harness import spans


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    return spans.decode_row_occupancy(run.trace)
