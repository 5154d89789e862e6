"""Median error of the roofline perf model's step estimate against the
measured dispatch: |predicted_s / duration - 1| of each ``ooco.rt.dispatch``
span in the traced span, in %."""
from harness import spans


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    return spans.perf_model_error(run.trace)
