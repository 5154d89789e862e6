"""Share of the traced span in which no operation ran on the device."""
from harness import trace


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(run.trace) / run.trace.window_s)
