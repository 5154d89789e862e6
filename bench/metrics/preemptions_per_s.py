"""Runtime counter ``preemptions`` (layer-level interruptions plus
chunk-boundary pauses of offline prefills) over the window."""


def read(run):
    d = run.counters1["preemptions"] - run.counters0["preemptions"]
    return d / run.seconds
