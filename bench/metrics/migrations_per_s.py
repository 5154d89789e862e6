"""Runtime counter ``migrations`` (KV moved between engines) over the
window."""


def read(run):
    d = run.counters1["migrations"] - run.counters0["migrations"]
    return d / run.seconds
