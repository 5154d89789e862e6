"""Median host time of a gateway round in the traced span: the
``ooco.gateway.round`` span less the time under ``ooco.engine.sync``, in ms."""
from harness import spans


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    return spans.host_round_ms(run.trace)
