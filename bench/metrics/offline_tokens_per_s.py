"""Offline tokens received by the clients inside the window, over the
window's seconds."""
from harness import measures


def read(run):
    return measures.tokens_in_window(run, "offline") / run.seconds
