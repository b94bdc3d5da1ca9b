"""Median host-clock time the server waits for one batch's logits in the
serving window, in ms: the program's span ``serving.cnn.fetch`` (the wait
for the forward and the copy of its logits to the host), read from its
histogram ``serving.cnn.fetch_s``."""
from perfbench import program_spans


def read(run):
    return program_spans.p50_ms("serving.cnn.fetch_s")
