"""Median host-clock time the server takes to build one batch in the
serving window, in ms: the program's span ``serving.cnn.batch_input``
(zero-padding the requests into the bucket's batch and copying it to the
device), read from its histogram ``serving.cnn.batch_input_s``."""
from perfbench import program_spans


def read(run):
    return program_spans.p50_ms("serving.cnn.batch_input_s")
