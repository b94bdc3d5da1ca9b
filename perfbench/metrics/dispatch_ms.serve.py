"""Median host-clock time of one engine dispatch in the serving window, in
ms: the program's span ``engine.dispatch`` (the compiled-forward lookup,
the execution report when telemetry is on, and the enqueue of the
forward), read from its histogram ``engine.dispatch_s``."""
from perfbench import program_spans


def read(run):
    return program_spans.p50_ms("engine.dispatch_s")
