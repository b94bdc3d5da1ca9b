"""Share of the offline window in which no operation ran on the device, in
percent: 1 - (union of the device's operation intervals in the window) /
(the window), from the profiler trace."""
from perfbench import trace


def read(run):
    if run.trace is None:
        return None
    idle = trace.idle_share(run.trace)
    return None if idle is None else 100.0 * idle
