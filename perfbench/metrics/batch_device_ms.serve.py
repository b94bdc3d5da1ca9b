"""Device time per dispatched batch in the serving window, in ms: the
union of the device's operation intervals in the window, from the profiler
trace, over the batches the server dispatched in it.  Its gap to
``tick_ms.serve`` is the host's share of a batch."""
from perfbench import trace


def read(run):
    if run.trace is None or not run.data.get("ticks"):
        return None
    busy = trace.busy_s(run.trace)
    return None if busy is None else 1e3 * busy / run.data["ticks"]
