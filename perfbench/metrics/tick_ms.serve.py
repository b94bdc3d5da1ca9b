"""Median host-clock time of one dispatched batch in the serving window,
in ms, as the server itself records it (``serving.cnn.tick_latency_s``:
from the tick's start to the batch's logits on the host)."""


def read(run):
    p50 = run.data.get("tick_p50_s")
    return None if p50 is None else 1e3 * p50
