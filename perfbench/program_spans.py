"""Readings of the program's own spans (``repro.telemetry.span``).

A span records its wall seconds into the histogram ``<span>_s`` of
``repro.telemetry``'s registry while telemetry is on.  The open-loop kind
resets the registry before the window and turns telemetry on only in a
traced run, and off after the window, so the histograms hold exactly the
window's spans.  A program that records no such span leaves no histogram,
and its reading is None.
"""
from __future__ import annotations

from typing import Optional


def p50_ms(histogram: str) -> Optional[float]:
    """The median of the program's histogram ``histogram`` in ms, or None
    when it holds no samples."""
    from repro import telemetry
    h = telemetry.REGISTRY.get(histogram)
    if h is None or not h.count:
        return None
    return 1e3 * h.p50
