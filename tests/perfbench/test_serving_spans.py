"""CPU tests of the readers of the program's serving spans: each gives the
median of its histogram in ms, and None when the histogram is empty or was
never recorded (a program without the span)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from perfbench import harness, system  # noqa: E402

system.import_program()
from repro import telemetry  # noqa: E402

READERS = [("batch_input_ms.serve", "serving.cnn.batch_input_s"),
           ("dispatch_ms.serve", "engine.dispatch_s"),
           ("fetch_ms.serve", "serving.cnn.fetch_s")]


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.mark.parametrize("metric, histogram", READERS)
def test_reader_gives_the_median_in_ms(metric, histogram):
    for s in (0.004, 0.001, 0.250, 0.002, 0.003):
        telemetry.histogram(histogram).observe(s)
    telemetry.histogram("serving.cnn.tick_latency_s").observe(9.0)
    assert harness.load_reader(metric)(None) == pytest.approx(3.0)


@pytest.mark.parametrize("metric, histogram", READERS)
def test_reader_gives_none_without_samples(metric, histogram):
    read = harness.load_reader(metric)
    assert read(None) is None
    assert histogram not in telemetry.REGISTRY  # reading creates nothing
    telemetry.histogram(histogram)
    assert read(None) is None


def test_readers_are_the_serving_cells_metrics():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, per = harness.cell_metrics("resnet50-server-poisson", bench)
    assert {m for m, _ in READERS} <= {m["name"] for m in per}
    for cell in ("resnet50-offline-b32", "googlenet-offline-b32"):
        _, per = harness.cell_metrics(cell, bench)
        assert not {m for m, _ in READERS} & {m["name"] for m in per}
