"""On-chip benchmark of the repository's sparse CNN inference path.

Run one cell of ``BENCHMARK.json`` from the root of a checkout::

    python3 perfbench/run.py --workload resnet50-offline-b32 --seed 7 \
        --seconds 30 --trace 0
"""
