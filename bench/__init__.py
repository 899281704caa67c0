"""The on-chip benchmark: one command, cells named in ``BENCHMARK.json``.

Run a cell from the root of a checkout::

    python3 bench/run.py --workload rnn124m-longgen --seed 7 --seconds 51 --trace 0

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, found by the name that ``BENCHMARK.json`` gives it.
"""
