"""The chip benchmark of the SpMV stack: harness, traffic, yardstick.

Run a cell as ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout that holds a TPU.
Cells, configurations, traffic mixes and metrics are found by the names in
``BENCHMARK.json``: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py`` and
``bench/limits/<workload>.json``; the operator module and the traffic
runner that a configuration or a mix names live in ``bench/operators/``
and ``bench/runners/``.
"""
