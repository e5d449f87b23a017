"""Chip benchmark of the ThundeRiNG generator: harness, references and readers.

Run a cell with ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the checkout root.  ``BENCHMARK.json``
names the cells; each cell's configuration, traffic mix, driver and
per-layer readers are files of their own under this directory.
"""
