"""GEEK's chip benchmark: one cell of ``BENCHMARK.json`` per run.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on the chip and prints one JSON
result line. Everything a cell needs is found by name: the
configuration in ``configs/<config>.json``, the traffic mix in
``traffic/<traffic>.json`` and its driver in ``drivers/<driver>.py``,
each per-layer metric's reader in ``metrics/<metric>.py`` and the
device peaks in ``peaks.json``. See ``README.md``.
"""
