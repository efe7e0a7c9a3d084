"""On-chip benchmark of the served path (``LiveEngine.submit`` -> drain).

Entry point: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``. Everything a cell needs is found by name
from ``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<mix>.json``,
``arrivals/<process>.py``, ``metrics/<metric>.py``, ``kernels/<kernel>.py``
and ``families/<family>.py`` (weights from the seed, the plain float32
reference and the lower-precision control).
"""
