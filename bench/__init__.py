"""On-chip benchmark of the AdaLomo training step and paged serving.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on.  Every
cell, configuration, traffic mix and per-layer metric is found by its name:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py``.
"""
