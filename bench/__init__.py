"""The chip benchmark: one command, cells described by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json``.  A cell names a configuration
(``configs/<name>.json``), a traffic mix (``traffic/<name>.json``, whose
``driver`` key picks the general driver module that reads it) and, through
``BENCHMARK.json``'s ``per_layer`` list, the metric readers
(``metrics/<name>.py``).  Adding a cell adds files and entries; no file
here needs an edit.
"""
