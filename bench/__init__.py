"""The on-chip benchmark of the sparse serving path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one JSON line.
Everything a cell needs is found by name: its configuration
(``configs/``), its traffic mix (``traffic/``), its generator
(``generators/``), each metric's reader (``metrics/``) and the device's
peaks (``peaks/``). The yardstick lives here too: the plain reference
(``reference.py``), the work count of a multiply (``work.py``) and the
reduction of a profiler trace (``devtrace.py``).
"""
