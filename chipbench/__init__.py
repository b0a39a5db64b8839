"""Chip benchmark: one harness, driven by the files beside it.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chip it is
started on and prints one JSON result line.  See ``chipbench/bench.py`` for
where each piece of a cell lives.
"""
