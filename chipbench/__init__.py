"""On-chip benchmark of the consensus solver: one command, data-driven cells.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``. Everything that
measures (traffic, the float64 reference, work counts, peaks, the trace
reduction) lives here; from the program it takes only the solver entries
(``repro.core.prepare``, ``PreparedPool``, ``SolveServer``) and its
compile-cache helper.
"""
