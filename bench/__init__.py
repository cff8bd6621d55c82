"""The chip benchmark of the FrogWild serving path and batch estimate.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on. PERF.md
says what each cell and metric is for.
"""
