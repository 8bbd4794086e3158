"""Chip benchmark of the GNN embedding-serving path (see ``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell once on the TPU it is started on and prints one JSON line.
Configurations, traffic mixes and metric readers are files of their own,
found by the names ``BENCHMARK.json`` gives them.
"""
