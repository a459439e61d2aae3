"""chipbench: the chip benchmark (BENCHMARK.json names its cells).

``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on a TPU and prints one JSON line;
``python3 -m chipbench.selftest`` checks the harness on the CPU.
See chipbench/README.md.
"""
