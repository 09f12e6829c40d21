"""The benchmark of shadow-tpu: `python3 -m benchmark --workload <cell>
--seed <n> --seconds <s> --trace <0|1>` runs one cell of BENCHMARK.json
once (run.py)."""
