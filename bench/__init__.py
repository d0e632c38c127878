"""The chip benchmark: cells of ``BENCHMARK.json`` driven through
``CFPQServer`` (run one with ``python bench/run.py --workload <cell>``)."""
