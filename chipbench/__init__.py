"""The chip benchmark: cells named in BENCHMARK.json, run one at a time."""
