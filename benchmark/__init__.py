"""The benchmark: the yardstick later PRs are measured with.

Everything under this directory belongs to the benchmark and to nothing
else. From the program (`paddle_tpu`) it takes only the system under test
and its spans, counters and kernel names; traffic generation, the
reduction from traces and spans to metrics, the table of peaks, the
closed-form operation counts, the plain references and the comparison
that decides `correct` live here. `BENCHMARK.json` at the root of the repo
names every cell, configuration and metric; `run.py` finds their files by
those names.
"""
