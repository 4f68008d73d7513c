"""The benchmark of record (BENCHMARK.json, PERF.md).

One command runs one cell once in a new process on the chip:

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one
driver or one per-layer metric is a file of its own, found by the name
BENCHMARK.json gives it: configs/<config>.json,
traffic/<config>.<traffic>.json, drivers/<driver>.py,
metrics/<metric>.py. A later PR adds a cell or a metric by adding such
files and one manifest entry; it edits nothing that is here.

The yardstick lives here and not in the program: traffic generation
(chain.py, loadgen.py), the plain references (kvref.py, OpenSSL through
`cryptography`), whole-pass accounting (passes.py), percentile
arithmetic (stats.py), the reduction from a profiler trace to busy,
idle and per-kernel seconds (trace_reduce.py) and the table of peaks
(peaks.json). From the program it takes the system under test, its
counters and its kernel names.
"""
