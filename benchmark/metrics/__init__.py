"""One reader per metric, found by the metric's name: `read(reading)`
returns the number, or None where there is nothing to read; LAYER and
MOVES repeat what BENCHMARK.json says of it."""
