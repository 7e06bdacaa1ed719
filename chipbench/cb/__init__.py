"""The on-chip benchmark's own code: everything that turns a cell named in
``BENCHMARK.json`` into measured numbers. Nothing here is imported by the
system under test, and nothing that decides a number (traffic, trace
reduction, work counts, peaks, the reference) lives in the program."""
