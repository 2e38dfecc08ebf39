"""The benchmark: harness, generators, references, rooflines, trace
reduction and per-layer metric readers.  See README.md."""
