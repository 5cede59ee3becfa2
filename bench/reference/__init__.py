"""Plain references the benchmark compares runs with."""
