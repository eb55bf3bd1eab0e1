"""Scripts that support the benchmark: compiles for a described chip, controls."""
