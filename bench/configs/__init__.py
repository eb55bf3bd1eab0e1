"""Model configurations of the benchmark, one file each, with their plain references."""
