"""The chip benchmark of the rollout path: harness, yardstick and cells (see run.py)."""
