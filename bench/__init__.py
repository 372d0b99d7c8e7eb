"""The benchmark of VSS on the chip; see bench/run.py."""
