"""The benchmark: cells, traffic, reference and trace reduction (see run.py)."""
