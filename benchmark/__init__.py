"""The benchmark: one cell, once, in a new process (see README.md)."""
