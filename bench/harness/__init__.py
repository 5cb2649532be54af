"""Shared pieces of the on-chip benchmark: spec loading, device checks, data,
traffic generation, references, trace reduction, peaks and work counts."""
