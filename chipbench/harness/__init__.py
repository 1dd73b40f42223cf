"""What the benchmark owns: peaks, FLOP and byte counts, traffic, the
trace reduction, the comparison that decides ``correct``."""
