"""Shared machinery of the on-chip benchmark: the harness, the traffic
generators, the trace reduction, the plain reference engine and the
comparison that decides ``correct``."""
