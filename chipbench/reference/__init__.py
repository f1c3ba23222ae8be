"""Plain references the benchmark judges the system by (no program code)."""
