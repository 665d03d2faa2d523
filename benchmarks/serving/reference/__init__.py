"""See ``benchmarks/serving/__init__.py``."""
