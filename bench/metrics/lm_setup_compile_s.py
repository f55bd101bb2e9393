"""Seconds of the LM cell's set-up spent on the chunk program's first call:
``setup_compile_s``'s reading of the ``dwfl.chunk.compile`` span (trace,
compile or persistent-cache load, and enqueue)."""
from bench.metrics.setup_compile_s import read  # noqa: F401
