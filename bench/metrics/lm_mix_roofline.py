"""Roofline share of the dense dp_mix kernel on the LM cell:
``dp_mix_roofline``'s arithmetic with the LM's ``dp_mix_work`` ([N, d] at
d = 535,060,992, each of the 4 chips mixing its own d/4 columns)."""
from bench.metrics.dp_mix_roofline import read  # noqa: F401
