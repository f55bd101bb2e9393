"""Device time per round of the LM round's mix around its kernel:
``mix_wrap_ms``'s arithmetic, the leaf ops under ``dwfl.mix`` (the plan
and what the wrapper does to each chip's [4, d/4] slab) less the
``dp_mix_round`` kernel, averaged over the chips."""
from bench.metrics.mix_wrap_ms import read  # noqa: F401
