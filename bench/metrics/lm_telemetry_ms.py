"""Device time per round of the LM round's telemetry: ``telemetry_ms``'s
arithmetic, the leaf ops under ``dwfl.telemetry`` (on the model-sharded
round: the consensus and norm reductions over each chip's column slab and
their [W] gathers), averaged over the chips."""
from bench.metrics.telemetry_ms import read  # noqa: F401
