"""Model FLOP utilisation of the LM cell's round: ``step_mfu_pct``'s
arithmetic (the configuration's ``round_flops`` times the rounds in the
traced window, over the window times the chips times their bf16 peak),
with the LM's work counts (``bench/flops/deepseek_v2.py``: causal attention
at S(S+1)/2 pairs, the held experts on their routed tokens)."""
from bench.metrics.step_mfu_pct import read  # noqa: F401
