"""Device time per round of the LM's gradient pass: ``grad_pass_ms``'s
arithmetic, the leaf ops under the program's ``dwfl.grad`` scope (latent
attention, the experts, the dense layer, the head, their backward and
remat's second forward, and the clip norm), averaged over the chips."""
from bench.metrics.grad_pass_ms import read  # noqa: F401
