"""Share of the LM cell's traced window in which no operation ran on the
device: ``device_idle_pct``'s arithmetic (one minus the union of the
device ops' intervals over the window, averaged over the four chips)."""
from bench.metrics.device_idle_pct import read  # noqa: F401
