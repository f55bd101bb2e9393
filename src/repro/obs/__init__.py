"""repro.obs — observability for the DWFL stack.

Two halves (DESIGN.md §13):

* **on-device** (``obs.telemetry``): ``TelemetrySpec`` threads through
  ``core.trajectory.make_round_body`` and selects per-round scalars —
  loss, grad-norm, consensus distance, realized SNR, deep-fade fraction,
  participation, per-round ε — computed inside the compiled scan and
  emitted as ONE stacked [K, M] array per chunk, with the ε composition
  moments accumulated in the scan carry.
* **host** (``obs.runlog`` / ``obs.report``): structured run directories
  (manifest.json + events.jsonl), ε-budget and retrace watchdogs, and the
  ``python -m repro.obs.report`` summarizer.

``obs.guard.retrace_guard`` is the reusable zero-retrace checker the
kernel benchmarks and CI smokes assert with. ``obs.spans.span`` times the
driver's host phases on the profiler's clock and counts them in memory.
"""
from repro.obs import spans
from repro.obs.spans import span
from repro.obs.guard import (RetraceError, no_implicit_transfers,
                             retrace_guard)
from repro.obs.runlog import (EpsilonBudgetWatchdog, RetraceWatchdog, RunLog,
                              config_hash, console, git_sha)
from repro.obs.telemetry import (TelemetrySpec, accumulate_eps,
                                 channel_scalars, consensus_distance,
                                 epsilon_round, init_eps_moments)

__all__ = [
    "EpsilonBudgetWatchdog", "RetraceError", "RetraceWatchdog", "RunLog",
    "TelemetrySpec", "accumulate_eps", "channel_scalars",
    "config_hash", "console", "consensus_distance", "epsilon_round",
    "git_sha", "init_eps_moments", "no_implicit_transfers",
    "retrace_guard", "span", "spans",
]
