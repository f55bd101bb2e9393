"""repro.net — dynamic wireless network simulation for DWFL.

Turns the paper's one-shot, time-invariant channel (core.channel) into a
jit-traced, per-round process: block fading with temporal correlation
(net.fading), device geometry / path loss / mobility (net.geometry), worker
churn and stragglers (net.churn), named scenario presets (net.scenarios),
and the orchestrating NetworkSimulator (net.simulator). The per-round
channel is a TracedChannelState pytree (net.state) consumed by the train
step as an argument — one compiled step, any realization, zero retraces.

Entry points: ``ProtocolConfig(channel_model="dynamic", scenario=...)`` +
``protocol.make_dynamic_flat_train_step``; see examples/dynamic_quickstart.py.
"""
from repro.net.churn import ChurnConfig, ChurnState
from repro.net.fading import FadingConfig, FadingState, rho_from_doppler
from repro.net.geometry import (GeometryConfig, GeometryState,
                                sparse_metropolis)
from repro.net.scenarios import SCENARIOS, Scenario, get_scenario
from repro.net.simulator import NetState, NetworkSimulator, complete_mixing
from repro.net.sparse import SparseW, isolated_count, sparsify_dense
from repro.net.state import TracedChannelState, stack_states

__all__ = [
    "ChurnConfig", "ChurnState", "FadingConfig", "FadingState",
    "GeometryConfig", "GeometryState", "NetState", "NetworkSimulator",
    "SCENARIOS", "Scenario", "SparseW", "TracedChannelState",
    "complete_mixing", "get_scenario", "isolated_count", "rho_from_doppler",
    "sparse_metropolis", "sparsify_dense", "stack_states",
]
