"""Registry of the REAL driver programs repro.analysis checks.

Each entry builds the same chunk program ``launch/train.py`` ships —
``make_round_body`` under ``ChunkRunner.program(k)`` (the un-jitted scan
the driver jits with a donated carry) — at smoke scale (W=6 workers,
batch 4, K=3 rounds/chunk, R=2 fleet replicates; dwfl-paper arch), and
produces BOTH static views the checkers need:

* ``closed_jaxpr`` — traced with TYPED PRNG keys (``jax.random.key``) so
  key lineage is first-class in the jaxpr (keys.py);
* ``hlo_text`` — the optimized HLO of the donated compile with RAW
  uint32 keys, exactly as the driver runs it (donation.py).

The catalogue covers every shipped path: static/dynamic/fleet on the
flat buffer, the orthogonal baseline's planless flat round, telemetry+ε
in-carry, the sparse neighbor-list round (dense-mixing contract), and
the model-sharded flat round twice
— S=2 LOGICAL sharding (device-count independent) and the S=2 MESH
program (shard_map + the gather-free collectives; needs >= 2 devices,
so it drops out of ``available_programs()`` on a bare 1-device runtime
and CI's lint job forces a 4-device host platform).

Programs build lazily and independently: ``build_programs(["static-flat"])``
traces/compiles one program, the CLI default builds all of them (<60 s
CPU total — acceptance bound, pinned by tests/test_analysis.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence

import jax

from repro.analysis import donation as donation_lib

N_WORKERS = 6
BATCH = 4
CHUNK = 3
REPLICATES = 2
_SEED = 0


@dataclasses.dataclass
class BuiltProgram:
    """One registry program, ready for the checkers."""
    name: str
    dynamic: bool          # declared channel model (weak-closure severity)
    n_workers: int
    closed_jaxpr: object   # typed-key trace of the shipped chunk program
    hlo_text: str          # optimized HLO of the donated raw-key compile
    donated: List          # [(carry leaf path, HLO signature)]
    sharded: bool = False  # model-sharded: gather-free contract applies
    flat_width: int = 0    # physical padded buffer width (sharded only)
    shard_width: int = 0   # per-device column count (sharded only)
    sparse: bool = False   # neighbor-list mixing: dense-mixing contract


@functools.lru_cache(maxsize=1)
def _base():
    from repro.configs.registry import get_arch
    from repro.data import (FederatedBatcher, classification_dataset,
                            dirichlet_partition, store_from_batcher)
    cfg = get_arch("dwfl-paper")
    x, y = classification_dataset(512, seed=_SEED)
    parts = dirichlet_partition(y, N_WORKERS, alpha=0.5, seed=_SEED)
    batcher = FederatedBatcher(x, y, parts, BATCH, seed=_SEED)
    return cfg, store_from_batcher(batcher)


def _proto(**kw):
    from repro.core import protocol as P
    base = dict(scheme="dwfl", n_workers=N_WORKERS, seed=_SEED)
    base.update(kw)
    return P.ProtocolConfig(**base)


def _finish(name: str, body: Callable, wp, net=None, eps=None,
            dynamic: bool = False, spec=None,
            sparse: bool = False) -> BuiltProgram:
    from repro.core import trajectory as TJ
    program = TJ.ChunkRunner(body).program(CHUNK)
    typed = TJ.TrajCarry(jax.random.key(_SEED), wp, net, eps)
    closed = jax.make_jaxpr(program)(typed)
    raw = TJ.TrajCarry(jax.random.PRNGKey(_SEED), wp, net, eps)
    hlo = (jax.jit(program, donate_argnums=(0,))
           .lower(raw).compile().as_text())
    def _sig(leaf):
        # SPMD-compiled entry layouts carry PER-DEVICE shapes: a leaf
        # committed to a mesh must be matched by its shard shape, not the
        # global one (single-device shardings return the shape unchanged)
        shape = leaf.shape
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            shape = sharding.shard_shape(leaf.shape)
        return donation_lib.aval_signature(leaf.dtype, shape)

    leaves = jax.tree_util.tree_flatten_with_path(raw)[0]
    donated = [(f"carry{jax.tree_util.keystr(path)}", _sig(leaf))
               for path, leaf in leaves]
    sharded = spec is not None and getattr(spec, "layout", None) is not None
    return BuiltProgram(
        name, dynamic, N_WORKERS, closed, hlo, donated,
        sharded=sharded,
        flat_width=spec.layout.padded_width if sharded else 0,
        shard_width=spec.layout.shard_width if sharded else 0,
        sparse=sparse)


def _static(name: str, n_shards: int = 1, mesh: bool = False,
            scheme: str = "dwfl") -> BuiltProgram:
    from repro.core import exchange as X
    from repro.core import protocol as P
    from repro.core import trajectory as TJ
    cfg, store = _base()
    proto = _proto(scheme=scheme)
    wp = P.init_worker_params(jax.random.PRNGKey(_SEED), cfg, N_WORKERS)
    spec = X.make_flat_spec(wp, n_shards=n_shards)
    wp = spec.flatten(wp)
    shard_mesh = None
    if mesh:
        from repro.launch import mesh as mesh_lib
        from repro.launch import shardings as shardings_lib
        shard_mesh = mesh_lib.make_shard_mesh(n_shards)
        # place the buffer exactly as the driver does — donation aliasing
        # only holds when the compiled input sharding matches the output's
        wp = jax.device_put(
            wp, shardings_lib.flat_buffer_sharding(spec, shard_mesh))
    body = TJ.make_round_body(cfg, proto, store, spec=spec,
                              shard_mesh=shard_mesh)
    return _finish(name, body, wp, spec=spec if mesh else None)


def _dynamic(name: str, telemetry: bool = False,
             sparse_k: int = 0) -> BuiltProgram:
    from repro.core import exchange as X
    from repro.core import protocol as P
    from repro.core import trajectory as TJ
    cfg, store = _base()
    proto = _proto(channel_model="dynamic", scenario="iot_dense",
                   coherence_rounds=4, sparse_neighbors=sparse_k)
    sim = proto.simulator()
    net = sim.init(jax.random.PRNGKey(1))
    wp = P.init_worker_params(jax.random.PRNGKey(_SEED), cfg, N_WORKERS)
    spec = X.make_flat_spec(wp)
    wp = spec.flatten(wp)
    tele = eps0 = None
    if telemetry:
        from repro import obs
        tele = obs.TelemetrySpec()
        if getattr(tele, "epsilon", False):
            # widened [4+A] accountant carry (advanced-composition moments
            # + the per-order RDP ledger, core.accounting.ORDER_GRID) —
            # the lint programs exercise the fused-accountant epilogue
            eps0 = obs.init_eps_moments(None)
    body = TJ.make_round_body(cfg, proto, store, sim=sim, spec=spec,
                              telemetry=tele)
    return _finish(name, body, wp, net=net, eps=eps0, dynamic=True,
                   sparse=sparse_k > 0)


def _fleet(name: str) -> BuiltProgram:
    from repro.core import trajectory as TJ
    from repro.fleet import FleetEngine
    cfg, store = _base()
    proto = _proto(channel_model="dynamic", scenario="iot_dense",
                   coherence_rounds=4, replicates=REPLICATES)
    fleet = FleetEngine(proto)
    wp, spec = fleet.init_flat_spec(jax.random.PRNGKey(_SEED), cfg)
    net = fleet.init(jax.random.PRNGKey(1))
    body = TJ.make_round_body(cfg, proto, store, fleet=fleet, spec=spec)
    return _finish(name, body, wp, net=net, dynamic=True)


# name -> zero-arg builder; ORDER is the CLI report order
PROGRAMS: Dict[str, Callable[[], BuiltProgram]] = {
    "static-flat": lambda: _static("static-flat"),
    # the baselines' planless round: the local step and the orthogonal
    # exchange on the [W, d] buffer as one leaf (no dp_mix kernel)
    "static-flat-orthogonal": lambda: _static("static-flat-orthogonal",
                                              scheme="orthogonal"),
    "dynamic-flat-tele": lambda: _dynamic("dynamic-flat-tele",
                                          telemetry=True),
    # the sparse neighbor-list round (padded [N, k] W, O(N·k·d) mixing):
    # the program the dense-mixing checker enforces the no-[N,N]-
    # contraction contract on — telemetry+ε in-carry so the graph-aware
    # accountant's sparse branch is inside the checked jaxpr too.
    "dynamic-sparse-flat": lambda: _dynamic("dynamic-sparse-flat",
                                            telemetry=True, sparse_k=3),
    "fleet-flat": lambda: _fleet("fleet-flat"),
    "shard-flat-s2": lambda: _static("shard-flat-s2", n_shards=2),
    # the REAL mesh program (shard_map + collectives): the one the
    # gather-free checker enforces the memory contract on. Needs >= 2
    # devices (CI lint exports XLA_FLAGS=--xla_force_host_platform_
    # device_count=4; see available_programs).
    "shard-flat-s2-mesh": lambda: _static("shard-flat-s2-mesh", n_shards=2,
                                          mesh=True),
}

# programs with an environment precondition: name -> () -> bool
_REQUIRES: Dict[str, Callable[[], bool]] = {
    "shard-flat-s2-mesh": lambda: jax.device_count() >= 2,
}


def available_programs() -> List[str]:
    """Registry names buildable in THIS environment (the CLI default):
    mesh programs drop out when the runtime has too few devices rather
    than failing the whole lint."""
    return [n for n in PROGRAMS if _REQUIRES.get(n, lambda: True)()]


def build_programs(names: Optional[Sequence[str]] = None
                   ) -> List[BuiltProgram]:
    if names is None:
        names = available_programs()
    unknown = [n for n in names if n not in PROGRAMS]
    if unknown:
        raise KeyError(f"unknown program(s) {unknown}; "
                       f"registry: {list(PROGRAMS)}")
    return [PROGRAMS[n]() for n in names]
