"""Shared harness for the paper-figure benchmarks.

The paper trains a small CNN on CIFAR-10 over N wireless workers
(4x GTX1080Ti, PyTorch). Offline substitution (DESIGN.md): an MLP on the
synthetic CIFAR-shaped classification task, Dirichlet non-IID partition,
identical protocol/channel parameters. Scale is reduced (input 256-d,
64-hidden MLP) so the full 5-figure suite runs on one CPU core in minutes;
the *comparisons* (P, N, ε sweeps; scheme A vs B) are what reproduce the
paper's claims, not absolute accuracies.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.core import protocol as P
from repro.core.exchange import make_flat_spec
from repro.data import classification_dataset, dirichlet_partition, FederatedBatcher
import repro.models.mlp as mlp

INPUT_DIM = 256
HIDDEN = 64
BATCH = 32
DATA_N = 6000


def provenance(smoke: bool = False) -> Dict:
    """Provenance block every BENCH_*.json carries (repro.obs.runlog is
    the source of truth for git/backend identity): enough to answer
    "which commit, which machine class, full or smoke?" from the JSON
    alone when comparing bench files across branches."""
    from repro.obs import runlog
    return {
        "git_sha": runlog.git_sha(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax": jax.__version__,
        "smoke": bool(smoke),
    }


def _setup_task(proto: P.ProtocolConfig, seed: int):
    """Shared harness: the reduced benchmark task (config, batcher, the
    replicated init params as a flat [W, d] buffer, its row unraveler, an
    eval fn over the buffer) — identical between the static and dynamic
    runners so their rows stay comparable."""
    cfg = get_arch("dwfl-paper").replace(d_model=HIDDEN)
    x, y = classification_dataset(DATA_N, input_dim=INPUT_DIM, seed=seed)
    parts = dirichlet_partition(y, proto.n_workers, alpha=0.5, seed=seed)
    bat = FederatedBatcher(x, y, parts, batch_size=BATCH, seed=seed)
    key = jax.random.PRNGKey(seed)
    params = mlp.init(key, cfg, input_dim=INPUT_DIM)
    wp = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (proto.n_workers,) + a.shape),
        params)
    spec = make_flat_spec(wp)
    ev = jax.jit(P.make_eval_fn(cfg))
    evaluate = lambda flat, batch: ev(spec.unravel(flat), batch)
    return cfg, bat, spec.flatten(wp), spec.unravel_row, evaluate, key


def _finish(wp, bat, evaluate, us_per_step: float, eps_fields: Dict,
            curve: List) -> Dict:
    ev_loss, ev_acc = evaluate(wp, bat.full(128))
    return {
        "us_per_call": us_per_step,
        "final_loss": float(ev_loss),
        "final_acc": float(ev_acc),
        "curve": curve,
        **eps_fields,
    }


def run_protocol(scheme: str, *, n_workers: int, epsilon: float,
                 p_dbm: float = 60.0, steps: int = 250, gamma: float = 0.02,
                 eta: float = 0.4, clip: float = 1.0, seed: int = 0,
                 eval_every: int = 0, participation: float = 1.0) -> Dict:
    proto = P.ProtocolConfig(scheme=scheme, n_workers=n_workers, gamma=gamma,
                             eta=eta, clip=clip, p_dbm=p_dbm, seed=seed,
                             target_epsilon=epsilon,
                             participation=participation)
    chan = proto.channel()
    rep = P.epsilon_report(proto, chan)
    cfg, bat, wp, unravel_row, evaluate, key = _setup_task(proto, seed)
    step = jax.jit(P.make_flat_train_step(cfg, proto, unravel_row))

    curve: List = []
    # warmup/compile
    key, sk = jax.random.split(key)
    wp, _ = step(wp, bat.next(), sk)
    t0 = time.perf_counter()
    for t in range(steps):
        key, sk = jax.random.split(key)
        wp, metrics = step(wp, bat.next(), sk)
        if eval_every and t % eval_every == 0:
            el, ea = evaluate(wp, bat.full(128))
            curve.append((t, float(el), float(ea)))
    wp.block_until_ready()
    us_per_step = (time.perf_counter() - t0) / steps * 1e6

    return _finish(wp, bat, evaluate, us_per_step, {
        "epsilon": rep["epsilon_worst"],
        "epsilon_sampled": rep.get("epsilon_sampled"),
        "sigma": rep["sigma"],
    }, curve)


def run_dynamic_protocol(scenario: str, *, n_workers: int, epsilon: float,
                         coherence_rounds: int = 0, p_dbm: float = 60.0,
                         steps: int = 250, gamma: float = 0.02,
                         eta: float = 0.4, clip: float = 1.0,
                         seed: int = 0) -> Dict:
    """Dynamic-channel (repro.net) counterpart of run_protocol: same task,
    same metrics, but the channel/mixing matrix are per-round traced
    arguments from the scenario's NetworkSimulator; the returned dict adds
    the per-round ε trajectory stats."""
    from repro.net.state import stack_states

    proto = P.ProtocolConfig(scheme="dwfl", n_workers=n_workers, gamma=gamma,
                             eta=eta, clip=clip, p_dbm=p_dbm, seed=seed,
                             target_epsilon=epsilon,
                             channel_model="dynamic", scenario=scenario,
                             coherence_rounds=coherence_rounds)
    sim = proto.simulator()
    cfg, bat, wp, unravel_row, evaluate, key = _setup_task(proto, seed)
    step = jax.jit(P.make_dynamic_flat_train_step(cfg, proto, unravel_row))
    net_round = jax.jit(sim.round)

    key, nk = jax.random.split(key)
    net_state = sim.init(nk)
    # warmup/compile
    key, sk, ck = jax.random.split(key, 3)
    net_state, chan, mask, W = net_round(ck, net_state)
    wp, _ = step(wp, bat.next(), sk, chan, W)
    chan_log, w_log = [chan], [W]
    t0 = time.perf_counter()
    for t in range(steps):
        key, sk, ck = jax.random.split(key, 3)
        net_state, chan, mask, W = net_round(ck, net_state)
        chan_log.append(chan)
        w_log.append(W)
        wp, metrics = step(wp, bat.next(), sk, chan, W)
    wp.block_until_ready()
    us_per_step = (time.perf_counter() - t0) / steps * 1e6

    rep = P.epsilon_report(proto, stack_states(chan_log),
                           Ws=jnp.stack(w_log))
    return _finish(wp, bat, evaluate, us_per_step, {
        "epsilon": rep["epsilon_worst"],
        "epsilon_mean": rep["epsilon_mean"],
        "epsilon_composed": rep["epsilon_trajectory_composed"],
    }, [])


def row(name: str, res: Dict, derived_key: str = "final_acc") -> str:
    return f"{name},{res['us_per_call']:.1f},{res[derived_key]:.4f}"
