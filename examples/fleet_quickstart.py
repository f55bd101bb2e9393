"""Fleet quickstart: a whole FLEET of wireless networks per compiled step.

dynamic_quickstart.py advances ONE time-varying network per jitted call;
here the round is vmapped over a leading replicate axis R (repro.fleet), so
one call advances R independent realizations of the scenario — different
fading, placement, churn, data order and noise per replicate, same compiled
program (the trace counter stays at 1 across rounds AND replicate batches).
At the end, the batched accounting turns the R stacked channel trajectories
into [R, T, N] per-round budgets in one vmapped pass and reports the
composed ε as an across-replicate mean ± 95% CI — error bars the paper's
single-seed figures cannot show.

    PYTHONPATH=src python examples/fleet_quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.core import protocol as P
from repro.core.exchange import make_flat_spec
from repro.data import classification_dataset, dirichlet_partition, FederatedBatcher
from repro.fleet import FleetEngine, fleet_epsilon_report, mean_ci, stack_rounds

# 1. A replicated federation: R=8 independent iot_dense networks.
N, R, ROUNDS = 8, 8, 30
proto = P.ProtocolConfig(
    scheme="dwfl", n_workers=N,
    gamma=0.02, eta=0.4, clip=1.0, p_dbm=75.0,
    target_epsilon=1.0,          # per-round σ re-calibration, per replicate
    channel_model="dynamic", scenario="iot_dense",
    noise_policy="equal",        # bounded self-noise (the "surplus" policy's
                                 # param-scale noise destabilizes short demos)
    replicates=R,
)
fleet = FleetEngine(proto)

# 2. Data + model. Each replicate gets its own batch stream (stacked to
#    [R, N, B, ...]); all replicates share the dataset and partition.
x, y = classification_dataset(4000, input_dim=64, seed=0)
parts = dirichlet_partition(y, N, alpha=0.5, seed=0)
batchers = [FederatedBatcher(x, y, parts, batch_size=16, seed=r)
            for r in range(R)]
next_batch = lambda: jax.tree_util.tree_map(
    lambda *xs: jnp.stack(xs), *[b.next() for b in batchers])

cfg = get_arch("dwfl-paper").replace(d_model=32)
import repro.models.mlp as mlp
key = jax.random.PRNGKey(0)
wp = jax.vmap(lambda k: jax.tree_util.tree_map(
    lambda a: jnp.broadcast_to(a[None], (N,) + a.shape),
    mlp.init(k, cfg, input_dim=64)))(jax.random.split(key, R))
spec = make_flat_spec(wp, lead_axes=2)       # the [R, N, d] buffer contract
wp = spec.flatten(wp)                        # raveled once

# 3. ONE jitted call per round for the whole fleet: network evolution
#    (fading/geometry/churn for all R) + the R-way vmapped DWFL step.
traces = {"n": 0}
_round = fleet.make_fleet_round(cfg, spec=spec)

def _counted(k, states, wp, batch):
    traces["n"] += 1             # python side effect: runs once per (re)trace
    return _round(k, states, wp, batch)

fleet_round = jax.jit(_counted)
evaluate = jax.jit(jax.vmap(P.make_eval_fn(cfg)))

key, nk = jax.random.split(key)
states = fleet.init(nk)
chan_log, w_log = [], []
for t in range(ROUNDS):
    key, rk = jax.random.split(key)
    states, wp, metrics, chans, Ws = fleet_round(rk, states, wp, next_batch())
    chan_log.append(chans)
    w_log.append(Ws)
    if t % 10 == 0:
        print(f"round {t:3d}  loss/replicate="
              f"{[round(float(v), 3) for v in metrics['loss']]}  "
              f"traces={traces['n']}")

# 4. Across-replicate read-out: eval mean ± CI and the batched ε report.
full = jax.tree_util.tree_map(
    lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), batchers[0].full(256))
losses, accs = evaluate(spec.unravel(wp), full)
lm, lc = mean_ci(losses)
am, ac = mean_ci(accs)
rep = fleet_epsilon_report(proto, stack_rounds(chan_log), stack_rounds(w_log))
print(f"\nafter {ROUNDS} rounds x {R} replicates (traces={traces['n']}):")
print(f"  eval loss {lm:.4f} ± {lc:.4f}   acc {am:.3f} ± {ac:.3f}")
print(f"  composed eps {rep['epsilon_composed_mean']:.3g} "
      f"± {rep['epsilon_composed_ci95']:.2g} "
      f"(worst single round {rep['epsilon_worst']:.3g}, "
      f"delta {rep['delta_composed']:.2g})")
assert traces["n"] == 1, "the fleet round must compile exactly once"
