"""Dynamic-scenario quickstart: DWFL over a time-varying wireless network.

The static quickstart bakes ONE channel realization into the compiled step;
here the channel is a per-round traced pytree from repro.net — block
fading re-aligned on device every coherence block, geometry-derived path
gains, worker churn — and ONE compiled step serves every realization
(watch the trace counter: it stays at 1 across all rounds).

    PYTHONPATH=src python examples/dynamic_quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.core import protocol as P
from repro.data import classification_dataset, dirichlet_partition, FederatedBatcher
from repro.net.state import stack_states

# 1. A federation on a DYNAMIC network: pick any repro.net scenario —
#    static_paper | iot_dense | vehicular | drone_sparse.
N = 10
proto = P.ProtocolConfig(
    scheme="dwfl",
    n_workers=N,
    gamma=0.02, eta=0.4, clip=1.0,
    p_dbm=75.0,
    target_epsilon=1.0,        # σ re-calibrated EVERY round to pin ε (traced)
    channel_model="dynamic",
    scenario="iot_dense",      # quasi-static fading, short radio range, churn
    coherence_rounds=10,       # override the scenario's fading block length
)
sim = proto.simulator()

# 2. Data + model, identical to the static quickstart.
x, y = classification_dataset(6000, input_dim=256, seed=0)
batcher = FederatedBatcher(x, y, dirichlet_partition(y, N, alpha=0.5, seed=0),
                           batch_size=32)
cfg = get_arch("dwfl-paper").replace(d_model=64)
import repro.models.mlp as mlp
params = mlp.init(jax.random.PRNGKey(0), cfg, input_dim=256)
worker_params = jax.tree_util.tree_map(
    lambda a: jnp.broadcast_to(a[None], (N,) + a.shape), params)
from repro.core import exchange as E
spec = E.make_flat_spec(worker_params)                 # the buffer contract
flat = spec.flatten(worker_params)                     # [N, d] — once

# 3. The dynamic round: channel + mixing matrix are ARGUMENTS of the jitted
#    step, not constants — count the traces to see it compile exactly once.
traces = {"n": 0}
_step = P.make_dynamic_flat_train_step(cfg, proto, spec.unravel_row)

def _counted(flat, batch, key, chan, W):
    traces["n"] += 1           # python side effect: runs once per (re)trace
    return _step(flat, batch, key, chan, W)

step = jax.jit(_counted)
net_round = jax.jit(sim.round)
evaluate = jax.jit(P.make_eval_fn(cfg))

key = jax.random.PRNGKey(1)
key, nk = jax.random.split(key)
net_state = sim.init(nk)
chan_log, w_log = [], []
for t in range(151):
    key, sk, ck = jax.random.split(key, 3)
    net_state, chan, mask, W = net_round(ck, net_state)   # the radio round
    chan_log.append(chan)
    w_log.append(W)
    flat, metrics = step(flat, batcher.next(), sk, chan, W)
    if t % 50 == 0:
        ev_loss, ev_acc = evaluate(spec.unravel(flat), batcher.full(128))
        print(f"round {t:4d}  c={float(chan.c):6.2f}  "
              f"active={int(jnp.sum(mask))}/{N}  "
              f"train_loss={float(metrics['loss']):.3f}  "
              f"eval_acc={float(ev_acc):.3f}")

# 4. Privacy is a TRAJECTORY under a time-varying channel: Thm 4.1 on each
#    realized round (credited only with the masking noise of workers each
#    receiver actually heard), composed worst-case across the run.
rep = P.epsilon_report(proto, stack_states(chan_log), Ws=jnp.stack(w_log))
traj = rep["epsilon_per_round"]
print(f"\nper-round eps over {rep['rounds']} rounds: "
      f"min={traj.min():.3f} mean={rep['epsilon_mean']:.3f} "
      f"max={rep['epsilon_worst']:.3f}")
print(f"trajectory composition: eps={rep['epsilon_trajectory_composed']:.2f} "
      f"delta={rep['delta_trajectory_composed']:.1e}")
print(f"jit traces of the train step: {traces['n']} "
      f"(one compile served {len(chan_log)} channel realizations)")
assert traces["n"] == 1
