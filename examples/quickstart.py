"""Quickstart: 60 lines to run DWFL (the paper's Algorithm 1) end to end.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.core import protocol as P
from repro.data import classification_dataset, dirichlet_partition, FederatedBatcher

# 1. A federation: N wireless workers, non-IID local data.
N = 10
x, y = classification_dataset(6000, input_dim=256, seed=0)
parts = dirichlet_partition(y, N, alpha=0.5, seed=0)
batcher = FederatedBatcher(x, y, parts, batch_size=32)

# 2. The protocol: analog over-the-air exchange, per-round (ε, δ)-DP.
proto = P.ProtocolConfig(
    scheme="dwfl",        # the paper's algorithm ("orthogonal"/"centralized" = baselines)
    n_workers=N,
    gamma=0.02,           # step size γ
    eta=0.4,              # averaging rate η
    clip=1.0,             # gradient clip -> g_max sensitivity bound
    p_dbm=75.0,           # transmit power budget (alignment is worst-channel
                          # limited — see the paper's Fig. 2 / our Fig-2 bench)
    target_epsilon=1.0,   # calibrate DP noise σ to hit this per-round ε
)
chan = proto.channel()
print("privacy:", {k: round(v, 4) for k, v in P.epsilon_report(proto, chan).items()
                   if isinstance(v, float)})

# 3. A model (the paper-scale classifier) replicated across workers.
cfg = get_arch("dwfl-paper").replace(d_model=64)
import repro.models.mlp as mlp
params = mlp.init(jax.random.PRNGKey(0), cfg, input_dim=256)
worker_params = jax.tree_util.tree_map(
    lambda a: jnp.broadcast_to(a[None], (N,) + a.shape), params)

# 4. Train on the persistent FLAT buffer (the only parameter layout):
#    params are raveled ONCE into a [N, d] f32 buffer, every round is one
#    fused dp_mix kernel call (local SGD + on-chip DP noise + mixing
#    matmul + self-correction + AWGN — a single pass over the buffer), and
#    the pytree is recovered only at eval time.
from repro.core import exchange as E
spec = E.make_flat_spec(worker_params)                 # the buffer contract
flat = spec.flatten(worker_params)                     # [N, d] — once
step = jax.jit(P.make_flat_train_step(cfg, proto, spec.unravel_row))
evaluate = jax.jit(P.make_eval_fn(cfg))
key = jax.random.PRNGKey(1)
for t in range(301):
    key, sk = jax.random.split(key)
    flat, metrics = step(flat, batcher.next(), sk)
    if t % 100 == 0:
        ev_loss, ev_acc = evaluate(spec.unravel(flat), batcher.full(128))
        print(f"round {t:4d}  train_loss={float(metrics['loss']):.3f}  "
              f"eval_acc={float(ev_acc):.3f}")
print("done — per-round epsilon:",
      round(P.epsilon_report(proto, chan)["epsilon_worst"], 3))
