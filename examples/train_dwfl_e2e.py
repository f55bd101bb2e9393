"""End-to-end driver: DWFL-train a transformer LM for a few hundred rounds.

The paper's kind is TRAINING, so this is the required end-to-end example.
``--size 100m`` is the production configuration (a ~100M-param dense LM —
run it on real accelerators); ``--size 2m`` (default) is the same code path
scaled to finish on this CPU rig in minutes.

    PYTHONPATH=src python examples/train_dwfl_e2e.py --steps 200
    PYTHONPATH=src python examples/train_dwfl_e2e.py --size 100m --steps 300   # TPU-scale
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import protocol as P
from repro.core.exchange import make_flat_spec
from repro.checkpoint import save_flat
from repro.data import lm_dataset, LMBatcher
from repro.models import model as M

SIZES = {
    # ~2M params: CPU-friendly validation of the exact production code path
    "2m": ModelConfig(name="dwfl-lm-2m", family="dense", num_layers=4,
                      d_model=128, num_heads=4, num_kv_heads=2, d_ff=512,
                      vocab_size=2048, tie_embeddings=True),
    # ~10M params
    "10m": ModelConfig(name="dwfl-lm-10m", family="dense", num_layers=6,
                       d_model=320, num_heads=8, num_kv_heads=4, d_ff=1280,
                       vocab_size=8192, tie_embeddings=True),
    # ~100M params: the "train a ~100M model" production config
    "100m": ModelConfig(name="dwfl-lm-100m", family="dense", num_layers=12,
                        d_model=768, num_heads=12, num_kv_heads=4, d_ff=3072,
                        vocab_size=32768, tie_embeddings=True),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="2m", choices=list(SIZES))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8, help="per-worker")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--epsilon", type=float, default=2.0,
                    help="per-round DP target; 0 disables (gossip-like noise)")
    ap.add_argument("--gamma", type=float, default=0.02)
    ap.add_argument("--p-dbm", type=float, default=80.0)
    ap.add_argument("--scheme", default="dwfl",
                    choices=["dwfl", "gossip", "orthogonal", "centralized"])
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()

    cfg = SIZES[args.size]
    W = args.workers
    proto = P.ProtocolConfig(scheme=args.scheme, n_workers=W, gamma=args.gamma,
                             eta=0.3, clip=1.0, target_epsilon=args.epsilon,
                             p_dbm=args.p_dbm)
    chan = proto.channel()
    rep = P.epsilon_report(proto, chan)

    key = jax.random.PRNGKey(0)
    wp = P.init_worker_params(key, cfg, W)
    n = M.count_params(wp) // W
    spec = make_flat_spec(wp)               # the [W, d] buffer contract
    flat = spec.flatten(wp)
    print(f"[e2e] {cfg.name}: {n/1e6:.1f}M params x {W} workers, "
          f"eps/round={rep['epsilon_worst']:.3g} sigma={rep['sigma']:.3g}")

    toks = lm_dataset(W * 120_000, cfg.vocab_size, seed=0)
    bat = LMBatcher(toks, W, args.batch, args.seq_len, seed=0)
    step = jax.jit(P.make_flat_train_step(cfg, proto, spec.unravel_row),
                   donate_argnums=0)

    t0 = time.time()
    losses = []
    for t in range(args.steps + 1):
        key, sk = jax.random.split(key)
        flat, metrics = step(flat, bat.next(), sk)
        losses.append(float(metrics["loss"]))
        if t % max(1, args.steps // 10) == 0:
            tok_s = (t + 1) * W * args.batch * args.seq_len / (time.time() - t0)
            print(f"[e2e] round {t:4d}  loss={losses[-1]:.4f}  ({tok_s:,.0f} tok/s)")

    first, last = sum(losses[:10]) / 10, sum(losses[-10:]) / 10
    print(f"[e2e] loss {first:.3f} -> {last:.3f} in {time.time()-t0:.0f}s")
    if last < first - 0.02:
        print("[e2e] loss IMPROVED under the protocol.")
    elif last < first * 1.15:
        print("[e2e] loss at the DP/channel noise floor (stable, not "
              "diverging): per-round DP training at this ε needs thousands "
              "of rounds to show net progress — the DP-SGD reality. Run "
              "--scheme gossip or --epsilon 0... for the noiseless dynamics, "
              "or benchmarks/ (classifier task) for visible-in-minutes "
              "convergence under DP.")
    else:
        print("[e2e] WARNING: loss diverged — check channel power "
              "(--p-dbm) vs the worst-channel alignment (DESIGN.md §6b).")
    if args.checkpoint:
        save_flat(args.checkpoint, flat, spec, step=args.steps,
                  metadata={"size": args.size})
        print(f"[e2e] checkpoint -> {args.checkpoint}")


if __name__ == "__main__":
    main()
