"""End-to-end DWFL training driver.

Runs the paper's protocol for real (executed, not dry-run) on whatever
devices exist. On this CPU rig it drives the reduced configs / the
paper-scale MLP; on a TPU pod the same driver drives the full configs (the
mesh and shardings come from repro.launch.mesh / shardings).

Parameters live in one flat [W, d] buffer (exchange.FlatSpec: ravel once
at init, train flat, unravel only at eval/checkpoint), and the trajectory
is executed by the scan-fused engine (repro.core.trajectory): whole chunks
of ``--chunk-rounds`` consecutive rounds — one coherence block or one eval
interval unless overridden — compile into a single ``lax.scan`` program
with on-device batch sampling (repro.data.device), so the driver
dispatches once per CHUNK. Eval/log happen at chunk boundaries.

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch dwfl-paper --steps 300
    PYTHONPATH=src python -m repro.launch.train --arch dwfl-paper \
        --steps 2000 --channel-model dynamic --scenario vehicular \
        --chunk-rounds 64 --eval-every 256
    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --reduced \
        --scheme dwfl --workers 4 --steps 50 --seq-len 128
    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --scheme orthogonal --epsilon 0.5
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.registry import ARCHS, get_arch
from repro.configs import dwfl_paper
from repro.core import privacy
from repro.core import protocol as P
from repro.core import trajectory as TJ
from repro.data import (FederatedBatcher, LMBatcher, classification_dataset,
                        dirichlet_partition, lm_dataset, store_from_batcher)
from repro.kernels.dp_mix import ops as mix_ops
from repro.launch.compile_cache import setup_compile_cache


class TrainResult(NamedTuple):
    """What a finished run hands back to an in-process caller.

    ``params``: the final flat buffer. ``epsilon``: the run's ε quote —
    composed from the scan carry's ledger under ``--accountant`` when
    telemetry keeps one, else the end-of-run report's (per round on the
    static channel). ``runner``: the scan engine's ChunkRunner, whose
    trace_counts(), compile_seconds and lowered_text() expose each
    compiled chunk length; ``carry``: its final TrajCarry, from which
    ``runner.run`` continues."""
    params: Any
    epsilon: float
    runner: TJ.ChunkRunner
    carry: TJ.TrajCarry


def _sharded_initial_buffer(key, cfg, n_workers, spec, mesh):
    """The initial [W, width] flat buffer, built straight into its
    model-sharded layout: the workers' shared initial weights once, then
    on each device its own column window of their row, broadcast over the
    W rows, so no device ever holds the whole buffer. The same values as
    ``spec.flatten(init_worker_params(key, cfg, W))``. The weights are
    drawn outside the compiled window program: fused into its
    concatenation, the draws take minutes to compile at 535 M
    parameters."""
    from jax.sharding import PartitionSpec
    sw = spec.layout.shard_width

    def window(tree):
        row = spec.flatten(tree)[0]
        col0 = jax.lax.axis_index("model") * sw
        win = jax.lax.dynamic_slice_in_dim(row, col0, sw)
        return jnp.broadcast_to(win[None], (n_workers, sw))
    return jax.jit(jax.shard_map(
        window, mesh=mesh, in_specs=PartitionSpec(),
        out_specs=PartitionSpec(None, "model"), check_vma=False))(
        P.init_worker_params(key, cfg, 1))


def main(argv=None) -> TrainResult:
    with obs.span("dwfl.main"):
        return _main(argv)


def _main(argv) -> TrainResult:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dwfl-paper", choices=list(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of the arch")
    ap.add_argument("--scheme", default="dwfl",
                    choices=["dwfl", "orthogonal", "centralized", "gossip"])
    ap.add_argument("--workers", type=int, default=10)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=32,
                    help="per-worker batch size")
    ap.add_argument("--hidden", type=int, default=0,
                    help="override the arch's d_model (worker-scale runs "
                         "shrink the model as N grows; 0 = arch default)")
    ap.add_argument("--dataset-size", type=int, default=20000,
                    help="classification dataset size (mlp archs); raise "
                         "with --workers so every worker keeps a "
                         "non-trivial local shard")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--gamma", type=float, default=0.01)
    ap.add_argument("--eta", type=float, default=0.4)
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--epsilon", type=float, default=1.0,
                    help="per-round target epsilon (0 = fixed sigma)")
    ap.add_argument("--total-epsilon", type=float, default=0.0,
                    help="whole-run (eps, delta) budget over all "
                         "--steps + 1 rounds; sigma is calibrated per "
                         "round against it under --accountant "
                         "(overrides --epsilon; dynamic channel only)")
    ap.add_argument("--accountant", default="composition",
                    choices=["composition", "rdp"],
                    help="privacy ledger: 'composition' = delta-split "
                         "advanced composition; 'rdp' = Renyi-DP moments "
                         "on core.accounting's order grid (tighter; "
                         "DESIGN.md §16). Picks both the watchdog/"
                         "report quote and the --total-epsilon sigma "
                         "calibration")
    ap.add_argument("--sigma", type=float, default=1.0)
    ap.add_argument("--sigma-m", type=float, default=1.0)
    ap.add_argument("--p-dbm", type=float, default=60.0)
    ap.add_argument("--dirichlet-alpha", type=float, default=0.5)
    ap.add_argument("--channel-model", default="static",
                    choices=["static", "dynamic"],
                    help="static: paper's one-shot channel; dynamic: "
                         "repro.net per-round traced channel")
    ap.add_argument("--scenario", default="static_paper",
                    help="repro.net scenario (dynamic only): static_paper, "
                         "iot_dense, vehicular, drone_sparse")
    ap.add_argument("--coherence-rounds", type=int, default=0,
                    help="override the scenario's fading block length")
    ap.add_argument("--sparse-neighbors", type=int, default=0,
                    help="dynamic + unit-disk scenarios: emit the per-round "
                         "mixing matrix as a padded [N, k] neighbor list "
                         "(repro.net.sparse.SparseW, degree cap k) and mix "
                         "O(N*k) instead of O(N^2) — the worker-scale path "
                         "(pair with e.g. --scenario mesh_sparse)")
    ap.add_argument("--graph-fallback", action="store_true",
                    help="bridge radius-isolated workers to their nearest "
                         "active neighbor (one listen-only edge) instead of "
                         "letting them sit out the round")
    ap.add_argument("--worker-shards", type=int, default=1,
                    help="shard the WORKER axis of the flat buffer over a "
                         "'workers' mesh axis (repro.shard.worker): each "
                         "device runs the grad pass + sparse mix for its "
                         "own N/S rows. Requires --sparse-neighbors > 0 "
                         "and S devices (CPU: XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=S).")
    ap.add_argument("--replicates", type=int, default=1,
                    help="dynamic only: batch R independent network "
                         "realizations through one compiled step "
                         "(repro.fleet); metrics/privacy report mean±CI "
                         "across replicates")
    ap.add_argument("--flat-buffer", action="store_true",
                    help="the flat buffer is the only layout; accepted for "
                         "existing command lines")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="shard the flat buffer's columns over a 'model' "
                         "mesh axis (repro.shard): each shard runs the "
                         "fused dp_mix round on its own [N, d/S] slice. "
                         "Uses a real device mesh when >= S devices exist "
                         "(CPU: XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=S); with fewer, shards logically on "
                         "one device on CPU and refuses on an accelerator. "
                         "Mixing-family schemes (dwfl/gossip) only.")
    ap.add_argument("--max-chunk-cols", type=int, default=0,
                    help="cap (in columns) on each collective of the "
                         "sharded round's gather-free grad pass "
                         "(repro.shard chunk plan): bounds the transient "
                         "gather buffer at ~n_workers x cap elements. "
                         "0 = unbounded (one chunk per leaf x window "
                         "intersection). Requires --model-shards > 1.")
    ap.add_argument("--remat", action="store_true",
                    help="checkpoint each layer of the model's layer scans "
                         "in the sharded grad block (its activations are "
                         "recomputed in the backward pass, only the layer "
                         "inputs are saved): trades compute for activation "
                         "memory on big configs. Requires "
                         "--model-shards > 1 or --worker-shards > 1.")
    ap.add_argument("--chunk-rounds", type=int, default=0,
                    help="scan-fused trajectory engine: rounds compiled "
                         "into one lax.scan dispatch (0 = auto: one "
                         "coherence block or one eval interval)")
    ap.add_argument("--no-transfer-guard", action="store_true",
                    help="disable jax.transfer_guard('disallow') around "
                         "the hot loop (the guard rejects IMPLICIT host<->"
                         "device transfers per dispatch; explicit "
                         "device_put/device_get stay allowed)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log", default=None, help="write metrics JSONL here")
    ap.add_argument("--runlog-dir", default=None,
                    help="open a structured run log under this directory "
                         "(repro.obs: manifest.json + events.jsonl; "
                         "summarize with `python -m repro.obs.report`)")
    ap.add_argument("--telemetry", default="auto",
                    choices=["auto", "on", "off"],
                    help="in-scan per-round telemetry (loss/grad-norm/"
                         "consensus/SNR/deep-fade/participation/eps), "
                         "emitted as one stacked array per chunk. auto: "
                         "on when --runlog-dir is set")
    ap.add_argument("--eps-budget", type=float, default=0.0,
                    help="warn when the composed trajectory epsilon "
                         "approaches (80%%) / exceeds this budget "
                         "(0 = no watchdog; needs telemetry)")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced and args.arch != "dwfl-paper":
        cfg = cfg.reduced()
    if args.hidden > 0:
        cfg = dataclasses.replace(cfg, d_model=args.hidden)
    W = args.workers

    if args.replicates > 1 and args.channel_model != "dynamic":
        raise SystemExit("--replicates requires --channel-model dynamic "
                         "(the static channel is baked into the compiled "
                         "step; there is nothing to batch)")
    if args.sparse_neighbors > 0 and args.channel_model != "dynamic":
        raise SystemExit("--sparse-neighbors requires --channel-model "
                         "dynamic (the sparse neighbor list is the "
                         "per-round unit-disk graph)")

    if args.total_epsilon > 0 and args.channel_model != "dynamic":
        raise SystemExit("--total-epsilon calibrates sigma against the "
                         "realized per-round neighborhoods; it requires "
                         "--channel-model dynamic (static runs: invert "
                         "accounting.sigma_for_total_epsilon by hand)")
    proto = P.ProtocolConfig(
        scheme=args.scheme, n_workers=W, gamma=args.gamma, eta=args.eta,
        clip=args.clip, sigma=args.sigma, sigma_m=args.sigma_m,
        p_dbm=args.p_dbm, seed=args.seed,
        target_epsilon=0.0 if args.total_epsilon > 0 else args.epsilon,
        channel_model=args.channel_model, scenario=args.scenario,
        coherence_rounds=args.coherence_rounds, replicates=args.replicates,
        sparse_neighbors=args.sparse_neighbors,
        graph_fallback=args.graph_fallback,
        accountant=args.accountant,
        target_total_epsilon=args.total_epsilon,
        horizon=args.steps + 1 if args.total_epsilon > 0 else 0)
    if args.total_epsilon > 0:
        print(f"[train] total budget: eps={args.total_epsilon} "
              f"delta={proto.delta} over {args.steps + 1} rounds "
              f"(accountant={args.accountant})")

    # observability: in-scan telemetry spec + structured run log. The spec
    # is compiled into the chunk program; "auto" switches it on exactly
    # when a run log wants the rows.
    tele = None
    if args.telemetry == "on" or (args.telemetry == "auto"
                                  and args.runlog_dir is not None):
        tele = obs.TelemetrySpec()
    if args.eps_budget > 0 and tele is None:
        raise SystemExit("--eps-budget needs telemetry (the composed eps "
                         "comes out of the scan carry); use --runlog-dir "
                         "or --telemetry on")
    runlog = None
    if args.runlog_dir is not None:
        runlog = obs.RunLog.open_under(
            args.runlog_dir, kind="train",
            config={"args": vars(args),
                    "protocol": dataclasses.asdict(proto)},
            seed=args.seed, argv=argv,
            extra={"telemetry": list(tele.fields) if tele else None})
        print(f"[train] run log -> {runlog.dir}")
    n_shards = max(1, args.model_shards)
    if n_shards > 1 and args.scheme not in ("dwfl", "gossip"):
        raise SystemExit("--model-shards runs the fused mixing round; the "
                         "orthogonal/centralized baselines have none")
    max_chunk_cols = args.max_chunk_cols if args.max_chunk_cols > 0 else None
    if max_chunk_cols is not None and n_shards <= 1:
        raise SystemExit("--max-chunk-cols caps the sharded round's "
                         "collective chunks; it requires --model-shards > 1")
    if args.remat and n_shards <= 1 and args.worker_shards <= 1:
        raise SystemExit("--remat rematerializes the sharded grad block; "
                         "it requires --model-shards > 1 or "
                         "--worker-shards > 1")
    worker_mesh = None
    if args.worker_shards > 1:
        if proto.sparse_neighbors <= 0:
            raise SystemExit("--worker-shards requires --sparse-neighbors "
                             "> 0 (only the sparse neighbor-list round has "
                             "a worker-sharded lowering)")
        if n_shards > 1 or args.replicates > 1:
            raise SystemExit("--worker-shards composes with neither "
                             "--model-shards nor --replicates yet")
        if W % args.worker_shards != 0:
            raise SystemExit(f"--workers {W} must divide evenly over "
                             f"--worker-shards {args.worker_shards}")
        if jax.device_count() < args.worker_shards:
            raise SystemExit(f"--worker-shards {args.worker_shards} needs "
                             f"that many devices; have "
                             f"{jax.device_count()} (CPU: XLA_FLAGS="
                             f"--xla_force_host_platform_device_count="
                             f"{args.worker_shards})")
        from repro.launch import mesh as mesh_lib
        worker_mesh = mesh_lib.make_worker_mesh(args.worker_shards)
        print(f"[train] worker shards: {args.worker_shards} x "
              f"{W // args.worker_shards} rows on a 'workers' mesh")
    sim, fleet = None, None
    if args.replicates > 1:
        from repro.fleet import FleetEngine
        fleet = FleetEngine(proto)
        sim = fleet.sim
        print(f"[train] {args.arch} scheme={args.scheme} N={W} "
              f"dynamic scenario={args.scenario} R={args.replicates} "
              f"replicates/compiled-step "
              f"coherence={sim.scenario.fading.coherence_rounds} rounds")
    elif args.channel_model == "dynamic":
        sim = proto.simulator()
        print(f"[train] {args.arch} scheme={args.scheme} N={W} "
              f"dynamic scenario={args.scenario} "
              f"coherence={sim.scenario.fading.coherence_rounds} rounds")
    else:
        chan = proto.channel()
        rep = P.epsilon_report(proto, chan)
        print(f"[train] {args.arch} scheme={args.scheme} N={W} "
              f"eps={rep['epsilon_worst']:.3g}/round sigma={rep['sigma']:.3g} "
              f"(orthogonal would be eps={rep['epsilon_orthogonal_worst']:.3g})")

    key = jax.random.PRNGKey(args.seed)
    with obs.span("dwfl.main.data"):
        if cfg.family == "mlp":
            x, y = classification_dataset(args.dataset_size, seed=args.seed)
            parts = dirichlet_partition(y, W, alpha=args.dirichlet_alpha,
                                        seed=args.seed)
            batcher = FederatedBatcher(x, y, parts, args.batch_size,
                                       seed=args.seed)
        else:
            toks = lm_dataset(W * 200_000, cfg.vocab_size, seed=args.seed)
            batcher = LMBatcher(toks, W, args.batch_size, args.seq_len,
                                seed=args.seed)
        # the chunks sample on the device from this store
        store = store_from_batcher(batcher)

    # spec: the layout-aware buffer contract (exchange.FlatSpec); its
    # unravel maps the persistent [.., W, width] buffer back to the
    # worker-stacked pytree at eval/checkpoint time
    shard_mesh = None
    if fleet is not None:
        wp, spec = fleet.init_flat_spec(key, cfg, n_shards=n_shards,
                                        max_chunk_cols=max_chunk_cols)
    else:
        from repro.core import exchange as X
        spec = X.make_flat_spec(
            jax.eval_shape(lambda: P.init_worker_params(key, cfg, W)),
            n_shards=n_shards, max_chunk_cols=max_chunk_cols)
        with obs.span("dwfl.main.params"):
            if n_shards > 1 and jax.device_count() >= n_shards:
                from repro.launch import mesh as mesh_lib
                shard_mesh = mesh_lib.make_shard_mesh(n_shards)
                wp = _sharded_initial_buffer(key, cfg, W, spec, shard_mesh)
            else:
                wp = spec.flatten(P.init_worker_params(key, cfg, W))
    if worker_mesh is not None:
        from jax.sharding import NamedSharding
        from repro.shard.worker import worker_partition_spec
        wp = jax.device_put(
            wp, NamedSharding(worker_mesh, worker_partition_spec()))
    if spec.n_shards > 1:
        # place the padded buffer on a real model mesh when the devices
        # exist; otherwise shard logically inside one device's program
        from repro.launch import mesh as mesh_lib
        from repro.launch import shardings as shardings_lib
        if shard_mesh is not None:
            where = f"{spec.n_shards}-device model mesh"
        elif jax.device_count() >= spec.n_shards:
            # fleet: 2-D (replicas=1, model=S) mesh — replicates stay
            # vmapped within each model group
            shard_mesh = mesh_lib.make_shard_mesh(spec.n_shards,
                                                  n_replicas=1)
            wp = jax.device_put(wp, shardings_lib.flat_buffer_sharding(
                spec, shard_mesh, replicate_axis="replicas"))
            where = f"{spec.n_shards}-device model mesh"
        elif jax.default_backend() != "cpu":
            raise SystemExit(f"--model-shards {spec.n_shards} needs "
                             f"{spec.n_shards} {jax.default_backend()} "
                             f"devices; have {jax.device_count()} (one-"
                             f"device logical sharding is a CPU test mode)")
        else:
            where = (f"1 device (logical — set XLA_FLAGS=--xla_force_host_"
                     f"platform_device_count={spec.n_shards} or run on a "
                     f"pod for a real mesh)")
        print(f"[train] model shards: {spec.n_shards} x "
              f"{spec.layout.shard_width} cols ({spec.width} padded, "
              f"d={spec.d}) on {where}")
        plan = spec.chunk_plan
        cap = plan.max_chunk_cols
        print(f"[train] grad-pass chunk plan: {len(plan.chunks)} chunks, "
              f"{len(plan.exec_segments())} collective segments"
              + (f", cap {cap} cols" if cap else " (unbounded)"))
    # d is per worker (the fleet's lead axes are [R, W])
    print(f"[train] params/worker: {spec.d/1e6:.2f}M (flat buffer)")
    if args.scheme in ("dwfl", "gossip"):
        print(f"[train] mix lowering: "
              f"{mix_ops.lowering(sparse=proto.sparse_neighbors > 0)}")

    net_state = None
    if fleet is not None:
        key, nk = jax.random.split(key)
        net_state = fleet.init(nk)
        evaluate = jax.jit(jax.vmap(P.make_eval_fn(cfg)))
    elif sim is not None:
        key, nk = jax.random.split(key)
        net_state = sim.init(nk)
        evaluate = jax.jit(P.make_eval_fn(cfg))
    else:
        evaluate = jax.jit(P.make_eval_fn(cfg))

    if (fleet is None and sim is not None
            and (sim.sparse_k > 0 or sim.scenario.geometry.comm_radius > 0)):
        # one host-side probe of the FIRST graph draw: radius-isolated
        # workers silently sit out their rounds (listen = 0), which looks
        # like slow convergence rather than a connectivity problem —
        # surface the count up front. The probe key is fold_in-derived, so
        # the training key stream is untouched.
        from repro.net.sparse import SparseW, isolated_count
        _, _, mask0, W0 = jax.jit(sim.round)(
            jax.random.fold_in(key, 0x150), net_state)
        if isinstance(W0, SparseW):
            iso = int(np.asarray(isolated_count(W0, mask0)))
        else:
            off = (jnp.asarray(W0) > 0) & ~jnp.eye(W0.shape[0], dtype=bool)
            iso = int(np.asarray(jnp.sum(
                (jnp.sum(off, axis=1) == 0) & (jnp.asarray(mask0) > 0))))
        if iso:
            msg = (f"{iso}/{W} active workers isolated in the first graph "
                   f"draw (comm_radius="
                   f"{sim.scenario.geometry.comm_radius:g})"
                   + ("" if args.graph_fallback
                      else " — consider --graph-fallback"))
            if runlog is not None:
                runlog.warn(msg, isolated=iso, n_workers=W,
                            graph_fallback=args.graph_fallback)
            print(f"[train] WARNING: {msg}")

    # The eval batch is pinned ONCE, device-resident, before the loop.
    # MLP: the fixed per-worker eval slice (broadcast to [R, ...] once for
    # the fleet — rebuilding + re-broadcasting it per eval call was a
    # per-eval host sync). LM: one pinned draw — evaluating on the live
    # training stream would both train on the eval data and make the
    # training-batch sequence depend on --eval-every.
    if args.eval_every <= 0:
        eval_batch = None       # worker-scale runs: no eval boundaries
    elif cfg.family == "mlp":
        eval_batch = jax.tree_util.tree_map(jnp.asarray, batcher.full(256))
        if fleet is not None:
            eval_batch = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(
                    a[None], (fleet.replicates,) + a.shape), eval_batch)
    elif fleet is not None:
        # R independent draws from the worker-batch stream, stacked to
        # [R, W, B, ...]
        eval_batch = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[batcher.next() for _ in range(fleet.replicates)])
    else:
        eval_batch = jax.tree_util.tree_map(jnp.asarray, batcher.next())

    logf = open(args.log, "w") if args.log else None
    t0 = time.time()

    def log_eval(t, metrics, params):
        # the persistent buffer is unravelled ONLY here
        wp_eval = spec.unravel(params)
        if fleet is not None:
            # across-replicate reduction happens ONLY at eval/log
            # boundaries — never once per round
            metrics = jax.tree_util.tree_map(jnp.mean, metrics)
            el_r, ea_r = evaluate(wp_eval, eval_batch)        # [R], [R]
            ev_loss, ev_acc = jnp.mean(el_r), jnp.mean(ea_r)
        else:
            ev_loss, ev_acc = evaluate(wp_eval, eval_batch)
        rec = {"step": t, "loss": float(metrics["loss"]),
               "eval_loss": float(ev_loss), "eval_acc": float(ev_acc),
               "grad_norm": float(metrics["grad_norm"]),
               "wall_s": round(time.time() - t0, 1)}
        print(f"[train] step={t:5d} loss={rec['loss']:.4f} "
              f"eval={rec['eval_loss']:.4f} acc={rec['eval_acc']:.3f} "
              f"({rec['wall_s']}s)")
        if logf:
            logf.write(json.dumps(rec) + "\n")
            logf.flush()
        if runlog is not None:
            runlog.eval_metrics(**rec)

    # scan-fused trajectory: one dispatch per chunk, on-device batch
    # sampling, eval/log at chunk boundaries only
    chan_chunks, w_chunks = [], []    # ONE [K, ...] array per chunk
    body = TJ.make_round_body(
        cfg, proto, store, sim=None if fleet is not None else sim,
        fleet=fleet, spec=spec, shard_mesh=shard_mesh,
        worker_mesh=worker_mesh, telemetry=tele, remat=args.remat)
    coher = (sim.scenario.fading.coherence_rounds
             if sim is not None else None)
    chunk = (args.chunk_rounds if args.chunk_rounds > 0
             else TJ.auto_chunk(args.eval_every, coher))
    print(f"[train] scan-fused trajectory: chunk={chunk} rounds/dispatch"
          + (f", telemetry: {','.join(tele.fields)}" if tele else ""))
    runner = TJ.ChunkRunner(body)
    eps0 = (obs.init_eps_moments(
                fleet.replicates if fleet is not None else None)
            if tele is not None and tele.epsilon else None)
    if shard_mesh is not None and fleet is None:
        # the chunk program hands the key and the ledger back
        # replicated over the mesh: hand them in so, or the second
        # chunk compiles again for the new placement of its inputs
        from jax.sharding import NamedSharding, PartitionSpec
        key, eps0 = jax.device_put(
            (key, eps0), NamedSharding(shard_mesh, PartitionSpec()))
    carry = TJ.TrajCarry(key, wp, net_state, eps0)
    eps_dog = (obs.EpsilonBudgetWatchdog(
                   args.eps_budget,
                   on_warn=runlog.warn if runlog is not None else
                   (lambda msg, **kw: print(f"[train] WARNING: {msg}")))
               if args.eps_budget > 0 else None)
    retrace_dog = obs.RetraceWatchdog(runner, runlog=runlog,
                                      label="chunk")
    t = 0
    for n, do_eval in TJ.plan_chunks(args.steps + 1, chunk,
                                     args.eval_every):
        # the chunk dispatch is the hot path: everything it touches is
        # device-resident by construction, and the transfer guard
        # (repro.obs) makes any regression — a host batch smuggled in,
        # an implicit readback — fail loudly at the call site
        with obs.no_implicit_transfers(not args.no_transfer_guard):
            carry, out = runner.run(carry, n)
        t += n
        if "chan" in out:
            chan_chunks.append(out["chan"])
            w_chunks.append(out["W"])
        if tele is not None and runlog is not None:
            # ONE device->host transfer per chunk: the stacked
            # [K, M] ([K, R, M] fleet: across-replicate mean) rows
            rows = np.asarray(out["telemetry"])
            if rows.ndim == 3:
                rows = rows.mean(axis=1)
            for i, row in enumerate(rows):
                runlog.round_metrics(
                    t - n + i, **{f: float(v)
                                  for f, v in zip(tele.fields, row)})
        retrace_dog.check(step=t - 1)
        if carry.eps is not None and (do_eval or eps_dog is not None):
            m = np.asarray(carry.eps)
            e_c, d_c = privacy.compose_from_moments(m, proto.delta)
            # fleet: worst replicate is the binding budget
            e_worst = float(np.max(e_c))
            # the widened carry also holds the per-order RDP ledger —
            # quote the tighter budget and let the watchdog track
            # whichever accountant the run selected
            e_rdp = None
            if m.shape[-1] > 4:
                e_r, _ = privacy.compose_from_moments(
                    m, proto.delta, accountant="rdp")
                e_rdp = float(np.max(e_r))
            e_track = (e_rdp if args.accountant == "rdp"
                       and e_rdp is not None else e_worst)
            if eps_dog is not None:
                eps_dog.check(e_track, step=t - 1)
            if do_eval and runlog is not None:
                extra = ({"eps_rdp": e_rdp,
                          "accountant": args.accountant}
                         if e_rdp is not None else {})
                runlog.epsilon(
                    step=t - 1, eps_composed=e_worst,
                    delta_composed=float(np.max(d_c)),
                    rounds=int(np.max(m[..., 3])),
                    eps_round=float(np.asarray(
                        out["telemetry"])[-1, ...,
                                          tele.fields.index("epsilon")]
                        .max()),
                    **extra)
        if do_eval:
            metrics = jax.tree_util.tree_map(lambda a: a[-1],
                                             out["metrics"])
            log_eval(t - 1, metrics, carry.params)
    key, wp, net_state = carry.key, carry.params, carry.net
    if fleet is not None:
        # batched accounting over ALL replicates' realized trajectories:
        # [R, T, N] budgets in one vmapped program, composed per replicate,
        # reported as across-replicate mean ± CI (DESIGN.md §repro.fleet).
        from repro.fleet import fleet_epsilon_report
        # one stacked [K, R, ...] array per chunk — concatenate ONCE and
        # flip to the replicate-major [R, T, ...]
        chans = TJ.replicate_major(TJ.concat_chunks(chan_chunks))
        Ws = TJ.replicate_major(TJ.concat_chunks(w_chunks))
        rep = fleet_epsilon_report(proto, chans, Ws)
        print(f"[train] eps over {rep['rounds']} rounds x "
              f"{rep['replicates']} replicates: worst/round="
              f"{rep['epsilon_worst']:.3g} composed="
              f"{rep['epsilon_composed_mean']:.3g}"
              f"±{rep['epsilon_composed_ci95']:.2g} "
              f"(delta={rep['delta_composed']:.2g})")
        print(f"[train] accountant[{rep['accountant']}]: "
              f"rdp={rep['epsilon_rdp_mean']:.3g} vs "
              f"advanced={rep['epsilon_advanced_mean']:.3g} "
              f"-> quoting {rep['epsilon_total_mean']:.3g}"
              f"±{rep['epsilon_total_ci95']:.2g} "
              f"(delta={rep['delta_total']:.2g}, "
              f"gap {rep['accountant_gap']:.2g}x)")
        if runlog is not None:
            runlog.event("epsilon_report", rounds=rep["rounds"],
                         replicates=rep["replicates"],
                         eps_worst_round=float(rep["epsilon_worst"]),
                         eps_composed_mean=float(
                             rep["epsilon_composed_mean"]),
                         eps_composed_ci95=float(
                             rep["epsilon_composed_ci95"]),
                         delta_composed=float(rep["delta_composed"]),
                         eps_rdp_mean=float(rep["epsilon_rdp_mean"]),
                         eps_total_mean=float(rep["epsilon_total_mean"]),
                         eps_total_ci95=float(rep["epsilon_total_ci95"]),
                         delta_total=float(rep["delta_total"]),
                         accountant_gap=float(rep["accountant_gap"]),
                         accountant=rep["accountant"],
                         saturated=bool(rep["saturated"]))
    elif sim is not None:
        # per-round privacy over the REALIZED fading trajectory (not a
        # scalar): Thm 4.1 on each round's channel + worst-case
        # heterogeneous composition (DESIGN.md §repro.net).
        chans = TJ.concat_chunks(chan_chunks)
        Ws = TJ.concat_chunks(w_chunks)
        rep = P.epsilon_report(proto, chans, Ws=Ws)
        traj = rep["epsilon_per_round"]
        print(f"[train] per-round eps over {rep['rounds']} rounds: "
              f"min={traj.min():.3g} mean={rep['epsilon_mean']:.3g} "
              f"max={rep['epsilon_worst']:.3g}  "
              f"composed(eps,delta)=({rep['epsilon_trajectory_composed']:.3g}, "
              f"{rep['delta_trajectory_composed']:.2g})")
        print(f"[train] accountant[{rep['accountant']}]: "
              f"rdp={rep['epsilon_rdp']:.3g} vs "
              f"advanced={rep['epsilon_advanced']:.3g} "
              f"-> quoting {rep['epsilon_total']:.3g} "
              f"(delta={rep['delta_total']:.2g}, "
              f"gap {rep['accountant_gap']:.2g}x, "
              f"order={rep['rdp_order']:.3g})")
        if runlog is not None:
            runlog.event("epsilon_report", rounds=rep["rounds"],
                         eps_worst_round=float(rep["epsilon_worst"]),
                         eps_mean_round=float(rep["epsilon_mean"]),
                         eps_composed=float(
                             rep["epsilon_trajectory_composed"]),
                         delta_composed=float(
                             rep["delta_trajectory_composed"]),
                         eps_rdp=float(rep["epsilon_rdp"]),
                         eps_total=float(rep["epsilon_total"]),
                         delta_total=float(rep["delta_total"]),
                         accountant_gap=float(rep["accountant_gap"]),
                         rdp_order=float(rep["rdp_order"]),
                         accountant=rep["accountant"],
                         saturated=bool(rep["saturated"]))
    if args.checkpoint:
        meta = {"arch": args.arch, "scheme": args.scheme,
                "epsilon": rep["epsilon_worst"]}
        if proto.sparse_neighbors > 0:
            # record the padded neighbor-list contract so a restore knows
            # how the run's Ws were laid out (DESIGN.md §15)
            from repro.net.sparse import SparseW
            meta["sparse_neighbors"] = proto.sparse_neighbors
            if isinstance(Ws, SparseW):
                meta["sparse_w"] = Ws.layout_meta()
        # the buffer itself, with the shard-layout metadata — restorable
        # under ANY shard count (checkpoint.restore_flat). The state
        # pytree carries the PRNG carry key AND the net/fleet NetState
        # (dynamic runs): exactly the TrajCarry a bitwise resume needs.
        from repro.checkpoint import save_flat
        state = {"key": key}
        if net_state is not None:
            state["net"] = net_state
        save_flat(args.checkpoint, wp, spec, step=args.steps,
                  state=state, metadata=meta)
        print(f"[train] checkpoint -> {args.checkpoint}")
        if runlog is not None:
            runlog.checkpoint(args.checkpoint, step=args.steps)
    if logf:
        logf.close()
    if runlog is not None:
        # a run whose manifest still says "open" crashed before this line
        runlog.close("ok", steps=args.steps)
        print(f"[train] run log closed: {runlog.dir} "
              f"({runlog.n_events} events, {runlog.n_warnings} warnings) — "
              f"summarize with `python -m repro.obs.report {runlog.dir}`")
    if carry.eps is not None:
        m = np.asarray(carry.eps)
        e_c, _ = privacy.compose_from_moments(
            m, proto.delta,
            accountant=args.accountant if m.shape[-1] > 4 else "composition")
        epsilon = float(np.max(e_c))
    elif fleet is not None:
        epsilon = float(rep["epsilon_total_mean"])
    elif sim is not None:
        epsilon = float(rep["epsilon_total"])
    else:
        epsilon = float(rep["epsilon_worst"])
    return TrainResult(wp, epsilon, runner, carry)


if __name__ == "__main__":
    main()
