"""Kernel micro-benchmarks (interpret-mode on CPU: correctness-scale, not
perf-scale — TPU timing happens on real hardware). derived = max abs error
vs the pure-jnp oracle, proving the kernels' numerics at bench shapes.

The retrace cases guard with ``repro.obs.retrace_guard`` (the promoted
form of the closure trace-counters that used to live here): the guarded
block RAISES on any compilation after the warmup, and the printed derived
value is the lifetime trace count (must print 1.00e+00)."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.models.ssm import ssd_chunked
from repro.obs import retrace_guard


def _time(fn, *a, n=3):
    fn(*a)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*a)
    jax.tree_util.tree_leaves(r)[0].block_until_ready()
    return (time.perf_counter() - t0) / n * 1e6, r


def main():
    rows = []
    key = jax.random.PRNGKey(0)

    q = jax.random.normal(key, (1, 256, 4, 64))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 256, 2, 64))
    us, got = _time(lambda a, b, c: fa_ops.flash_attention(
        a, b, c, block_q=64, block_k=64), q, k, v)
    want = fa_ref.attention_ref(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2))
    err = float(jnp.max(jnp.abs(got - want)))
    rows.append(f"kernel/flash_attention_256,{us:.1f},{err:.2e}")

    xh = jax.random.normal(key, (1, 256, 8, 32)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 4), (1, 256, 8)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 5), (8,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(key, 6), (1, 256, 32)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(key, 7), (1, 256, 32)) * 0.3
    us, (y1, s1) = _time(lambda *a: ssd_ops.ssd_scan(*a, chunk=64),
                         xh, dt, A, Bm, Cm)
    y2, s2 = ssd_chunked(xh, dt, A, Bm, Cm, chunk=64)
    err = float(jnp.max(jnp.abs(y1 - y2)))
    rows.append(f"kernel/ssd_scan_256,{us:.1f},{err:.2e}")

    rows.append(_bench_dp_mix())
    rows.append(_bench_dp_mix_retrace())
    rows.append(_bench_net_retrace())
    rows.append(_bench_fleet_retrace())
    rows.append(_bench_trajectory_scan())
    return rows


def _dp_mix_pair(N=8, sizes=((256, 512), (512,), (512, 512), (512,),
                             (512, 256), (256,), (256, 10), (10,))):
    """(unfused bucketed dwfl round, fused dp_mix flat round) on the same
    multi-leaf worker tree — the fusion acceptance comparison."""
    from repro.core import dwfl, exchange as X
    from repro.core.channel import ChannelConfig
    from repro.kernels.dp_mix import ops as mix_ops

    chan = ChannelConfig(n_workers=N, p_dbm=60.0, sigma=0.7, sigma_m=0.5,
                         seed=0).realize()
    key = jax.random.PRNGKey(0)
    tree = {f"l{i}": jax.random.normal(jax.random.fold_in(key, i), (N,) + s)
            for i, s in enumerate(sizes)}
    gtree = {k: 0.01 * v for k, v in tree.items()}
    gamma, eta = 0.05, 0.4
    plan = X.plan_complete(None, chan)
    fspec = X.make_flat_spec(tree)

    def unfused(tree, gtree, k):
        # the tree bucketed into one [N, d] leaf each round, then unravelled
        Xs = jax.tree_util.tree_map(lambda p, g: p - gamma * g, tree, gtree)
        Xb = {"flat": fspec.flatten(Xs)}
        k1, k2 = jax.random.split(k)
        n = dwfl.dp_noise(k1, Xb, chan)
        m = dwfl.channel_noise(k2, Xb, chan.awgn_sigma)
        return fspec.unravel(dwfl.exchange_dwfl(Xb, n, m, chan, eta)["flat"])

    def fused(flat, gflat, seed):
        return mix_ops.dp_mix_round_plan(flat, gflat, seed, plan,
                                         gamma=gamma, eta=eta)

    flat = fspec.flatten(tree)
    gflat = fspec.flatten(gtree)
    return (jax.jit(unfused), (tree, gtree, key),
            jax.jit(fused), (flat, gflat, mix_ops.seed_from_key(key)))


def _bench_dp_mix():
    """ACCEPTANCE: the fused flat-buffer dp_mix round must beat the
    unfused bucketed dwfl round (per-leaf-free but still concat + 2
    threefry sweeps + einsum + unravel) by >= 1.5x at bench shape.
    derived = speedup."""
    unfused, ua, fused, fa = _dp_mix_pair()
    us_u, _ = _time(unfused, *ua)
    us_f, _ = _time(fused, *fa)
    speedup = us_u / us_f
    assert speedup >= 1.5, (
        f"fused dp_mix round only {speedup:.2f}x vs unfused (need >= 1.5x): "
        f"{us_f:.0f}us vs {us_u:.0f}us")
    return f"kernel/dp_mix_fused_8x528k,{us_f:.1f},{speedup:.2f}"


def _bench_dp_mix_retrace():
    """dp_mix acceptance: every channel quantity is an operand, so the
    fused round compiles ONCE across fresh traced-channel draws — derived
    = number of jit traces over 4 draws (must print 1.00e+00)."""
    from repro.core import exchange as X
    from repro.kernels.dp_mix import ops as mix_ops
    from repro.net import NetworkSimulator, get_scenario

    N, d = 8, 65536
    sim = NetworkSimulator(get_scenario("vehicular"), N, p_dbm=70.0)
    key = jax.random.PRNGKey(0)
    state = sim.init(key)
    net_round = jax.jit(sim.round)

    fused = jax.jit(lambda p, g, seed, plan: mix_ops.dp_mix_round_plan(
        p, g, seed, plan, gamma=0.05, eta=0.4))
    p = jax.random.normal(key, (N, d))
    draws = []
    for t in range(4):
        key, k1 = jax.random.split(key)
        state, chan, _mask, W = net_round(k1, state)
        draws.append((mix_ops.seed_from_key(k1),
                      X.plan_dynamic(None, chan, W_arg=W)))
    fused(p, 0.01 * p, *draws[0])  # compile
    t0 = time.perf_counter()
    with retrace_guard(fused, label="fused dp_mix round") as g:
        for d_ in draws:
            out = fused(p, 0.01 * p, *d_)
        out.block_until_ready()
    us = (time.perf_counter() - t0) / len(draws) * 1e6
    return f"dp_mix/retrace_{N}x{d},{us:.1f},{g.total_traces:.2e}"


def _bench_trajectory_scan():
    """ACCEPTANCE (ISSUE 4): the K=32 scan-chunked trajectory must beat
    the per-round-dispatch legacy loop (host batching + one jitted call
    per round) by >= 2x rounds/sec on the fused flat-buffer round.
    derived = speedup."""
    from benchmarks.trajectory_bench import smoke_case
    c = smoke_case()
    assert c["speedup"] >= 2.0, (
        f"scan trajectory only {c['speedup']:.2f}x vs per-round dispatch "
        f"at K={c['chunk']} (need >= 2x): {c}")
    return (f"trajectory/scan_k{c['chunk']}_{c['workers']}w,"
            f"{c['scan_us_per_round']:.1f},{c['speedup']:.2f}")


def _bench_net_retrace():
    """repro.net acceptance case: the traced-channel exchange compiles ONCE
    and serves every fresh fading realization — derived = number of jit
    traces across 8 distinct channel draws (must print 1.00e+00; the seed's
    static ChannelState re-traced per draw)."""
    from repro.core import dwfl
    from repro.net import NetworkSimulator, get_scenario

    sim = NetworkSimulator(get_scenario("vehicular"), 16, p_dbm=70.0)
    key = jax.random.PRNGKey(0)
    state = sim.init(key)
    net_round = jax.jit(sim.round)

    exchange = jax.jit(lambda X, n, m, chan, W: dwfl.exchange_dwfl_dynamic(
        X, n, m, chan, 0.4, W))
    X = {"w": jax.random.normal(key, (16, 4096))}
    draws = []
    for t in range(8):
        key, k1, k2, k3 = jax.random.split(key, 4)
        state, chan, _mask, W = net_round(k1, state)
        n = dwfl.dp_noise(k2, X, chan)
        m = dwfl.channel_noise(k3, X, chan.awgn_sigma)
        draws.append((n, m, chan, W))
    exchange(X, *draws[0])  # compile
    t0 = time.perf_counter()
    with retrace_guard(exchange, label="dynamic exchange") as g:
        for d in draws:
            out = exchange(X, *d)
        out["w"].block_until_ready()
    us = (time.perf_counter() - t0) / len(draws) * 1e6
    return f"net/retrace_16x4096,{us:.1f},{g.total_traces:.2e}"


def _bench_fleet_retrace():
    """repro.fleet acceptance case: the R-way vmapped exchange compiles
    ONCE and serves every fresh replicate BATCH — derived = number of jit
    traces across 4 distinct stacked [R, ...] realizations (must print
    1.00e+00; zero retraces across replicate batches)."""
    from repro.core import dwfl, protocol as P
    from repro.fleet import FleetEngine

    R, N, d = 8, 8, 2048
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=N, p_dbm=70.0,
                             channel_model="dynamic", scenario="vehicular",
                             replicates=R)
    fleet = FleetEngine(proto)
    key = jax.random.PRNGKey(0)
    states = fleet.init(key)
    fleet_round = jax.jit(fleet.round)

    exchange = jax.jit(lambda X, n, m, chans, Ws: jax.vmap(
        lambda x, nn, mm, ch, w: dwfl.exchange_dwfl_dynamic(
            x, nn, mm, ch, 0.4, w))(X, n, m, chans, Ws))
    X1 = {"w": jax.random.normal(key, (N, d))}
    Xb = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), X1)
    batches = []
    for t in range(4):
        key, k1, k2, k3 = jax.random.split(key, 4)
        states, chans, _masks, Ws = fleet_round(k1, states)
        n = jax.vmap(lambda k, ch: dwfl.dp_noise(k, X1, ch))(
            jax.random.split(k2, R), chans)
        m = jax.vmap(lambda k, ch: dwfl.channel_noise(k, X1, ch.awgn_sigma))(
            jax.random.split(k3, R), chans)
        batches.append((n, m, chans, Ws))
    exchange(Xb, *batches[0])  # compile
    t0 = time.perf_counter()
    with retrace_guard(exchange, label="fleet exchange") as g:
        for b in batches:
            out = exchange(Xb, *b)
        out["w"].block_until_ready()
    us = (time.perf_counter() - t0) / len(batches) * 1e6
    return f"fleet/retrace_{R}x{N}x{d},{us:.1f},{g.total_traces:.2e}"


if __name__ == "__main__":
    print("\n".join(main()))
