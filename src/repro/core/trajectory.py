"""Scan-fused trajectory engine — whole coherence blocks of DWFL rounds
compiled into ONE program.

The paper's guarantees (Thms 4.1/4.2) are statements about a T-round
trajectory, but the seed driver executed that trajectory as T separate
jitted dispatches from a Python loop: per-round host NumPy batch assembly,
per-round ``jax.random.split`` on the host, per-round device arrays
appended to unbounded Python lists. After PR 3 fused the O(d) round body
(dp_mix), that dispatch + host work dominates wall-clock for the small-
model long-horizon (T >> 1e3) sweeps the fleet engine targets.

This module rolls K consecutive rounds into a single ``lax.scan``:

    body(carry) -> (carry', out)           one full DWFL round, on device
    ChunkRunner.run(carry, K)              ONE dispatch = K rounds

with a donated carry (PRNG key, the flat [W, d] / [R, W, d] parameter
buffer, and the repro.net ``NetState`` when dynamic) and
stacked ``[K, ...]`` outputs (metrics, per-round TracedChannelState and
mixing matrices) that feed ``epsilon_report`` / ``fleet_epsilon_report``
directly. Inside the scan: on-device key folding (the SAME split
discipline whether the trajectory is chunked K-at-a-time or stepped one
round per dispatch — chunk boundaries cannot change the realized PRNG
stream), net evolution via ``NetworkSimulator.round``, the flat-buffer
round (fused dp_mix for the mixing family), and on-device batch sampling
from a
device-resident store (repro.data.device) instead of per-round host
NumPy.

All three paths share the one body factory:

    make_round_body(cfg, proto, store, spec=spec)              static channel
    make_round_body(cfg, proto, store, spec=spec, sim=sim)     dynamic (repro.net)
    make_round_body(cfg, proto, store, spec=spec, fleet=fleet) fleet ([R, ...])

``run_per_round`` executes the same body one jitted dispatch per round —
the equivalence/benchmark baseline (tests/test_trajectory.py asserts the
two are BITWISE identical on CPU; benchmarks/trajectory_bench.py measures
the speedup).
"""
from __future__ import annotations

from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import protocol as protocol_lib
from repro.obs.spans import span


class TrajCarry(NamedTuple):
    """The donated scan carry: everything a round consumes and rewrites.

    ``params`` is the persistent flat buffer ([W, d]; [R, W, d] for the
    fleet; the physical padded width under a model-sharded layout). ``net`` is the repro.net NetState (stacked for the fleet),
    or None on the static-channel path. ``eps`` is the running accountant
    accumulator ([Σε, Σε², Σε(e^ε−1), T | Σε(α₁..α_A)] — [4+A] f32 with
    the per-order RDP ledger appended (A = accounting.N_ORDERS; the
    legacy [4] layout still composes), [R, 4+A] for the fleet;
    obs.telemetry.init_eps_moments) when telemetry with ε accounting is
    enabled, else None — the composed trajectory budget under BOTH
    accountants then comes out of the compiled chunk for free
    (privacy.compose_from_moments ``accountant=`` dispatch)."""
    key: jnp.ndarray
    params: Any
    net: Any = None
    eps: Any = None


def make_round_body(cfg, proto, store, *, sim=None, fleet=None,
                    unravel_row=None, spec=None, shard_mesh=None,
                    worker_mesh=None, telemetry=None,
                    remat: bool = False) -> Callable:
    """Build ``body(carry) -> (carry', out)`` — one full DWFL round.

    ``store`` is a repro.data.device store (sample/sample_fleet). Exactly
    one of the three paths is taken: ``fleet`` (FleetEngine — vmapped
    [R, ...] round), ``sim`` (NetworkSimulator — single dynamic network),
    neither (static channel). The round runs on the flat buffer
    (protocol.make_*_flat_train_step); ``unravel_row`` maps one buffer row
    to one worker's tree, and defaults to ``spec.unravel_row``.

    ``spec`` (exchange.FlatSpec): the layout-aware buffer contract. With a model-sharded spec (repro.shard) the carry's
    flat buffer is the physical [.., width] padded buffer — sharded over
    ``shard_mesh``'s "model" axis when given (the scan then runs
    shard_map bodies with the carry donated in place on every device), or
    logically sharded on one device otherwise. The key discipline is
    unchanged, so sharded and unsharded trajectories realize the SAME
    noise stream (bitwise on CPU; tests/test_shard.py).

    Key discipline (shared by every path, and by the per-round reference
    ``run_per_round``): the carry key splits once per round into the
    round key, which splits into (data key, [net key,] step key) — a pure
    function of the initial key and the round INDEX, never of the chunk
    partition.

    ``out`` carries the round's stacked outputs: ``metrics`` always;
    ``chan`` (TracedChannelState) and ``W`` (mixing matrix) on the
    dynamic/fleet paths — [K, ...] / [K, R, ...] leaves after a K-round
    scan, one array per chunk instead of one Python list entry per round.

    ``worker_mesh`` (sim path, flat buffer, sparse_neighbors > 0): run
    each round worker-axis sharded over the mesh's "workers" axis
    (repro.shard.worker — N beyond one device). The carry's flat buffer
    and the store's batches are row-sharded; channel/W stay replicated.
    Mutually exclusive with a model-sharded ``spec`` for now.

    ``remat`` (sharded specs only) turns on the model's layer-level
    checkpoint in the gather-free grad block (each layer recomputed in
    the backward pass) — the big-model knob; a no-op on unsharded paths.

    ``telemetry`` (obs.telemetry.TelemetrySpec) wraps the built body in
    pure read-only instrumentation: the enabled per-round scalars are
    packed into ``out["telemetry"]`` ([M] per round, [R, M] for the
    fleet — [K, M] / [K, R, M] per chunk) and, when ε is enabled and the
    carry holds an ``eps`` accumulator, the ε composition moments are
    folded into the carry. The wrapper consumes NO PRNG keys and never
    touches params, so chunked-vs-per-round trajectories stay BITWISE
    identical with telemetry on (tests/test_trajectory.py).
    """
    if unravel_row is None:
        if spec is None:
            raise ValueError("make_round_body needs the buffer's layout: "
                             "pass spec= (or unravel_row=)")
        unravel_row = spec.unravel_row
    sharded = spec is not None and spec.layout is not None

    if fleet is not None:
        step = fleet.make_fleet_step(cfg, mesh=shard_mesh if sharded else None,
                                     unravel_row=unravel_row, spec=spec,
                                     remat=remat)
        R = fleet.replicates

        def body(carry: TrajCarry):
            key, sk = jax.random.split(carry.key)
            k_data, k_net, k_step = jax.random.split(sk, 3)
            states, chans, _masks, Ws = fleet.round(k_net, carry.net)
            batch = store.sample_fleet(k_data, R)
            params, metrics = step(carry.params, batch,
                                   fleet.split_keys(k_step), chans, Ws)
            return (TrajCarry(key, params, states, carry.eps),
                    {"metrics": metrics, "chan": chans, "W": Ws})

        return _maybe_instrument(body, telemetry, proto, fleet=fleet)

    if worker_mesh is not None and (sim is None or sharded or spec is None):
        raise ValueError("worker_mesh requires the sim path with an "
                         "unsharded flat spec")

    if sim is not None:
        if worker_mesh is not None:
            from repro.shard.worker import \
                make_worker_sharded_dynamic_flat_train_step
            step = make_worker_sharded_dynamic_flat_train_step(
                cfg, proto, spec, mesh=worker_mesh, remat=remat)
        elif sharded:
            from repro.shard.round import \
                make_sharded_dynamic_flat_train_step
            step = make_sharded_dynamic_flat_train_step(
                cfg, proto, spec, mesh=shard_mesh, remat=remat)
        else:
            step = protocol_lib.make_dynamic_flat_train_step(
                cfg, proto, unravel_row)

        def body(carry: TrajCarry):
            key, sk = jax.random.split(carry.key)
            k_data, k_net, k_step = jax.random.split(sk, 3)
            net, chan, _mask, W = sim.round(k_net, carry.net)
            batch = store.sample(k_data)
            params, metrics = step(carry.params, batch, k_step, chan, W)
            return (TrajCarry(key, params, net, carry.eps),
                    {"metrics": metrics, "chan": chan, "W": W})

        return _maybe_instrument(body, telemetry, proto)

    if sharded:
        from repro.shard.round import make_sharded_flat_train_step
        step = make_sharded_flat_train_step(cfg, proto, spec,
                                            mesh=shard_mesh, remat=remat)
    else:
        step = protocol_lib.make_flat_train_step(cfg, proto, unravel_row)

    def body(carry: TrajCarry):
        key, sk = jax.random.split(carry.key)
        k_data, k_step = jax.random.split(sk)
        batch = store.sample(k_data)
        params, metrics = step(carry.params, batch, k_step)
        return (TrajCarry(key, params, carry.net, carry.eps),
                {"metrics": metrics})

    return _maybe_instrument(body, telemetry, proto)


def _maybe_instrument(body: Callable, tele, proto, *, fleet=None) -> Callable:
    """Wrap a round body with read-only telemetry (obs.telemetry).

    The instrumentation splits along what each scalar can see, which is
    also exactly the cheap placement for each:

    * PER ROUND, inside the scan: the scalars that read transient round
      state — loss/grad_norm (the step's metrics) and the consensus
      distance (the live params). These are packed into a per-round
      ``out["telemetry"]`` prefix the scan stacks like any other output.
    * PER CHUNK, in a ``chunk_epilogue`` the ChunkRunner fuses into the
      SAME compiled program after the scan: the channel-derived columns
      (SNR, deep-fade, participation, per-round ε). The chunk already
      stacks the realized channel/mixing log (``ys["chan"]``/``ys["W"]``),
      so these evaluate ONCE, vectorized over all K rounds, instead of as
      K sequential tiny-op clusters inside the scan — measurably cheaper
      on CPU and bit-for-bit the same per-round values. On the static
      channel they collapse further, to compile-time constants broadcast
      over K. The epilogue also folds the chunk's per-round ε into the
      carry's composition-moment accumulator (one reduce per chunk).

    The wrapper splits no keys and writes no params — the realized
    trajectory is bitwise the un-instrumented one.

    Consensus is measured on the params ENTERING the round (row t is the
    state the round-t gossip step acts on). Besides being the natural
    pre-mixing quantity, this placement is what keeps telemetry cheap:
    the pre-round buffer is already live as the grad-step input, whereas
    reading the post-mix params adds a second consumer to the freshly
    written buffer and measurably (~2x) inflates the reduce inside the
    compiled scan. The post-trajectory consensus, when wanted, is one
    host-side ``consensus_distance(carry.params)`` on the final carry."""
    if tele is None:
        return body
    from repro.obs import telemetry as tele_lib

    if tele.n_fields == 0 and not tele.epsilon:
        return body
    needs_chan = (tele.snr_db or tele.deep_fade or tele.participation
                  or tele.epsilon)
    R = None if fleet is None else fleet.replicates
    worker_axis = 0 if R is None else 1
    # catalogue order puts the in-scan fields first, so the per-round
    # prefix and the epilogue's channel columns concatenate in field order
    in_fields = tuple(f for f in ("loss", "grad_norm", "consensus")
                      if getattr(tele, f))
    chan_fields = tuple(f for f in tele.fields if f not in in_fields)

    # static channel: every chan-derived scalar is the SAME every round —
    # evaluate them HERE, eagerly, so the compiled epilogue only embeds
    # the resulting constants (zero per-round work for those fields)
    static_vals: dict = {}
    static_eps = static_rdp = None
    if needs_chan and proto.channel_model != "dynamic":
        from repro.net.state import TracedChannelState
        static_chan = TracedChannelState.from_static(proto.channel())
        static_W = jnp.asarray(proto.mixing_matrix(), jnp.float32)
        static_vals = {k: jnp.asarray(v, jnp.float32) for k, v in
                       tele_lib.channel_scalars(tele, static_chan,
                                                static_W).items()}
        if tele.epsilon:
            static_eps = jnp.asarray(
                tele_lib.epsilon_round(proto, static_chan, static_W),
                jnp.float32)
            static_rdp = jnp.asarray(
                tele_lib.rdp_round(proto, static_chan, static_W),
                jnp.float32)

    def instrumented(carry: TrajCarry):
        new_carry, out = body(carry)
        if not in_fields:
            return new_carry, out
        with jax.named_scope("dwfl.telemetry"):
            vals = {}
            if tele.loss:
                vals["loss"] = out["metrics"]["loss"]
            if tele.grad_norm:
                vals["grad_norm"] = out["metrics"]["grad_norm"]
            if tele.consensus:
                vals["consensus"] = tele_lib.consensus_distance(
                    carry.params, worker_axis=worker_axis)
            cols = [jnp.asarray(vals[f], jnp.float32) for f in in_fields]
            return new_carry, dict(out, telemetry=jnp.stack(cols, axis=-1))

    @jax.named_scope("dwfl.telemetry")
    def chunk_epilogue(carry: TrajCarry, ys):
        k = jax.tree_util.tree_leaves(ys)[0].shape[0]
        lead = (k,) if R is None else (k, R)
        parts = [ys["telemetry"]] if in_fields else []
        eps = rdp = None
        acc = carry.eps
        # carry width is static per program: [4] folds composition
        # moments only, [4+A] also folds the per-order RDP ledger
        wide = acc is not None and acc.shape[-1] > 4
        if needs_chan:
            chans, Ws = ys.get("chan"), ys.get("W")
            if chans is None:                     # static: constants
                vals = {f: jnp.broadcast_to(v, lead)
                        for f, v in static_vals.items()}
                if static_eps is not None:
                    eps = jnp.broadcast_to(static_eps, lead)
                    if wide:
                        rdp = jnp.broadcast_to(static_rdp,
                                               lead + static_rdp.shape)
            else:
                def one(ch, w):
                    v = tele_lib.channel_scalars(tele, ch, w)
                    if tele.epsilon:
                        v["epsilon"] = tele_lib.epsilon_round(proto, ch, w)
                        if wide:
                            v["_rdp"] = tele_lib.rdp_round(proto, ch, w)
                    return v
                fn = jax.vmap(one) if R is None else jax.vmap(jax.vmap(one))
                vals = fn(chans, Ws)
                eps = vals.get("epsilon")
                rdp = vals.pop("_rdp", None)      # [K, A] / [K, R, A]
            if eps is not None:
                vals["epsilon"] = eps
            parts.extend(jnp.asarray(vals[f], jnp.float32)[..., None]
                         for f in chan_fields)
        if parts:
            tele_cols = (parts[0] if len(parts) == 1
                         else jnp.concatenate(parts, axis=-1))
            ys = dict(ys, telemetry=tele_cols)
        if acc is not None and eps is not None:
            e = jnp.asarray(eps, jnp.float32)
            upd = jnp.stack([e, e ** 2, e * jnp.expm1(e),
                             jnp.ones_like(e)], axis=-1)
            if wide:
                upd = jnp.concatenate(
                    [upd, jnp.asarray(rdp, jnp.float32)], axis=-1)
            carry = TrajCarry(carry.key, carry.params, carry.net,
                              acc + jnp.sum(upd, axis=0))
        return carry, ys

    instrumented.chunk_epilogue = chunk_epilogue
    return instrumented


class ChunkRunner:
    """Compile-once-per-length scan driver: ``run(carry, k)`` advances k
    rounds in ONE jitted dispatch (lax.scan over the round body, carry
    donated) and returns (carry', out) with stacked [k, ...] out leaves.

    Distinct chunk lengths compile distinct programs (k is a static scan
    length); a driver that cuts chunks at eval boundaries sees at most a
    handful of lengths (plan_chunks), each cached here."""

    def __init__(self, body: Callable, donate: bool = True):
        self._body = body
        self._donate = donate
        self._cache = {}
        # wall seconds of each length's FIRST call: trace + compile (or a
        # persistent-cache load) + enqueue — the set-up cost a run pays
        # once per chunk length, read from its ``dwfl.chunk.compile`` span
        self.compile_seconds = {}
        self._calls = 0

    def trace_counts(self):
        """{chunk_length: lifetime compilation count} over the cached scan
        programs — each distinct length legitimately compiles exactly once;
        any count above 1 is a retrace (obs.retrace_guard sums these)."""
        return {k: fn._cache_size() for k, (fn, _) in self._cache.items()}

    def program(self, k: int) -> Callable:
        """The un-jitted k-round chunk program ``carry -> (carry', ys)`` —
        scan over the body plus any attached chunk_epilogue, exactly what
        ``run`` wraps in ``jax.jit(..., donate_argnums=(0,))``. Exposed so
        repro.analysis traces/compiles the SAME program the driver ships
        rather than a reconstruction that could drift."""
        k = int(k)
        if k < 1:
            raise ValueError(f"chunk length must be >= 1, got {k}")
        body = self._body
        # telemetry (or any body wrapper) may attach a chunk_epilogue:
        # a (carry, stacked_ys) -> (carry, stacked_ys) transform fused
        # into the SAME compiled program after the scan — one
        # vectorized pass over the chunk's stacked outputs instead of
        # k per-round op clusters (see _maybe_instrument)
        post = getattr(body, "chunk_epilogue", None)

        def scan_k(c):
            c, ys = jax.lax.scan(lambda cc, _: body(cc), c, None,
                                 length=k)
            return (c, ys) if post is None else post(c, ys)

        return scan_k

    def _jit(self, k: int, carry: TrajCarry):
        """``(fn, arrays)``: the jitted k-round program taking the device
        arrays the body closes over (the data store, simulator tables) as
        arguments, ``fn(carry, arrays)``. Closed over, jit would embed them
        as constants: a copy to the host at every compile, which an
        accelerator's transfer guard refuses, and the whole dataset baked
        into the program. Host (NumPy) constants stay embedded."""
        from jax.extend.core import ClosedJaxpr, jaxpr_as_fun
        closed, out_shape = jax.make_jaxpr(self.program(k),
                                           return_shape=True)(carry)
        on_dev = [isinstance(c, jax.Array) for c in closed.consts]
        arrays = [c for c, d in zip(closed.consts, on_dev) if d]
        host = [c for c, d in zip(closed.consts, on_dev) if not d]
        out_tree = jax.tree_util.tree_structure(out_shape)

        def program(c, dev):
            dev, hst = iter(dev), iter(host)
            consts = [next(dev) if d else next(hst) for d in on_dev]
            outs = jaxpr_as_fun(ClosedJaxpr(closed.jaxpr, consts))(
                *jax.tree_util.tree_leaves(c))
            return jax.tree_util.tree_unflatten(out_tree, outs)

        return (jax.jit(program, donate_argnums=(0,) if self._donate else ()),
                arrays)

    def run(self, carry: TrajCarry, k: int) -> Tuple[TrajCarry, Any]:
        k = int(k)
        self._calls += 1
        if k not in self._cache:
            with span("dwfl.chunk.compile", rounds=k) as s:
                fn, arrays = self._cache[k] = self._jit(k, carry)
                out = fn(carry, arrays)
            self.compile_seconds[k] = s.seconds
            return out
        fn, arrays = self._cache[k]
        with span("dwfl.chunk.dispatch", rounds=k, chunk=self._calls):
            return fn(carry, arrays)

    def compiled_text(self, carry: TrajCarry, k: int) -> str:
        """HLO text of the k-round program ``run`` dispatches, compiled
        for the backend at ``carry``'s shapes, with each op's ``op_name``
        metadata (the named scopes a device trace attributes it by)."""
        fn, arrays = self._cache[int(k)]
        return fn.lower(carry, arrays).compile().as_text()

    def lowered_text(self, carry: TrajCarry, k: int) -> str:
        """StableHLO text of the k-round program ``run`` dispatches, at
        ``carry``'s shapes — where a Pallas kernel shows up as a
        ``tpu_custom_call``. Lowering neither compiles nor counts in
        trace_counts()."""
        fn, arrays = self._cache[int(k)]
        return fn.lower(carry, arrays).as_text()


def run_per_round(body: Callable, carry: TrajCarry, k: int
                  ) -> Tuple[TrajCarry, Any]:
    """Reference executor: the SAME round body, one jitted dispatch per
    round, outputs stacked on the host afterwards — the per-round-dispatch
    baseline that ChunkRunner.run(carry, k) must reproduce bitwise (and
    beat on wall-clock; benchmarks/trajectory_bench.py)."""
    step = jax.jit(body)
    outs = []
    for _ in range(int(k)):
        carry, out = step(carry)
        outs.append(out)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)
    post = getattr(body, "chunk_epilogue", None)
    if post is not None:
        carry, stacked = jax.jit(post)(carry, stacked)
    return carry, stacked


def plan_chunks(total: int, k: int, eval_every: int
                ) -> List[Tuple[int, bool]]:
    """Partition ``total`` rounds into scan chunks of at most ``k``,
    cutting at every eval boundary. Returns [(length, do_eval), ...] where
    ``do_eval`` marks chunks whose LAST round t satisfies
    t % eval_every == 0 (the legacy per-round driver's eval points, t
    counted from 0) — eval/log happen only at those chunk boundaries."""
    if total < 1:
        return []
    if k < 1:
        raise ValueError(f"chunk length must be >= 1, got {k}")
    out: List[Tuple[int, bool]] = []
    done = 0
    while done < total:
        if eval_every > 0:
            # next eval cut strictly after `done`: round t = multiple of
            # eval_every with t + 1 > done, cut after it (at t + 1)
            t_next = (done // eval_every) * eval_every
            if t_next + 1 <= done:
                t_next += eval_every
            cut = min(t_next + 1, total)
        else:
            cut = total
        n = min(k, cut - done)
        done += n
        out.append((n, eval_every > 0 and (done - 1) % eval_every == 0))
    return out


def auto_chunk(eval_every: int, coherence_rounds: Optional[int] = None,
               cap: int = 512) -> int:
    """Default chunk length: one fading coherence block when the scenario
    defines a finite one, else one eval interval — never longer than an
    eval interval (plan_chunks would cut it anyway) and bounded by ``cap``
    (compile time / stacked-output memory)."""
    k = eval_every if eval_every > 0 else cap
    if coherence_rounds and 0 < coherence_rounds <= cap:
        k = coherence_rounds
    if eval_every > 0:
        k = min(k, eval_every)
    return max(1, min(int(k), cap))


def concat_chunks(chunks):
    """Per-chunk stacked pytrees ([K_i, ...] leaves) -> one [T, ...] tree:
    the single concatenate at report time that replaces T per-round list
    appends."""
    chunks = list(chunks)
    if len(chunks) == 1:
        return chunks[0]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *chunks)


def replicate_major(stacked):
    """Fleet chunk logs are round-major ([T, R, ...] after concat_chunks);
    the batched accounting (privacy.epsilon_trajectory_batched /
    fleet_epsilon_report) wants replicate-major [R, T, ...]."""
    return jax.tree_util.tree_map(
        lambda a: jnp.swapaxes(a, 0, 1), stacked)
