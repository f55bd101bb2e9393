"""DeepSeek-V2-Lite's layers against the plain reference of the benchmark
(``bench/reference/deepseek_v2_lite.py``) at a CPU size: the loss and every
gradient leaf on seeded weights, YaRN's frequencies and score scale at the
published rope settings, the expert shares of one MoE layer adding up
to the uncut layer, and the model-sharded mesh round against the
unsharded flat step.

The CPU multiplies float32 operands in float32, so program and reference
(its products at ``float32``) differ by the order of float32 accumulation
alone; each tolerance against the reference is also checked to fail
against it with bfloat16 operands, the precision one step down."""
import math
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import deepseek_v2_lite
from repro.models import mla, moe
from repro.models import model as M

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench.reference import deepseek_v2_lite as R  # noqa: E402



def tiny(held=0, first=0):
    """The published architecture at test widths: 1 dense + 1 MoE layer,
    8 routed experts (``held`` of them here), YaRN at its settings."""
    return deepseek_v2_lite.CONFIG.replace(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=96,
        moe_d_ff=32, num_experts=8, num_experts_per_tok=3, vocab_size=128,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=16, experts_held=held, first_expert=first)


def dims_of(cfg) -> R.Dims:
    """The reference's sizes for a program config."""
    return R.Dims(
        d=cfg.d_model, heads=cfg.num_heads, kv_rank=cfg.kv_lora_rank,
        nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
        v=cfg.v_head_dim, ff=cfg.d_ff, expert_ff=cfg.moe_d_ff,
        router=cfg.num_experts, held=cfg.experts_local,
        first=cfg.first_expert, topk=cfg.num_experts_per_tok,
        shared=cfg.num_shared_experts, dense=cfg.first_dense_layers,
        moe=cfg.num_layers - cfg.first_dense_layers, vocab=cfg.vocab_size,
        eps=1e-6, theta=cfg.rope_theta, rope_factor=cfg.rope_factor,
        rope_orig=cfg.rope_original_max_pos, beta_fast=cfg.rope_beta_fast,
        beta_slow=cfg.rope_beta_slow, mscale=cfg.rope_mscale,
        mscale_all=cfg.rope_mscale_all_dim, norm_topk=cfg.norm_topk_prob,
        routed_scale=cfg.routed_scaling_factor, alpha=cfg.router_aux_weight)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("held", [0, 2])
def test_loss_and_gradients_match_the_reference(held):
    cfg = tiny(held=held, first=4 if held else 0)
    m = dims_of(cfg)
    key = jax.random.PRNGKey(3)
    params, ref_params = jax.jit(
        lambda k: (M.init_params(k, cfg), R.init_params(k, m)))(key)
    # the init law, restated: the same weights, leaf for leaf
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(ref_params))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0,
                                cfg.vocab_size)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: M.loss_fn(p, {"tokens": tokens}, cfg)))(params)
    weight = jnp.ones((31,), jnp.float32)
    def ref(kind):
        return jax.jit(jax.value_and_grad(
            lambda p: R.loss_fn(p, tokens, weight, m, kind)))(ref_params)
    want, want_g = ref("float32")
    low, low_g = ref("bfloat16")
    # float32 on both sides: the sums associate differently, a few ULP of
    # the loss and ~1e-6 of a leaf's norm; bfloat16 operands move the loss
    # by ~1e-3 and each leaf by ~1e-2
    assert _rel(loss, want) < 2e-6 < _rel(low, want)
    for a, b, c in zip(jax.tree_util.tree_leaves(grads),
                       jax.tree_util.tree_leaves(want_g),
                       jax.tree_util.tree_leaves(low_g)):
        if np.linalg.norm(np.asarray(b)) == 0:
            continue       # the norm scales' of unrouted experts: none here
        assert _rel(a, b) < 1e-4, _rel(a, b)
    assert max(_rel(c, b) for b, c in zip(jax.tree_util.tree_leaves(want_g),
                                          jax.tree_util.tree_leaves(low_g))
               ) > 1e-3


def test_yarn_at_the_published_rope_settings():
    cfg = deepseek_v2_lite.CONFIG
    f = np.asarray(mla.rope_inv_freq(cfg), np.float64)
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # c(r) = 64 ln(4096 / (2 pi r)) / (2 ln 1e4): floor(c(32)) = 10,
    # ceil(c(1)) = 23; below 10 the frequency is kept, from 23 on it is
    # interpolated by the factor 40, linearly between
    c = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))
    assert (math.floor(c(32)), math.ceil(c(1))) == (10, 23)
    keep = 1 - np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(f, extra / 40 * (1 - keep) + extra * keep,
                               rtol=2e-6)      # float32 powers
    g = 0.1 * 0.707 * math.log(40) + 1
    assert mla.score_scale(cfg) == pytest.approx(g * g / math.sqrt(192),
                                                 rel=1e-12)
    m = dims_of(cfg)
    np.testing.assert_array_equal(np.asarray(R.rope_freqs(m)),
                                  np.asarray(mla.rope_inv_freq(cfg)))
    assert R.score_scale(m) == mla.score_scale(cfg)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips holding 2 of the 8 experts each: their routed parts
    summed, with the shared experts and the balance loss counted once, give
    the layer that holds all 8."""
    full = tiny()
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, full.d_model))
    p_full = moe.moe_init(key, full, jnp.float32)
    y_full, aux_full = jax.jit(lambda p: moe.moe_apply(p, x, full))(p_full)
    parts, shared = [], None
    for first in range(0, 8, 2):
        share = tiny(held=2, first=first)
        p = moe.moe_init(key, share, jnp.float32)
        # a share holds exactly the uncut layer's experts
        np.testing.assert_array_equal(
            np.asarray(p["w_up"]), np.asarray(p_full["w_up"][first:first + 2]))
        y, aux = jax.jit(lambda p: moe.routed(p, x, share))(p)
        parts.append(y)
        assert float(aux) == pytest.approx(float(aux_full), rel=1e-6)
        shared = moe.L.mlp_apply(p["shared"], x, share)
    got = sum(parts) + shared
    # float32: the shares' sums associate differently from the whole's
    np.testing.assert_allclose(np.asarray(got), np.asarray(y_full),
                               rtol=1e-5, atol=1e-6)
    assert _rel(parts[0] + shared, y_full) > 0.1     # one share is not all


def _poisoned_ragged_dot(real):
    """``real`` with every row past the last group set to NaN in its output
    and in its input's gradient, as a grouped product that leaves them
    unwritten may hold anything there."""
    def fill(y, sizes):
        rows = jnp.arange(y.shape[0]) < jnp.sum(sizes)
        return jnp.where(rows[:, None], y, jnp.nan)

    @jax.custom_vjp
    def dot(a, w, sizes):
        return fill(real(a, w, sizes), sizes)

    def fwd(a, w, sizes):
        return dot(a, w, sizes), (a, w, sizes)

    def bwd(res, ct):
        a, w, sizes = res
        da, dw = jax.vjp(lambda a, w: real(a, w, sizes), a, w)[1](ct)
        return fill(da, sizes), dw, None
    dot.defvjp(fwd, bwd)
    return lambda a, w, sizes, **kw: dot(a, w, sizes)


def _ragged_batch_dims(jaxpr):
    """The batch dimensions of every grouped product in ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "ragged_dot_general":
            nums = eqn.params["ragged_dot_dimension_numbers"]
            yield nums.dot_dimension_numbers[1]
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _ragged_batch_dims(j)


@pytest.mark.parametrize("workers", [0, 2])
def test_grouped_products_never_read_the_rows_past_the_last_group(
        workers, monkeypatch):
    """A share holding 2 of 8 experts sorts the pairs held elsewhere past
    its last group. With the grouped product's rows there poisoned, the
    routed output and every gradient still equal the reference's dense
    layer over the held experts; ``workers`` > 0 runs the layer under
    ``jax.vmap``, one model per worker, as the gradient pass does."""
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _poisoned_ragged_dot(jax.lax.ragged_dot))
    jax.clear_caches()
    cfg = tiny(held=2, first=4)
    m = dims_of(cfg)
    n = max(workers, 1)
    p = jax.vmap(lambda k: moe.moe_init(k, cfg, jnp.float32))(
        jax.random.split(jax.random.PRNGKey(7), n))
    x = jax.random.normal(jax.random.PRNGKey(8), (n, 2, 32, cfg.d_model))
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def program(p, x):
        return jnp.sum(moe.routed(p, x, cfg)[0] * ct[0])

    def reference(p, x):
        y, _ = R.moe({**p, "shared": None}, x, m._replace(shared=0),
                     "float32")
        return jnp.sum(y * ct[0])
    if workers:
        batched = jax.vmap(jax.value_and_grad(program, (0, 1)))
        # the TPU's grouped product takes no batch dimension: none is asked
        for dims in _ragged_batch_dims(jax.make_jaxpr(batched)(p, x).jaxpr):
            assert dims == ((), ()), dims
        got = jax.jit(batched)(p, x)
        want = jax.jit(jax.vmap(jax.value_and_grad(reference, (0, 1))))(p, x)
    else:
        one = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
        got = jax.jit(jax.value_and_grad(program, (0, 1)))(one(p), x[0])
        want = jax.jit(jax.value_and_grad(reference, (0, 1)))(one(p), x[0])
    # float32 on both sides: the grouped and the dense sums associate
    # differently, ~1e-7 of each norm; a NaN anywhere fails
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(np.asarray(a)))
        if np.linalg.norm(np.asarray(b)) > 0:
            assert _rel(a, b) < 1e-5, _rel(a, b)


def test_latent_attention_is_training_only():
    cfg = tiny()
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    with pytest.raises(NotImplementedError, match="training mode only"):
        M.prefill(params, {"tokens": jnp.zeros((1, 8), jnp.int32)}, cfg)


def test_the_cut_keeps_widths_and_refuses_below_the_floors():
    c = deepseek_v2_lite.CONFIG
    ep8 = deepseek_v2_lite.EP8
    assert (ep8.num_layers, ep8.experts_local, ep8.vocab_size) == (5, 8, 12800)
    for f in ("d_model", "d_ff", "moe_d_ff", "num_heads", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "num_experts", "num_experts_per_tok", "num_shared_experts"):
        assert getattr(ep8, f) == getattr(c, f), f
    for bad in ({"moe_layers": 3, "experts_held": 8, "vocab_size": 12800},
                {"moe_layers": 4, "experts_held": 7, "vocab_size": 12800},
                {"moe_layers": 4, "experts_held": 8, "vocab_size": 12799},
                {"moe_layers": 4, "experts_held": 8, "vocab_size": 12800,
                 "first_expert": 57}):
        with pytest.raises(ValueError):
            c.cut(**bad)


_MESH_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, numpy as np
    from repro.configs import deepseek_v2_lite
    from repro.core import exchange as X
    from repro.core import protocol as P
    from repro.launch import mesh as mesh_lib
    from repro.launch.shardings import flat_buffer_sharding
    from repro.shard import make_sharded_flat_train_step

    cap = None if sys.argv[1] == "none" else int(sys.argv[1])
    W, S = 4, 4
    cfg = deepseek_v2_lite.EP8.replace(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=96,
        moe_d_ff=32, num_experts=8, num_experts_per_tok=3, vocab_size=128,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=16, experts_held=2, first_expert=2)
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=W, gamma=0.05,
                             eta=0.4, clip=1.0, p_dbm=60.0, sigma=0.7,
                             sigma_m=0.5)
    wp = P.init_worker_params(jax.random.PRNGKey(0), cfg, W)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                          (W, 1, 32), 0, cfg.vocab_size)}
    key = jax.random.PRNGKey(42)
    spec0 = X.make_flat_spec(wp)
    f1, m1 = jax.jit(P.make_flat_train_step(cfg, proto, spec0.unravel_row))(
        spec0.flatten(wp), batch, key)

    mesh = mesh_lib.make_shard_mesh(S)
    spec = X.make_flat_spec(wp, n_shards=S, max_chunk_cols=cap)
    sw = spec.layout.shard_width
    offs, sizes = spec.leaf_offsets(), spec.leaf_sizes()
    # a leaf that two windows share, and (with a budget) a segment cut
    # inside a leaf's span of one window
    assert any(o // sw != (o + n - 1) // sw for o, n in zip(offs, sizes))
    ends = {e % sw for o, n in zip(offs, sizes) for e in (o, o + n)}
    cuts = {b for _, b in spec.chunk_plan.exec_segments()}
    assert (cap is not None) == bool(cuts - ends - {sw})
    flat = jax.device_put(spec.flatten(wp), flat_buffer_sharding(spec, mesh))
    step = jax.jit(make_sharded_flat_train_step(cfg, proto, spec, mesh=mesh,
                                                remat=True))
    f, m = step(flat, batch, key)
    # the clip norm's rounding, carried through the mix (measured: 1 ULP
    # of the largest entry, 1.9e-6 at 24.7; loss and mean norm ~1e-7)
    f1 = np.asarray(f1)
    np.testing.assert_allclose(
        np.asarray(spec.unpad(f)), f1, rtol=1e-6,
        atol=4 * float(np.spacing(np.abs(f1).max())))
    for k in m1:
        np.testing.assert_allclose(np.asarray(m[k]), np.asarray(m1[k]),
                                   rtol=1e-6)
    print("DSV2_MESH_OK")
""")


@pytest.mark.parametrize("cap", [None, 1000], ids=["unbounded", "budget"])
def test_the_model_sharded_round_agrees_with_the_flat_step(cap):
    """The cut at test widths, 4 workers on a model=4 host-device mesh
    (one a device, as the benchmark's cell), under the layer checkpoint:
    the mesh round, which assembles each worker's leaves from the
    ``all_to_all`` pieces and differentiates and clips on them, agrees
    with the unsharded flat step to float32 rounding — with a leaf that
    spans two windows, and with a budget that cuts segments inside a
    leaf."""
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = (str(root / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _MESH_SCRIPT,
                          "none" if cap is None else str(cap)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "DSV2_MESH_OK" in res.stdout
