"""Checkpointing the persistent flat DWFL buffer (ISSUE 5 satellite).

The invariant: a mid-trajectory checkpoint (flat buffer + net state +
PRNG carry key, checkpoint.save_flat) restores into a run that is
BITWISE-identical on CPU to the uninterrupted one — whatever shard layout
wrote the checkpoint and whatever layout restores it, because the stored
form is the canonical [.., d] view plus layout metadata and the sharded
round realizes the identical noise stream (repro.shard)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_flat, save, save_flat
from repro.core import exchange as X
from repro.core import protocol as P
from repro.core import trajectory as TJ
from repro.data.device import ClassificationStore

W, DIM, BATCH, NDATA = 5, 12, 4, 160


def _cfg():
    from repro.configs.registry import get_arch
    return get_arch("dwfl-paper").replace(d_model=8)


def _proto(**kw):
    base = dict(scheme="dwfl", n_workers=W, gamma=0.05, eta=0.4, clip=1.0,
                p_dbm=60.0, sigma=0.7, sigma_m=0.5)
    base.update(kw)
    return P.ProtocolConfig(**base)


def _wp(cfg):
    import repro.models.mlp as mlp
    params = mlp.init(jax.random.PRNGKey(0), cfg, input_dim=DIM)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), params)


def _store(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(NDATA, DIM)).astype(np.float32)
    y = rng.integers(0, 10, NDATA).astype(np.int32)
    parts = [np.arange(w, NDATA, W) for w in range(W)]
    return ClassificationStore.build(x, y, parts, BATCH)


def _dynamic_setup(n_shards, max_chunk_cols=None):
    cfg = _cfg()
    proto = _proto(channel_model="dynamic", scenario="iot_dense")
    sim = proto.simulator()
    wp = _wp(cfg)
    spec = X.make_flat_spec(wp, n_shards=n_shards,
                            max_chunk_cols=max_chunk_cols) \
        if n_shards > 1 else X.make_flat_spec(wp)
    body = TJ.make_round_body(cfg, proto, _store(), sim=sim, spec=spec)
    net0 = sim.init(jax.random.PRNGKey(4))
    carry0 = TJ.TrajCarry(jax.random.PRNGKey(5), spec.flatten(wp), net0)
    return spec, body, carry0


def _orthogonal_setup():
    """The static orthogonal baseline: its exchange runs on the buffer as
    one leaf, and the carry holds no net state."""
    cfg = _cfg()
    wp = _wp(cfg)
    spec = X.make_flat_spec(wp)
    body = TJ.make_round_body(cfg, _proto(scheme="orthogonal"), _store(),
                              spec=spec)
    return spec, body, TJ.TrajCarry(jax.random.PRNGKey(5), spec.flatten(wp))


def _run(body, carry, k):
    runner = TJ.ChunkRunner(body, donate=False)
    carry, _ = runner.run(carry, k)
    return carry


@pytest.mark.parametrize("case", ["unsharded", "sharded", "orthogonal"])
def test_mid_trajectory_checkpoint_resumes_bitwise(case, tmp_path):
    """Run 6 rounds straight; run 3, checkpoint (buffer + net state, where
    the round has one, + PRNG key), restore into a FRESH spec, run 3
    more: final buffer, net state and carry key are bitwise-identical.
    Dynamic DWFL unsharded and model-sharded, and the static orthogonal
    baseline."""
    setup = (_orthogonal_setup if case == "orthogonal" else
             lambda: _dynamic_setup(2 if case == "sharded" else 1))
    spec, body, carry0 = setup()
    ref = _run(body, carry0, 6)

    mid = _run(body, carry0, 3)
    kept = {"key": mid.key}
    if mid.net is not None:
        kept["net"] = mid.net
    path = os.path.join(tmp_path, "ckpt")
    save_flat(path, mid.params, spec, step=3, state=kept,
              metadata={"test": "mid-trajectory"})

    spec2, body2, carry_fresh = setup()
    flat, state, manifest = restore_flat(path, spec2, state_like=kept)
    assert manifest["step"] == 3
    assert manifest["metadata"]["flat_layout"]["d"] == spec2.d
    net = state.get("net")
    got = _run(body2, TJ.TrajCarry(
        jnp.asarray(state["key"]), flat,
        None if net is None else jax.tree_util.tree_map(jnp.asarray, net)),
        3)
    np.testing.assert_array_equal(np.asarray(spec2.unpad(got.params)),
                                  np.asarray(spec.unpad(ref.params)))
    np.testing.assert_array_equal(np.asarray(got.key), np.asarray(ref.key))
    for a, b in zip(jax.tree_util.tree_leaves(got.net),
                    jax.tree_util.tree_leaves(ref.net)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_relayout_across_shard_counts(tmp_path):
    """A checkpoint written under S=2 restores under S=1 and S=4 and all
    three continued runs agree bitwise on the canonical columns — the
    layout metadata makes shard count a pure execution detail."""
    spec2, body2, carry2 = _dynamic_setup(2)
    mid = _run(body2, carry2, 3)
    path = os.path.join(tmp_path, "relayout")
    save_flat(path, mid.params, spec2, step=3,
              state={"key": mid.key, "net": mid.net})
    assert "shard" in __import__("json").load(
        open(path + ".json"))["metadata"]["flat_layout"]

    finals = {}
    for S in (1, 2, 4):
        spec, body, _ = _dynamic_setup(S)
        flat, state, _m = restore_flat(
            path, spec, state_like={"key": mid.key, "net": mid.net})
        assert flat.shape[-1] == spec.width
        got = _run(body, TJ.TrajCarry(jnp.asarray(state["key"]), flat,
                                      jax.tree_util.tree_map(
                                          jnp.asarray, state["net"])), 3)
        finals[S] = np.asarray(spec.unpad(got.params))
    np.testing.assert_array_equal(finals[1], finals[2])
    np.testing.assert_array_equal(finals[1], finals[4])


def test_checkpoint_relayout_across_chunk_budgets(tmp_path):
    """The grad-pass chunk budget (max_chunk_cols) is a pure execution
    detail: a checkpoint written under one budget restores and continues
    bitwise under any other budget or shard count, and the manifest's
    flat_layout records the writer's chunk plan."""
    import json

    spec_w, body_w, carry_w = _dynamic_setup(2, max_chunk_cols=64)
    ref = _run(body_w, carry_w, 6)
    mid = _run(body_w, carry_w, 3)
    path = os.path.join(tmp_path, "budget")
    save_flat(path, mid.params, spec_w, step=3,
              state={"key": mid.key, "net": mid.net})

    # the chunk plan round-trips through the manifest metadata
    recorded = json.load(open(path + ".json"))
    plan_meta = recorded["metadata"]["flat_layout"]["chunk_plan"]
    assert plan_meta == spec_w.chunk_plan.to_meta()
    assert plan_meta["max_chunk_cols"] == 64
    assert plan_meta["n_chunks"] == len(spec_w.chunk_plan.chunks)

    ref_cols = np.asarray(spec_w.unpad(ref.params))
    for S, cap in ((2, None), (2, 13), (4, 200)):
        spec, body, _ = _dynamic_setup(S, max_chunk_cols=cap)
        flat, state, _m = restore_flat(
            path, spec, state_like={"key": mid.key, "net": mid.net})
        got = _run(body, TJ.TrajCarry(jnp.asarray(state["key"]), flat,
                                      jax.tree_util.tree_map(
                                          jnp.asarray, state["net"])), 3)
        np.testing.assert_array_equal(np.asarray(spec.unpad(got.params)),
                                      ref_cols)


def test_restore_flat_rejects_mismatched_contract(tmp_path):
    cfg = _cfg()
    wp = _wp(cfg)
    spec = X.make_flat_spec(wp, n_shards=2)
    path = os.path.join(tmp_path, "ck")
    save_flat(path, spec.flatten(wp), spec)
    other = X.make_flat_spec(
        jax.tree_util.tree_map(
            lambda a: jnp.concatenate([a, a], axis=-1), wp))
    with pytest.raises(ValueError):
        restore_flat(path, other)
    # same per-worker d, different worker count: descriptive rejection
    wp6 = jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a, a[:1]], axis=0), wp)
    with pytest.raises(ValueError, match="lead shape"):
        restore_flat(path, X.make_flat_spec(wp6))
    # a drifted shard record trips the layout guard
    import json as _json
    man = _json.load(open(path + ".json"))
    man["metadata"]["flat_layout"]["shard"]["shard_width"] = 64
    _json.dump(man, open(path + ".json", "w"))
    with pytest.raises(ValueError, match="layout metadata mismatch"):
        restore_flat(path, spec)


def test_save_flat_without_state_and_plain_save_coexist(tmp_path):
    """save_flat with no extra state restores (state is None); the
    generic save() API is untouched by the flat layer."""
    cfg = _cfg()
    wp = _wp(cfg)
    spec = X.make_flat_spec(wp)
    path = os.path.join(tmp_path, "plain")
    save_flat(path, spec.flatten(wp), spec, step=7)
    flat, state, manifest = restore_flat(path, spec)
    assert state is None and manifest["step"] == 7
    np.testing.assert_array_equal(np.asarray(flat),
                                  np.asarray(spec.flatten(wp)))
    save(os.path.join(tmp_path, "tree"), wp, step=1)
