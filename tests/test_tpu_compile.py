"""Compile rehearsals for a TPU v5e: the main path's mixing rounds, compiled
at the paper's width for a described (not attached) chip.

Nothing runs: each case lowers and compiles one round for the first device
of a described ``v5e:2x2`` topology, which is what the chip's compiler
would accept or refuse (unsupported casts, blocks the TPU lowering cannot
tile, VMEM over the kernel's limit, HBM over the chip's). The topology is
described inside a fixture, never while the module is imported, and the
persistent compilation cache is off around the compiles (an entry compiled
for a described chip cannot be read back without one).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import exchange
from repro.kernels.dp_mix import dp_mix as K
from repro.kernels.dp_mix import ops
from repro.net.sparse import SparseW
from repro.shard.layout import ShardLayout

D_PAPER = 855_050       # dwfl-paper: MLP 3072-256-256-10, params per worker
D_SPARSE = 150_346      # the same MLP at hidden 48 (chip_smoke.py phase d)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def S(topo):
    """``S(shape, dtype=f32)``: an argument placed on one described chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _hlo(fn, *args):
    """Text of ``fn`` compiled for the described chip."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _round_args(S, N, d, lead=()):
    """(p, g, seed, W, amp, c, sigma_m) of a dense round."""
    return (S(lead + (N, d)), S(lead + (N, d)), S(lead, jnp.int32),
            S(lead + (N, N)), S(lead + (N,)), S(lead), S(lead))


@pytest.mark.parametrize("noisy", [True, False], ids=["noisy", "gossip"])
@pytest.mark.parametrize("N", [10, 30])
def test_dense_round_compiles_at_paper_width(S, N, noisy):
    def step(p, g, seed, W, amp, c, sm):
        return ops.dp_mix_round(p, g, seed, W, amp, c, sm, gamma=0.01,
                                eta=0.4, noisy=noisy, impl="pallas")
    assert "tpu_custom_call" in _hlo(step, *_round_args(S, N, D_PAPER))


def _shapes(text):
    """{instruction name: its result shape} of an HLO module's text."""
    out = {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])", ln)
        if m:
            out[m.group(1)] = m.group(2)
    return out


@pytest.mark.parametrize("noisy", [True, False], ids=["noisy", "gossip"])
def test_dense_round_reads_buffer_in_place(S, noisy):
    """At the paper's buffer the kernel takes p and g as they are, at the
    tile width ops._pallas_block_d picks: the custom call's operands and
    result are the [10, 855050] buffer (with the kernel's replicate axis),
    nothing pads that buffer around it, and a caller that gives up p (as
    the scan body does) gets the update in p's buffer, with no copy."""
    N = 10

    def step(p, g, seed, W, amp, c, sm):
        return ops.dp_mix_round(p, g, seed, W, amp, c, sm, gamma=0.01,
                                eta=0.4, noisy=noisy, impl="pallas")
    text = jax.jit(step, donate_argnums=0).lower(
        *_round_args(S, N, D_PAPER)).compile().as_text()
    shapes = _shapes(text)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert len(calls) == 1
    buf = {f"f32[{N},{D_PAPER}]", f"f32[1,{N},{D_PAPER}]"}
    operands = re.findall(r"%([\w.\-]+)",
                          calls[0].split(" custom-call(", 1)[1].split(")")[0])
    assert shapes[re.match(r"\s*(?:ROOT )?%(\S+)", calls[0]).group(1)] in buf
    assert sum(shapes[o] in buf for o in operands) == 2, operands
    around = [ln for ln in text.splitlines()
              if re.search(r" (pad|copy|copy-start)\(", ln)
              and str(D_PAPER) in ln.split("(")[0]]
    assert not around, around
    assert ops._pallas_block_d(16, D_PAPER, noisy) == K.BLOCK_D_CAP


def test_fleet_round_compiles_at_r8(S):
    """The fleet vmaps the round over its replicates; the kernel takes the
    replicate axis as its own grid axis."""
    def step(p, g, seed, W, amp, c, sm):
        return ops.dp_mix_round(p, g, seed, W, amp, c, sm, gamma=0.01,
                                eta=0.4, impl="pallas")
    assert "tpu_custom_call" in _hlo(jax.vmap(step),
                                     *_round_args(S, 10, D_PAPER, (8,)))


def test_model_shard_window_compiles_at_s4(S):
    """One shard's window of a 4-way model-axis sharding: its own columns,
    a traced global offset, the canonical counter stride."""
    layout = ShardLayout(D_PAPER, 4)

    def step(p, g, seed, W, amp, c, sm, col0):
        return ops.dp_mix_round(p, g, seed, W, amp, c, sm, gamma=0.01,
                                eta=0.4, impl="pallas", col0=col0,
                                counter_width=layout.counter_width)
    args = _round_args(S, 10, layout.shard_width) + (S((), jnp.int32),)
    assert "tpu_custom_call" in _hlo(step, *args)


def test_sparse_round_compiles_at_n1024(S):
    """The sparse neighbor-list round is an XLA gather on the chip: it
    compiles without a Pallas kernel."""
    N, k = 1024, 10

    def step(p, g, seed, sw, amp, c, sm):
        return ops.dp_mix_round_sparse(p, g, seed, sw, amp, c, sm,
                                       gamma=0.01, eta=0.4)
    sw = SparseW(S((N, k), jnp.int32), S((N, k)), S((N,)))
    text = _hlo(step, S((N, D_SPARSE)), S((N, D_SPARSE)), S((), jnp.int32),
                sw, S((N,)), S(()), S(()))
    assert "tpu_custom_call" not in text and "gather" in text


@pytest.mark.parametrize("past", [False, True], ids=["at", "past"])
@pytest.mark.parametrize("noisy", [True, False], ids=["noisy", "gossip"])
def test_dense_vmem_ceiling_matches_compiler(S, noisy, past):
    """dp_mix.MAX_NP against the compiler: the kernel compiles at the
    ceiling with 512-column tiles, and one sublane step past it the
    compiler runs out of VMEM (the round refuses that N before compiling —
    tests/test_kernels.py — so this calls the kernel directly). Four
    column tiles, so the tiles are double-buffered as in a real round."""
    Np = K.MAX_NP[noisy] + (K.SUBLANES if past else 0)
    bd = K.CEILING_BLOCK_D

    def kernel(p, g, W, vec, seed, scal):
        return K.dp_mix_2d(p, g, seed, seed, scal, vec, vec, vec, vec, W,
                           gamma=0.01, eta=0.4, noisy=noisy, block_d=bd,
                           interpret=False)
    args = (S((Np, 4 * bd)), S((Np, 4 * bd)), S((Np, Np)), S((Np,)),
            S((1,), jnp.int32), S((2,)))
    if past:
        with pytest.raises(Exception, match="memory space vmem"):
            _hlo(kernel, *args)
    else:
        assert "tpu_custom_call" in _hlo(kernel, *args)


@pytest.mark.parametrize("scheme", ["orthogonal", "centralized"])
def test_baseline_flat_round_mixes_at_f32_precision(S, scheme):
    """The baselines' exchange on the flat [10, d] buffer at the paper's
    width (their specs have no fused plan, so it runs as XLA ops): it
    cancels noise terms against the signal it rides on, so it compiles
    with no bf16 convert and every dot at full f32 precision, not TPU's
    default single bf16 pass."""
    from repro.core.protocol import ProtocolConfig
    proto = ProtocolConfig(scheme=scheme, n_workers=10, target_epsilon=1.0)
    spec = exchange.resolve_spec(proto)
    assert spec.plan is None
    chan = proto.channel()

    def step(flat, key):
        return spec.run(flat, jax.random.split(key, 3), chan, proto)
    text = _hlo(step, S((10, D_PAPER)), S((2,), jnp.uint32))
    assert "bf16" not in text
    dots = [ln for ln in text.splitlines() if " dot(" in ln
            or "convolution(" in ln]
    assert dots and all("operand_precision={highest,highest}" in ln
                        for ln in dots), dots
