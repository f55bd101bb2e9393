"""repro.analysis: each checker fires on its adversarial fixture, and the
shipped driver programs (registry: static/dynamic/fleet × tree/flat,
sharded round) are clean — zero findings at WARNING or above. INFO
findings are allowed by policy: they record expected-by-construction
facts (the static path's baked-in channel realization, the reserved
``k_m``/``k_x`` slots of the uniform exchange key layout)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is optional offline (see tests/_hypo_fallback.py)
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypo_fallback import given, settings, st

from repro import obs
from repro.analysis import (Finding, PROGRAMS, Severity, analyze_program,
                            aval_signature, build_programs, check_donation,
                            check_dtype_discipline, check_host_sync,
                            check_key_discipline, check_weak_closure,
                            lint_source, report_json)
from repro.core import exchange as X_lib
from repro.core import protocol as P


def _errors(findings):
    return [f for f in findings if f.severity == Severity.ERROR]


# ---------------------------------------------------------------------------
# key-discipline: adversarial fixtures
# ---------------------------------------------------------------------------


def test_key_checker_fires_on_double_consumption():
    def bad(key):
        a = jax.random.normal(key, (4,))
        b = jax.random.normal(key, (4,))     # same key consumed twice
        return a + b

    fs = check_key_discipline(jax.make_jaxpr(bad)(jax.random.key(0)), "fix")
    errs = _errors(fs)
    assert errs and "reused" in errs[0].message


def test_key_checker_fires_on_split_and_consume():
    def bad(key):
        _, k2 = jax.random.split(key)
        x = jax.random.normal(key, (2,))     # key BOTH split and consumed
        return x + jax.random.normal(k2, (2,))

    assert _errors(check_key_discipline(jax.make_jaxpr(bad)(
        jax.random.key(0)), "fix"))


def test_key_checker_fires_on_bundle_reuse():
    def bad(key):
        ks = jax.random.split(key, 4)
        a = jax.vmap(lambda k: jax.random.normal(k, ()))(ks)
        b = jax.vmap(lambda k: jax.random.normal(k, ()))(ks)  # bundle x2
        return a + b

    assert _errors(check_key_discipline(jax.make_jaxpr(bad)(
        jax.random.key(0)), "fix"))


def test_key_checker_fires_on_key_constant():
    k0 = jax.random.key(7)

    def bad(x):
        return x + jax.random.normal(k0, x.shape)   # closed-over key

    errs = _errors(check_key_discipline(
        jax.make_jaxpr(bad)(jnp.ones(3, jnp.float32)), "fix"))
    assert errs and "constant" in errs[0].message


def test_key_checker_clean_on_proper_discipline():
    # the repo's scan-carry pattern: split once per iteration, each
    # half consumed exactly once — including disjoint bundle slices
    def body(key, _):
        key, sk = jax.random.split(key)
        k1, k2 = jax.random.split(sk)
        return key, (jax.random.normal(k1, (2,)),
                     jax.random.uniform(k2, (2,)))

    def good(key):
        return jax.lax.scan(body, key, None, length=3)

    fs = check_key_discipline(jax.make_jaxpr(good)(jax.random.key(0)), "fix")
    assert not _errors(fs)


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------


def _donated_hlo(fn, *args):
    return (jax.jit(fn, donate_argnums=0)
            .lower(*args).compile().as_text())


def test_donation_checker_fires_on_dead_donation():
    # donated [8,16] input but scalar output: nothing to alias into
    x = jnp.ones((8, 16), jnp.float32)
    hlo = _donated_hlo(lambda x: x.sum(), x)
    errs = _errors(check_donation(
        hlo, [("carry.x", aval_signature(np.float32, (8, 16)))], "fix"))
    assert errs and "dead" in errs[0].message


def test_donation_checker_clean_on_real_aliasing():
    x = jnp.ones((8, 16), jnp.float32)
    hlo = _donated_hlo(lambda x: x + 1.0, x)
    fs = check_donation(
        hlo, [("carry.x", aval_signature(np.float32, (8, 16)))], "fix")
    assert not _errors(fs)
    assert any(f.severity == Severity.INFO for f in fs)


# ---------------------------------------------------------------------------
# weak-closure detector
# ---------------------------------------------------------------------------


def _traced_with_const(const):
    return jax.make_jaxpr(lambda x: x * const)(jnp.ones(6, jnp.float32))


def test_weak_closure_fires_on_dynamic_baked_realization():
    h = jnp.asarray(np.random.default_rng(0).rayleigh(size=6), jnp.float32)
    errs = _errors(check_weak_closure(_traced_with_const(h), 6,
                                      dynamic=True, program="fix"))
    assert errs and "traced operand" in errs[0].message


def test_weak_closure_info_on_static_path():
    h = jnp.asarray(np.random.default_rng(0).rayleigh(size=6), jnp.float32)
    fs = check_weak_closure(_traced_with_const(h), 6, dynamic=False,
                            program="fix")
    assert not _errors(fs)
    assert any(f.severity == Severity.INFO for f in fs)


def test_weak_closure_ignores_structural_constants():
    # identity / complete-graph mixing and uniform scales: <= 3 distinct
    # values, worker-shaped, but NOT realizations
    for const in (jnp.ones(6, jnp.float32),
                  jnp.eye(6, dtype=jnp.float32),
                  jnp.full((6, 6), 1 / 5, jnp.float32)):
        cj = jax.make_jaxpr(lambda x: (x * const).sum())(
            jnp.ones(6, jnp.float32))
        assert not check_weak_closure(cj, 6, dynamic=True, program="fix")


# ---------------------------------------------------------------------------
# dtype discipline
# ---------------------------------------------------------------------------


def test_dtype_checker_fires_on_f64():
    with jax.enable_x64(True):
        cj = jax.make_jaxpr(lambda x: x * 2.0)(np.ones(3, np.float64))
    errs = _errors(check_dtype_discipline(cj, "fix"))
    assert errs and "f64" in " ".join(f.message for f in errs)


def test_dtype_checker_clean_on_f32():
    cj = jax.make_jaxpr(lambda x: jnp.sin(x) * 2.0)(jnp.ones(3, jnp.float32))
    assert not check_dtype_discipline(cj, "fix")


# ---------------------------------------------------------------------------
# host-sync guard
# ---------------------------------------------------------------------------


def test_host_sync_fires_on_callback_in_scan():
    def body(c, _):
        jax.debug.print("c={c}", c=c)
        return c + 1, c

    cj = jax.make_jaxpr(
        lambda c: jax.lax.scan(body, c, None, length=3))(jnp.float32(0))
    errs = _errors(check_host_sync(cj, "fix"))
    assert errs and "scan" in errs[0].message


def test_host_sync_clean_on_pure_scan():
    cj = jax.make_jaxpr(lambda c: jax.lax.scan(
        lambda c, _: (c + 1, c), c, None, length=3))(jnp.float32(0))
    assert not check_host_sync(cj, "fix")


# ---------------------------------------------------------------------------
# AST source lint
# ---------------------------------------------------------------------------


def test_source_lint_fires_on_real_print_only(tmp_path):
    (tmp_path / "mod.py").write_text("def f():\n    print('x')\n")
    # the grep version's false positives: strings, pprint, comments
    (tmp_path / "ok.py").write_text(
        "s = 'print('\n"
        "def pprint(*a):\n    pass\n"
        "pprint('y')\n"
        "# print('z')\n")
    (tmp_path / "launch").mkdir()
    (tmp_path / "launch" / "cli.py").write_text("print('driver output')\n")
    (tmp_path / "__main__.py").write_text("print('cli output')\n")
    fs = lint_source(tmp_path)
    assert [f.where for f in fs] == ["mod.py:2"]
    assert fs[0].severity == Severity.ERROR


def test_source_lint_clean_on_library_tree():
    assert lint_source() == []


# ---------------------------------------------------------------------------
# gather-free checker (repro.shard memory contract)
# ---------------------------------------------------------------------------


def test_gather_checker_noop_on_unsharded_program():
    from repro.analysis import check_gather_free
    cj = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((4, 8), jnp.float32))
    fs = check_gather_free(cj, "fix", sharded=False, flat_width=0,
                           shard_width=0)
    assert not _errors(fs)
    assert any(f.severity == Severity.INFO for f in fs)


_GATHER_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.analysis import (Severity, analyze_program, build_programs,
                            check_gather_free)
from repro.launch import mesh as mesh_lib

W, SW = 4, 256
width = 2 * SW
mesh = mesh_lib.make_shard_mesh(2)

def gathered(flat):     # adversarial: the old gather-compute-slice round
    def body(fl):
        full = jax.lax.all_gather(fl, "model", axis=1, tiled=True)
        return full.sum(axis=1, keepdims=True) * jnp.ones_like(fl)
    return jax.shard_map(body, mesh=mesh, in_specs=(P(None, "model"),),
                         out_specs=P(None, "model"), check_vma=False)(flat)

cj = jax.make_jaxpr(gathered)(jnp.zeros((W, width), jnp.float32))
fs = check_gather_free(cj, "adversarial", sharded=True,
                       flat_width=width, shard_width=SW)
errs = [f for f in fs if f.severity == Severity.ERROR]
assert errs, "checker must fire on the gathered fixture"
assert "all_gather" in errs[0].message, errs[0].message

# ... and the SHIPPED mesh program (gather-free pass) is clean across
# every checker, gather-free included
prog, = build_programs(["shard-flat-s2-mesh"])
assert prog.sharded and prog.flat_width > 0 and prog.shard_width > 0
bad = [f for f in analyze_program(prog) if f.severity >= Severity.WARNING]
assert not bad, "\\n".join(str(f) for f in bad)
print("GATHER_CHECK_OK")
"""


def test_gather_checker_fires_on_fixture_clean_on_shipped_subprocess():
    """The satellite acceptance pair in one forced-2-device subprocess:
    the checker ERRORs on the adversarial full-width-gather round and
    stays silent on the shipped gather-free mesh program."""
    import os as _os
    import subprocess
    import sys as _sys
    env = dict(_os.environ)
    env["PYTHONPATH"] = (_os.path.join(_os.path.dirname(__file__), "..",
                                       "src")
                         + _os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([_sys.executable, "-c", _GATHER_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "GATHER_CHECK_OK" in res.stdout


# ---------------------------------------------------------------------------
# Finding schema / report
# ---------------------------------------------------------------------------


def test_finding_schema_and_report_roundtrip():
    f = Finding("key-discipline", Severity.ERROR, "prog", "msg",
                where="scan/pjit", detail={"n": 2})
    assert f.to_json()["severity"] == "error"
    assert "ERROR" in str(f) and "scan/pjit" in str(f)
    rep = json.loads(report_json([f], ["prog"], {"elapsed_s": 1.0}))
    assert rep["summary"] == {"error": 1, "warning": 0, "info": 0}
    assert rep["findings"][0]["detail"] == {"n": 2}


# ---------------------------------------------------------------------------
# the shipped programs are clean (the acceptance gate)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="session")
def shipped():
    return build_programs()


def test_registry_covers_all_driver_paths():
    assert {"static-flat", "static-flat-orthogonal", "dynamic-flat-tele",
            "dynamic-sparse-flat", "fleet-flat", "shard-flat-s2",
            "shard-flat-s2-mesh"} <= set(PROGRAMS)


def test_shipped_programs_have_no_findings(shipped):
    for prog in shipped:
        bad = [f for f in analyze_program(prog)
               if f.severity >= Severity.WARNING]
        assert not bad, "\n".join(str(f) for f in bad)


def test_shipped_donations_fully_aliased(shipped):
    # every donated carry leaf aliased — the scan engine's in-place
    # buffer contract, now proven on the compiled executables
    for prog in shipped:
        fs = check_donation(prog.hlo_text, prog.donated, prog.name)
        assert not _errors(fs), prog.name


def test_dynamic_programs_close_over_no_realizations(shipped):
    for prog in shipped:
        fs = check_weak_closure(prog.closed_jaxpr, prog.n_workers,
                                prog.dynamic, prog.name)
        if prog.dynamic:
            assert fs == [], prog.name   # not even INFO on dynamic paths


# ---------------------------------------------------------------------------
# regression: run_orthogonal key lineage (each leaf key was split TWICE —
# k1 = split(k)[0], k2 = split(k)[1] — before the checker flagged it)
# ---------------------------------------------------------------------------


def test_orthogonal_exchange_key_lineage_clean():
    proto = P.ProtocolConfig(scheme="orthogonal", n_workers=4)
    chan = proto.channel()
    X = {"w": jnp.ones((4, 8), jnp.float32),
         "b": jnp.ones((4, 3), jnp.float32)}
    cj = jax.make_jaxpr(
        lambda k: X_lib.run_orthogonal(X, k, chan, 0.4))(jax.random.key(0))
    assert not _errors(check_key_discipline(cj, "orthogonal"))


def test_orthogonal_split_fix_is_stream_preserving():
    # the fix computes ONE split pair and slices both halves; the old
    # double-split derived the same pair twice — bitwise identical draws
    key = jax.random.PRNGKey(3)
    pair = jax.random.split(key)
    np.testing.assert_array_equal(np.asarray(pair[0]),
                                  np.asarray(jax.random.split(key)[0]))
    np.testing.assert_array_equal(np.asarray(pair[1]),
                                  np.asarray(jax.random.split(key)[1]))


# ---------------------------------------------------------------------------
# property: every ExchangeSpec / FlatSpec shard layout traces clean
# ---------------------------------------------------------------------------


@given(scheme=st.sampled_from(("dwfl", "gossip", "orthogonal",
                               "centralized")),
       n=st.integers(min_value=3, max_value=8),
       participation=st.sampled_from((1.0, 0.5)))
@settings(max_examples=10, deadline=None)
def test_exchange_specs_trace_clean(scheme, n, participation):
    proto = P.ProtocolConfig(scheme=scheme, n_workers=n,
                             participation=participation)
    spec = X_lib.resolve_spec(proto)
    chan = proto.channel()
    X = {"a": jnp.ones((n, 6), jnp.float32),
         "b": jnp.ones((n, 3), jnp.float32)}

    def f(key):
        return spec.run(X, jax.random.split(key, 3), chan, proto)

    cj = jax.make_jaxpr(f)(jax.random.key(0))
    assert not _errors(check_key_discipline(cj, f"{scheme}-N{n}"))
    assert not _errors(check_dtype_discipline(cj, f"{scheme}-N{n}"))


@given(n_shards=st.sampled_from((1, 2, 4)),
       d1=st.integers(min_value=3, max_value=40),
       d2=st.integers(min_value=1, max_value=16),
       n=st.integers(min_value=3, max_value=6))
@settings(max_examples=8, deadline=None)
def test_flat_shard_layouts_trace_clean(n_shards, d1, d2, n):
    from repro.kernels.dp_mix import ops as mix_ops
    wp = {"w": jnp.zeros((n, d1, d2), jnp.float32),
          "b": jnp.zeros((n, d2), jnp.float32)}
    spec = X_lib.make_flat_spec(wp, n_shards=n_shards)
    flat = spec.flatten(wp)
    proto = P.ProtocolConfig(scheme="dwfl", n_workers=n)
    chan = proto.channel()
    xspec = X_lib.resolve_spec(proto)
    g = jnp.zeros_like(flat)

    def f(key):
        k_n, k_m, k_x = jax.random.split(key, 3)
        plan = xspec.plan(proto, chan, k_x)
        return mix_ops.dp_mix_round_plan(flat, g, mix_ops.seed_from_key(k_n),
                                         plan, gamma=0.01, eta=0.4)

    cj = jax.make_jaxpr(f)(jax.random.key(0))
    label = f"flat-S{n_shards}"
    assert not _errors(check_key_discipline(cj, label))
    assert not _errors(check_dtype_discipline(cj, label))
    # and the layout roundtrips: padding never leaks into the tree
    rt = spec.unravel(flat)
    for k in wp:
        np.testing.assert_array_equal(np.asarray(rt[k]), np.asarray(wp[k]))


# ---------------------------------------------------------------------------
# runtime half: the transfer guard
# ---------------------------------------------------------------------------


def test_transfer_guard_blocks_implicit_and_allows_explicit():
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(3, jnp.float32))                       # warm up
    host = np.ones(3, np.float32)
    with pytest.raises(Exception, match="[Dd]isallowed.*transfer|transfer"):
        with obs.no_implicit_transfers():
            f(host)                                   # implicit upload
    with obs.no_implicit_transfers():
        f(jax.device_put(host))                       # explicit: fine
    with obs.no_implicit_transfers(False):            # opt-out: fine
        f(host)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_source_only_writes_report(tmp_path, capsys):
    from repro.analysis.__main__ import main
    out = tmp_path / "report.json"
    rc = main(["--source-only", "--json", str(out)])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["programs"] == ["source"]
    assert rep["summary"]["error"] == 0
    assert "[analysis]" in capsys.readouterr().out
