"""The trace reduction and the metric readers, on the CPU, by hand on a
made-up summary."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "bench"))
import run  # noqa: E402
from bench import trace  # noqa: E402

PAPER = json.loads((ROOT / "bench/configs/dwfl-paper-n10-static.json")
                   .read_text())


def reader(name):
    return run.load_module(ROOT / "bench/metrics" / f"{name}.py")


def made_up():
    # window 0..100 ns; chip 0 busy 10-30 and 25-40 (union 30) and 60-70;
    # chip 1 busy 0-50; the host in bench.readback over 40-60
    Op = trace.Op
    return trace.Summary(
        window=(0.0, 100.0),
        devices=[[Op(10.0, 30.0, "fusion.1"),
                  Op(25.0, 40.0, "dp_mix_round.13"),
                  Op(60.0, 70.0, "dp_mix_round.13")],
                 [Op(0.0, 50.0, "fusion.1")]],
        spans=[(0.0, 40.0, "bench.dispatch"), (40.0, 60.0, "bench.readback"),
               (60.0, 100.0, "bench.wait")])


def test_busy_union_and_idle_share_by_hand():
    s = made_up()
    assert trace.merged(s.devices[0]) == [(10.0, 40.0), (60.0, 70.0)]
    assert trace.busy_ns(s) == (40.0 + 50.0) / 2
    idle = reader("device_idle_pct").read(s, {})
    assert idle == pytest.approx(100.0 * (1 - 45.0 / 100.0))


def test_idle_gaps_are_named_by_the_host_span():
    s = made_up()
    assert trace.idle_gaps(s) == [(0.0, 10.0), (40.0, 60.0), (70.0, 100.0)]
    b = trace.breakdown(s)
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(30e-9)]
    assert ["bench.readback", pytest.approx(20e-9)] in b["idle_gaps"]
    assert b["device_ops"][0][0] == "fusion.1"


def test_kernel_time_and_roofline_by_hand():
    s = made_up()
    # dp_mix events: 15 + 10 ns on chip 0, none on chip 1 -> 12.5 ns/chip
    assert sum(trace.op_totals(s, "dp_mix_round").values()) == pytest.approx(
        12.5e-9)
    peak = run.peaks_for("TPU v5 lite")
    flops = run.load_module(ROOT / "bench/flops/mlp.py")
    ctx = {"cfg": PAPER, "peak": peak, "rounds": 2, "chunks": 1,
           "chips": 1, "flops": flops}
    f, b = flops.dp_mix_work(PAPER)
    least = max(f / peak["bf16_flops_per_s"], b / peak["hbm_bytes_per_s"])
    got = reader("dp_mix_roofline").read(s, ctx)
    assert got == pytest.approx(100.0 * least * 2 / 12.5e-9)
    s.devices = [[trace.Op(0.0, 5.0, "fusion.1")]]
    assert reader("dp_mix_roofline").read(s, ctx) is None


def test_driver_host_time_per_chunk_by_hand():
    got = reader("driver_host_ms").read(made_up(), {"chunks": 4})
    assert got == pytest.approx(20.0 * 1e-6 / 4)


def test_the_roofline_on_four_chips_reads_the_one_chip_share():
    """On c chips each mixes its own d/c columns: a kernel that takes a
    quarter of the one-chip time on each of four chips runs as near its
    roofline as the one-chip kernel does."""
    Op = trace.Op
    rounds, ns = 3, 400_000.0

    def kernel_calls(length):
        return [Op(i * 1e6, i * 1e6 + length, "dp_mix_round.7")
                for i in range(rounds)]
    one = trace.Summary((0.0, 3e6), [kernel_calls(ns)])
    four = trace.Summary((0.0, 3e6), [kernel_calls(ns / 4)
                                      for _ in range(4)])
    flops = run.load_module(ROOT / "bench/flops/mlp.py")
    ctx = {"cfg": PAPER, "peak": run.peaks_for("TPU v5 lite"),
           "rounds": rounds, "chunks": 1, "flops": flops}
    got_one = reader("dp_mix_roofline").read(one, dict(ctx, chips=1))
    got_four = reader("dp_mix_roofline").read(four, dict(ctx, chips=4))
    assert got_four == pytest.approx(got_one, rel=1e-12)
    f, b = flops.dp_mix_work(PAPER)
    assert got_one == pytest.approx(100.0 * b / 819e9 / (ns * 1e-9))


def test_the_roofline_reads_less_where_chips_repeat_the_mix():
    """Four chips that each mix every column (each as long as one chip
    alone) do four times the least work: a quarter of the share."""
    Op = trace.Op
    rounds, ns = 3, 400_000.0
    calls = [Op(i * 1e6, i * 1e6 + ns, "dp_mix_round.7")
             for i in range(rounds)]
    one = trace.Summary((0.0, 3e6), [calls])
    four = trace.Summary((0.0, 3e6), [list(calls) for _ in range(4)])
    ctx = {"cfg": PAPER, "peak": run.peaks_for("TPU v5 lite"),
           "rounds": rounds, "chunks": 1,
           "flops": run.load_module(ROOT / "bench/flops/mlp.py")}
    got_one = reader("dp_mix_roofline").read(one, dict(ctx, chips=1))
    got_four = reader("dp_mix_roofline").read(four, dict(ctx, chips=4))
    assert got_four == pytest.approx(got_one / 4, rel=1e-12)


def test_the_scope_readers_by_hand():
    Op = trace.Op
    ops = [Op(0.0, 100.0, "%while.4", "unscoped"),
           Op(0.0, 20.0, "%fusion.1", "dwfl.grad"),
           Op(20.0, 25.0, "%pad.2", "dwfl.mix"),
           Op(25.0, 65.0, "%dp_mix_round.7", "dwfl.mix"),
           Op(65.0, 80.0, "%reduce.3", "dwfl.telemetry"),
           Op(80.0, 100.0, "%convert.9", "unscoped")]
    s = trace.Summary((0.0, 100.0), [trace.mark_containers(ops)])
    ctx = {"rounds": 2}
    assert reader("grad_pass_ms").read(s, ctx) == pytest.approx(1e-5)
    assert reader("mix_wrap_ms").read(s, ctx) == pytest.approx(2.5e-6)
    assert reader("telemetry_ms").read(s, ctx) == pytest.approx(7.5e-6)
    # no rounds, or no op under the scope: nothing to read
    assert reader("grad_pass_ms").read(s, {"rounds": 0}) is None
    s.devices = [[Op(0.0, 5.0, "%fusion.1")]]
    assert reader("telemetry_ms").read(s, ctx) is None
