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
    return trace.Summary(
        window=(0.0, 100.0),
        devices=[[(10.0, 30.0, "fusion.1"), (25.0, 40.0, "dp_mix_round.13"),
                  (60.0, 70.0, "dp_mix_round.13")],
                 [(0.0, 50.0, "fusion.1")]],
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
    s.devices = [[(0.0, 5.0, "fusion.1")]]
    assert reader("dp_mix_roofline").read(s, ctx) is None


def test_driver_host_time_per_chunk_by_hand():
    got = reader("driver_host_ms").read(made_up(), {"chunks": 4})
    assert got == pytest.approx(20.0 * 1e-6 / 4)
