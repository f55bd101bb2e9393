"""The readers of the program's own spans (set-up phases and the chunk
dispatch), on the CPU, against the span counters by hand."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))
import run  # noqa: E402
from repro import obs  # noqa: E402

READERS = ("dispatch_host_ms", "setup_train_main_s", "setup_data_s",
           "setup_compile_s")


def reader(name):
    return run.load_module(ROOT / "bench/metrics" / f"{name}.py")


@pytest.fixture
def clean_counters():
    obs.spans.reset()
    yield
    obs.spans.reset()


def test_span_readers_by_hand(clean_counters):
    # the bench's order: train.main (its data inside), then the first
    # chunk's compile, then the window's dispatches
    with obs.span("dwfl.main"):
        with obs.span("dwfl.main.data"):
            pass
    with obs.span("dwfl.chunk.compile", rounds=5):
        pass
    for i in range(4):
        with obs.span("dwfl.chunk.dispatch", rounds=5, chunk=i + 2):
            pass
    c = obs.spans.counters()
    got = {name: reader(name).read(None, {}) for name in READERS}
    assert got["setup_train_main_s"] == c["dwfl.main"]["total_s"]
    assert got["setup_data_s"] == c["dwfl.main.data"]["total_s"]
    assert got["setup_compile_s"] == c["dwfl.chunk.compile"]["total_s"]
    assert got["dispatch_host_ms"] == pytest.approx(
        1e3 * c["dwfl.chunk.dispatch"]["total_s"] / 4)
    assert got["setup_data_s"] <= got["setup_train_main_s"]


def test_span_readers_read_nothing_before_any_span(clean_counters):
    assert all(reader(n).read(None, {}) is None for n in READERS)


def test_span_readers_read_nothing_from_a_program_without_spans(
        clean_counters, monkeypatch):
    """A program older than its span counters has no
    ``repro.obs.spans``: the readers return nothing and raise nothing."""
    import repro.obs
    with obs.span("dwfl.main"):
        pass
    monkeypatch.delattr(repro.obs, "spans")
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert all(reader(n).read(None, {}) is None for n in READERS)
