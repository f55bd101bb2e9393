"""Host spans of the DWFL driver, on the profiler's clock.

``span(name, **attrs)`` times a block of host code twice over, from one
pair of clock readings:

* it opens ``jax.profiler.TraceAnnotation(name, **attrs)``, so a running
  profile records the span on the device trace's clock (without a profile
  that is the constructor's cost and nothing more);
* it adds the block's duration to an in-memory counter per name: count,
  total seconds and longest single span. ``counters()`` returns a copy,
  ``reset()`` clears them.

Nothing is written to disk. The spans the program opens:

==========================  ==============================================
``dwfl.main``               all of ``launch.train.main``
``dwfl.main.data``          its data set, Dirichlet split, batcher, store
``dwfl.main.params``        the initial weights or flat buffer (on a model
                            mesh: built in its sharded layout)
``dwfl.chunk.compile``      ``ChunkRunner.run``'s first call for a length
``dwfl.chunk.dispatch``     every later call (``rounds``, ``chunk`` attrs);
                            ends when the asynchronous call returns
==========================  ==============================================

The device side carries ``jax.named_scope``s instead (``dwfl.grad`` with
``dwfl.grad.mla``, ``dwfl.grad.moe`` and ``dwfl.grad.moe.experts`` inside
the model, ``dwfl.shard``, ``dwfl.mix``, ``dwfl.telemetry``), which XLA
keeps in each op's
``op_name`` metadata at no run-time cost.
"""
from __future__ import annotations

import threading
import time
from typing import Dict

import jax

_lock = threading.Lock()
_counters: Dict[str, Dict[str, float]] = {}


class span:
    """Context manager: a profiler annotation plus a per-name counter.
    ``seconds`` holds the block's duration once it has exited."""

    __slots__ = ("name", "seconds", "_annotation", "_t0")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.seconds = None
        self._annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        with _lock:
            c = _counters.setdefault(
                self.name, {"count": 0, "total_s": 0.0, "max_s": 0.0})
            c["count"] += 1
            c["total_s"] += dt
            c["max_s"] = max(c["max_s"], dt)
        return False


def counters() -> Dict[str, Dict[str, float]]:
    """{name: {"count", "total_s", "max_s"}} of every span closed since
    the last ``reset()``, as a copy."""
    with _lock:
        return {k: dict(v) for k, v in _counters.items()}


def reset() -> None:
    with _lock:
        _counters.clear()
