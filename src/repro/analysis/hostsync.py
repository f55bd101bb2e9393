"""Host-sync guard — no callbacks or host round-trips inside compiled
programs, especially not inside scan bodies.

The whole point of the PR 4 scan engine is that K rounds run as ONE
device program; a ``jax.pure_callback`` / ``io_callback`` /
``jax.debug.print`` left inside the round body serializes the scan on
the host (every iteration round-trips), and an ``infeed``/``outfeed``
does the same at the XLA level. This checker walks the jaxpr for
callback-family primitives; anything found inside a ``scan`` path is the
hot-loop case and gets called out as such. The runtime half of the
invariant — implicit ndarray→device transfers in the drivers — is closed
by ``repro.obs.no_implicit_transfers`` (jax.transfer_guard) around the
launch/fleet hot loops; this static half covers what a guard at the call
boundary cannot see, work smuggled INTO the compiled program.
"""
from __future__ import annotations

from typing import List

from repro.analysis.findings import Finding, Severity
from repro.analysis.walk import iter_eqns

CHECKER = "host-sync"

# callback-family primitive names across jax versions
_CALLBACKS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "python_callback", "callback", "host_callback_call", "outside_call",
    "infeed", "outfeed",
})


def check_host_sync(closed_jaxpr, program: str = "") -> List[Finding]:
    findings: List[Finding] = []
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    for path, eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in _CALLBACKS:
            continue
        in_scan = "scan" in path.split("/") if path else False
        cb = eqn.params.get("callback")
        detail = {"primitive": name}
        if cb is not None:
            detail["callback"] = repr(cb)
        if in_scan:
            msg = (f"{name} inside a scan body: every scan iteration "
                   f"round-trips to the host, serializing the compiled "
                   f"chunk")
        else:
            msg = (f"{name} inside a compiled program forces a host sync "
                   f"at every dispatch")
        findings.append(Finding(CHECKER, Severity.ERROR, program, msg,
                                where=path or "<top>", detail=detail))
    return findings
