"""Host-sync detector: callbacks and host transfers inside the step.

A compiled train step should touch the host exactly once per log
interval (the MetricBag contract, monitor/metrics.py) — anything else
serializes the device against Python. The offenders hide well because
they are *correct*: ``jax.debug.print`` left over from a debugging
session, a ``pure_callback`` smuggled in by a library, an
``io_callback`` logger — each one stalls the XLA pipeline for a host
round-trip every
single step, which swamps small-step training without changing any
output. This pass finds them in the traced jaxpr before a step runs:

- ``host-sync.callback`` — ``pure_callback`` / ``io_callback`` /
  ``debug_print`` / ``debug_callback`` (``jax.debug.print`` /
  ``jax.debug.callback``) and the legacy host_callback primitives.
- ``host-sync.transfer`` — explicit ``device_put`` equations whose
  destination is a host memory space (the memories API): an in-step
  device->host transfer.

Debug taps that are MEANT to ship (none today) would get a documented
allowlist entry; everything else is a finding.
"""

from typing import Iterable

from apex_tpu.analysis.findings import Finding, SEV_ERROR
from apex_tpu.analysis.passes import eqn_site, jaxpr_pass

__all__ = ["host_sync_pass"]

#: primitives that call back into Python (one host round-trip per step,
#: per occurrence), with the user-facing API name for the message
_CALLBACK_PRIMS = {
    "pure_callback": "jax.pure_callback",
    "io_callback": "jax.experimental.io_callback",
    "debug_print": "jax.debug.print",
    "debug_callback": "jax.debug.callback",
    "outside_call": "jax.experimental.host_callback (legacy)",
    "host_callback": "jax.experimental.host_callback (legacy)",
}


def _targets_host(eqn) -> bool:
    """True when a device_put equation's destination is host memory."""
    for key in ("devices", "srcs", "memory_kind", "sharding"):
        val = eqn.params.get(key)
        if val is not None and "host" in repr(val).lower():
            return True
    return False


@jaxpr_pass("host-sync")
def host_sync_pass(ctx) -> Iterable[Finding]:
    for eqn in ctx.iter_eqns():
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS:
            yield ctx.finding(
                "host-sync.callback",
                f"{_CALLBACK_PRIMS[name]} inside the compiled step: one "
                f"host round-trip EVERY step (the bag/router path exists "
                f"so this crossing is paid once per interval)",
                site=eqn_site(eqn), severity=SEV_ERROR,
                data={"primitive": name},
            )
        elif name == "device_put" and _targets_host(eqn):
            yield ctx.finding(
                "host-sync.transfer",
                "device_put to host memory inside the compiled step: an "
                "in-step device->host transfer serializes the device "
                "against host RAM",
                site=eqn_site(eqn), severity=SEV_ERROR,
                data={"primitive": name},
            )
