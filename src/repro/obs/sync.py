"""The executor's host costs beside its device work: host syncs and
compile requests.

- ``to_host(x, site)`` is the one door for deliberate device-to-host reads
  on the served path. It counts ``executor.syncs`` and opens the span
  ``query.to_host`` (annotation argument ``site``) around the read, so a
  profiler trace shows the wait on the host plane. The read itself is
  allowed under ``jax.transfer_guard_device_to_host("disallow")``: run a
  call under that guard on a device backend to find the reads that do
  not come through here (the guard does not fire on the CPU backend).
- ``executor.compiles`` counts every lowering of a jaxpr to an MLIR
  module, which is what a jit cache miss reaches whether or not the
  persistent compilation cache then serves the executable; the event
  fires with that cache off too. One ``jax.monitoring`` listener feeds
  it, registered when this module is imported; it counts compiles from
  anywhere in the process, so read it over a window in which only the
  code under study runs.
"""
from __future__ import annotations

from typing import Any

import jax

from .metrics import registry
from .spans import span

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def to_host(x: Any, site: str) -> Any:
    """``jax.device_get(x)``: numpy arrays for a device array or a pytree
    of them, read in one round trip, counted and spanned as one sync."""
    registry().counter("executor.syncs").inc()
    with span("query.to_host", site=site), \
            jax.transfer_guard_device_to_host("allow"):
        return jax.device_get(x)


def _on_duration(event: str, duration_secs: float, **_: Any) -> None:
    if event == LOWERING_EVENT:
        registry().counter("executor.compiles").inc()


jax.monitoring.register_event_duration_secs_listener(_on_duration)
