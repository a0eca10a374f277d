"""flow_build_info: one constant-1 gauge whose labels pin what
actually ran.

Benchmark results and dashboards routinely need to answer "was the fused
native pass really engaged? which trace mode? host or device sketch?"
after the fact — and the honest answer lives in process state
(capabilities(), TRACER.mode, the worker config), not in the command
line someone believes was used. Publishing it as an info-style gauge
(the ``prometheus_build_info`` convention: value 1, identity in the
labels) lets a dashboard join any panel against the exact runtime that
produced it.

Labels:

- ``role``   — worker | member | coordinator (the mesh role, or the
  standalone worker)
- ``native`` — comma-joined native capability set from
  ``native.capabilities()`` (``decode,group,sketch,fused``; ``none``
  when no library loads) — a stale .so shows up here before it shows
  up as a silent slowdown
- ``trace``  — the flowtrace recorder mode at publish time
- ``sketch`` — the sketch backend (device | host)
- ``hh_sketch`` — the heavy-hitter sketch family actually serving
  (table | invertible | none when the model set has no sketch-backed
  hh family) — bench artifacts and dashboards must be able to tell
  which family produced every series (-hh.sketch)
"""

from __future__ import annotations

from .metrics import REGISTRY

BUILD_INFO = (
    "flow_build_info",
    "build/runtime identity (constant 1; labels pin the native "
    "capability set, trace mode, sketch backend, and mesh role)",
)


def publish_build_info(role: str, sketch_backend: str = "device",
                       hh_sketch: str = "table", **labels):
    """Set the identity gauge for this process/role; returns the gauge
    (tests read it back). Safe to call repeatedly — re-publishing the
    same label set is an idempotent set(1)."""
    from ..native import capabilities
    from .trace import TRACER

    caps = capabilities()
    native = ",".join(sorted(f for f, ok in caps.items() if ok)) or "none"
    g = REGISTRY.gauge(*BUILD_INFO)
    g.set(1, role=role, native=native, trace=TRACER.mode,
          sketch=sketch_backend, hh_sketch=hh_sketch, **labels)
    return g
