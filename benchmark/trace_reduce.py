"""From the profiler's trace to numbers: busy time, step times, the
device operations that took most time, the longest idle gaps and what the
host was doing in each.

Reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and nothing
else. Device planes are ``/device:TPU:<n>``; on each, the line ``XLA Ops``
holds one event per operation run and ``XLA Modules`` one per program
execution. A run on the CPU backend (the dry run, ``platform`` ``cpu``)
has no device plane: there, and only there, the XLA client's threads of
``/host:CPU`` stand in, so that the reduction is exercised; what it
yields is never a device number. A run on any other platform whose trace
holds no device plane is an error.
Host spans are the ``TraceAnnotation`` events the benchmark's own
wrappers wrote (``spans.py``), on the same clock as the device events.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field


@dataclass
class Reduction:
    busy_s: float
    window_s: float
    step_ms: list = field(default_factory=list)
    breakdown: dict = field(default_factory=dict)
    chips: int = 1


def _union(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _gaps(busy: list) -> list:
    return [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]


def reduce_planes(planes: dict, span_names: set, step_pattern: str,
                  window_s: float, platform: str) -> Reduction:
    """``planes``: {plane name: {line name: [(name, start_ns, dur_ns)]}}."""
    devices = {p: ls for p, ls in planes.items()
               if p.startswith("/device:TPU")}
    if not devices:
        if platform != "cpu":
            raise ValueError(
                f"the trace of a run on {platform!r} holds no /device:TPU "
                f"plane (it has {sorted(planes)}): nothing in it is a "
                f"device number")
        host = planes.get("/host:CPU", {})
        devices = {"/host:CPU": {"XLA Ops": [
            e for ln, evs in host.items() if ln.startswith("tf_XLA")
            for e in evs if e[2] > 0]}}
    step_re = re.compile(step_pattern)
    busy_s, op_time, step_ms, first_busy = 0.0, {}, [], None
    for name in sorted(devices):
        lines = devices[name]
        ops = lines.get("XLA Ops") or [e for evs in lines.values()
                                       for e in evs]
        busy = _union([(s, s + d) for _n, s, d in ops if d > 0])
        busy_s += sum(b - a for a, b in busy) / 1e9
        if first_busy is None:
            first_busy = busy
            for n, _s, d in lines.get("XLA Modules", []):
                if step_re.search(n):
                    step_ms.append(d / 1e6)
        for n, _s, d in ops:
            n = n.split(" = ", 1)[0][:64]  # "%fusion.535", not its HLO text
            op_time[n] = op_time.get(n, 0.0) + d / 1e9
    chips = len(devices)
    host_spans = [e for p, ls in planes.items() if p.startswith("/host")
                  for evs in ls.values() for e in evs if e[0] in span_names]
    gaps = []
    for a, b in sorted(_gaps(first_busy or []),
                       key=lambda g: g[0] - g[1])[:10]:
        owner, best = "(no span: the loop waited for flows)", 0.0
        for n, s, d in host_spans:
            ov = min(b, s + d) - max(a, s)
            # the innermost span that covers most of the gap owns it: the
            # per-batch "process" span only where nothing inside it does
            if ov > 0 and (ov > best and n != "process"
                           or best == 0.0 and n == "process"):
                if n != "process":
                    best = ov
                owner = n
        gaps.append([owner, (b - a) / 1e9])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return Reduction(
        busy_s=busy_s / chips, window_s=window_s, step_ms=step_ms,
        chips=chips,
        breakdown={"device_ops": [[n, t / chips] for n, t in top_ops],
                   "idle_gaps": gaps})


def load_planes(path: str) -> dict:
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return out


def reduce_dir(trace_dir: str, run) -> Reduction:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    names = {s[0] for s in run.spans.spans}
    return reduce_planes(
        load_planes(found[0]), names,
        run.cell.config.get("trace", {}).get("step_module", "jit_step"),
        run.trace_span[1] - run.trace_span[0], run.device["platform"])
