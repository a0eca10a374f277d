"""Device time by sharded program and busy time by chip, from the traced
run's ``.xplane.pb``.

Under ``-processor.mesh`` a batch is not one step but one sharded program
a model (``parallel/sharded.py``), each jitted from a function named for
its family and model: ``mesh_hh_update_<model>``,
``mesh_dense_update_<model>``, ``mesh_ddos_update``, ``mesh_ddos_close``,
``mesh_wagg_update``, and at a close ``mesh_hh_merge_<model>``,
``mesh_dense_merge_<model>``. Every ``/device:TPU:<n>`` plane's ``XLA
Modules`` line holds one event per execution, named ``jit_<function>(<id>)``:
that is all the attribution needs. What has no ``mesh_`` name (extraction,
resets, slices) is kept under its own name, so the rest can be told too.

On the CPU dry run, which has no device plane, the XLA client's host
threads stand in: their thunk events carry ``hlo_module``, ``run_id`` and
``device_ordinal``, and one execution is the span from its first thunk
to its last. What that yields is not a device number.

Everything here returns None, and raises nothing, where there is no
trace or the program has no such names (a parent commit from before
them: every program was ``per_chip`` there).
"""

from __future__ import annotations

import re
import statistics

from benchmark import kernel_scopes
from benchmark.trace_reduce import _union

UPDATE_FAMILIES = {"hh": "mesh_hh_update_", "dense": "mesh_dense_update_",
                   "ddos": "mesh_ddos_", "wagg": "mesh_wagg_"}
MERGE = re.compile(r"^mesh_(hh|dense)_merge_")
_MODULE = re.compile(r"^jit_(.+?)(?:\(\d+\))?$")


def program_name(module_event: str) -> str:
    m = _MODULE.match(module_event)
    return m.group(1) if m else module_event


def _device_programs(planes) -> dict:
    """{chip: [(program, start_ns, dur_ns)]} from the device planes."""
    out = {}
    for plane in planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                out[plane.name] = [
                    (program_name(e.name), e.start_ns, e.duration_ns)
                    for e in line.events]
    return out


def _host_programs(planes) -> dict:
    """The CPU dry run's stand-in (see the module's docstring)."""
    runs = {}  # (device, module, run_id) -> [first start, last end]
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if not line.name.startswith("tf_XLA"):
                continue
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_module" not in stats or "run_id" not in stats:
                    continue
                key = (stats.get("device_ordinal", 0),
                       str(stats["hlo_module"]), stats["run_id"])
                end = e.start_ns + e.duration_ns
                span = runs.setdefault(key, [e.start_ns, end])
                span[0], span[1] = min(span[0], e.start_ns), max(span[1],
                                                                 end)
    out = {}
    for (device, module, _run_id), (a, b) in runs.items():
        out.setdefault(f"/host:CPU:{device}", []).append(
            (program_name(module), a, b - a))
    return out


def programs(run):
    """{chip: [(program, start_ns, dur_ns)]} of the traced part of the
    window; None where there is no trace, or no program in it carries a
    ``mesh_`` name."""
    if not hasattr(run, "_mesh_programs"):
        run._mesh_programs = None
        if run.trace is not None:
            planes = kernel_scopes._planes(run)
            found = _device_programs(planes)
            if not found and run.device.get("platform") == "cpu":
                found = _host_programs(planes)
            if any(name.startswith("mesh_") for events in found.values()
                   for name, _s, _d in events):
                run._mesh_programs = found
    return run._mesh_programs


def batches_traced(run) -> int:
    """Polled batches whose step started inside the traced part."""
    if run.trace_span is None:
        return 0
    return len(run.spans.named("process", *run.trace_span))


def ms_per_batch(run, keep):
    """Device ms a global batch, on one chip (the mean over the chips),
    in the programs whose name ``keep`` accepts."""
    found, batches = programs(run), batches_traced(run)
    if not found or not batches:
        return None
    total_ns = sum(d for events in found.values()
                   for name, _s, d in events if keep(name))
    return total_ns / 1e6 / len(found) / batches


def family_ms_per_batch(run, family: str):
    prefix = UPDATE_FAMILIES[family]
    return ms_per_batch(run, lambda name: name.startswith(prefix))


def rest_ms_per_batch(run):
    """What the four update families leave: the merges of closes and
    publishes, extraction, resets, and programs with no mesh name."""
    prefixes = tuple(UPDATE_FAMILIES.values())
    return ms_per_batch(run, lambda name: not name.startswith(prefixes))


def execution_ms(run, keep):
    """Sum over the programs ``keep`` accepts of one execution's device
    ms (the median over executions and chips): what one global step, or
    one close, costs a chip when each of them runs once."""
    found = programs(run)
    by_program = {}
    for events in (found or {}).values():
        for name, _s, d in events:
            if keep(name):
                by_program.setdefault(name, []).append(d / 1e6)
    if not by_program:
        return None
    return sum(statistics.median(v) for v in by_program.values())


def is_update(name: str) -> bool:
    """A per-step update program (the detector's sub-window close is a
    collective, not one)."""
    return name != "mesh_ddos_close" and name.startswith(
        tuple(UPDATE_FAMILIES.values()))


def is_merge(name: str) -> bool:
    return bool(MERGE.match(name))


def chip_busy_s(run):
    """[busy seconds] of each chip over the traced part: the union of
    its ops' intervals; None where the trace has fewer than two chips."""
    if run.trace is None:
        return None
    busy = []
    for plane in kernel_scopes._planes(run):
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                spans = _union([(e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events if e.duration_ns > 0])
                busy.append(sum(b - a for a, b in spans) / 1e9)
    if not busy and run.device.get("platform") == "cpu":
        found = programs(run) or {}
        busy = [sum(b - a for a, b in _union(
            [(s, s + d) for _n, s, d in events])) / 1e9
            for _chip, events in sorted(found.items())]
    return busy if len(busy) >= 2 else None
