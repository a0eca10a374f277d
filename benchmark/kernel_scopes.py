"""Device time of the fused step by kernel scope, and the device's idle
time by the program span it fell under, from the traced run's
``.xplane.pb``.

The fused step (``engine/fused.py::_cached_step``) wraps each kernel in a
``jax.named_scope`` (``SCOPES``). On the v5e the trace's ``XLA Ops``
events carry no ``op_name``: an event is named by its instruction's HLO
text (``%fusion.535 = ...``) and its own stats are times only (read on
the chip, PR 24). So the scope comes from a map instruction name ->
``op_name`` parsed from the compiled step's HLO text, which the program
hands out (``FusedPipeline.compiled_step_text``: a compile-cache hit,
after the window). A fused op counts to the scope of its root, which is
whose ``op_name`` XLA gives the fusion; an instruction the compiler made
without metadata (the reduce-windows of a cumsum, copies, bitcasts)
counts to the scope of its first operand that has one.

Only ops inside an execution of the step program (the ``XLA Modules``
events the configuration's ``trace.step_module`` matches) are counted:
other programs reuse the same instruction names. An op's time is its
self time: a ``while`` holds its body's ops as nested events. On the CPU
dry run, which has no device plane, the XLA client's host threads stand
in (events with ``hlo_module``/``hlo_op``/``run_id`` stats), so that this
code is exercised; what it yields there is not a device number.

Everything here returns None, and raises nothing, where the program has
no scopes or spans to read (a parent commit from before them).
"""

from __future__ import annotations

import glob
import os
import re
import statistics
import sys

from benchmark.trace_reduce import _union

SCOPES = ("hh_chain_sort", "dst_sort", "hh_group_own", "hh_table_merge",
          "dense_scatter", "ddos_accumulate", "wagg_groupby")
UNSCOPED = "(unscoped)"
# the program's spans on the dispatch loop's thread (obs/trace.py)
PROGRAM_SPANS = frozenset((
    "poll_wait", "apply", "spread_fold", "lane_build", "h2d",
    "step_dispatch", "wagg_wait", "wagg_d2h", "wagg_fold", "flush",
    "ckpt_state", "ckpt_d2h", "ckpt_serialize", "ckpt_write",
    "ckpt_commit"))

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def _scope_of(op_name: str):
    for part in op_name.split("/"):
        for scope in SCOPES:
            if part.startswith(scope):
                return scope
    return None


def scope_map(hlo_text: str) -> dict:
    """{instruction name: scope or None} over every computation of the
    module (instruction names are unique in a module)."""
    scope, operands = {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        found = _OP_NAME.search(rest)
        scope[name] = _scope_of(found.group(1)) if found else None
        if scope[name] is None:
            body = rest.split(", metadata=", 1)[0]
            operands[name] = _OPERAND.findall(body.split("(", 1)[-1])

    def inherit(name, depth=0):
        if scope.get(name) is None and depth < 8:
            for operand in operands.get(name, ()):
                found = inherit(operand, depth + 1) if operand in scope \
                    else None
                if found:
                    scope[name] = found
                    break
        return scope.get(name)

    for name in list(operands):
        inherit(name)
    return scope


def _instruction(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(events: list) -> list:
    """[(name, self_ns)] of one line's events ``(name, start, dur)``: an
    event's duration less that of the events nested in it."""
    out, stack = [], []  # stack: [name, end, self]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _end, own = stack.pop()
            out.append((name, own))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def step_scope_ms(steps: list, scopes: dict) -> list:
    """``steps``: per execution of the step, its ops as ``(name,
    self_ns)``. Returns per execution {scope: ms}, UNSCOPED for what maps
    to none."""
    out = []
    for owned in steps:
        row = {}
        for name, own in owned:
            key = scopes.get(_instruction(name)) or UNSCOPED
            row[key] = row.get(key, 0.0) + own / 1e6
        out.append(row)
    return out


# ---- reading the run's trace ---------------------------------------------


def _planes(run) -> list:
    """The planes of the run's ``.xplane.pb``, parsed once."""
    if not hasattr(run, "_xplane"):
        found = glob.glob(os.path.join(run.rundir, "trace", "plugins",
                                       "profile", "*", "*.xplane.pb"))
        from jax.profiler import ProfileData

        # the planes are views: the ProfileData has to outlive them
        data = ProfileData.from_file(found[0]) if found else None
        run._xplane = (data, list(data.planes) if data else [])
    return run._xplane[1]


def _device_steps(planes, step_re) -> list:
    """Ops ``(name, self_ns)`` of each execution of the step program on
    the first device plane."""
    for plane in planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        spans = sorted((e.start_ns, e.start_ns + e.duration_ns)
                       for e in lines["XLA Modules"].events
                       if step_re.search(e.name))
        steps = [[] for _ in spans]
        i = 0
        for e in sorted(lines["XLA Ops"].events,
                        key=lambda e: e.start_ns):
            while i < len(spans) and spans[i][1] <= e.start_ns:
                i += 1
            if i == len(spans):
                break
            if spans[i][0] <= e.start_ns:
                steps[i].append((e.name, e.start_ns, e.duration_ns))
        return [self_times(events) for events in steps]
    return []


def _host_steps(planes, step_re) -> list:
    """The CPU dry run's stand-in: thunk events of the XLA client's host
    threads, by the run_id of the step execution they belong to."""
    by_run = {}
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if not line.name.startswith("tf_XLA"):
                continue
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" in stats and step_re.search(
                        str(stats.get("hlo_module", ""))):
                    by_run.setdefault(
                        (line.name, stats.get("run_id")), []).append(
                        (str(stats["hlo_op"]), e.start_ns, e.duration_ns))
    steps = {}  # self times per thread line, then one step per run_id
    for (_line, run_id), events in by_run.items():
        steps.setdefault(run_id, []).extend(self_times(events))
    return list(steps.values())


def _step_text(run):
    worker = getattr(run.sut, "worker", None)
    text = getattr(getattr(worker, "fused", None), "compiled_step_text",
                   None)
    if text is None:
        return None
    try:
        return text()
    except Exception as e:  # noqa: BLE001 -- reported, and nothing read
        print(f"benchmark: the step's HLO text could not be had: {e!r}",
              file=sys.stderr)
        return None


def per_step(run):
    """Per execution of the fused step in the traced window, {scope: ms};
    None where there is no trace, no step, or no scope in the program."""
    if not hasattr(run, "_kernel_scopes"):
        run._kernel_scopes = None
        text = _step_text(run) if run.trace is not None else None
        if text is not None and _OP_NAME.search(text):
            scopes = scope_map(text)
            if any(scopes.values()):
                planes = _planes(run)
                step_re = re.compile(run.cell.config.get("trace", {}).get(
                    "step_module", "jit_step"))
                steps = _device_steps(planes, step_re)
                if not steps and run.device.get("platform") == "cpu":
                    steps = _host_steps(planes, step_re)
                rows = step_scope_ms([s for s in steps if s], scopes)
                run._kernel_scopes = rows or None
    return run._kernel_scopes


def scope_ms_p50(run, prefix: str):
    """Median over the step's executions of the device ms under the
    scopes that start with ``prefix`` (families summed)."""
    rows = per_step(run)
    if not rows:
        return None
    return statistics.median(
        sum(ms for scope, ms in row.items() if scope.startswith(prefix))
        for row in rows)


def unscoped_share(run):
    rows = per_step(run)
    total = sum(sum(row.values()) for row in rows or ())
    if not total:
        return None
    return 100.0 * sum(row.get(UNSCOPED, 0.0) for row in rows) / total


# ---- idle time by owner --------------------------------------------------


def _overlap(gaps: list, cover: list) -> float:
    """Total length of ``gaps`` covered by ``cover`` (both disjoint and
    sorted)."""
    total, j = 0.0, 0
    for a, b in gaps:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return total


def idle_unowned_share(run):
    """Of the device's idle time between its first and last op of the
    traced window, the share (%) under no program span of the dispatch
    loop's thread. None where the trace holds no ``apply`` annotation."""
    if run.trace is None:
        return None
    planes = _planes(run)
    busy, spans = [], []
    for plane in planes:
        for line in plane.lines:
            if plane.name.startswith("/device:TPU") \
                    and line.name == "XLA Ops" and not busy:
                busy = _union([(e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events if e.duration_ns > 0])
            elif plane.name.startswith("/host"):
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name in PROGRAM_SPANS]
                if any(n == "apply" for n, _a, _b in events):
                    spans = _union([(a, b) for _n, a, b in events])
    if not busy and run.device.get("platform") == "cpu":
        host = [ln for p in planes if p.name == "/host:CPU"
                for ln in p.lines if ln.name.startswith("tf_XLA")]
        busy = _union([(e.start_ns, e.start_ns + e.duration_ns)
                       for ln in host for e in ln.events
                       if e.duration_ns > 0])
    if not busy or not spans:
        return None
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    idle = sum(b - a for a, b in gaps)
    if not idle:
        return None
    return 100.0 * (1.0 - _overlap(gaps, spans) / idle)
