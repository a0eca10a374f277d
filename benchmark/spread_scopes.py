"""Device time of the spread detectors' two kernels inside the fused step.

Under ``-spread.enabled`` on the device backend the step
(``engine/fused.py::_cached_step``) updates each detector under two
``jax.named_scope``s of its own, by the detector's name:
``spread_regs_<name>`` (the element hashes and the scatter-max into the
flat register plane) and ``spread_table_<name>`` (what the batch's
sources decode to, the admission metric, and the candidate table's
merge).
``kernel_scopes.SCOPES`` does not know them (they count as ``(unscoped)``
in ``step_unscoped_share``), so this maps instruction -> full scope name
by ``family_scopes``' rules: a fused op counts to its root's scope, a
fusion without metadata to the scope its called computation names, an
instruction without either to that of its first operand that has one.

Returns None, and raises nothing, where the program names no spread
detectors in its step (a parent commit, whose step has none; a
configuration without the flag) or the trace has no step.
"""

from __future__ import annotations

import re
import statistics

from benchmark import kernel_scopes as ks

_SCOPE = re.compile(r"^(spread_(?:regs|table)_[\w\-]+)$")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def scope_names(hlo_text: str) -> dict:
    """{instruction name: the spread scope that holds it}; instructions
    outside every spread scope are left out."""
    found, operands, calls, inside = {}, {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        opened = _COMPUTATION.match(line)
        if opened:
            computation = opened.group(1)
            continue
        m = ks._INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op_name = ks._OP_NAME.search(rest)
        found[name] = None
        for part in (op_name.group(1).split("/") if op_name else ()):
            hit = _SCOPE.match(part)
            if hit:
                found[name] = hit.group(1)
                inside.setdefault(computation, found[name])
        if found[name] is None and not (
                op_name and ks._scope_of(op_name.group(1))):
            body = rest.split(", metadata=", 1)[0]
            operands[name] = ks._OPERAND.findall(body.split("(", 1)[-1])
            called = _CALLS.search(body)
            calls[name] = called.group(1) if called else None

    def inherit(name, depth=0):
        if found.get(name) is None and depth < 8:
            called = calls.get(name)
            found[name] = inside.get(called) if called else None
            for operand in operands.get(name, ()):
                if found[name] is not None:
                    break
                if operand in found:
                    found[name] = inherit(operand, depth + 1)
        return found.get(name)

    for name in list(operands):
        inherit(name)
    return {k: v for k, v in found.items() if v is not None}


def _per_step(run):
    """[{spread scope: device ns}] an execution of the step in the traced
    window, read once a run (the three readers share one pass over the
    trace's ops and one fetch of the step's text: each costs a traced
    run tens of seconds); None where the step has no spread detector or
    there is no trace."""
    if not hasattr(run, "_spread_scope_steps"):
        run._spread_scope_steps = None
        fused = getattr(getattr(run.sut, "worker", None), "fused", None)
        text = ks._step_text(run) if (
            run.trace is not None
            and getattr(fused, "spread_families", None)) else None
        if text is not None:
            scopes = scope_names(text)
            step_re = re.compile(run.cell.config.get("trace", {}).get(
                "step_module", "jit_step"))
            planes = ks._planes(run)
            steps = ks._device_steps(planes, step_re)
            if not steps and run.device.get("platform") == "cpu":
                steps = ks._host_steps(planes, step_re)
            rows = []
            for step in steps:
                row = {}
                for name, own in step:
                    scope = scopes.get(ks._instruction(name))
                    if scope is not None:
                        row[scope] = row.get(scope, 0) + own
                if step:
                    rows.append(row)
            run._spread_scope_steps = rows or None
    return run._spread_scope_steps


def per_step_ms(run, prefix: str):
    """Per execution of the step in the traced window, the device ms
    under the scopes that start with ``prefix`` (the detectors summed);
    None where the step has no spread detector or there is no trace."""
    rows = _per_step(run)
    return rows and [sum(ns for scope, ns in row.items()
                         if scope.startswith(prefix)) / 1e6 for row in rows]


def scope_ms_p50(run, prefix: str):
    per_step = per_step_ms(run, prefix)
    return statistics.median(per_step) if per_step else None
