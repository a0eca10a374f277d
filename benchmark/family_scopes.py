"""Device time of one ranked family's table merge inside the fused step.

``kernel_scopes.py`` sums the step's ``hh_table_merge_<i>`` scopes into
one (``step_table_merge_ms``); the index ``i`` is the family's place among
the program's sketch families, which the program hands out beside the
step's text (``FusedPipeline.hh_families``: names in scope order). This
maps instruction -> the full scope name by ``kernel_scopes``' own rules
(a fused op counts to its root's scope, an instruction without metadata
to that of its first operand that has one) and sums one family's.

Returns None, and raises nothing, where the program names no families
(a parent commit from before them) or the trace has no step.
"""

from __future__ import annotations

import re
import statistics

from benchmark import kernel_scopes as ks

_MERGE = re.compile(r"^hh_table_merge_(\d+)$")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def merge_index_map(hlo_text: str) -> dict:
    """{instruction name: index of the family whose merge scope holds
    it}; instructions outside every merge scope are left out. On the
    v5e the step's largest ops, the count-min scatters, are fusions that
    carry no metadata themselves while the instructions fused into them
    do (and their operands may be fusions XLA made across families), so
    a fusion without a scope of its own counts to the family its called
    computation names before it looks at its operands."""
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
            hit = _MERGE.match(part)
            if hit:
                found[name] = int(hit.group(1))
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


def merge_ms_p50(run, family: str):
    """Median over the step's executions in the traced window of the
    device ms under ``family``'s merge scope."""
    fused = getattr(getattr(run.sut, "worker", None), "fused", None)
    names = getattr(fused, "hh_families", None)
    if run.trace is None or not names or family not in names:
        return None
    index = list(names).index(family)
    text = ks._step_text(run)
    if text is None:
        return None
    scopes = merge_index_map(text)
    step_re = re.compile(run.cell.config.get("trace", {}).get(
        "step_module", "jit_step"))
    planes = ks._planes(run)
    steps = ks._device_steps(planes, step_re)
    if not steps and run.device.get("platform") == "cpu":
        steps = ks._host_steps(planes, step_re)
    per_step = [sum(own for name, own in step
                    if scopes.get(ks._instruction(name)) == index) / 1e6
                for step in steps if step]
    return statistics.median(per_step) if per_step else None
