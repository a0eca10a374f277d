"""The fused step's device ops, one by one, from a kept traced run.

    python3 -m benchmark.run --workload estate-catchup --seed <n> \
        --seconds 51 --trace 1 --keep
    python3 tools/step_ops.py [--scope hh_table_merge] [--out table.json]
        [--config estate-spread]

The benchmark's result line names the ten longest ops of the whole trace
by XLA's instruction numbering (``_while.25``); its kernel-scope metrics
sum a scope. This prints what lies between: for every instruction of the
``jit_step`` program, the ``op_name`` JAX gave it and its self time a step
(median over the step's executions in the trace), so that the next cut
at a scope is sized from the chip. It reads the newest
``.bench_run/*/trace`` of this checkout with the benchmark's own readers
(``benchmark/kernel_scopes.py``) and compiles the step once more for its
text: the default processor's, or with ``--config`` the one that
``benchmark/configs/<name>.json``'s ``processor_flags`` build (a compile-cache hit after a traced run), so
it needs the device the run had. A measurement aid: no test and no cell
runs it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import kernel_scopes as ks  # noqa: E402


def _frames(hlo_text: str) -> dict:
    """{stack_frame_id: "function (file:line)"} of the innermost frame,
    from the tables at the head of the module's text."""
    tables, table = {}, None
    for line in hlo_text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = tables.setdefault(line, {})
        elif table is not None:
            m = re.match(r"^(\d+) (.*)$", line)
            if m:
                table[int(m.group(1))] = m.group(2).strip('"')
            elif line.strip():
                table = None

    def field(text, key):
        return int(re.search(key + r"=(\d+)", text).group(1))

    out = {}
    for frame, text in tables.get("StackFrames", {}).items():
        loc = tables["FileLocations"][field(text, "file_location_id")]
        path = tables["FileNames"][field(loc, "file_name_id")]
        func = tables["FunctionNames"][field(loc, "function_name_id")]
        out[frame] = (f"{func} "
                      f"({os.path.basename(path)}:{field(loc, 'line')})")
    return out


def _op_names(hlo_text: str) -> dict:
    """{instruction: op_name and the frame that made it}; a fusion without
    metadata takes the op_names inside the computations it calls."""
    names, calls, members, comp = {}, {}, {}, None
    frames = _frames(hlo_text)
    for line in hlo_text.splitlines():
        head = re.match(r"^%?([\w.\-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = ks._INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        members.setdefault(comp, []).append(name)
        found = ks._OP_NAME.search(rest)
        if found:
            frame = re.search(r"stack_frame_id=(\d+)", rest)
            names[name] = found.group(1) + (
                " <- " + frames[int(frame.group(1))]
                if frame and int(frame.group(1)) in frames else "")
        called = re.search(r"calls=%?([\w.\-]+)", rest)
        if called:
            calls[name] = called.group(1)

    def inside(comp, depth=0) -> set:
        out = set()
        for name in members.get(comp, ()):
            if name in names:
                out.add(names[name])
            elif name in calls and depth < 4:
                out |= inside(calls[name], depth + 1)
        return out

    for name, comp in calls.items():
        if name not in names:
            found = inside(comp)
            if found:
                names[name] = "(inside) " + " | ".join(sorted(found))
    return names


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scope", default="", help="only this kernel scope")
    ap.add_argument("--batch", default="32768",
                    help="-processor.batch of the run (a tiny dry run's)")
    ap.add_argument("--config", default="",
                    help="the run's configuration, a name under "
                         "benchmark/configs/ (its processor_flags)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import jax
    from flow_pipeline_tpu import cli
    from flow_pipeline_tpu.engine.fused import FusedPipeline
    from flow_pipeline_tpu.utils.flags import FlagSet
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        ROOT, ".bench_run", "*", "trace", "plugins", "profile", "*",
        "*.xplane.pb")), key=os.path.getmtime)
    if not found:
        print("step_ops: no kept traced run under .bench_run/",
              file=sys.stderr)
        return 2
    data = ProfileData.from_file(found[-1])
    planes, step_re = list(data.planes), re.compile("jit_step")
    steps = ks._device_steps(planes, step_re)
    if not steps and jax.default_backend() == "cpu":
        print("# CPU dry run: host thunk times, not device times")
        steps = ks._host_steps(planes, step_re)
    steps = [s for s in steps if s]
    flags = cli._processor_flags(cli._common_flags(FlagSet("processor")))
    argv = ["-processor.batch", args.batch]
    if args.config:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               args.config + ".json")) as f:
            argv = json.load(f)["processor_flags"]
    text = FusedPipeline(cli._build_models(
        flags.parse(argv))).compiled_step_text()
    scopes, names = ks.scope_map(text), _op_names(text)
    per_step = []
    for owned in steps:
        row = {}
        for event, own in owned:
            inst = ks._instruction(event)
            row[inst] = row.get(inst, 0.0) + own / 1e6
        per_step.append(row)
    table = []
    for inst in {i for row in per_step for i in row}:
        scope = scopes.get(inst) or ks.UNSCOPED
        if args.scope and not scope.startswith(args.scope):
            continue
        table.append({
            "instruction": inst, "scope": scope,
            "op_name": names.get(inst, ""),
            "ms_a_step_p50": statistics.median(
                row.get(inst, 0.0) for row in per_step)})
    table.sort(key=lambda r: -r["ms_a_step_p50"])
    out = {"trace": os.path.relpath(found[-1], ROOT), "steps": len(steps),
           "step_ms_p50": statistics.median(
               sum(row.values()) for row in per_step),
           "ops": table}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(f"# {out['steps']} executions of jit_step, "
          f"{out['step_ms_p50']:.3f} ms of ops a step (p50); {out['trace']}")
    for r in table:
        if r["ms_a_step_p50"] >= 0.005:
            print(f"{r['ms_a_step_p50']:8.3f}  {r['instruction']:<28} "
                  f"{r['scope']:<16} {r['op_name'][:200]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
