"""durability-protocol: crash-consistency lint for durable surfaces.

Four modules in this repo own state a crash must not lose (the mesh
journal, the dead-letter spill, the history archive, the sketch
checkpoint — docs/FAULT_TOLERANCE.md). Each of them speaks the same
durable-write protocol through ``utils/fsutil``:

- file CONTENTS become durable at ``fsync_file`` (never at flush);
- a created/renamed NAME becomes durable at ``fsync_dir`` on its
  containing directory;
- an atomic publish is ``write tmp -> fsync tmp -> replace ->
  fsync_dir`` (``staged_durable`` is the whole sentence round a writer,
  ``write_bytes_durable`` the same for whole bytes).

This rule models that protocol over the AST of every module marked
``# flowlint: durable-checked``. Within a marked module it reports:

- **bare-open**: ``open(...)`` in a write/append/exclusive mode (or an
  unclassifiable non-literal mode) — durable state must go through
  ``fsutil.open_durable`` / ``write_bytes_durable`` so the crash-point
  recorder sees it;
- **raw-op**: ``os.fsync`` / ``os.replace`` / ``os.rename`` /
  ``os.remove`` / ``os.unlink`` / ``os.truncate`` / ``os.rmdir`` /
  ``os.link`` / ``shutil.rmtree`` / ``shutil.move`` — same reason
  (``utils/fsutil.py`` itself is exempt: raw calls there ARE the
  implementation);
- **unsynced-write**: a write to a tracked durable handle with no
  lexically-later ``fsync_file`` on that handle in the same function
  and no group-commit annotation (see below). A ``yield`` of the
  handle is a write to it: the block that takes it writes through it
  (``fsutil.staged_durable``);
- **replace-before-fsync**: ``fsutil.replace``/``rename`` whose source
  is a temp file that was written but never fsynced first — the
  published file could be empty or torn after a crash;
- **unpublished-temp**: a ``*.tmp``-style staging path opened via
  ``open_durable`` but never the source of a ``replace``/``rename``;
- **missing-dir-fsync**: a name operation (replace, rename, remove,
  rmtree, or a name-creating open) with no lexically-later
  ``fsync_dir`` in the same function and no dir-fsync annotation;
- **unacked-append**: a buffered group-commit append (``self.X.append``
  where the module also calls ``self.X.sync``) with no lexically-later
  ``self.X.sync()`` in the same method and no group-commit annotation.

Deferred barriers are declared, not waved through::

    # durable: group-commit=<method> -- <why the barrier is elsewhere>
    # durable: dir-fsync=<method> -- <why the barrier is elsewhere>

on the flagged line or the comment line directly above. The reason
after ``--`` is mandatory, and the named method must actually exist in
the module (or class) and contain the promised barrier — a
group-commit method must call ``fsync_file``/``os.fsync``/``.sync()``,
a dir-fsync method must call ``fsync_dir``. Annotations are verified
on every run: delete the fsync out of the named method and every
annotation pointing at it turns into a finding (that is the static
half of the ``make lint-mutation`` durability gate; the dynamic half
is ``utils/crashsim.py`` under ``make crash-parity``).

The analysis is deliberately lexical and per-function, like the
lock-discipline rule: flow-insensitive, no false negatives from clever
control flow slipping a barrier behind a branch the common path skips
— if the barrier is conditional, that is exactly what the annotation
grammar is for.
"""

from __future__ import annotations

import ast
import re

from .core import Finding, SourceFile, dotted_name

RULE = "durability-protocol"
MARKER = "durable-checked"

# the one file where raw os.* durability calls are the implementation,
# not a bypass (everything else routes through its helpers)
CORE_REL = "flow_pipeline_tpu/utils/fsutil.py"

# fsutil helper names, recognized both bare (inside fsutil itself) and
# as the trailing attribute of a dotted call (fsutil.replace(...))
_H_OPEN = "open_durable"
_H_FSYNC = "fsync_file"
_H_FSYNC_DIR = "fsync_dir"
_H_WHOLE = {"write_bytes_durable", "staged_durable"}
_H_NAME_OPS = {"replace": "replace", "rename": "rename",
               "remove": "remove", "rmtree": "rmtree"}

_RAW_OPS = {
    "os.fsync", "os.replace", "os.rename", "os.remove", "os.unlink",
    "os.truncate", "os.rmdir", "os.link", "shutil.rmtree", "shutil.move",
}

_ANNOT_RE = re.compile(
    r"#\s*durable:\s*(group-commit|dir-fsync)=(\w+)"
    r"(?:\s*--\s*(\S.*?))?\s*$")


def _annotations(sf: SourceFile) -> list[tuple[int, str, str, str | None]]:
    """[(line, kind, method, reason)] for every `# durable:` comment."""
    out = []
    for i, line in enumerate(sf.lines, start=1):
        m = _ANNOT_RE.search(line)
        if m:
            out.append((i, m.group(1), m.group(2), m.group(3)))
    return out


def _annotated(sf: SourceFile, line: int, kind: str,
               annots, verified: set[tuple[int, str]]) -> bool:
    """True when a VERIFIED annotation of ``kind`` sits on ``line`` or
    on a comment-only line directly above (same placement contract as
    suppressions). Marks the annotation used via ``verified``."""
    for aline, akind, _method, _reason in annots:
        if akind != kind:
            continue
        hit = aline == line or (
            aline == line - 1
            and sf.lines[aline - 1].lstrip().startswith("#"))
        if hit and (aline, akind) in verified:
            return True
    return False


def _call_name(call: ast.Call) -> str | None:
    """'open_durable' for bare calls, 'fsutil.replace' -> 'replace',
    raw ops ('os.replace', 'shutil.rmtree') kept dotted. Anything else
    — crucially list methods like ``self._order.remove(...)`` — is None:
    only the fsutil namespace spells protocol events."""
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    full = dotted_name(f)
    if full is None:
        return None
    if full in _RAW_OPS:
        return full
    head, _, tail = full.partition(".")
    if head == "fsutil" and tail and "." not in tail:
        return tail
    return None


def _arg_name(call: ast.Call, pos: int) -> str | None:
    if len(call.args) > pos and isinstance(call.args[pos], ast.Name):
        return call.args[pos].id
    return None


def _handle_expr(node: ast.AST) -> str | None:
    """Canonical key for a file-handle expression: 'f' or 'self._fh'."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return dotted_name(node)
    return None


class _Fn:
    """One analyzed function: its calls (source order), assignments and
    with-bindings — everything the per-function protocol check needs."""

    def __init__(self, node: ast.FunctionDef):
        self.node = node
        self.calls: list[ast.Call] = []
        self.yields: list[ast.Yield] = []
        self.handles: dict[str, ast.Call] = {}  # handle key -> open call
        self.temp_paths: set[str] = set()  # staging path variable names
        self._scan(node)
        self.calls.sort(key=lambda c: (c.lineno, c.col_offset))

    def _scan(self, root: ast.AST) -> None:
        for child in ast.iter_child_nodes(root):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue  # nested scopes run elsewhere
            if isinstance(child, ast.Assign):
                self._scan_assign(child)
            if isinstance(child, (ast.With, ast.AsyncWith)):
                for item in child.items:
                    if isinstance(item.context_expr, ast.Call) and \
                            _call_name(item.context_expr) == _H_OPEN and \
                            item.optional_vars is not None:
                        key = _handle_expr(item.optional_vars)
                        if key:
                            self.handles[key] = item.context_expr
            if isinstance(child, ast.Call):
                self.calls.append(child)
            if isinstance(child, ast.Yield) and child.value is not None:
                self.yields.append(child)
            self._scan(child)

    def _scan_assign(self, node: ast.Assign) -> None:
        if len(node.targets) != 1:
            return
        key = _handle_expr(node.targets[0])
        if key is None:
            return
        if isinstance(node.value, ast.Call) and \
                _call_name(node.value) == _H_OPEN:
            self.handles[key] = node.value
        # `tmp = path + ".tmp"`: a staging-path variable by construction
        if isinstance(node.value, ast.BinOp) and \
                isinstance(node.value.op, ast.Add) and \
                isinstance(node.value.right, ast.Constant) and \
                isinstance(node.value.right.value, str):
            self.temp_paths.add(key)
        if key.startswith("tmp") or key.endswith("tmp"):
            self.temp_paths.add(key)


def _functions(tree: ast.Module):
    """Every (class name or None, FunctionDef) in the module, plus the
    class-level handle attrs (self.X opened via open_durable ANYWHERE
    in the class — journal appends write a handle __init__ opened)."""
    out = []
    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            out.append((None, node))
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, ast.FunctionDef):
                    out.append((node.name, sub))
    return out


def _class_handles(tree: ast.Module) -> dict[str, set[str]]:
    """{class name: {'self._f', ...}} for attrs assigned from
    open_durable anywhere in the class body."""
    out: dict[str, set[str]] = {}
    for node in tree.body:
        if not isinstance(node, ast.ClassDef):
            continue
        attrs: set[str] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1 and \
                    isinstance(sub.value, ast.Call) and \
                    _call_name(sub.value) == _H_OPEN:
                key = _handle_expr(sub.targets[0])
                if key and key.startswith("self."):
                    attrs.add(key)
        if attrs:
            out[node.name] = attrs
    return out


def _seam_attrs(tree: ast.Module) -> set[str]:
    """self-attrs the module both ``.append(...)``s and ``.sync(...)``s
    — a buffered group-commit seam (the coordinator journal shape)."""
    appended: set[str] = set()
    synced: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute):
            base = dotted_name(node.func.value)
            if base is None or not base.startswith("self."):
                continue
            if node.func.attr == "append":
                appended.add(base)
            elif node.func.attr == "sync":
                synced.add(base)
    return appended & synced


def _method_has_barrier(tree: ast.Module, method: str,
                        kind: str) -> bool:
    """Does any function named ``method`` contain the promised barrier?
    group-commit: fsync_file/os.fsync/.sync(...) — content durability.
    dir-fsync: fsync_dir — name durability."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef) or node.name != method:
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            name = _call_name(sub)
            if kind == "dir-fsync" and name == _H_FSYNC_DIR:
                return True
            if kind == "group-commit":
                if name in (_H_FSYNC, "os.fsync"):
                    return True
                if isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr == "sync":
                    return True
    return False


def _check_annotations(sf: SourceFile, annots,
                       findings: list[Finding]) -> set[tuple[int, str]]:
    """Verify every `# durable:` annotation; returns the (line, kind)
    set of VERIFIED ones — only those excuse findings."""
    verified: set[tuple[int, str]] = set()
    for line, kind, method, reason in annots:
        if not reason:
            findings.append(Finding(
                RULE, sf.rel, line,
                f"`# durable: {kind}={method}` annotation without a "
                f"justification (use `# durable: {kind}=<method> -- "
                f"<why the barrier lives elsewhere>`)"))
            continue
        if not _method_has_barrier(sf.tree, method, kind):
            want = "fsync_dir" if kind == "dir-fsync" else \
                "fsync_file/os.fsync/.sync()"
            findings.append(Finding(
                RULE, sf.rel, line,
                f"`# durable: {kind}={method}` names a method that "
                f"does not contain the promised barrier ({want}) — "
                f"the deferred durability step is gone"))
            continue
        verified.add((line, kind))
    return verified


def _check_function(sf: SourceFile, cls: str | None, fn: _Fn,
                    class_handles: dict[str, set[str]],
                    seams: set[str], annots,
                    verified: set[tuple[int, str]],
                    findings: list[Finding]) -> None:
    core = sf.rel == CORE_REL
    handles = dict(fn.handles)
    if cls is not None:
        for attr in class_handles.get(cls, ()):
            handles.setdefault(attr, None)

    # event sweep: (line, kind, payload), in source order
    writes: list[tuple[int, str]] = []       # (line, handle)
    fsyncs: list[tuple[int, str]] = []       # (line, handle)
    dirsyncs: list[int] = []                 # lines
    name_ops: list[tuple[int, str, str | None]] = []  # (line, what, src)
    published: set[str] = set()              # replaced/renamed src names
    opened_tmp: dict[str, int] = {}          # temp path var -> open line
    appends: list[tuple[int, str]] = []      # (line, seam attr)
    seam_syncs: list[tuple[int, str]] = []   # (line, seam attr)

    for call in fn.calls:
        line = call.lineno
        # ---- handle writes + group-commit seams (any attribute call) -------
        if isinstance(call.func, ast.Attribute):
            base = dotted_name(call.func.value)
            if base:
                if call.func.attr == "write" and base in handles:
                    writes.append((line, base))
                if base in seams:
                    if call.func.attr == "append":
                        appends.append((line, base))
                    elif call.func.attr == "sync":
                        seam_syncs.append((line, base))
        name = _call_name(call)
        if name is None:
            continue
        # ---- raw calls -----------------------------------------------------
        if name in _RAW_OPS:
            if not core:
                findings.append(Finding(
                    RULE, sf.rel, line,
                    f"raw {name}() in a durable-checked module — route "
                    f"it through utils/fsutil so the protocol is "
                    f"checkable and the crash-point recorder sees it"))
            continue  # raw ops in CORE are the implementation, not events
        if name == "open":
            mode = call.args[1] if len(call.args) > 1 else None
            for kw in call.keywords:
                if kw.arg == "mode":
                    mode = kw.value
            if mode is None:
                continue  # default "r"
            if isinstance(mode, ast.Constant) and \
                    isinstance(mode.value, str):
                if not any(c in mode.value for c in "wxa"):
                    continue  # read-only
                findings.append(Finding(
                    RULE, sf.rel, line,
                    f"bare open(..., {mode.value!r}) writes durable "
                    f"state without the durable-write protocol — use "
                    f"fsutil.open_durable or fsutil.write_bytes_durable"))
            else:
                findings.append(Finding(
                    RULE, sf.rel, line,
                    "open() with a non-literal mode in a durable-checked "
                    "module — the protocol checker cannot classify it; "
                    "use fsutil.open_durable or a literal mode"))
            continue
        # ---- protocol events ----------------------------------------------
        if name == _H_OPEN:
            src = _arg_name(call, 0)
            mode_node = call.args[1] if len(call.args) > 1 else None
            mode = mode_node.value if isinstance(mode_node, ast.Constant) \
                else "wb"
            # any open_durable mode creates-or-extends the name: the
            # entry is durable only after a dir fsync
            name_ops.append((line, f"open_durable({src or '...'}, "
                                   f"{mode!r})", None))
            if src and src in fn.temp_paths:
                opened_tmp.setdefault(src, line)
            continue
        if name == _H_FSYNC:
            if call.args:
                key = _handle_expr(call.args[0])
                if key:
                    fsyncs.append((line, key))
            continue
        if name == _H_FSYNC_DIR:
            dirsyncs.append(line)
            continue
        if name in _H_WHOLE:
            continue  # the whole protocol in one self-contained call
        if name in _H_NAME_OPS:
            src = _arg_name(call, 0)
            name_ops.append((line, f"{name}({src or '...'})", src))
            if name in ("replace", "rename") and src:
                published.add(src)
            continue

    # ---- a handle yielded: the block that takes it writes through it -------
    for node in fn.yields:
        key = _handle_expr(node.value)
        if key in handles:
            writes.append((node.lineno, key))

    # ---- unsynced handle writes --------------------------------------------
    for line, handle in writes:
        if any(fl > line and fh == handle for fl, fh in fsyncs):
            continue
        if _annotated(sf, line, "group-commit", annots, verified):
            continue
        findings.append(Finding(
            RULE, sf.rel, line,
            f"write to durable handle {handle} with no later "
            f"fsutil.fsync_file({handle}) in this function — buffered "
            f"contents die with a crash; fsync before acking, or "
            f"declare the seam with `# durable: group-commit=<method> "
            f"-- <reason>`"))

    # ---- replace of an unsynced temp ---------------------------------------
    for line, what, src in name_ops:
        if src is None:
            continue
        # the handle whose open() first arg was this src name
        hkeys = [k for k, c in fn.handles.items()
                 if c is not None and _arg_name(c, 0) == src]
        for hkey in hkeys:
            wlines = [wl for wl, wh in writes if wh == hkey and wl < line]
            if not wlines:
                continue
            last_write = max(wlines)
            if any(last_write <= fl < line and fh == hkey
                   for fl, fh in fsyncs):
                continue
            findings.append(Finding(
                RULE, sf.rel, line,
                f"{what} publishes a temp file whose contents were "
                f"never fsynced — a crash can publish an empty or torn "
                f"file; fsutil.fsync_file({hkey}) before the replace"))

    # ---- staged temp never published ---------------------------------------
    for src, line in sorted(opened_tmp.items()):
        if src in published:
            continue
        findings.append(Finding(
            RULE, sf.rel, line,
            f"staging file {src} is opened durably but never "
            f"published via fsutil.replace/rename — the atomic-publish "
            f"sentence is incomplete"))

    # ---- name ops need a directory barrier ---------------------------------
    for line, what, _src in name_ops:
        if any(dl > line for dl in dirsyncs):
            continue
        if _annotated(sf, line, "dir-fsync", annots, verified):
            continue
        findings.append(Finding(
            RULE, sf.rel, line,
            f"{what} changes a durable directory entry with no later "
            f"fsutil.fsync_dir in this function — power loss can "
            f"silently undo it after the ack; fsync the directory, or "
            f"declare the seam with `# durable: dir-fsync=<method> -- "
            f"<reason>`"))

    # ---- buffered appends need the group-commit barrier --------------------
    for line, attr in appends:
        if any(sl > line and sa == attr for sl, sa in seam_syncs):
            continue
        if _annotated(sf, line, "group-commit", annots, verified):
            continue
        findings.append(Finding(
            RULE, sf.rel, line,
            f"{attr}.append(...) is a buffered group-commit append "
            f"with no later {attr}.sync() in this method — the record "
            f"is not durable when the caller acks; sync before acking, "
            f"or declare the seam with `# durable: group-commit="
            f"<method> -- <reason>`"))


def check(files: list[SourceFile]) -> list[Finding]:
    findings: list[Finding] = []
    for sf in files:
        if MARKER not in sf.markers or sf.tree is None:
            continue
        annots = _annotations(sf)
        verified = _check_annotations(sf, annots, findings)
        class_handles = _class_handles(sf.tree)
        seams = _seam_attrs(sf.tree)
        for cls, node in _functions(sf.tree):
            _check_function(sf, cls, _Fn(node), class_handles, seams,
                            annots, verified, findings)
    return sorted(findings, key=lambda f: (f.path, f.line))
