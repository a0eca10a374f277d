"""flowcensus: the SketchFamily registry — one descriptor per sketch
family, owning every per-kind fact the layers used to hardcode.

ROADMAP item 4's friction ledger is the motivation: onboarding
flowspread ("one kernel + one monoid") meant hand-editing ~20 files of
per-kind ``elif`` ladders, and nothing but reviewer diligence caught a
family that silently missed one surface. This module is the cure the
repo already proved twice (``KNOWN_FLAGS`` for flags, ``ABI_ALLOWLIST``
for the C seam): a single literal source of truth, with a both-ways
coverage lint (``tools/flowlint/rules_family.py``, rule
``family-citizenship``) that statically parses THIS file and checks

- every registered family is a complete citizen of every dispatch
  surface (mesh merge, codec payload, serve capture, gateway delta,
  checkpoint, flags, docs, Makefile parity target, CI wiring,
  Grafana/alert presence), and
- conversely, any string-literal kind tag at a dispatch site that is
  NOT registered here is a finding (the abi-contract "stale allowlist
  entries are themselves findings" discipline applied to families).

Registration style matters: each ``register(SketchFamily(...))`` call
below uses keyword literals only, so the lint rule can read the whole
registry with ``ast.literal_eval``-grade confidence and a deleted
kwarg (the ``make lint-mutation`` smoke) stays syntactically valid
but visibly incomplete.

Hooks are "module:attr" string references resolved lazily via
:func:`resolve` — strings keep the registry import-cycle-free (the
engine, mesh, serve and gateway layers all import this module) AND
statically checkable (the lint rule verifies each target exists by
parsing the named module, no imports needed).
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Any, Optional

# Kind tags that legitimately appear at dispatch sites but are NOT
# mergeable sketch families — the lint rule treats any other
# unregistered literal as a finding, and (abi-contract discipline)
# flags entries HERE that no dispatch site mentions any more.
#
# - "ddos": a detector, not a family — no mesh payload, no gateway
#   delta, no /query surface of its own (alerts ride the sink).
# - "flowguard": the serve publisher's pseudo-model carrying guard
#   status rows; state lives in guard/, not in a sketch.
NON_FAMILY_KINDS = (
    "ddos",
    "flowguard",
)


@dataclass(frozen=True)
class SketchFamily:
    """Every per-kind fact one sketch family owns, in one place.

    Optional hook fields default to ``None`` so an incomplete
    registration still *parses* — completeness is the lint rule's job,
    not the interpreter's. ``ranked`` families (top-K surface) must
    additionally carry ``top_rows`` + both serve captures + an
    ``endpoint``; ``wagg`` is unranked (exact rows, served by slot
    range) and legitimately leaves those ``None``.
    """

    # ---- identity ------------------------------------------------------
    kind: str                       # mesh ModelSpec.kind / FamilyView.kind
    snapshot_kind: Optional[str] = None   # model.snapshot_kind tag
    checkpoint_kind: Optional[str] = None  # tag in worker checkpoints
    payload_kinds: tuple = ()       # mesh codec payload["kind"] values
    # ---- merge algebra -------------------------------------------------
    merge_monoid: Optional[str] = None  # "u64-sum" | "max" | "rank-fold" | "i64-sum"
    ranked: bool = True             # has a top-K surface
    state_attr: Optional[str] = None    # model attr holding mergeable state
    # ---- hooks ("module:attr" refs, resolved lazily) -------------------
    payload: Optional[str] = None   # model state -> mesh payload dict
    merge: Optional[str] = None     # fold payloads -> merged state
    top_rows: Optional[str] = None  # merged state -> ranked rows
    serve_capture: Optional[str] = None         # worker FamilyView parts
    serve_capture_merged: Optional[str] = None  # mesh FamilyView parts
    checkpoint_save: Optional[str] = None       # model -> state dict
    checkpoint_restore: Optional[str] = None    # state dict -> model
    # ---- gateway delta -------------------------------------------------
    # (snapshot-state key, planes-first?) per diffable plane array; the
    # gateway's sparse/tile delta coder iterates this instead of
    # hardcoding "cms" vs "regs" cases. planes-first=True means the
    # array is stored lanes-last (HLL regs: [depth, width, regs]) and
    # must be viewed plane-major for per-plane diffing.
    delta_planes: tuple = ()
    # ---- audit shadow --------------------------------------------------
    audit_attr: Optional[str] = None    # HostGroupPipeline attribute
    audit_class: Optional[str] = None   # "module:Class" shadow auditor
    # ---- native dataplane probes ---------------------------------------
    # (feature, C symbol, since-revision) triples the hostsketch
    # pipeline resolves at startup: available -> mark_native_serving,
    # absent under a native backend -> report_native_degradation.
    native_probes: tuple = ()
    # ---- citizenship surfaces the lint pins ----------------------------
    flag_namespace: Optional[str] = None  # KNOWN_FLAGS prefix, e.g. "spread."
    endpoint: Optional[str] = None        # serve route, e.g. "/query/spread"
    parity_target: Optional[str] = None   # Makefile bit-exactness gate
    doc_token: Optional[str] = None       # must appear in ARCHITECTURE.md
    obs_token: Optional[str] = None       # metric in Grafana/alerts surface


FAMILIES: dict[str, SketchFamily] = {}

_BY_SNAPSHOT: dict[str, SketchFamily] = {}
_BY_CHECKPOINT: dict[str, SketchFamily] = {}
_BY_PAYLOAD: dict[str, SketchFamily] = {}
_RESOLVED: dict[str, Any] = {}


def register(fam: SketchFamily) -> SketchFamily:
    if fam.kind in FAMILIES:
        raise ValueError(f"sketch family {fam.kind!r} registered twice")
    FAMILIES[fam.kind] = fam
    if fam.snapshot_kind:
        _BY_SNAPSHOT[fam.snapshot_kind] = fam
    if fam.checkpoint_kind:
        _BY_CHECKPOINT[fam.checkpoint_kind] = fam
    for pk in fam.payload_kinds:
        _BY_PAYLOAD[pk] = fam
    return fam


def families() -> tuple[SketchFamily, ...]:
    """All registered families, in registration order (deterministic —
    dispatch loops built on this stay bit-stable run to run)."""
    return tuple(FAMILIES.values())


def family(kind: str) -> SketchFamily:
    try:
        return FAMILIES[kind]
    except KeyError:
        raise KeyError(
            f"unknown sketch family kind {kind!r} (registered: "
            f"{sorted(FAMILIES)}; see flow_pipeline_tpu/families/"
            "registry.py)") from None


def family_for_snapshot(snapshot_kind: str) -> Optional[SketchFamily]:
    """Family owning a ``model.snapshot_kind`` tag, else None (callers
    keep their own unknown-kind handling — a loud skip at restore, a
    TypeError at capture)."""
    return _BY_SNAPSHOT.get(snapshot_kind)


def family_for_payload(payload_kind: str) -> Optional[SketchFamily]:
    return _BY_PAYLOAD.get(payload_kind)


def family_for_checkpoint(checkpoint_kind: str) -> Optional[SketchFamily]:
    """Family owning a checkpoint "kind" tag, else None (unknown tags
    skip restore silently — the pre-registry fall-through)."""
    return _BY_CHECKPOINT.get(checkpoint_kind)


def resolve(ref: str) -> Any:
    """Import-and-cache a "module:attr" hook reference."""
    hit = _RESOLVED.get(ref)
    if hit is None:
        mod, _, attr = ref.partition(":")
        hit = getattr(importlib.import_module(mod), attr)
        _RESOLVED[ref] = hit
    return hit


def hook(fam: SketchFamily, name: str) -> Any:
    """Resolved hook callable for one family field, or None when the
    family does not participate in that surface."""
    ref = getattr(fam, name)
    return resolve(ref) if ref else None


def audit_attrs() -> tuple[tuple[str, str], ...]:
    """(kind, HostGroupPipeline audit attribute) for every family with
    a shadow auditor — the guard pause/serve merge loops iterate this
    instead of naming `audit` and `spread_audit` one by one."""
    return tuple((f.kind, f.audit_attr) for f in FAMILIES.values()
                 if f.audit_attr)


def delta_planes(payload_kind: str) -> tuple:
    """(state key, planes-first?) plane specs for one gateway snapshot
    family kind; () for unregistered kinds (the gateway falls back to
    full-ship, never guesses a diff layout)."""
    fam = _BY_PAYLOAD.get(payload_kind)
    return fam.delta_planes if fam else ()


# ---------------------------------------------------------------------------
# The registry proper. Keyword literals ONLY — tools/flowlint/
# rules_family.py parses these calls with ast and enforces both-ways
# coverage; computed values would blind it.
# ---------------------------------------------------------------------------

register(SketchFamily(
    kind="hh",
    snapshot_kind="windowed_hh",
    checkpoint_kind="windowed_hh",
    payload_kinds=("hh", "hh_inv"),
    merge_monoid="u64-sum",
    ranked=True,
    state_attr="state",
    payload="flow_pipeline_tpu.mesh.codec:hh_payload",
    merge="flow_pipeline_tpu.mesh.merge:merge_hh",
    top_rows="flow_pipeline_tpu.mesh.merge:hh_top_rows",
    serve_capture="flow_pipeline_tpu.serve.publisher:hh_view_parts",
    serve_capture_merged="flow_pipeline_tpu.serve.publisher:hh_merged_view",
    checkpoint_save="flow_pipeline_tpu.engine.worker:save_hh_state",
    checkpoint_restore="flow_pipeline_tpu.engine.worker:restore_hh_state",
    delta_planes=(("cms", False),),
    audit_attr="audit",
    audit_class="flow_pipeline_tpu.obs.audit:SketchAudit",
    native_probes=(("fused", "ff_fused_update", "r10"),
                   ("invsketch", "hs_inv_update", "r16")),
    flag_namespace="hh.",
    endpoint="/query/topk",
    parity_target="invertible-parity",
    doc_token="`hh`",
    obs_token="sketch_hh_recall",
))

register(SketchFamily(
    kind="wagg",
    snapshot_kind=None,
    checkpoint_kind="window_agg",
    payload_kinds=("wagg",),
    merge_monoid="u64-sum",
    ranked=False,
    state_attr=None,
    payload="flow_pipeline_tpu.mesh.codec:wagg_payload",
    merge="flow_pipeline_tpu.mesh.merge:merge_wagg",
    top_rows="flow_pipeline_tpu.models.window_agg:wagg_rows",
    serve_capture=None,
    serve_capture_merged=None,
    checkpoint_save="flow_pipeline_tpu.engine.worker:save_wagg_state",
    checkpoint_restore="flow_pipeline_tpu.engine.worker:restore_wagg_state",
    delta_planes=(),
    audit_attr=None,
    audit_class=None,
    native_probes=(),
    flag_namespace="window.",
    endpoint="/query/range",
    parity_target="mesh-parity",
    doc_token="`wagg`",
    obs_token="flow_commit_watermark_seconds",
))

register(SketchFamily(
    kind="dense",
    snapshot_kind="windowed_dense",
    checkpoint_kind="windowed_dense",
    payload_kinds=("dense",),
    merge_monoid="i64-sum",
    ranked=True,
    state_attr="totals",
    payload="flow_pipeline_tpu.mesh.codec:dense_payload",
    merge="flow_pipeline_tpu.mesh.merge:merge_dense",
    top_rows="flow_pipeline_tpu.mesh.merge:dense_top_rows",
    serve_capture="flow_pipeline_tpu.serve.publisher:dense_view_parts",
    serve_capture_merged="flow_pipeline_tpu.serve.publisher:dense_merged_view",
    checkpoint_save="flow_pipeline_tpu.engine.worker:save_dense_state",
    checkpoint_restore="flow_pipeline_tpu.engine.worker:restore_dense_state",
    delta_planes=(),
    audit_attr=None,
    audit_class=None,
    native_probes=(),
    flag_namespace="sketch.",
    endpoint="/query/topk",
    parity_target="fused-parity",
    doc_token="`dense`",
    obs_token="serve_queries_total",
))

register(SketchFamily(
    kind="spread",
    snapshot_kind="windowed_spread",
    checkpoint_kind="windowed_spread",
    payload_kinds=("spread",),
    merge_monoid="max",
    ranked=True,
    state_attr="canonical",
    payload="flow_pipeline_tpu.mesh.codec:spread_payload",
    merge="flow_pipeline_tpu.mesh.merge:merge_spread",
    top_rows="flow_pipeline_tpu.mesh.merge:spread_top_rows",
    serve_capture="flow_pipeline_tpu.serve.publisher:spread_view_parts",
    serve_capture_merged="flow_pipeline_tpu.serve.publisher:spread_merged_view",
    checkpoint_save="flow_pipeline_tpu.engine.worker:save_spread_state",
    checkpoint_restore="flow_pipeline_tpu.engine.worker:restore_spread_state",
    delta_planes=(("regs", True),),
    audit_attr="spread_audit",
    audit_class="flow_pipeline_tpu.obs.audit:SpreadAudit",
    native_probes=(("spread", "hs_spread_update", "r21"),),
    flag_namespace="spread.",
    endpoint="/query/spread",
    parity_target="spread-parity",
    doc_token="`spread`",
    obs_token="spread_top_max",
))
