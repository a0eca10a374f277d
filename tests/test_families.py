"""flowcensus runtime contracts: the SketchFamily registry the
dispatch layers iterate (flow_pipeline_tpu/families/registry.py).

The static side — completeness of every registration, both-ways kind
coverage — is the family-citizenship lint rule's job
(tests/test_flowlint.py). Here: the runtime API the refactored
dispatch sites actually call."""

import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

from flow_pipeline_tpu.families import registry  # noqa: E402


class TestRegistryShape:
    def test_registration_order_is_deterministic(self):
        # dispatch loops built on families() must stay bit-stable
        assert [f.kind for f in registry.families()] == \
            ["hh", "wagg", "dense", "spread"]

    def test_unknown_kind_raises_helpfully(self):
        with pytest.raises(KeyError, match="registered:"):
            registry.family("hll")

    def test_snapshot_kind_index(self):
        assert registry.family_for_snapshot("windowed_hh").kind == "hh"
        assert registry.family_for_snapshot("windowed_spread").kind \
            == "spread"
        assert registry.family_for_snapshot("no_such_kind") is None
        # wagg has no snapshot kind: windows are exact stores, captured
        # by the member's isinstance branch, never via snapshot_kind
        assert registry.family("wagg").snapshot_kind is None

    def test_checkpoint_kind_index(self):
        assert registry.family_for_checkpoint("window_agg").kind == "wagg"
        assert registry.family_for_checkpoint("windowed_dense").kind \
            == "dense"
        assert registry.family_for_checkpoint("ddos") is None

    def test_payload_kind_index_covers_invertible(self):
        # both wire tags of the hh family route to one descriptor
        assert registry.family_for_payload("hh").kind == "hh"
        assert registry.family_for_payload("hh_inv").kind == "hh"
        assert registry.family_for_payload("spread").kind == "spread"


class TestHooks:
    def test_every_registered_hook_resolves(self):
        # the lint checks this statically (parse, no imports); the
        # runtime twin actually imports every target once
        hook_fields = ("payload", "merge", "top_rows", "serve_capture",
                       "serve_capture_merged", "checkpoint_save",
                       "checkpoint_restore", "audit_class")
        for fam in registry.families():
            for field in hook_fields:
                ref = getattr(fam, field)
                if ref:
                    assert callable(registry.resolve(ref)), \
                        (fam.kind, field)

    def test_hook_returns_none_for_absent_surface(self):
        wagg = registry.family("wagg")
        assert registry.hook(wagg, "serve_capture") is None

    def test_merge_hooks_share_one_signature(self):
        # the coordinator calls every merge hook as (payloads, config)
        from flow_pipeline_tpu.mesh import merge as merge_ops

        assert registry.hook(registry.family("hh"), "merge") \
            is merge_ops.merge_hh
        assert registry.hook(registry.family("wagg"), "merge") \
            is merge_ops.merge_wagg
        assert len(merge_ops.merge_wagg([], config=None)) == 0

    def test_resolve_caches(self):
        ref = registry.family("spread").merge
        assert registry.resolve(ref) is registry.resolve(ref)


class TestFacts:
    def test_audit_attrs_iterates_shadowed_families(self):
        # the guard pause and serve merge loops iterate this instead of
        # naming `audit` / `spread_audit` one by one
        assert registry.audit_attrs() == (("hh", "audit"),
                                          ("spread", "spread_audit"))

    def test_delta_planes_by_payload_kind(self):
        assert registry.delta_planes("hh") == (("cms", False),)
        assert registry.delta_planes("hh_inv") == (("cms", False),)
        assert registry.delta_planes("spread") == (("regs", True),)
        assert registry.delta_planes("wagg") == ()
        assert registry.delta_planes("never_registered") == ()

    def test_merge_monoids_match_the_algebra(self):
        monoids = {f.kind: f.merge_monoid for f in registry.families()}
        assert monoids == {"hh": "u64-sum", "wagg": "u64-sum",
                           "dense": "i64-sum", "spread": "max"}

    def test_double_registration_rejected(self):
        with pytest.raises(ValueError, match="registered twice"):
            registry.register(registry.SketchFamily(kind="hh"))
