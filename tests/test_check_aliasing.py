"""Arena aliasing analysis (AL2xx): negatives per rule + clean layouts."""

import numpy as np
import pytest

from repro.check.aliasing import (
    check_agreement,
    check_feed_layout,
    check_plan,
    check_ring,
)
from repro.core.devicefeed import FeedLayout, SlotSpec


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------------- AL201
def test_al201_overlapping_intervals():
    assert _rules(check_plan([256, 256], [0, 128], 512)) == ["AL201"]


def test_al201_last_slot_overruns_total():
    assert _rules(check_plan([128, 256], [0, 128], 256)) == ["AL201"]


def test_al201_unordered_offsets_still_detected():
    # Overlap check sorts by offset first.
    assert _rules(check_plan([256, 256], [128, 0], 512)) == ["AL201"]


# ------------------------------------------------------------------- AL202
def test_al202_misaligned_offset():
    assert _rules(check_plan([64], [8], 128)) == ["AL202"]


def test_al202_misaligned_total():
    assert _rules(check_plan([64], [0], 100)) == ["AL202"]


def test_al202_custom_alignment():
    assert check_plan([64], [8], 128, align=8) == []
    assert _rules(check_plan([64], [4], 128, align=8)) == ["AL202"]


# ------------------------------------------------------------------- AL203
def test_al203_negative_size():
    assert _rules(check_plan([-1], [0], 128)) == ["AL203"]


def test_al203_int32_overflow():
    assert "AL203" in _rules(check_plan([2**31], [0], 2**31 + 128))


def test_al203_overflowing_layout_reports_instead_of_crashing():
    layout = FeedLayout(slots=(SlotSpec("huge", 2**22, "float32"),))
    findings = check_feed_layout(layout, rows=2**10)
    assert _rules(findings) == ["AL203"]


# ------------------------------------------------------------------- AL204
def test_al204_planner_disagreement():
    findings = check_agreement({"a": ([0, 128], 256), "b": ([0, 256], 384)})
    assert _rules(findings) == ["AL204"]


def test_al204_offset_count_mismatch():
    assert _rules(check_plan([64, 64], [0], 128)) == ["AL204"]


def test_al204_agreeing_planners_are_clean():
    assert check_agreement({"a": ([0, 128], 256), "b": ([0, 128], 256)}) == []


# ------------------------------------------------------------------- AL205
def test_al205_zero_buffers_is_an_error():
    findings = check_ring(None, -1, buffers=0)
    assert [f.rule for f in findings] == ["AL205"]
    assert findings[0].severity == "error"


def test_al205_underprovisioned_ring_warns():
    findings = check_ring(None, -1, buffers=2, queue_capacity=2,
                          donate=False)
    assert _rules(findings) == ["AL205"]
    assert all(f.severity == "warning" for f in findings)


def test_al205_default_queue_bound_is_satisfied():
    # PipelinedRunner's maxsize=max(1, buffers-2) keeps buffers >= 3 clean.
    layout = FeedLayout(slots=(SlotSpec("batch_label", 1, "float32",
                                        rank1=True),))
    assert check_ring(layout, 8, buffers=3) == []


# ------------------------------------------------------------------- AL206
def test_al206_donation_fence_unreachable():
    findings = check_ring(None, -1, buffers=1, queue_capacity=1)
    assert "AL206" in _rules(findings)
    al206 = [f for f in findings if f.rule == "AL206"]
    assert al206[0].severity == "error"


def test_al206_not_raised_without_donation():
    findings = check_ring(None, -1, buffers=1, queue_capacity=1,
                          donate=False)
    assert "AL206" not in _rules(findings)


# ----------------------------------------------------------- clean layouts
@pytest.mark.parametrize("preset", ["ads_ctr", "dlrm", "bst"])
@pytest.mark.parametrize("split", [False, True])
def test_compiled_layouts_pass_the_tri_oracle(preset, split):
    from repro.fe import featureplan, get_spec
    plan = featureplan.compile(get_spec(preset))
    layout = plan.feed_layout(split_sparse_fields=split)
    findings = check_feed_layout(layout, rows=64)
    assert findings == [], "\n".join(f.render() for f in findings)
    assert check_ring(layout, 64, buffers=3) == []


def test_hand_built_layout_tri_oracle_matches_arena_pool():
    layout = FeedLayout(slots=(
        SlotSpec("a", 3, "float32"),
        SlotSpec("b", 1, "int64", rank1=True),
        SlotSpec("c", 17, "int32"),
    ))
    assert check_feed_layout(layout, rows=33) == []


def test_corrupt_plan_offsets_detected_against_oracle():
    layout = FeedLayout(slots=(SlotSpec("a", 4, "float32"),
                               SlotSpec("b", 4, "float32")))
    offsets, total = layout.plan(16)
    bad = np.array(offsets)
    bad[1] = 0  # collide with slot a
    findings = check_plan(layout.sizes(16), list(bad), total,
                          names=layout.slot_names)
    assert "AL201" in _rules(findings)


def test_feed_layout_consults_every_planner(monkeypatch):
    """AL204 compares all of PLANNERS, the compiled-or-interpreted Pallas
    kernel among them (no planner is skipped silently)."""
    from repro.check import aliasing

    seen = {}
    real = aliasing.check_agreement

    def spy(plans, **kw):
        seen.update(plans)
        return real(plans, **kw)

    monkeypatch.setattr(aliasing, "check_agreement", spy)
    layout = FeedLayout((SlotSpec("a", 3, "float32"), SlotSpec("b", 1, "int32", rank1=True)))
    assert check_feed_layout(layout, rows=64) == []
    assert sorted(seen) == sorted(aliasing.PLANNERS)
