"""What the program names itself, per window step: the device time of its
train step's phases, and the host time of its own spans.

The phases: with tracing on, the program's step records on its tracer,
before the window, which compiled instruction runs in which phase
(``repro.obs.phases``; the annotation ``step.phases`` of the tracer
``repro.obs.trace.get_tracer`` returns). The trace's per-op device time,
summed over the instructions of one phase, is that phase's time. Ops
nested inside a top-level instruction (a while loop's body) are in no map
and count once, through their loop. A program that records no map, or a
run without a device trace, reads None.
"""

from __future__ import annotations

from typing import Dict, Optional

from . import tracing

STEP_PHASES = "step.phases"


def phase_map() -> Optional[Dict[str, str]]:
    """``{instruction name: phase}`` of the step, where the program
    recorded one."""
    from repro.obs.trace import get_tracer
    metadata = getattr(get_tracer(), "metadata", None) or {}
    return metadata.get(STEP_PHASES) or None


def phase_ms_per_step(run, phase: str) -> Optional[float]:
    """Device milliseconds per window step of the step's ``phase``, per
    chip."""
    phases = phase_map()
    if run.trace is None or phases is None:
        return None
    s = sum(t for label, t in run.trace["ops_by_name"].items()
            if phases.get(label.split(" ", 1)[0]) == phase)
    return s / run.steps * 1e3


def span_ms_per_step(run, name: str) -> Optional[float]:
    """Milliseconds per window step of the program's ``name`` spans,
    summed over threads; None where the program records no such span."""
    if run.spans is None or not any(s[0] == name for s in run.spans):
        return None
    return tracing.span_seconds(run.spans, name, run.window_ns) / run.steps * 1e3
