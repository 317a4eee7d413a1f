"""repro.check — static analysis over compiled plans, arenas, and threads.

Four analyzers behind one :class:`~repro.check.findings.Finding`-based
report, run as a driver preflight (``launch/train.py --check``) and CI gate
(``python -m repro.check --preset ... --arch ...``), with NO execution of
the plan:

* :mod:`repro.check.planverify` — abstract dtype/shape flow over the
  compiled OpGraph/Schedule, placement-boundary legality, OutputLayout
  contract, projection completeness, ModelFeed remap bounds (PV1xx);
* :mod:`repro.check.aliasing`   — arena block-plan interference (interval
  disjointness, alignment, int32 safety, planner-oracle agreement) and
  ring/donation lifetime safety (AL2xx);
* :mod:`repro.check.effects`    — jaxpr effects scan of every fused
  superlayer and the fused train step on abstract shapes, plus donation
  marker verification (EF3xx);
* :mod:`repro.check.lockset`    — AST lockset audit of the pipeline's
  thread-shared state against the :mod:`repro.check.annotations`
  convention (LK4xx).

This ``__init__`` stays import-light on purpose: :mod:`repro.core` modules
import the annotation decorators from here, so pulling the analyzers in
eagerly would create an import cycle through :mod:`repro.fe`. Analyzers
load lazily inside :func:`run_check`.
"""

from repro.check.annotations import guarded_by, shared_entry, single_writer
from repro.check.findings import SEVERITIES, Finding, Report

__all__ = [
    "SEVERITIES",
    "Finding",
    "Report",
    "guarded_by",
    "run_check",
    "shared_entry",
    "single_writer",
]


def run_check(preset: str, arch: str, *, rows: int = 8,
              analyzers=("plan", "aliasing", "effects", "lockset")) -> Report:
    """Run the static analyzers against one FE preset x model arch pair.

    Compiles the ``preset`` FeatureSpec and the ``arch``'s smoke config
    exactly the way ``launch/train.py`` streaming mode wires them, then
    audits the compiled artifacts without executing a batch. Returns a
    :class:`Report` whose ``exit_code`` follows the 0/1/2 contract of
    ``benchmarks/run.py --compare`` (0 clean, 1 analyzer crashed, 2 error
    findings).
    """
    report = Report()

    if "lockset" in analyzers:
        try:
            from repro.check import lockset
            report.record_analyzer("lockset", lockset.audit_default())
        except Exception as e:  # noqa: BLE001 - crash IS the report payload
            report.record_crash("lockset", e)

    plan = mf = None
    try:
        from repro.configs import get_arch
        from repro.fe import featureplan, get_spec

        spec = get_spec(preset)
        plan = featureplan.compile(spec)
        cfg = get_arch(arch).smoke()
        mf = plan.model_feed(cfg, split_sparse_fields=True)
    except Exception as e:  # noqa: BLE001
        report.record_crash("compile", e)
        return report

    if "plan" in analyzers:
        try:
            from repro.check import planverify
            findings = planverify.verify_plan(plan, rows=rows)
            findings += planverify.verify_model_feed(
                mf, plan.feed_layout(split_sparse_fields=mf.split))
            report.record_analyzer("plan", findings)
        except Exception as e:  # noqa: BLE001
            report.record_crash("plan", e)

    if "aliasing" in analyzers:
        try:
            from repro.check import aliasing
            findings = []
            for split in (False, True):
                layout = plan.feed_layout(split_sparse_fields=split)
                where = (f"{preset}/feed_layout"
                         f"{'[split]' if split else '[packed]'}")
                findings += aliasing.check_feed_layout(layout, rows,
                                                       location=where)
                findings += aliasing.check_ring(layout, rows, buffers=3,
                                                location=where)
            if not any(f.rule == "AL204" for f in findings):
                from repro.kernels.mempool_alloc.ops import kernel_interpreted
                mode = ("interpreted" if kernel_interpreted()
                        else "compiled for the TPU")
                findings.append(Finding(
                    rule="AL204", severity="info", location=preset,
                    message=(f"planners {', '.join(aliasing.PLANNERS)} agree "
                             f"on both feed layouts; pallas_kernel {mode}")))
            report.record_analyzer("aliasing", findings)
        except Exception as e:  # noqa: BLE001
            report.record_crash("aliasing", e)

    if "effects" in analyzers:
        try:
            from repro.check import effects
            report.record_analyzer(
                "effects", effects.scan_preset(plan, mf, rows=rows))
        except Exception as e:  # noqa: BLE001
            report.record_crash("effects", e)

    return report
