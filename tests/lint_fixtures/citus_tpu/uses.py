"""Fixture use sites driving both directions of every registry rule."""

from .planner.explain import explain_tag
from .stats import counters as sc
from .stats.tracing import stage_scope, trace_span
from .utils.faultinjection import FAULT_POINTS  # noqa: F401


def fault_point(name):
    return name


class _Counters:
    def increment(self, name, by=1):
        return by


counters = _Counters()


def run(settings):
    fault_point("store.x")               # registered: clean
    fault_point("not.registered")        # fault-point-registry
    counters.increment(sc.ROWS_SEEN)     # listed: clean
    counters.increment(sc.UNKNOWN_NAME)  # counter-registry (undefined)
    settings.get("live_knob")            # registered: clean
    settings.get("ghost_knob")           # config-registry (unregistered)
    explain_tag("Live Tag")              # registered: clean
    explain_tag("Ghost Tag")             # explain-tag-registry
    trace_span("live.span")              # registered: clean
    trace_span("ghost.span")             # span-registry
    stage_scope("live_stage")            # registered: clean
    counters._record(0, "live_kind", 0, 0)   # a capacity stage: clean
    counters._record(0, "ghost_kind", 0, 0)  # span-registry
    return stage_scope("ghost_stage")    # span-registry
