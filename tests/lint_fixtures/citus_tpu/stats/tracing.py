"""Fixture registry: span names (one recorded, one dead)."""

SPAN_NAMES = {
    "live.span": "recorded by uses.py",
    "dead.span": "never recorded",        # span-registry
}

STAGE_NAMES = {
    "live_stage": "scoped by uses.py",
    "live_kind": "a capacity stage recorded by uses.py",
    "dead_stage": "never scoped",        # span-registry
}


def stage_scope(name):
    STAGE_NAMES[name]
    return name


def trace_span(name, **meta):
    SPAN_NAMES[name]
    return name


def span_name(name):
    SPAN_NAMES[name]
    return name
