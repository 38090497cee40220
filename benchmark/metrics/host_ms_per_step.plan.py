"""Host milliseconds a step in ``serve.step.plan``: ``plan_spans``, page
taking, copy-on-write and the span arrays (a cache kind's ``table_row``
and ``step_aux`` with them)."""

from benchmark.work import host_phases

PHASES = ("serve.step.plan",)


def read(ctx):
    return host_phases.ms_per_step(ctx, PHASES)
