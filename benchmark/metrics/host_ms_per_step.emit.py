"""Host milliseconds a step in ``serve.step.emit``: the sampled tokens
to events, retirement, and a cache kind's window closes."""

from benchmark.work import host_phases

PHASES = ("serve.step.emit",)


def read(ctx):
    return host_phases.ms_per_step(ctx, PHASES)
