"""Host milliseconds a step in ``serve.step.dispatch``: the step's
``jnp.asarray`` calls and the call of the jitted step, during which the
device has nothing to run."""

from benchmark.work import host_phases

PHASES = ("serve.step.dispatch",)


def read(ctx):
    return host_phases.ms_per_step(ctx, PHASES)
