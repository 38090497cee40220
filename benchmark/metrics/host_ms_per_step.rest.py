"""Host milliseconds a step in the loop's other working phases:
admission, drafting, accounting, the front door's pump and the routing of
events to the handlers' queues.  Not ``serve.step.sync`` and not
``serve.loop.wait``: those wait, for the device and for work."""

from benchmark.work import host_phases

PHASES = ("serve.step.admit", "serve.step.draft", "serve.step.account",
          "serve.pump", "serve.stream.route")


def read(ctx):
    return host_phases.ms_per_step(ctx, PHASES)
