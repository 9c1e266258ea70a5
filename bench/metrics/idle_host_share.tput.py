"""idle_host_share: the share of the traced window in which a replica's
device was idle while its decode loop was inside an iteration
(``ham.loop.iter``) but outside its waits on the device (``ham.admit.wait``,
``ham.block.wait``): the device waiting on host work.  Mean over replicas.
Needs the program's spans on the trace's clock (``ctx.spans.idle``)."""

from bench.program_trace import host_share


def read(ctx):
    spans = getattr(ctx, "spans", None)
    return host_share(spans.idle) if spans else None
