"""worker_busy_share — service / scheduler (core/service, data/loader), in %.

For each pool worker thread of the traced session (a line of the trace
holding a ``presto.claim`` span), 1 less the share of the window its
``presto.idle`` spans cover (asleep on the pool's wake-up condition with
nothing claimable, as under backpressure), clipped to the window; the mean
over worker threads.  Near 100 says the workers are saturated; lower says
they wait on the consumer or the queue depth.  Moves samples_per_s.
"""

from chipbench.spans import busy_share

WORKER_SPAN = "presto.claim"
IDLE_SPAN = "presto.idle"


def read(ctx):
    return busy_share(ctx.trace, WORKER_SPAN, IDLE_SPAN)
