"""Names of the host spans on the served produce path.

Each span is a ``jax.profiler.TraceAnnotation`` opened at its call site.
There is no switch of its own: a span is recorded only while a profiler
session is active (``jax.profiler.trace``, ``start_trace`` or
``start_server``), and otherwise costs the annotation's inactive check.
Recorded spans land on the host plane, on the same clock as the device's
"XLA Ops", so each device-idle gap can be put down to one phase.

Every span is a leaf: none encloses another ``presto.*`` span on the same
thread.  Per-partition spans carry ``pid=``; per-launch spans carry ``k=``
(the launch's megabatch width) and the ``pid=`` of its first partition.

This module imports nothing, so the data layer can name its spans without
loading JAX.
"""

# pool worker: the scheduler's claim of a task, and the extra claims a
# megabatch coalesces into it
CLAIM = "presto.claim"
# pool worker: ``columnar.read_partition`` in three parts — file I/O (open,
# header, body), the body's checksum, and the page-table decode
READ_IO = "presto.read.io"
READ_VERIFY = "presto.read.verify"
READ_DECODE = "presto.read.decode"
# pool worker: ``stage_pages``, with ``stored=1`` where the staged array is
# the partition's stored body and ``stored=0`` where it was concatenated
# (``pages_from_partition``, after inflation, where a mesh needs it)
PAGE_BUILD = "presto.page_build"
# pool worker: ``stack_pages`` of a chunk (a view at K=1, a copy at K>1)
STACK = "presto.stack"
# pool worker: host-to-device placement of a chunk's pages
PUT = "presto.put"
# pool worker: the call of the compiled produce program (asynchronous)
DISPATCH = "presto.dispatch"
# pool worker: waiting on the device for a dispatched chunk
FINISH = "presto.finish"
# pool worker: futures resolved and ledgers charged for a finished chunk
DELIVER = "presto.deliver"
# pool worker: asleep on the pool's wake-up condition, nothing claimable
IDLE = "presto.idle"
# consumer: ``Session`` iteration blocked on its next batch
CONSUMER_WAIT = "presto.consumer_wait"

SPANS = (
    CLAIM, READ_IO, READ_VERIFY, READ_DECODE, PAGE_BUILD, STACK, PUT,
    DISPATCH, FINISH, DELIVER, IDLE, CONSUMER_WAIT,
)
