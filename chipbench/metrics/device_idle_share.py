"""device_idle_share — device, in %.

1 minus the union of the device's operation intervals ("XLA Ops" of the
trace) over the traced session's window, which runs from the submit of the
session to its last delivery.  A high share says the host holds the chip
back; moves samples_per_s.
"""


def read(ctx):
    window = ctx.trace.window_s()
    if window <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / window)
