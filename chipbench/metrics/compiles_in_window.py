"""compiles_in_window — compiled produce program (core/presto,
core/execcache), a count.

Programs compiled, or read from the persistent compilation cache, while
the traced session ran: JAX's ``/jax/core/compile/backend_compile_duration``
events, which span both.  Set-up compiles and runs every program the window
can launch, so the target is 0; one inside the window stalls deliveries and
moves samples_per_s.
"""


def read(ctx):
    return ctx.compiles
