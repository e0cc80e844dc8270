"""page_build_ms — page build (core/preprocess), in ms per partition.

The mean self time of the engine's stage spans (``stage_partition``: the
store read plus ``pages_from_partition``) less the read spans nested in
them, over the traced session.  ``stack_pages`` of a megabatch runs inside
the service, outside any span the benchmark can open, and is not counted.
Moves samples_per_s where the host bounds the rate.
"""


def read(ctx):
    builds = ctx.trace.page_builds()
    if not builds:
        return None
    return sum(b[3] for b in builds) / len(builds) / 1e6
