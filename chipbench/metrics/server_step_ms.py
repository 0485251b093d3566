"""Device time per round of the server step: the fused flat-buffer program
(fl/flatbuf.py ``ServerStep._step_impl``), the stacking of client rows into
deltas (``rows_to_deltas``), the unflatten of the new global, and the
gathers and scatters of client and error-feedback rows around them
(fl/loop.py, ``take_rows``), in ms/round.  The trace names each executable
after its jitted function; the gathers and scatters are JAX's own."""

MODULES = (r"_step_impl|_deltas_stacked_impl|_unflatten_impl"
           r"|^jit_gather\(|^jit_scatter\(")


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    s = ctx.trace.module_s(MODULES)
    return 1e3 * s / ctx.out["rounds"] if s > 0 else None
