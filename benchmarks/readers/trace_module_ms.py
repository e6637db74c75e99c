"""Device milliseconds of one execution of a program, by its name on the
trace's ``XLA Modules`` line (``{"module": "jit_lm_decode"}``): the mean
over the executions that ran whole inside the traced slice's window. The
program's whole time on the device, whatever its instructions' scopes
say. An untraced run, or no whole execution of that name: nothing
returned."""

from benchmarks.harness import host_trace


def read(ctx: dict, args: dict):
    trace = host_trace.of_run(ctx)
    if trace is None:
        return None
    return trace.module_ms(args["module"])
