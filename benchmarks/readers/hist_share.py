"""One of the program's histograms as a share of the window: 100 x the
delta of its sum over ``window_s``. A histogram of seconds the program
spends in some state (a collection of the cyclic collector). Nothing
observed in the window (a program without it, or a window the state
never entered): nothing returned, as ``hist_mean``."""


def read(ctx: dict, args: dict):
    total, count = ctx["window"].hist(args["hist"])
    if count <= 0:
        return None
    return 100.0 * total / ctx["window_s"]
