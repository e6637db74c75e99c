"""Mean of one of the program's histograms over the window: delta of its
sum over delta of its count, times ``scale``. Nothing observed: nothing
returned."""


def read(ctx: dict, args: dict):
    name = args.get("hist") or ctx["cell"].config[args["hist_from_config"]]
    total, count = ctx["window"].hist(name)
    if count <= 0:
        return None
    return args.get("scale", 1.0) * total / count
