"""Several of the program's histograms summed, over the count of one:
(sum of the deltas of each ``hists`` sum) over the delta of ``per``'s
count, times ``scale``. The host's time a dispatch when one dispatch
observes its parts in more than one place. ``per`` not observed in the
window (a program without it): nothing returned."""


def read(ctx: dict, args: dict):
    window = ctx["window"]
    _total, count = window.hist(args["per"])
    if count <= 0:
        return None
    total = sum(window.hist(name)[0] for name in args["hists"])
    return args.get("scale", 1.0) * total / count
