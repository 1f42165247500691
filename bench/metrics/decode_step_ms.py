"""Device time of one run of the batcher's compiled decode step (profiler
trace: the ``XLA Modules`` events of the ``_step_fn`` executable, summed
and divided by their number). Moves ``tokens_per_s``."""

EXECUTABLE = "_step_fn"


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("serve") is None or t is None:
        return None
    runs = [v for k, v in t.modules.items() if EXECUTABLE in k]
    n = sum(r[0] for r in runs)
    if not n:
        return None
    return 1e3 * sum(r[1] for r in runs) / n
