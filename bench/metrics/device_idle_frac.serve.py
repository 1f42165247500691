"""Share of the traced serving session in which no operation ran on the
device (profiler trace: one minus the union of op intervals over the
window). Moves ``tokens_per_s``."""


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("serve") is None or t is None or t.window_s <= 0:
        return None
    return 100.0 * t.idle_frac
