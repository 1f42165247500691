"""Model FLOPs of every token fed in the traced slice of the window
(prompt and decode tokens, ``bench/flops.py``, counted per engine step)
over the slice's length times the chips' bf16 peak, in percent. Moves
``tokens_per_s``."""


def read(ctx):
    s, t = ctx.get("serve"), ctx.get("trace")
    if s is None or t is None or not s["model_flops"]:
        return None
    peak = ctx["peak"]["bf16_flops_per_s"] * t.n_devices
    return 100.0 * s["model_flops"] / (t.window_s * peak)
