"""Share of the batcher's slots that fed a token, over the engine steps of
the traced slice of the window: the slots in use at each step (counted
around the frontend's calls into the engine) over slots times steps.
Moves ``tokens_per_s``."""


def read(ctx):
    s = ctx.get("serve")
    if s is None or not s["steps"]:
        return None
    return 100.0 * s["slot_steps"] / (s["n_slots"] * s["steps"])
