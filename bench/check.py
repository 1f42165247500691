"""The comparison that decides ``correct`` for a served model.

Once the window has closed, every request due in it that finished is run
through the plain reference, one sequence per call at one padded length
(prompt and served tokens, causal, so padding changes nothing before it).
At each served token the reference's logit of that token lies some way
below the reference's best logit at that position: its *shortfall*, 0 when
the served token is the reference's first choice. The cell's limits file
names which numbers over the served tokens are compared:

- ``max_logit_gap``: the widest shortfall;
- ``mean_cube_logit_gap``: the mean of the cubed shortfalls, which weighs
  the many small shortfalls of near-ties little and the large ones much.

The control puts the reference in bfloat16 in the program's place and
reads the same numbers for the tokens it would put first at the same
positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = {
    "max_logit_gap": lambda g: float(g.max(initial=0.0)),
    "mean_cube_logit_gap": lambda g: float(np.mean(g ** 3)) if len(g)
    else 0.0,
}


def moments(g) -> dict:
    """Shortfalls summed up for choosing what to compare: how many are not
    0, the widest, and the mean of their powers 1 to 4."""
    g = np.asarray(g, np.float64)
    out = {"n": int(g.size), "nonzero": int((g > 0).sum()),
           "max": float(g.max(initial=0.0))}
    out.update({f"mean_pow{k}": float(np.mean(g ** k)) if g.size else 0.0
                for k in (1, 2, 3, 4)})
    return out


def gaps(forward, params, c: dict, seqs: list, length: int,
         control: bool = False) -> dict:
    """Shortfalls of the served tokens (and of the control's picks) over
    ``seqs`` = [(prompt, served tokens)] -> {"program": array, "control":
    array or None}."""

    def one(params, toks, target, mask):
        ref = forward(params, c, toks, jnp.float32)
        best = ref.max(-1)
        prog = jnp.where(mask, best - jnp.take_along_axis(
            ref, target[:, None], -1)[:, 0], 0.0)
        if not control:
            return prog, jnp.zeros_like(prog)
        pick = jnp.argmax(forward(params, c, toks, jnp.bfloat16), -1)
        ctl = jnp.where(mask, best - jnp.take_along_axis(
            ref, pick[:, None], -1)[:, 0], 0.0)
        return prog, ctl

    fn = jax.jit(one)
    prog, ctl = [], []
    for prompt, out in seqs:
        seq = list(prompt) + list(out)
        if len(seq) > length:
            raise ValueError(f"sequence of {len(seq)} > reference length "
                             f"{length}")
        toks = np.zeros(length, np.int32)
        toks[:len(seq)] = seq
        target = np.zeros(length, np.int32)
        target[:len(seq) - 1] = seq[1:]
        mask = np.zeros(length, bool)
        mask[len(prompt) - 1:len(seq) - 1] = True   # predicts a served token
        p, q = fn(params, jnp.asarray(toks), jnp.asarray(target),
                  jnp.asarray(mask))
        prog.append(np.asarray(p)[mask])
        ctl.append(np.asarray(q)[mask])
    return {"program": np.concatenate(prog) if prog else np.zeros(0),
            "control": np.concatenate(ctl) if control and ctl else None}
