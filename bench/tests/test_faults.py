"""The rest of a run, without the look for a chip, at a toy size on the
CPU: the sound program comes out ``correct``, and each fault planted under
the timed path makes ``correct`` false.

Faults a serving cell can have: a token altered where it is produced, and
a decode step that returns its state (the KV cache) unchanged. The
bfloat16 control, put in the program's place (``--control``), has to come
out not correct as well; its limit-setting readings come from the chip at
the cell's own size (``PERF.md``).

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest bench/tests -q``.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
import run  # noqa: E402

SEED = 2**31 + 12345
CELLS = [("chat.8x1024", "qwen3-0.6b.chat"),
         ("solve.4x512", "qwen3-0.6b.solve")]


def _run(traffic, name, control=False):
    return run.run_cell(tiny.cell(traffic, name), SEED, seconds=1.5,
                        trace=False, control=control)


@pytest.mark.parametrize("traffic,name", CELLS)
def test_sound_program_is_correct(traffic, name):
    result, compared = _run(traffic, name)
    assert result["correct"], compared
    assert result["attempted"] > 0 and result["failed"] == 0
    assert compared["compiles_in_window"][0] == 0
    assert set(result["metrics"]) >= {"tokens_per_s", "setup_s"}


@pytest.mark.parametrize("traffic,name", CELLS)
def test_control_is_judged(traffic, name):
    """With ``--control``, ``correct`` judges the bfloat16 reference's picks,
    in the program's place, by the cell's limits; the program's own
    numbers, reported beside them, read lower and within the limits."""
    result, compared = _run(traffic, name, control=True)
    assert result["correct"] == all(v <= lim for v, lim in compared.values())
    limits = tiny.run.read_json(tiny.BENCH / "limits" / f"{name}.json")
    for k, lim in limits.items():
        assert compared[k][0] > result["program"][k], (compared, result)
        assert result["program"][k] <= lim


def test_control_is_not_correct():
    """At toy size the solve cell's control already fails its limit. The
    chat cell's control reads about its limit at toy size; at the cell's
    own size on the chip ``bench/tests/control_on_chip.py`` shows it
    failing (readings in ``PERF.md``)."""
    result, compared = _run("solve.4x512", "qwen3-0.6b.solve", control=True)
    assert not result["correct"], compared


@pytest.fixture
def altered_token(monkeypatch):
    """Every request's first output token is replaced by the next id, in
    the step that produced it (the next step feeds the altered token)."""
    from repro.launch import batching
    orig = batching.ContinuousBatcher.step

    def step(self):
        live = [r for r in self.active if r is not None and not r.out]
        moved = orig(self)
        for r in live:
            if r.out:
                r.out[0] = (r.out[0] + 1) % self.cfg.vocab_size
        return moved

    monkeypatch.setattr(batching.ContinuousBatcher, "step", step)


@pytest.fixture
def state_unchanged(monkeypatch):
    """The decode step returns the cache it was given."""
    from repro.launch import batching
    orig = batching.decode_step

    def decode_step(params, cfg, cache, tokens, dist):
        logits, _ = orig(params, cfg, cache, tokens, dist)
        return logits, cache

    monkeypatch.setattr(batching, "decode_step", decode_step)


@pytest.mark.parametrize("traffic,name", CELLS)
@pytest.mark.parametrize("fault", ["altered_token", "state_unchanged"])
def test_fault_is_not_correct(fault, traffic, name, request):
    request.getfixturevalue(fault)
    result, compared = _run(traffic, name)
    assert not result["correct"], compared
    limits = tiny.run.read_json(tiny.BENCH / "limits" / f"{name}.json")
    assert all(compared[k][0] > lim for k, lim in limits.items()), compared
