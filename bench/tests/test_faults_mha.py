"""``test_faults.py``'s checks for the MHA cell, ``qwen1.5-4b.chat``, at a
toy size on the CPU, plus the fault only a model with q/k/v biases can
have: projections that drop their biases.

``tiny.SIZES`` gives every toy 4 heads over 2 KV heads; here the toy keeps
the configuration's ratio (20/20 → 4/4), so the one-group path of the
grouped attention runs, and the biases are drawn from the seed.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest bench/tests -q``.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
import run  # noqa: E402
from test_faults import SEED, altered_token, state_unchanged  # noqa: E402,F401

NAME, TRAFFIC = "qwen1.5-4b.chat", "chat4b.8x1024"
LIMITS = run.read_json(tiny.BENCH / "limits" / f"{NAME}.json")


def _run(control=False):
    c = tiny.cell(TRAFFIC, NAME)
    c.doc["num_key_value_heads"] = c.doc["num_attention_heads"]     # MHA
    return run.run_cell(c, SEED, seconds=1.5, trace=False, control=control)


@pytest.fixture
def biases_dropped(monkeypatch):
    """Every projection runs without its bias."""
    from repro.models import layers
    dense = layers.dense

    def unbiased(x, w, site, bias=None, plan=None):
        return dense(x, w, site, plan=plan)

    monkeypatch.setattr(layers, "dense", unbiased)


def test_sound_program_is_correct():
    result, compared = _run()
    assert result["correct"], compared
    assert result["attempted"] > 0 and result["failed"] == 0
    assert compared["compiles_in_window"][0] == 0
    assert set(result["metrics"]) >= {"tokens_per_s", "setup_s",
                                      "itl_p95_ms"}


def test_control_is_judged():
    result, compared = _run(control=True)
    assert result["correct"] == all(v <= lim for v, lim in compared.values())
    for k, lim in LIMITS.items():
        assert compared[k][0] > result["program"][k], (compared, result)
        assert result["program"][k] <= lim


@pytest.mark.parametrize(
    "fault", ["altered_token", "state_unchanged", "biases_dropped"])
def test_fault_is_not_correct(fault, request):
    request.getfixturevalue(fault)
    result, compared = _run()
    assert not result["correct"], compared
    assert all(compared[k][0] > lim for k, lim in LIMITS.items()), compared
