"""A benchmark cell at a size a CPU test run holds: the qwen3 configuration
file with its widths cut to a toy, the chat or solve mix scaled down to
match. Tests drive ``run.run_cell`` on it, with the look for a chip left
out."""

from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SIZES = {"hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256}


def cell(traffic: str = "chat.8x1024",
         name: str = "qwen3-0.6b.chat") -> "run.Cell":
    manifest = run.read_json(run.ROOT / "BENCHMARK.json")
    real = run.Cell.from_manifest(manifest, name)
    doc = dict(real.doc, **SIZES)
    mix = copy.deepcopy(run.read_json(BENCH / "traffic" / f"{traffic}.json"))
    mix["buckets"] = "4x64" if mix["loop"] == "open" else "2x48"
    for k, hi in (("prompt", 24), ("output", 8)):
        mix[k].update(median=min(mix[k]["median"], hi // 2), min=4, max=hi)
    if mix["loop"] == "open":
        mix["rate_per_s"] = 20.0
    else:
        mix["clients"] = 3
    return run.Cell(name, real.entry, doc, mix, real.limits, real.reference,
                    real.end_to_end, real.per_layer)
