#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload qwen3-0.6b.chat --seed 7 --seconds 40 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its files are
found by name: ``bench/configs/<config>.json`` (sizes, source, reduction;
its plain reference is ``bench/references/<reference>.py``),
``bench/traffic/<traffic>.json`` (the mix), ``bench/limits/<cell>.json``
(the limits of the comparison) and ``bench/metrics/<metric>.py`` (one
reader per per-layer metric).

A run loads and warms up (``setup_s``), measures for ``--seconds``, checks
what the timed path produced against the reference, and prints the numbers
compared beside their limits as the last lines of stderr and, as the last
line of stdout, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (``--trace 0``: the cell's end-to-end metrics; ``--trace 1``:
its per-layer metrics, read from a profiler trace of the window),
``device``, and, last, ``compared``. It exits non-zero, printing no
result, where JAX sees no TPU or another number of chips than the cell
asks for.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import trace_reduce  # noqa: E402


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One ``workloads`` entry with the files it names."""

    def __init__(self, name: str, entry: dict, doc: dict, mix: dict,
                 limits: dict, reference, end_to_end: list,
                 per_layer: list):
        self.name, self.entry, self.doc, self.mix = name, entry, doc, mix
        self.limits, self.reference = limits, reference
        self.end_to_end, self.per_layer = end_to_end, per_layer

    @classmethod
    def from_manifest(cls, manifest: dict, name: str) -> "Cell":
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"have {sorted(cells)}")
        entry = cells[name]
        cfg = next(c for c in manifest["configs"]
                   if c["name"] == entry["config"])
        doc = read_json(ROOT / cfg["file"])
        return cls(
            name, entry, doc,
            read_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
            read_json(BENCH / "limits" / f"{name}.json"),
            load_module(BENCH / "references" / f"{doc['reference']}.py"),
            [m for m in manifest["end_to_end"]
             if name in m.get("workloads", [name])],
            [m for m in manifest["per_layer"]
             if name in m.get("workloads", [name])])


def check_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"JAX sees {devs[0].platform!r}, not a TPU: "
                         "this benchmark measures the chip only")
    if len(devs) != chips:
        raise SystemExit(f"JAX sees {len(devs)} chips; the cell asks "
                         f"for {chips}")
    return devs


def peak_for(kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"device_kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout; every
    compile is kept, however short. The directory is the benchmark's own:
    the program's default ``.jax_cache/`` also takes entries from CPU test
    runs, and one of those without its ``-atime`` file made every later
    cache write fail on the chip."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".bench_jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def read_layers(cell: Cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def finite(x):
    """JSON has no infinity: a missing latency is printed as null."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    return x if not isinstance(x, float) or math.isfinite(x) else None


def report(result: dict, compared: dict) -> None:
    """Numbers compared, beside their limits: last on stderr, last in the
    result line."""
    for k, (v, lim) in compared.items():
        print(f"compared {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    sys.stderr.flush()
    print(json.dumps(finite(result)), flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             control: bool = False, peak: dict = None) -> tuple:
    """Set-up, window and check of one run -> (result line, compared)."""
    import serve
    if cell.mix["kind"] != "serve":
        raise SystemExit(f"unknown job kind {cell.mix['kind']!r}")
    result, ctx, compared = serve.run(
        ROOT, cell.doc, cell.mix, cell.limits, cell.reference, seed, seconds,
        trace, control=control, t_start=T_START)
    if trace:
        ctx["peak"] = peak
        result["metrics"] = read_layers(cell, ctx)
        t = ctx["trace"]
        top = sorted(t.modules.items(), key=lambda kv: -kv[1][1])[:5]
        print(f"trace: window {t.window_s:.3f} s, busy {t.busy_s:.3f} s, "
              f"executables {top}", file=sys.stderr)
        result["device"].update(busy_s=t.busy_s, window_s=t.window_s)
        result["breakdown"] = trace_reduce.breakdown(t)
    else:
        names = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": names[k]}
                             for k, v in result["metrics"].items()
                             if k in names}
    return result, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the bfloat16 control in the program's "
                         "place: `correct` judges its picks by the cell's "
                         "limits, and the program's numbers are reported "
                         "under `program` (limits are set from both; the "
                         "cell's own runs do not use it)")
    args = ap.parse_args(argv)

    cell = Cell.from_manifest(read_json(ROOT / "BENCHMARK.json"),
                              args.workload)
    devs = check_devices(cell.entry["chips"])
    peak = peak_for(devs[0].device_kind)
    enable_compile_cache()
    result, compared = run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), args.control, peak)
    report(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
