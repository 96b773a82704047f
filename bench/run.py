"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, on the machine that holds the chips the
cell asks for. Without a TPU, with fewer chips than the cell asks for, or on
a device kind that bench/peaks.json does not list, it exits non-zero and
prints no result. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
``breakdown`` (``--trace 1``) and, last, ``checks``: each number the
correctness check compared, with its limit.
"""

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".jax_cache"


def cell_metrics(bench: dict, cell: str) -> dict:
    """The end-to-end and per-layer entries that apply to ``cell``: those
    listing it under ``workloads``, or listing no workloads (per-layer ones
    then apply where the metric they move is reported)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if cell in m.get("workloads", ()) or (
               "workloads" not in m and m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": per}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"run.py: no workload {args.workload!r} in BENCHMARK.json "
                 f"({sorted(cells)})")
    cell = cells[args.workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((ROOT / "bench" / "traffic" /
                      f"{cell['traffic']}.json").read_text())

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"run.py: no TPU: JAX platform is {devices[0].platform!r}; "
                 "the benchmark runs on the chip only")
    if len(devices) < int(cell["chips"]):
        sys.exit(f"run.py: {args.workload} needs {cell['chips']} chips, "
                 f"found {len(devices)}")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness, peaks as peaks_mod

    try:
        peaks = peaks_mod.peaks_for(devices[0].device_kind)
    except KeyError as e:
        sys.exit(f"run.py: {e}")
    # inside the checkout at a fixed path, whatever the environment says:
    # the path is part of the cache's key, and two checkouts share nothing
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run(cell, config, mix, cell_metrics(bench, cell["name"]),
                         seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), peaks=peaks,
                         t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
