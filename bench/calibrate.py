"""Readings that set a cell's ``logit_gap`` limit: the program's widest
served-token gap and the control's, seed by seed, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed it runs the cell's timed path at the cell's own load for a
short window, exactly as ``run.py`` does, samples the finished requests as
the check does, and reads two numbers against the plain reference: the
widest gap of a served token (the program's reading) and the widest gap of
the token the control ranks first (the reference computed with the
activations the configuration keeps in bfloat16 rounded to the
configuration's ``control_act_dtype``). The limit goes between the
program's largest reading and the control's smallest. The benchmark's own
runs never run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell: dict, config: dict, mix: dict, seed: int, seconds: float,
             peaks: dict, **program) -> dict:
    """One seed's program and control readings (``program``: the test
    overrides of ``harness.run``)."""
    import jax.numpy as jnp

    from bench import check, harness, traffic, weights as W

    out = harness._drive_batch(
        cell, config, mix, seed=seed, seconds=seconds, trace=False,
                peaks=peaks, t_start=time.perf_counter(),
                builds=harness._Builds(),
                **{"program_cfg": None, "backend": None, "fault": None,
                   **program})
    sample = check.sample(out.finished, seed)
    ref = check.load_reference(config)
    w = W.make_weights(config, seed)
    g = check.gaps(ref, w, config, sample,
                   length=-(-out.max_len // ref.Q_BLOCK) * ref.Q_BLOCK,
                   n_rows=traffic.max_lengths(mix)[1],
                   control_dtype=jnp.dtype(config["quant"]["control_act_dtype"]))
    return {"seed": seed,
            "logit_gap": max(float(x.max()) for x in g["served"]),
            "control_gap": max(float(x.max()) for x in g["control"]),
            "sampled_tokens": sum(len(s.out) for s in sample),
            "served_mismatch": float(sum((x > 0).sum() for x in g["served"])
                                     / sum(x.size for x in g["served"]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench import peaks as peaks_mod

    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate.py: no TPU")
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {c["name"]: c for c in bench["workloads"]}[args.workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((ROOT / "bench" / "traffic" /
                      f"{cell['traffic']}.json").read_text())
    peaks = peaks_mod.peaks_for(jax.devices()[0].device_kind)
    rows = []
    for s in args.seeds.split(","):
        r = readings(cell, config, mix, int(s), args.seconds, peaks)
        rows.append(r)
        print(json.dumps(r), flush=True)
    lower = max(r["logit_gap"] for r in rows)
    upper = min(r["control_gap"] for r in rows)
    print(json.dumps({"lower": lower, "upper": upper,
                      "upper_over_lower": upper / lower if lower else None}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
