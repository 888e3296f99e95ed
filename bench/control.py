"""Readings that set a cell's check limits: the program's numbers over many
seeds (the lower reading) and the control's (the upper reading).

    python3 bench/control.py --workload resnet9-w2a2.saturate \\
        --seeds 11,12,13 --seconds 3 --out control.jsonl

For each seed, in one process: the cell's set-up and a short window of its
own traffic, then the answers sampled as a run samples them, compared with
the reference (the program's reading), and the control's logits for the
same images compared with the reference (the control's reading). The
control is the reference with its float steps in bfloat16, one step below
the float32 the configuration states. Benchmark runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import reference, run_cell, spec  # noqa: E402


def readings(plan: dict, seed: int, seconds: float) -> dict:
    """One seed: the program's numbers and the control's."""
    served = run_cell.Served(plan, seed)
    rec, _ = run_cell.drive(served, plan, seed, seconds)
    done, failed = rec.results()
    picked = run_cell.sample(rec, done, plan, seed)
    got = np.stack([np.asarray(rec.futures[i].result()) for i in picked])
    imgs = served.pool[rec.image[picked]]
    params, calib, cfg = served.params, served.calib, plan["config"]
    served.close()
    ref = reference.logits(params, calib, imgs, cfg)
    ctl = reference.logits(params, calib, imgs, cfg, dtype=jnp.bfloat16)
    return {"seed": seed, "answered": int(len(done)),
            "failed": int(len(failed)), "checked": int(len(picked)),
            "program": run_cell.numbers(got, ref),
            "control": run_cell.numbers(ctl, ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None,
                    help="also append each seed's line to this file")
    args = ap.parse_args(argv)
    plan = spec.cell(ROOT, args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("error: the control readings are taken on the chip",
              file=sys.stderr)
        return 1
    for s in args.seeds.split(","):
        line = json.dumps({"workload": args.workload,
                           **readings(plan, int(s), args.seconds)})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
