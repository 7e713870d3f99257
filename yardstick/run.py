"""``python -m yardstick.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one cell, once, in this process, on the TPU it is started
on. The last line of standard output is the result.

Order: refuse anything but a TPU with the cell's chips -> build the model
and its weights from the seed on the device -> warm up only this cell's
shapes -> measure -> check the outputs -> print. ``--trace 0`` prints the
cell's end-to-end metrics, taken with the program's tracing off;
``--trace 1`` profiles a shorter window and prints its per-layer metrics
and the breakdown.
"""

import time

T_PROCESS = time.perf_counter()     # before the heavy imports: they are set-up

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

from yardstick import cells, device, xplane  # noqa: E402
from yardstick.compiles import Compiles  # noqa: E402


def _per_layer(cell, obs):
    metrics = {}
    for entry in cell.per_layer:
        try:
            value = cells.load_reader(cell, entry["name"]).read(obs)
        except Exception:       # one reader's fault leaves one metric out
            traceback.print_exc()
            continue
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    return metrics


def _end_to_end(cell, outcome):
    metrics = {}
    for entry in cell.end_to_end:
        if entry["name"] not in outcome.end_to_end:
            raise KeyError(f"cell {cell.name} is to report "
                           f"{entry['name']!r} and its driver gave "
                           f"{sorted(outcome.end_to_end)}")
        metrics[entry["name"]] = {
            "value": float(outcome.end_to_end[entry["name"]]),
            "unit": entry["unit"]}
    return metrics


def main(argv=None, root: Path = cells.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    cell = cells.resolve_cell(args.workload, root)
    devices = device.require_tpu(cell.chips)
    import deeplearning4j_tpu  # noqa: F401  (applies the compile-cache rule)
    compiles = Compiles()
    outcome = cells.load_driver(cell).run(
        cell, args.seed, args.seconds, bool(args.trace), compiles, devices,
        T_PROCESS)

    described = device.describe(devices)
    line = {"correct": outcome.correct, "attempted": outcome.attempted,
            "failed": outcome.failed}
    if args.trace:
        obs = outcome.observed
        line["metrics"] = _per_layer(cell, obs)
        described["busy_s"] = xplane.busy_s(obs.device)
        described["window_s"] = obs.device.window_s
        line["breakdown"] = obs.breakdown()
    else:
        line["metrics"] = _end_to_end(cell, outcome)
    line["device"] = described
    print("notes " + json.dumps(outcome.notes, default=str), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
