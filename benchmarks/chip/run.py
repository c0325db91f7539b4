#!/usr/bin/env python3
"""
The chip benchmark's one command: one cell, once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

resolves the cell in ``BENCHMARK.json`` to its configuration
(``configs/``), its traffic mix (``traffic/<mix>.json``, read by the one
generator of its ``kind``, ``traffic/<kind>.py``) and, for a traced run,
the reader of every per-layer metric the manifest lists for the cell
(``layer_metrics/<metric>.py``). This process never touches the chip:
it pins itself to the CPU before anything imports JAX and starts exactly
one child that holds the chip (``procs/``). Where JAX finds no
accelerator, too few chips, or a ``device_kind`` that ``peaks.json``
does not hold, the command exits non-zero and prints no result.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. Everything else a run writes goes to
``benchmarks/chip/out/<cell>/<seed>/``.
"""

import os
import sys
import time

STARTED = time.time()  # set-up counts from here

# the parent must not take the chip: gordo_tpu's client and wire modules
# import JAX, so the platform is pinned before any import; the child is
# given back what this process found (harness/child.py)
os.environ["CHIPBENCH_PARENT_JAX_PLATFORMS"] = os.environ.get("JAX_PLATFORMS", "<unset>")
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402

CHIP_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(CHIP_DIR))
for _path in (ROOT, CHIP_DIR):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from harness import breakdown, child, manifest  # noqa: E402


def per_layer(cell: manifest.Cell, evidence: dict) -> dict:
    """Every per-layer metric of the cell whose reader found something
    to read; a reader that finds nothing returns None and its metric is
    left out of the line."""
    metrics = {}
    readers = cell.readers()
    for metric in cell.per_layer:
        try:
            value = readers[metric["name"]](evidence)
        except (KeyError, IndexError, TypeError, ZeroDivisionError, ValueError) as exc:
            print(f"per-layer {metric['name']}: nothing to read ({exc!r})", flush=True)
            continue
        if value is not None:
            metrics[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return metrics


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "gordo_tpu")):
        print("chipbench: the system under test (gordo_tpu/) is not in this "
              "checkout; nothing to measure", file=sys.stderr)
        return 4
    document = manifest.load_manifest(ROOT)
    found = manifest.problems(document, ROOT)
    if found:
        print("chipbench: BENCHMARK.json: " + "; ".join(found), file=sys.stderr)
        return 4
    cell = manifest.Cell(document, args.workload, ROOT)
    run_dir = os.path.join(manifest.OUT_DIR, cell.name, str(args.seed))
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)

    try:
        evidence = cell.generator().run(
            cell, args.seed, args.seconds, bool(args.trace), run_dir
        )
    except RuntimeError as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 3
    evidence.update(
        {"cell": cell.entry, "config": cell.config, "traffic": cell.traffic,
         "seed": args.seed, "seconds": args.seconds}
    )
    device = evidence["device"]
    setup_s = evidence["window"]["start"] - STARTED
    for note in evidence.get("notes", []):
        print(note, flush=True)
    print(
        f"set-up {setup_s:.3f}s; programs built or loaded before the window "
        f"{evidence['compiles']['before_window']}, inside it "
        f"{evidence['in_window']} (compiles: misses of the persistent cache; "
        f"loads: every executable built or fetched); compile cache "
        f"{device.get('compile_cache')}",
        flush=True,
    )
    failures = evidence.get("failures", [])
    for failure in failures[:20]:
        print(f"FAILED CHECK: {failure}", flush=True)
    if len(failures) > 20:
        print(f"... and {len(failures) - 20} more failed checks", flush=True)

    line = {
        "correct": bool(evidence["correct"]) and evidence["in_window"]["compiles"] == 0,
        "attempted": evidence["attempted"],
        "failed": evidence["failed"],
        "device": {
            "platform": device["platform"],
            "kind": device["kind"],
            "count": device["count"],
            "memory_peak_bytes": device["memory_peak_bytes"],
        },
    }
    if evidence["in_window"]["compiles"]:
        print(f"FAILED CHECK: {evidence['in_window']['compiles']} program(s) "
              "compiled inside the window", flush=True)
    if args.trace:
        trace = evidence.get("trace") or {}
        line["metrics"] = per_layer(cell, evidence)
        line["device"]["busy_s"] = trace.get("busy_s", 0.0)
        line["device"]["window_s"] = trace.get("window_s", 0.0)
        line["breakdown"] = breakdown.build(evidence)
    else:
        values = dict(evidence["end_to_end"], setup_s=setup_s)
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end
        }
    with open(os.path.join(run_dir, "evidence.json"), "w") as f:
        json.dump(evidence, f, default=str)
    # what a run costs a check is all of it, not its window: a traced
    # run spends most of its time after the window, stopping the profiler
    print(f"whole run {time.time() - STARTED:.1f}s, of which {time.time() - evidence['window']['end']:.1f}s "
          "after the window", flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    child.guard()  # told to end, it ends what it started first
    sys.exit(main())
