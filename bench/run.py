"""The benchmark's one command: one run of one cell on the chips of the
machine it starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): make the weights on the device from the
seed, build the engine, compile or load from the persistent cache every
program the cell's traffic reaches.  Then the window: the cell's traffic
for ``--seconds``, and with ``--trace 1`` a profiler trace of part of it.
Then the check of what the window served against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``check``: each number compared beside its
limit.  The same numbers are the last lines of standard error.

A machine whose first device is not a TPU, or that has fewer chips than
the cell asks for, ends the run with exit code 2 and no result line; a
metric of the cell that read nothing (a trace name that did not match,
say) ends it with exit code 3 and no result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TOP = 10          # entries of each breakdown list


def find_chips(chips: int):
    """The machine's devices, or an error message if they cannot run a
    cell on ``chips`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return None, f"no TPU: JAX sees {devs[0].platform!r} devices"
    if len(devs) < chips:
        return None, f"the cell asks for {chips} chips, JAX sees {len(devs)}"
    return devs, None


def unread(cell, metrics: dict, trace: bool) -> list:
    """The cell's metrics of this kind (per-layer with ``trace``, else
    end-to-end) that the run did not read."""
    kind = cell.per_layer if trace else cell.end_to_end
    return [m["name"] for m in kind if m["name"] not in metrics]


def breakdown(summary) -> dict:
    """The trace's heaviest device operations and its idle time by what
    the host was doing."""
    ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(summary.idle_by_span().items(), key=lambda kv: -kv[1][1])
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[f"{n} ({k} gaps)", s]
                          for n, (k, s) in idle[:TOP]]}


def result_line(out: dict, devs, chips: int, trace: bool) -> dict:
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": device,
            "compiles_in_window": out["compiles_in_window"]}
    summary = out["record"].trace
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = breakdown(summary)
    line["check"] = out["check"].numbers
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, peaks
    cell = harness.load_cell(args.workload)
    devs, err = find_chips(cell.chips)
    if err:
        print(f"bench/run.py: {err}", file=sys.stderr)
        return 2
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, peaks=peaks.for_kind(
                          devs[0].device_kind))
    missing = unread(cell, out["metrics"], bool(args.trace))
    if missing:
        print(f"bench/run.py: the cell's metrics {missing} read nothing "
              f"in this run", file=sys.stderr)
    for line in out["check"].lines():
        print(line, file=sys.stderr)
    if missing:
        return 3
    print(json.dumps(result_line(out, devs, cell.chips, bool(args.trace))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
