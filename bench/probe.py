"""Measurements that set the benchmark's fixed numbers, run by hand on
the chip; the benchmark's own runs never call this.

    python bench/probe.py sweep   --workload C --rates 1.5,2.5 --seconds S --seed N
    python bench/probe.py control --workload C --seeds 1,2,3 --seconds S
    python bench/probe.py trace   --workload C --seed N --seconds S

``sweep``: one open-loop run per rate, no check, and for each the
first token's median wait by quarter of the window: a rate whose last
quarter waits more than twice as long as its first, and half a second
longer, has a growing backlog.  The knee is the highest rate below
which no rate has one; the last row gives it.  ``control``: one run per seed with the float8
control in the program's place in the check (``correct`` comes out
false under a sound limit); each gives the upper reading (the control's
widest gap) and, on the same sample, the lower (the program's).
``trace``: one traced run that keeps the raw trace and writes what its
planes, lines and events are named.  Each writes JSON lines under
``--out`` (``probe_out/`` by default).
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


def _emit(out: Path, name: str, row: dict) -> None:
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"{name}.jsonl", "a") as f:
        f.write(json.dumps(row, default=str) + "\n")
    print(json.dumps(row, default=str), flush=True)


def _quarters(rec) -> list:
    from bench.stats import percentile
    out = []
    for q in range(4):
        lo, hi = q * rec.seconds / 4, (q + 1) * rec.seconds / 4
        waits = [(r.recv[0] if r.recv and r.recv[0] <= rec.seconds
                  else rec.seconds) - r.due
                 for r in rec.requests if lo <= r.due < hi]
        out.append(percentile(waits, 50))
    return out


def _summary(out: dict) -> dict:
    rec = out["record"]
    from bench import harness
    extra = {m: harness.reader(m)(rec) for m in
             ("output_tokens_per_s", "batch_occupancy", "gen_lag_p99_ms",
              "itl_p95_ms", "ttft_p90_ms")}
    return {"metrics": out["metrics"], "extra": extra,
            "memory_peak_bytes": out["memory_peak_bytes"],
            "compiles_in_window": out["compiles_in_window"],
            "attempted": out["attempted"], "failed": out["failed"],
            "correct": out["correct"], "check": out["check"].numbers,
            "other": out["check"].other,
            "ttft_p50_by_quarter_s": _quarters(rec),
            "setup_s": rec.setup_s}


def sustained(quarters: list) -> bool:
    """No growing backlog: the last quarter's median wait is within
    twice the first's, or within half a second of it."""
    return quarters[-1] <= max(2.0 * quarters[0], quarters[0] + 0.5)


def _trace_layout(path: str) -> dict:
    """Names, counts and total durations of each plane's lines."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        lines = []
        for line in p.lines:
            evs = list(line.events)
            by: dict = {}
            for e in evs:
                n, t = by.get(e.name, (0, 0.0))
                by[e.name] = (n + 1, t + e.duration_ns * 1e-9)
            top = sorted(by.items(), key=lambda kv: -kv[1][1])[:25]
            stats = [dict(e.stats) for e in evs[:2]]
            lines.append({"line": line.name, "events": len(evs),
                          "first_ns": evs[0].start_ns if evs else None,
                          "last_ns": evs[-1].end_ns if evs else None,
                          "top": top, "stats": stats})
        planes.append({"plane": p.name, "lines": lines})
    return {"planes": planes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("sweep", "control", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", default=str(ROOT / "probe_out"))
    args = ap.parse_args(argv)

    import glob
    import jax
    from bench import harness, peaks
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    pk = peaks.for_kind(jax.devices()[0].device_kind)
    cell = harness.load_cell(args.workload)
    tag = f"{args.mode}-{args.workload}"
    out_dir = Path(args.out)
    if args.mode == "sweep":
        knee = None
        for rate in sorted(float(r) for r in args.rates.split(",")):
            cell.traffic["rate_per_s"] = rate
            t0 = time.monotonic()
            out = harness.run(cell, args.seed, args.seconds, False,
                              t_start=t0, peaks=pk, compare=False)
            row = {"rate_per_s": rate, **_summary(out)}
            row["sustained"] = sustained(row["ttft_p50_by_quarter_s"])
            _emit(out_dir, tag, row)
            if not row["sustained"]:
                break
            knee = rate
        _emit(out_dir, tag, {"knee_per_s": knee})
    elif args.mode == "control":
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.monotonic()
            out = harness.run(cell, seed, args.seconds, False, t_start=t0,
                              peaks=pk, control=True)
            _emit(out_dir, tag, {"seed": seed,
                                 "wall_s": time.monotonic() - t0,
                                 **_summary(out)})
    else:
        import traceback
        keep = str(ROOT / ".probe_trace" / args.workload)
        try:
            out = harness.run(cell, args.seed, args.seconds, True,
                              t_start=T_START, peaks=pk, keep_trace=keep)
            s = out["record"].trace
            _emit(out_dir, tag, {
                "seed": args.seed, **_summary(out), "busy_s": s.busy_s,
                "window_s": s.window_s,
                "ops": sorted(s.ops.items(), key=lambda kv: -kv[1])[:30],
                "modules": s.modules, "idle": s.idle_by_span()})
        except Exception:
            traceback.print_exc()
        path = sorted(glob.glob(f"{keep}/**/*.xplane.pb", recursive=True))
        _emit(out_dir, tag + "-layout", _trace_layout(path[-1]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
