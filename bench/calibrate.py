"""Calibration runs on the chip, in one process that holds it; the
benchmark's own runs never call this.

    python bench/calibrate.py readings --workload <cell> --seeds 1,2,3 --seconds 30
        per seed: the numbers compared for the served outputs (the lower
        readings) and for the control put in the program's place (the upper
        readings), over the same sessions and output counts.
    python bench/calibrate.py timeline --workload <cell> --seeds 1 --seconds 30
        one run per seed: every session due in the window (due time from
        the window's start, generator lateness, first and last output
        latency) and every garbage-collector pass inside it, one JSON
        object per line.
    python bench/calibrate.py sweep --workload <cell> --rates 10,20,40 --seconds 10
        an open-loop cell at each rate: sessions in flight, sampled every
        0.1 s, averaged over the window's first and last third (sustained
        when the last is at most 2 or 1.25x the first), completions and
        tails.

A ``<cell>`` not in ``BENCHMARK.json`` is read as ``<config>.<traffic>``
(``fir32.serve.clips_open``), so that a mix can be calibrated before it has
a cell.  It refuses to run without a TPU unless ``--cpu`` is given
(rehearsal only; its numbers are then not device numbers).
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _session_span(rec):
    s = rec["session"]
    end = s.last_delivery_ns if s.finished.is_set() else None
    return rec["due"], (math.inf if end is None else end)


def load_cell(name: str):
    from bench import harness

    spec = harness.read_json(ROOT / "BENCHMARK.json")
    if all(w["name"] != name for w in spec["workloads"]):
        config, traffic = name.split(".", 1)
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": traffic, "chips": 1})
        if all(c["name"] != config for c in spec["configs"]):
            spec["configs"].append(
                {"name": config, "file": f"bench/configs/{config}.json"})
    return harness.load_cell(name, spec)


def readings(cell, seeds, seconds, platform, out):
    from bench import harness

    prog = harness.build_program(cell.config, platform)
    for seed in seeds:
        server = harness.new_server(prog, cell.config)
        win, _t0, _t1, _ = harness.drive_window(server, cell, seed, seconds,
                                                False)
        server.stop()
        del server
        gc.collect()
        program = harness.compare(cell.config, win.checked, harness.served)
        control = harness.compare(cell.config, win.checked,
                                  harness.controlled(cell.config))
        row = {"seed": seed, "sessions": len(win.checked),
               "tokens": sum(len(c.session.results[cell.config["collect"]])
                             for c in win.checked),
               "program": {k: v["value"] for k, v in program.items()},
               "control": {k: v["value"] for k, v in control.items()},
               "values": win.values}
        print(json.dumps(row), file=out, flush=True)
        del win
        gc.collect()


def timeline(cell, seeds, seconds, platform, out):
    from bench import drive, harness

    if cell.mix["loop"] != "open":
        raise SystemExit(f"timeline needs an open-loop cell, not {cell.name}")
    prog = harness.build_program(cell.config, platform)
    for seed in seeds:
        server = harness.new_server(prog, cell.config)
        gen = drive.make(cell.mix, cell.config, seed, seconds)
        win, _t0, _t1, _ = harness.drive_window(server, cell, seed, seconds,
                                                False, gen=gen)
        server.stop()
        t0 = gen.window_recs[0]["due"]
        for r in gen.window_recs:
            s = r["session"]
            row = {"seed": seed, "due_s": (r["due"] - t0) / 1e9,
                   "late_ms": r["late"] / 1e6,
                   "ttfo_ms": (s.first_delivery_ns - r["due"]) / 1e6
                   if s.first_delivery_ns else None,
                   "stream_ms": (s.last_delivery_ns - r["due"]) / 1e6
                   if s.last_delivery_ns else None}
            print(json.dumps(row), file=out)
        t0_s = t0 / 1e9
        for g, t, secs in win.gc_passes:
            print(json.dumps({"seed": seed, "gc_generation": g,
                              "at_s": t - t0_s, "seconds": secs}), file=out)
        print(json.dumps({"seed": seed, "values": win.values,
                          "notes": win.notes}), file=out, flush=True)
        del server, gen, win
        gc.collect()


def sweep(cell, rates, seconds, platform, out):
    from bench import drive, harness

    prog = harness.build_program(cell.config, platform)
    for rate in rates:
        mix = dict(cell.mix, rate_per_s=rate, drain_s=min(10.0, seconds))
        server = harness.new_server(prog, cell.config)
        gen = drive.make(mix, cell.config, 1234, seconds)
        server.start()
        gen.lead_in(server)
        win = gen.window(server, seconds)
        gen.finish(server, win)
        server.stop()
        t0 = gen.window_recs[0]["due"]
        t1 = t0 + int(win.seconds * 1e9)
        spans = [_session_span(r) for r in gen.records]
        grid = range(t0, t1, 100_000_000)
        inflight = [sum(1 for a, b in spans if a <= t < b) for t in grid]
        third = max(len(inflight) // 3, 1)
        early = sum(inflight[:third]) / third
        late = sum(inflight[-third:]) / third
        done_in_window = sum(1 for _a, b in spans if t0 <= b < t1)
        row = {"rate_per_s": rate, "window_s": win.seconds,
               "due_in_window": len(gen.window_recs),
               "finished_in_window": done_in_window,
               "inflight_first_third": early, "inflight_last_third": late,
               "sustained": late <= max(2.0, 1.25 * early),
               "tokens_per_s": win.values["tokens_per_s"],
               "ttfo_p95_ms": win.values["ttfo_p95_ms"],
               "stream_p95_ms": win.values["stream_p95_ms"],
               **{k: v for k, v in win.notes.items()
                  if k.startswith(("generator", "ttfo", "stream"))}}
        print(json.dumps(row), file=out, flush=True)
        del server, gen, win
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("readings", "timeline", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness

    platform = None if args.cpu else "tpu"
    if platform and jax.devices()[0].platform != platform:
        harness.log("no TPU found: calibration runs only on the chip")
        return 2
    if platform:
        from repro.runtime import compile_cache

        compile_cache.enable(ROOT / ".jax_cache")
    cell = load_cell(args.workload)
    out = open(args.out, "a") if args.out else sys.stdout
    t = time.perf_counter()
    try:
        seeds = [int(s) for s in args.seeds.split(",")]
        if args.mode == "readings":
            readings(cell, seeds, args.seconds, platform, out)
        elif args.mode == "timeline":
            timeline(cell, seeds, args.seconds, platform, out)
        else:
            sweep(cell, [float(r) for r in args.rates.split(",")],
                  args.seconds, platform, out)
    finally:
        if out is not sys.stdout:
            out.close()
    harness.log(f"{args.mode} done in {time.perf_counter() - t!r} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
