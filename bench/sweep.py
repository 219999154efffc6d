"""Find the knee of an open-loop cell: the highest rate it sustains.

  python3 -m bench.sweep --workload <name> --seed <n> --seconds <s> \
      --rates 50,100,150

One set-up, then one window per rate (the mix's own rate is ignored). Each
prints a JSON line: offered and completed requests per second, p50 and p95
due-to-ready latency, p95 queue wait, mean real rows per batch, and how far
the queue grew: the median latency of the last tenth of the requests over
that of the first tenth. A sustained rate completes what it is offered and
keeps that ratio near 1; past the knee the queue, and the ratio, grow
through the window. The sweep stops after the first rate that completes
less than 95% of what it is offered. The knee is the highest rate whose
growth is near 1 and whose p95 has not left the plateau of the lowest
rates; a cell is then set at about four fifths of it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys

import numpy as np

from bench.run import ROOT, T_START, log, use_checkout


def sweep(su, rates, seconds):
    from bench import readers
    for rate in rates:
        run = dataclasses.replace(su.run, batches=[], steps=[])
        su.system.window(run, seconds, lambda name: contextlib.nullcontext(),
                         rate_per_s=rate)
        lat = np.asarray(readers.latencies_ms(run))
        tenth = max(1, len(lat) // 10)
        completed = len(lat) / run.window_s
        yield {"rate_per_s": rate, "requests": len(lat),
               "completed_per_s": completed,
               "latency_p50_ms": readers.latency_ms(run, 50),
               "latency_p95_ms": readers.latency_ms(run, 95),
               "queue_wait_p95_ms": readers.queue_wait_ms(run, 95),
               "rows_per_batch": float(np.mean(run.batches)),
               "growth": float(np.median(lat[-tenth:])
                               / np.median(lat[:tenth]))}
        if completed < 0.95 * rate:
            return


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    if not use_checkout():
        return 1
    from bench import harness
    try:
        su = harness.set_up(ROOT, args.workload, args.seed, T_START,
                            log=log)
    except harness.NoChip as e:
        log(str(e))
        return 3
    for row in sweep(su, [float(r) for r in args.rates.split(",")],
                     args.seconds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
