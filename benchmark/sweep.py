"""Find the knee of an open-loop cell, once, on the chip (a builder's tool,
not part of a check): one process, one server, one warm-up, then a window at
each of several fixed rates.

    python3 benchmark/sweep.py --workload qwen3-0.6b.chat-open \
        --rates 4,5,6 --seconds 30 --seed 11

For each rate it prints offered and completed requests per second, TTFT
p50/p95, tpot p95, output tokens/s, how many requests were still in flight
when the window closed, and the p95 queue wait. A rate is SUSTAINED when the
backlog does not grow: requests in flight at the window's close stay near
rate x mean latency, and TTFT p95 stays within a small multiple of its value
at the lowest rate. The knee is the highest sustained rate; the cell's mix
then fixes ``rate`` at four fifths of it.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse     # noqa: E402
import json         # noqa: E402
import logging      # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--rehearsal", default="")
    opts = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)

    from benchlib import files
    from benchlib.session import Session, say

    bench_path = opts.rehearsal or os.path.join(files.ROOT, "BENCHMARK.json")
    cell = files.Cell(bench_path, opts.workload)
    sess = Session(cell, opts.seed, bool(opts.rehearsal), T_START)
    rows = []
    for i, rate in enumerate(float(r) for r in opts.rates.split(",")):
        mix = dict(cell.traffic, rate=rate)
        # another seed for every window: the same prompts again would hit
        # the prefix cache the earlier window filled
        sess.seed = opts.seed + 1000 * (i + 1)
        res = sess.measure(opts.seconds, False, traffic=mix)
        sess.srv.wait_idle(60.0)
        row = {"rate": rate, "attempted": res["attempted"],
               "failed": res["failed"],
               "compiles_in_window": res["compiles_in_window"],
               **res["values"], **res["extra"]}
        row.pop("setup_s", None)
        rows.append(row)
        say("sweep " + json.dumps(row))
    sess.srv.drain()
    print(json.dumps({"sweep": rows, "device": sess.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
