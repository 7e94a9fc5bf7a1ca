"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration and traffic files by
name, starts the server in this process by the server's own entry points on
weights made from the seed, warms what the cell can dispatch, measures one
window through POST /v1/completions (streamed), checks the outputs against
the plain float32 reference, and prints ONE JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` in a traced run). With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics. Everything else goes on earlier lines.

No TPU, or fewer chips than the cell asks for: exit 2, no result line.
``--rehearsal <file>`` reads a BENCHMARK-shaped file of a tiny cell instead
and allows the CPU (benchmark/tests/rehearsal/): plumbing only, its output
names ``platform: cpu`` and no number of it is a device number.
"""

from __future__ import annotations

import time

T_START = time.monotonic()          # set-up counts from process start

import argparse     # noqa: E402
import json         # noqa: E402
import logging      # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                       # benchlib, by name
sys.path.insert(1, os.path.dirname(HERE))      # the program's package


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", default="",
                    help="a BENCHMARK-shaped file of a tiny cell; allows CPU")
    opts = ap.parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, os.environ.get("BENCH_LOG", "WARNING")),
        stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    from benchlib import correctness, files
    from benchlib import trace_reduce
    from benchlib.session import Session, say

    bench_path = opts.rehearsal or os.path.join(files.ROOT, "BENCHMARK.json")
    cell = files.Cell(bench_path, opts.workload)
    sess = Session(cell, opts.seed, bool(opts.rehearsal), T_START)
    res = sess.measure_valid(opts.seconds, bool(opts.trace))
    say(f"end to end: {json.dumps(res['values'])} extra "
        f"{json.dumps(res['extra'])}")

    # outputs, outside the window
    if not sess.srv.wait_idle(30.0):
        say("the engine did not go idle after the window")
    t0 = time.monotonic()
    numerics_ok = correctness.check(
        sess.srv.port, sess.srv.served_model, cell.config, sess.tree,
        opts.seed, say)
    say(f"correctness took {time.monotonic() - t0:.1f}s")
    correct = (numerics_ok and res["malformed"] == 0
               and res["compiles_in_window"] == 0)
    if res["compiles_in_window"]:
        say(f"{res['compiles_in_window']} program(s) compiled or loaded "
            f"inside the second window too: correct=false")

    device = dict(sess.device, memory_peak_bytes=sess.memory_peak_bytes())
    line = {"correct": bool(correct), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": {}, "device": device}
    if not opts.trace:
        for name in cell.metric_names("end_to_end"):
            v = res["values"].get(name)
            if v is not None:
                line["metrics"][name] = {"value": v,
                                         "unit": cell.metric(name)["unit"]}
    else:
        ctx = res["layer_context"]
        for name in cell.metric_names("per_layer"):
            reader = files.load_module("layer_metrics", name)
            v = reader.read(ctx) if reader is not None else None
            if v is None:
                say(f"layer metric {name}: nothing to read, left out")
                continue
            line["metrics"][name] = {"value": v,
                                     "unit": cell.metric(name)["unit"]}
        if ctx.trace is not None and ctx.trace.devices:
            t0n, t1n = trace_reduce.span_ns(ctx.trace)
            device["busy_s"] = trace_reduce.busy_seconds(ctx.trace)
            device["window_s"] = (t1n - t0n) / 1e9
            line["breakdown"] = {
                "device_ops": trace_reduce.top_ops(ctx.trace, 10),
                "idle_gaps": trace_reduce.idle_by_host_span(
                    ctx.trace, cell.config.get("engine_thread_re", "."), 10)}
    sess.srv.drain()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
