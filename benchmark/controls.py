#!/usr/bin/env python3
"""Does a cell's comparison SEE the mechanisms of its model?

    python3 benchmark/controls.py --workload <cell> --seed <n>

Starts the cell's server as benchmark/run.py does (the same seeded weights,
flags and warm-up; no measured window), then calls
``benchlib/correctness.py::check`` — the function that decides ``correct``,
with its own limits — once with the cell's plain reference, which has to
come out correct, and once for every control the reference module lists:

    CONTROLS = {label: keyword arguments of its ``logprobs``}   must FAIL
    CONTROLS_REPORTED = {label: ...}        held the same way, reported only

each at the configuration's LONGEST ``correctness_prompt_lens`` entry (the
one that runs every mechanism). A control is the reference with one
mechanism left out or one precision lowered; a comparison that passes it
would pass a server with that fault. The last stdout line is JSON:
``{"plain": true, "controls": {label: refused}, "reported": {...},
"ok": bool}``; the exit code is 0 only if the plain reference passed and
every control was refused. A reference module without ``CONTROLS`` exits 2.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


class _With:
    """A reference module whose ``logprobs`` always takes ``instruments``."""

    def __init__(self, ref, instruments: dict):
        self._ref, self._kw = ref, instruments

    def logprobs(self, mc, tree, ids, n_last):
        return self._ref.logprobs(mc, tree, ids, n_last, **self._kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearsal", default="",
                    help="a BENCHMARK-shaped file of a tiny cell; allows CPU")
    opts = ap.parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, os.environ.get("BENCH_LOG", "WARNING")),
        stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    from benchlib import correctness, files
    from benchlib.session import Session, say

    cell = files.Cell(opts.rehearsal
                      or os.path.join(files.ROOT, "BENCHMARK.json"),
                      opts.workload)
    ref = files.load_module("reference", cell.config["reference"])
    if not hasattr(ref, "CONTROLS"):
        say(f"reference {cell.config['reference']} lists no CONTROLS")
        return 2
    sess = Session(cell, opts.seed, bool(opts.rehearsal), T_START)
    own_load = files.load_module

    def held(instruments: dict, cfg_file: dict) -> bool:
        files.load_module = lambda kind, name: (
            _With(ref, instruments) if kind == "reference"
            else own_load(kind, name))
        try:
            return correctness.check(sess.srv.port, sess.srv.served_model,
                                     cfg_file, sess.tree, opts.seed, say)
        finally:
            files.load_module = own_load

    plain = held({}, cell.config)
    say(f"plain reference: {'correct' if plain else 'NOT correct'}")
    longest = dict(cell.config, correctness_prompt_lens=[
        max(cell.config.get("correctness_prompt_lens", [300]))])
    out = {"plain": bool(plain), "controls": {}, "reported": {}}
    for key, controls in (("controls", ref.CONTROLS),
                          ("reported", getattr(ref, "CONTROLS_REPORTED", {}))):
        for label, kw in controls.items():
            out[key][label] = not held(kw, longest)
            say(f"control, reference with {label}: "
                f"{'NOT correct' if out[key][label] else 'correct'}")
    sess.srv.drain()
    out["ok"] = out["plain"] and all(out["controls"].values())
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
