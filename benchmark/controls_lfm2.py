#!/usr/bin/env python3
"""benchmark/controls.py for a model whose mechanisms show at DIFFERENT
prompt lengths: every control at EVERY ``correctness_prompt_lens`` entry.

    python3 benchmark/controls_lfm2.py --workload <cell> --seed <n>

controls.py holds each control at the configuration's longest prompt alone
(the one that runs every mechanism). A gated short convolution's tail is the
opposite case: rows that were not reset at admission reach the first
positions of a sequence and fade with distance, so the SHORT prompt is where
the comparison can see them. This script starts the cell's server the same
way, holds the plain reference (which has to come out correct at every
length) and then each of the reference module's ``CONTROLS`` at each length
by itself, through ``benchlib/correctness.py::check`` and its own limits. The
last stdout line is JSON: ``{"plain": true, "controls": {label: {length:
refused}}, "reported": {...}, "ok": bool}``; a control counts as SEEN if some
length refuses it, and the exit code is 0 only if the plain reference passed
and every one of ``CONTROLS`` was seen (``CONTROLS_REPORTED`` are held the
same way and shown only, as controls.py shows them).
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
    # the wrapper that hands a reference its instruments: controls.py's own
    with_instruments = files.load_module(".", "controls")._With
    sess = Session(cell, opts.seed, bool(opts.rehearsal), T_START)
    own_load = files.load_module

    def held(instruments: dict, cfg_file: dict) -> bool:
        files.load_module = lambda kind, name: (
            with_instruments(ref, instruments) if kind == "reference"
            else own_load(kind, name))
        try:
            return correctness.check(sess.srv.port, sess.srv.served_model,
                                     cfg_file, sess.tree, opts.seed, say)
        finally:
            files.load_module = own_load

    plain = held({}, cell.config)
    say(f"plain reference: {'correct' if plain else 'NOT correct'}")
    out = {"plain": bool(plain), "controls": {}, "reported": {}}
    for key, controls in (("controls", ref.CONTROLS),
                          ("reported", getattr(ref, "CONTROLS_REPORTED", {}))):
        for label, kw in controls.items():
            out[key][label] = {}
            for n in cell.config.get("correctness_prompt_lens", [63, 300]):
                refused = not held(kw, dict(cell.config,
                                            correctness_prompt_lens=[n]))
                out[key][label][str(n)] = refused
                say(f"control, reference with {label}, prompt of {n}: "
                    f"{'NOT correct' if refused else 'correct'}")
    sess.srv.drain()
    out["ok"] = out["plain"] and all(any(by_len.values())
                                     for by_len in out["controls"].values())
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
