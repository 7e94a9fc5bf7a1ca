#!/usr/bin/env python3
"""The controls of ``benchmark/reference/falcon_h1.py`` at EVERY
``correctness_prompt_lens`` entry of the cell.

    python3 benchmark/controls_falcon_h1.py --workload <cell> --seed <n>

A state that was not zeroed at admission shows at the SHORT prompts and fades
with the distance from position 0; a gated norm over the wrong groups and a
carried state grow with it. ``controls_lfm2.py`` already holds every control
of a reference module at every length through
``benchlib/correctness.py::check`` and its own limits, whatever the model:
this file runs ITS ``main`` (no second copy of the loop; PERF.md section 7
asks a ``benchmark`` PR to fold both into an option of ``controls.py``). The
last stdout line is its JSON; exit 0 only if the plain reference passed and
every one of ``CONTROLS`` was refused at some length (``CONTROLS_REPORTED``
are held the same way and shown only).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

if __name__ == "__main__":
    from benchlib import files

    sys.exit(files.load_module(".", "controls_lfm2").main())
