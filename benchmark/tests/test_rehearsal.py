"""run.py end to end at the tiny configuration (its own files under
benchmark/tests/rehearsal/): the result line names platform cpu, and without
the rehearsal flag run.py refuses a machine with no TPU."""

import json
import os
import subprocess
import sys

from benchlib import files

RUN = [sys.executable, os.path.join(files.BENCH_DIR, "run.py")]
REHEARSAL = os.path.join(files.BENCH_DIR, "tests", "rehearsal",
                         "BENCHMARK.json")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(args, **kw):
    return subprocess.run(RUN + args, cwd=files.ROOT, env=ENV, text=True,
                          capture_output=True, timeout=900, **kw)


def test_refuses_without_a_tpu_and_prints_no_result():
    p = _run(["--workload", "qwen3-0.6b.decode-closed", "--seed", "1",
              "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_rehearsal_cells_run_end_to_end_on_the_cpu():
    for cell, trace in (("tiny.closed", "0"), ("tiny.open", "1")):
        p = _run(["--rehearsal", REHEARSAL, "--workload", cell, "--seed",
                  "3000000011", "--seconds", "3", "--trace", trace])
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert set(line) >= KEYS
        assert line["device"]["platform"] == "cpu"
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] > 5
        if trace == "0":
            assert set(line["metrics"]) == {
                "ttft_p50_ms", "tpot_p95_ms", "out_tok_s", "setup_s"}
            assert all(m["value"] > 0 for m in line["metrics"].values())
        else:
            # program spans and counters read; device-trace metrics absent
            # on a machine with no device plane, never approximated
            assert {"queue_wait_p95_ms", "decode_batch_mean", "ttft_p95_ms",
                    "kv_pages_peak_pct"} <= set(line["metrics"])
            assert "decode_step_ms" not in line["metrics"]
            assert "busy_s" not in line["device"]
