"""The trace reducer on fixed event lists and on the recorded trace
(tests/data/: a slice of a chip run of qwen3-0.6b.decode-closed, gzipped)."""

import gzip
import os
import shutil

import pytest

from benchlib import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps_on_fixed_intervals():
    evs = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 32, 1)]
    assert tr.union_ns(evs) == 20
    assert tr.gaps(evs, 0, 40) == [(15, 15), (35, 5)]
    assert tr.gaps([], 0, 7) == [(0, 7)]


def test_names_as_the_trace_gives_them():
    assert tr.program_of("jit_decode_steps(4586551095000270797)") == \
        "decode_steps"
    assert tr.short_op("%fusion.123 = bf16[8,128]{1,0} fusion(...)") == \
        "fusion"
    assert tr.short_op("%copy-start = (bf16[4]) copy-start(%a.1)") == \
        "copy-start"


def _synthetic():
    t = tr.Trace()
    d = tr.DeviceTrace(0)
    d.modules = [("jit_decode_steps(1)", 0, 100), ("jit_mixed_step(2)", 150,
                                                   50),
                 ("jit_decode_steps(1)", 300, 100)]
    d.ops = [("%decode_attend_pallas_paged.1 = x custom-call()", 10, 20),
             ("%fusion.2 = x fusion()", 40, 50),
             ("%decode_attend_pallas_paged.3 = x custom-call()", 160, 30),
             ("%decode_attend_pallas_paged.1 = x custom-call()", 310, 20)]
    t.devices = [d]
    t.host = {"engine-loop/12": [("PjitFunction(decode_steps)", 100, 60),
                                 ("fetch", 200, 90)]}
    return t


def test_module_time_ops_inside_and_idle_attribution():
    t = _synthetic()
    assert tr.module_time(t, {"decode_steps"}) == (2, 200 / 1e9)
    inside = tr.ops_inside(t, {"decode_steps"}, "decode_attend_pallas_paged")
    assert [e[1] for e in inside] == [10, 310]      # not the mixed one
    assert tr.span_ns(t) == (10, 330)
    assert tr.busy_seconds(t) == (20 + 50 + 30 + 20) / 1e9
    top = dict(tr.top_ops(t, 5))
    assert top["decode_steps:decode_attend_pallas_paged"] == 40 / 1e9
    assert top["mixed_step:decode_attend_pallas_paged"] == 30 / 1e9
    idle = dict(tr.idle_by_host_span(t, "engine"))
    # gaps: 30-40 (10), 90-160 (70), 190-310 (120); all < 20 us here
    assert idle == {"<20us gaps": 200 / 1e9}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    src = os.path.join(DATA, "decode_closed_slice.xplane.pb.gz")
    if not os.path.isfile(src):
        pytest.skip("no recorded trace beside the tests")
    dst = tmp_path_factory.mktemp("trace") / "slice.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return tr.load(str(dst))


def test_recorded_trace_reduces(recorded):
    """What the readers rely on in today's trace: one TPU device plane with
    module and op lines, the step programs told apart by name, the paged
    decode kernel found by its body's name inside decode_steps."""
    t = recorded
    assert len(t.devices) == 1 and t.devices[0].ordinal == 0
    progs = {tr.program_of(m[0]) for m in t.devices[0].modules}
    assert "decode_steps" in progs
    n, secs = tr.module_time(t, {"decode_steps"})
    assert n >= 1 and secs > 0
    kern = tr.ops_inside(t, {"decode_steps"}, r"^%decode_attend_pallas_paged")
    assert kern, "the paged decode kernel is not told apart by name"
    assert (n, len(kern)) == (2, 2 * 8 * 28)   # dispatches x horizon x layers
    assert tr.module_time(t, {"mixed_step"})[0] == 1
    assert not any(tr.short_op(e[0]) in tr.CONTAINERS
                   for e in t.devices[0].ops)
    t0, t1 = tr.span_ns(t)
    busy = tr.busy_seconds(t)
    assert 0 < busy <= (t1 - t0) / 1e9
    assert len(tr.top_ops(t, 10)) == 10
    assert t.host, "no host lines"
