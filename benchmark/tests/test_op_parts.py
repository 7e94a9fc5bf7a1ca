"""The part of the model a device operation belongs to
(benchlib/op_parts.py, layer_metrics/{decode_named_share_pct,
decode_dense_share_pct, decode_dense_roofline_pct,
mixed_dense_roofline_pct}.py): the part parser, the few lines of protobuf
wire format on messages built here field by field, the program x part
reduction on fixed event lists, the readers on the recorded slice of a chip
run with its HLO modules and dispatch records (tests/data/parts_slice.*),
and on the recorded traces WITHOUT scopes that were already there — where
every new reader has to answer None."""

import gzip
import json
import os
import shutil

import pytest

from benchlib import files
from benchlib import op_parts as op
from benchlib import trace_reduce as tr
from benchlib.session import LayerContext

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("decode_named_share_pct", "decode_dense_share_pct",
       "decode_dense_roofline_pct", "mixed_dense_roofline_pct")
PARTS = ("embed", "norm", "attn.proj", "attn.core", "attn.out", "mlp",
         "router", "experts", "recur", "select", "head", "sample")


def test_the_closed_set_is_the_programs():
    assert op.PARTS == PARTS and set(op.DENSE) < set(op.PARTS)


def test_part_is_the_innermost_name_of_the_closed_set():
    scan = "jit(decode_steps)/while/body/closed_call/while/body/closed_call/"
    assert op.part_of(scan + "attn.proj/dot_general") == "attn.proj"
    assert op.part_of(scan + "attn.core/select/reduce_sum") == "select"
    assert op.part_of(scan + "attn.core/jit(decode_attend_pallas_paged)/"
                      "pallas_call") == "attn.core"
    assert op.part_of(scan + "dynamic_slice") is None
    assert op.part_of("") is None
    # a name that only CONTAINS a part's is none of it
    assert op.part_of("jit(f)/mlp_extra/mul") is None
    assert op.part_of("jit(f)/mlp/mul", parts=()) is None


# -- messages, field by field --------------------------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(field: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint((field << 3) | 2) + _varint(len(value)) + value


def _instr(name, op_name="", called=(), packed=True) -> bytes:
    body = _f(1, name) + _f(2, "fusion" if called else "add")
    if op_name:
        body += _f(7, _f(2, op_name))
    if called and packed:
        body += _f(38, b"".join(_varint(c) for c in called))
    else:
        body += b"".join(_f(38, c) for c in called)
    return _f(2, body)


def _module(comps) -> bytes:
    """HloProto of computations [(id, [instruction bytes])]."""
    return _f(1, b"".join(_f(3, _f(1, f"c{cid}") + b"".join(ins) + _f(5, cid))
                          for cid, ins in comps))


def _xspace(modules: dict, plane="/host:metadata") -> bytes:
    entries = b"".join(
        _f(4, _f(1, i) + _f(2, _f(1, i) + _f(2, name)
                            + _f(5, _f(1, 1) + _f(6, blob))))
        for i, (name, blob) in enumerate(modules.items(), 1))
    return _f(1, _f(2, "/device:TPU:0")) + _f(1, _f(2, plane) + entries)


def test_wire_reader_finds_the_modules_and_their_instructions_parts():
    pre = "jit(decode_steps)/while/body/closed_call/"
    hlo = _module([
        (1, [_instr("dot.1", pre + "attn.proj/dot_general"),
             _instr("bitcast.9"), _instr("mul.2", pre + "attn.proj/mul"),
             _instr("add.3", pre + "norm/add")]),
        (2, [_instr("copy.5")]),
        (3, [_instr("fusion.7", pre + "mlp/add", called=[1]),
             # rooted at the compiler's bitcast: takes what it holds
             _instr("convert_bitcast_fusion.6", called=[1]),
             _instr("nested.2", called=[4], packed=False),
             _instr("copy_fusion.1", called=[2]),
             _instr("slice.4", pre + "dynamic_slice")]),
        (4, [_instr("inner.1", called=[1])])])
    space = _xspace({"jit_decode_steps(77)": hlo, "jit_other(3)": _module([])})
    mods = op.hlo_modules(memoryview(space))
    assert set(mods) == {"jit_decode_steps(77)", "jit_other(3)"}
    got = op.instruction_parts(mods["jit_decode_steps(77)"], PARTS)
    assert got["dot.1"] == "attn.proj" and got["add.3"] == "norm"
    assert got["fusion.7"] == "mlp"                     # its own
    assert got["convert_bitcast_fusion.6"] == "attn.proj"   # 2 of 3 inside
    assert got["nested.2"] == "attn.proj"               # through a fusion
    for bare in ("bitcast.9", "copy.5", "copy_fusion.1", "slice.4"):
        assert bare not in got
    assert op.instruction_parts(mods["jit_other(3)"], PARTS) == {}
    # no metadata plane (a cut trace), no closed set (the parent): nothing
    assert op.hlo_modules(memoryview(_xspace({"m": hlo}, "/host:CPU"))) == {}
    assert op.instruction_parts(hlo, ()) == {}


# -- the reduction on fixed event lists ---------------------------------------


def _device(modules, ops):
    return tr.Trace(devices=[tr.DeviceTrace(0, modules=modules, ops=ops)])


MODS = {
    "jit_decode_steps(1)": {"fusion.1": "attn.proj", "fusion.2": "mlp",
                            "kernel.3": "attn.core"},
    # a second variant of the same function, and another program
    "jit_decode_steps(2)": {"fusion.90": "head"},
    "jit_mixed_step(5)": {"fusion.1": "mlp", "fusion.8": "sample"},
}


def test_events_join_the_program_by_time_and_the_part_by_instruction():
    us = 1_000
    trace = _device(
        [("jit_decode_steps(1)", 0, 100 * us),
         ("jit_mixed_step(5)", 200 * us, 100 * us),
         ("jit_decode_steps(1)", 400 * us, 100 * us)],
        [("%fusion.1 = bf16[8] fusion(x)", 1 * us, 10 * us),
         ("%kernel.3 = bf16[8] custom-call(x)", 20 * us, 50 * us),
         ("%copy.4 = bf16[8] copy(x)", 80 * us, 5 * us),
         ("%fusion.1 = bf16[8] fusion(x)", 201 * us, 30 * us),
         ("%fusion.8 = s32[8] fusion(x)", 240 * us, 20 * us),
         ("%fusion.2 = bf16[8] fusion(x)", 410 * us, 40 * us),
         ("%fusion.2 = bf16[8] fusion(x)", 900 * us, 7 * us)])
    evs = op.events(trace, MODS)
    assert [(e[0], e[1]) for e in evs] == [
        ("decode_steps", "attn.proj"), ("decode_steps", "attn.core"),
        ("decode_steps", "-"), ("mixed_step", "mlp"),
        ("mixed_step", "sample"), ("decode_steps", "mlp"), ("-", "-")]
    assert op.table(evs)["decode_steps"] == pytest.approx(
        {"attn.core": 50e-6, "mlp": 40e-6, "attn.proj": 10e-6, "-": 5e-6})
    assert list(op.table(evs)) == ["decode_steps", "mixed_step", "-"]
    assert op.seconds(evs, "decode_steps") == pytest.approx(105e-6)
    assert op.seconds(evs, "decode_steps", op.DENSE) == pytest.approx(50e-6)
    per = op.by_execution(evs, "decode_steps",
                          [(0, 100 * us), (400 * us, 500 * us)])
    assert per[0] == pytest.approx({"attn.proj": 10e-6, "attn.core": 50e-6,
                                    "-": 5e-6})
    assert per[1] == pytest.approx({"mlp": 40e-6})


def test_a_variant_is_told_by_the_instructions_it_ran():
    trace = _device([("jit_decode_steps(9)", 0, 100)],      # a name not held
                    [("%fusion.90 = f32[2] fusion(x)", 1, 9)])
    assert op.events(trace, MODS)[0][:2] == ("decode_steps", "head")


def test_nothing_named_is_none_not_a_table_of_dashes():
    trace = _device([("jit_decode_steps(1)", 0, 100)],
                    [("%copy.4 = f32[2] copy(x)", 1, 9)])
    assert op.events(trace, MODS) is None
    assert op.events(trace, {}) is None and op.events(None, MODS) is None


# -- the recorded slices ------------------------------------------------------


def _ctx(side, trace, spans=None):
    return LayerContext(
        cell=None, mc=side["mc"], peaks=side.get("peaks") or PEAKS, chips=1,
        t0=0.0, t1=40.0, counters={}, traced_counters={},
        spans=[tuple(s) for s in (side["spans"] if spans is None else spans)],
        samples=[], dispatches=[], trace=trace, trace_t0=0.0, trace_t1=0.0,
        engine=side["engine"], memory_peak_bytes=0, client={})


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """``lay(stem, weights)``: a recorded slice laid out as a traced run
    leaves its trace, with the gauge the run's server published."""
    from aws_k8s_ansible_provisioner_tpu.serving import metrics
    from benchlib import session

    was = metrics.params_by_part.by_part()

    def lay(stem, weights):
        d = tmp_path / "plugins" / "profile" / stem
        d.mkdir(parents=True)
        path = str(d / "slice.xplane.pb")
        with gzip.open(os.path.join(DATA, stem + ".xplane.pb.gz")) as f, \
                open(path, "wb") as g:
            shutil.copyfileobj(f, g)
        monkeypatch.setattr(session, "TRACE_DIR", str(tmp_path))
        metrics.params_by_part.publish(weights)
        return path

    yield lay
    metrics.params_by_part.publish(was)


def test_readers_on_the_recorded_slice_of_a_chip_run(traced):
    with open(os.path.join(DATA, "parts_slice.records.json")) as f:
        side = json.load(f)
    weights = {p: tuple(w) for p, w in side["param_weights"].items()}
    path = traced("parts_slice", weights)
    trace = tr.load(path)
    ctx = _ctx(side, trace)
    evs = op.of_context(ctx)
    table = op.table(evs)
    assert {"decode_steps", "mixed_step"} <= set(table)
    dec = table["decode_steps"]
    # the model's parts, and no other names: a dense model
    assert set(dec) <= set(PARTS) - {"router", "experts", "recur",
                                     "select"} | {"-"}
    assert set(op.DENSE) <= set(dec)
    assert max(dec, key=dec.get) == "attn.core"     # the kernel's cell
    got = {n: files.load_module("layer_metrics", n).read(ctx) for n in NEW}
    # what the whole run's line read (my chip run, PR 36: 98.3 / 13.5 /
    # 74.7 / 71.0): the slice holds 3 of its 35 decode dispatches
    assert got == pytest.approx({
        "decode_named_share_pct": 98.32, "decode_dense_share_pct": 13.45,
        "decode_dense_roofline_pct": 73.98,
        "mixed_dense_roofline_pct": 70.94}, abs=0.05)
    # a part the gauge does not weigh drops out of both sides
    from aws_k8s_ansible_provisioner_tpu.serving import metrics

    metrics.params_by_part.publish(
        {p: w for p, w in weights.items() if p != "head"})
    less = files.load_module("layer_metrics",
                             "decode_dense_roofline_pct").read(ctx)
    assert 5.0 < less < 100.0 and less != got["decode_dense_roofline_pct"]
    # no dispatch records (a program without them): the shares stay, the
    # rooflines have nothing to count
    bare = _ctx(side, trace, spans=[])
    assert files.load_module("layer_metrics",
                             "decode_dense_roofline_pct").read(bare) is None
    assert files.load_module("layer_metrics",
                             "decode_named_share_pct").read(bare) \
        == pytest.approx(got["decode_named_share_pct"])


@pytest.mark.parametrize("stem", ["decode_closed_slice", "mixed_slice"])
def test_every_new_reader_is_none_on_a_trace_without_scopes(traced, stem):
    """The recorded slices of PRs 23 and 24: a program with no scope, traces
    cut without their metadata plane."""
    path = traced(stem, {"mlp": (1e9, 1e9), "head": (1e8, 1e8)})
    side = {"mc": {"hidden_size": 1024}, "engine": {"horizon": 8},
            "spans": []}
    records = os.path.join(DATA, stem + ".records.json")
    if os.path.isfile(records):
        with open(records) as f:
            side = dict(side, **{k: v for k, v in json.load(f).items()
                                 if k in ("spans", "engine")})
    ctx = _ctx(side, tr.load(path))
    assert op.load_parts(path) == {} and op.of_context(ctx) is None
    for name in NEW:
        assert files.load_module("layer_metrics", name).read(ctx) is None
    # and with no trace at all
    ctx = _ctx(side, None)
    for name in NEW:
        assert files.load_module("layer_metrics", name).read(ctx) is None
