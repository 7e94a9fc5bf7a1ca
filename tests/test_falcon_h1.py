"""The Falcon-H1 list (every layer a Mamba-2 state-space mixer AND GQA
attention at a query group of 5 on ONE normed input, then a SwiGLU; an untied
head; twelve muP multipliers, none 1) at a tiny size on the CPU: hidden 64, 5
query heads on 1 KV head of 16, 4 SSM heads of 16 in 2 groups, state 32, 4
taps, page 8, three layers.

The reference (benchmark/reference/falcon_h1.py) is float32 at matmul
precision "highest", recomputes every sequence whole from its token ids, runs
the recurrence token by token and imports nothing from the program. The
served side is the code the step programs run: the SSM state and the conv
tail beside the paged pool in the donated cache, ``model_forward_carry`` over
one run of equal layers, the span form in blocks, the paged kernels
(interpret mode).

Tolerances, LOGITS of std ~0.6 (the maker's sizing). With float32
activations the served mathematics IS the reference's — pages for a dense
sequence, blocks of 64 rows for a token-by-token scan (the pairwise decay
from differences of running sums), a state and a tail carried across a chunk
boundary in float32 — so every row agrees to TOL_F32 = 5e-4 (measured 5e-7
to 3e-5). Each mechanism left out moves the worst row by 0.1 to 2
(``test_tolerance_catches``). int8 against bf16 weights: the same tree
dequantised IS the bf16 tree the maker rounds (a kernel's int8 bits times its
scale), so the two differ by bfloat16's rounding of a weight, 2^-9 relative:
TOL_QUANT = 0.05 on logits of that size (measured 0.004-0.012).
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from benchlib import files  # noqa: E402

from aws_k8s_ansible_provisioner_tpu.config import (  # noqa: E402
    MODEL_REGISTRY, ModelConfig, ServingConfig, tiny_falcon_h1)
from aws_k8s_ansible_provisioner_tpu.models import layers as L  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.models import parts  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.models.quant import (  # noqa: E402
    quantize_params)
from aws_k8s_ansible_provisioner_tpu.ops import (  # noqa: E402
    linear_attention as la)
from aws_k8s_ansible_provisioner_tpu.ops import (  # noqa: E402
    pallas_attention as pa)
from aws_k8s_ansible_provisioner_tpu.serving import flightrec  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.serving.engine import (  # noqa: E402
    Engine, Request)

TOL_F32, TOL_QUANT = 5e-4, 0.05
PS, CHUNK = 8, 32
CFG = tiny_falcon_h1()
BIG = MODEL_REGISTRY["tiiuae/Falcon-H1-34B-Instruct-pp8-stage0"]
MAKER = files.load_module("weight_makers", "falcon_h1")
REF = files.load_module("reference", "falcon_h1")


def _widen(tree):
    """bf16 leaves as float32 (the same numbers): both sides then compute in
    float32 and the tolerance is the mathematics' alone."""
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


@functools.lru_cache(maxsize=None)
def _params(cfg=CFG, seed=48, quant=False):
    return _widen(MAKER.make(dataclasses.asdict(cfg), seed, quant))


@pytest.fixture(scope="module", params=["bf16", "int8"])
def tree(request):
    return _params(quant=request.param == "int8")


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(2, 128, n).tolist()


def _forward(tree, ids, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        logits, _ = L.model_forward(
            tree, cfg, jnp.asarray(ids, jnp.int32)[None],
            jnp.arange(len(ids), dtype=jnp.int32)[None])
    return np.asarray(logits[0], np.float32)


# -- (a) the whole forward pass ------------------------------------------------


def test_full_forward_matches_the_reference(tree):
    ids = _ids(90)
    got = _forward(tree, ids)
    ref = np.asarray(REF.logits(dataclasses.asdict(CFG), tree, ids + [0],
                                len(ids)))
    assert 0.3 < ref.std() < 1.2
    assert np.abs(got - ref).max() < TOL_F32


@pytest.mark.parametrize("label", sorted(REF.CONTROLS))
def test_tolerance_catches(label):
    """Each control of the reference is another model, far outside the
    tolerance the program is held to."""
    ids, tree = _ids(90), _params()
    mc = dataclasses.asdict(CFG)
    ref = np.asarray(REF.logits(mc, tree, ids + [0], len(ids)))
    off = np.asarray(REF.forward(mc, tree, ids + [0], len(ids),
                                 **REF.CONTROLS[label]))
    assert np.abs(off - ref).max() > 100 * TOL_F32, label


def test_int8_is_bf16_within_the_quantisations_bound():
    ids = _ids(60, 1)
    a = _forward(_params(quant=True), ids)
    b = _forward(_params(quant=False), ids)
    assert 0 < np.abs(a - b).max() < TOL_QUANT


def test_the_stage_is_the_published_first_layers():
    """A two-stage cut of a four-layer model: stage 0's rows handed to stage
    1's layers give the uncut reference, and the PROGRAM's stage 0 (the
    first two layers' leaves, embedding and head) is the reference's pass
    over layers 0-1."""
    cfg4 = tiny_falcon_h1(num_layers=4, layer_pattern="hhhh")
    tree4 = _params(cfg4)
    mc4, ids = dataclasses.asdict(cfg4), _ids(40, 2)
    whole = np.asarray(REF.logits(mc4, tree4, ids + [0], 40))
    rows = REF.forward(mc4, tree4, ids + [0], 40, layers=range(2),
                       head=False)
    staged = np.asarray(REF.forward(mc4, tree4, ids + [0], 40,
                                    layers=range(2, 4), hidden_in=rows))
    assert np.abs(staged - whole).max() < 1e-5
    cfg2 = tiny_falcon_h1(num_layers=2, layer_pattern="hh")
    stage0 = dict(tree4, layers={"par": jax.tree.map(
        lambda a: a[:2], tree4["layers"]["par"])})
    ref0 = np.asarray(REF.forward(mc4, tree4, ids + [0], 40,
                                  layers=range(2)))
    assert np.abs(_forward(stage0, ids, cfg2) - ref0).max() < TOL_F32
    assert np.abs(ref0 - whole).max() > 0.05       # (the cut is a cut)


# -- (b) the recurrence's three forms ------------------------------------------


def _ssd_rows(T, seed, slow, N=2, H=4, P=16, G=2, S=32):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa: E731
    # decay near 1 (``slow``: exp(-1e-4) a token) or near 0 (exp(-20))
    A = -jnp.asarray(r.uniform(1e-3, 2e-3, H) if slow
                     else r.uniform(100.0, 200.0, H), jnp.float32)
    dt = jnp.asarray(r.uniform(0.05, 0.2, (N, T, H)), jnp.float32)
    return (f(N, H, S, P), f(N, T, H, P), f(N, T, G, S), f(N, T, G, S), dt,
            A, f(H))


@pytest.mark.parametrize("T,block,slow", [
    (128, 64, True), (64, 32, False), (64, 16, True), (48, 16, False),
    (5, 64, True), (33, 32, True)],
    ids=["two-blocks-slow", "two-blocks-fast", "four-blocks", "three-blocks",
         "shorter-than-a-block", "a-row-past-the-edge"])
def test_span_scan_and_step_agree(T, block, slow):
    """``ssd_span`` (blocks) = ``ssd_scan`` (token by token) = ``ssd_step``
    called T times: block edges, a span shorter than a block (padded with
    dead rows), decay near 0 and near 1."""
    S0, x, Bm, Cm, dt, A, D = _ssd_rows(T, T + block, slow)
    with jax.default_matmul_precision("highest"):
        o_scan, S_scan = jax.jit(la.ssd_scan)(S0, x, Bm, Cm, dt, A, D)
        pad = -T % block
        padded = [jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                  for a in (x, Bm, Cm, dt)]      # dt = 0: the identity
        o_span, S_span = jax.jit(functools.partial(la.ssd_span, block=block))(
            S0, *padded, A, D)
        S, rows, step = S0, [], jax.jit(la.ssd_step)
        for t in range(T):
            o, S = step(S, x[:, t], Bm[:, t], Cm[:, t], dt[:, t], A, D)
            rows.append(o)
    scale = float(jnp.abs(o_scan).max())
    assert float(jnp.abs(o_span[:, :T] - o_scan).max()) < 2e-5 * scale
    assert float(jnp.abs(S_span - S_scan).max()) < 2e-5 * scale
    assert float(jnp.abs(jnp.stack(rows, 1) - o_scan).max()) < 1e-6 * scale
    assert float(jnp.abs(S - S_scan).max()) < 1e-6 * scale


def test_the_decode_kernel_takes_a_state_that_is_not_square():
    """``kda_decode_update`` without its delta rule over [d_state, d_head]
    tiles (interpret mode) is ``lightning_step``, in place on the leaf."""
    r = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa: E731
    B, H, N, P = 3, 8, 32, 16
    state = f(2, 1, B, H, N, P)
    q, k, v = f(B, H, N), f(B, H, N), f(B, H, P)
    g = -jnp.abs(f(B, H)) * 0.1
    beta = jnp.abs(f(B, H)).at[1].set(0.0)
    g = g.at[1].set(0.0)                           # slot 1: a dead row
    o, out = la.kda_decode_update(
        state, jnp.int32(1), 0, q, k, v,
        jnp.broadcast_to(g[..., None], q.shape), beta, interpret=True,
        delta_rule=False)
    o_ref, S_ref = la.lightning_step(state[1, 0], q, k, v, g, beta)
    assert float(jnp.abs(o - o_ref).max()) < 1e-5
    assert float(jnp.abs(out[1, 0] - S_ref).max()) < 1e-5
    assert bool(jnp.all(out[0] == state[0]))       # the other layer
    assert bool(jnp.all(out[1, 0, 1] == state[1, 0, 1]))     # bitwise


# -- (c) the callbacks over the per-slot leaves --------------------------------


def _mixer_rows(T, seed, N=1):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa: E731
    C, H = CFG.ssm_conv_size, CFG.ssm_num_heads
    conv = {"weight": f(CFG.conv_taps, C) * 0.5, "bias": f(C) * 0.1}
    return conv, f(N, T, C), jnp.abs(f(N, T, H)) * 0.1, \
        -jnp.asarray([0.5, 1.0, 4.0, 30.0]), f(H)


def _leaves(slots=3, seed=9):
    r = np.random.default_rng(seed)
    return {n: jnp.asarray(r.standard_normal(shape), dt) for n, (shape, dt)
            in la._state_shapes(CFG, slots, jnp.float32).items()}


def test_a_chunk_from_a_carried_state_and_tail_is_the_unbroken_span():
    """Rows 0-69 of slot 1 as ONE span from position 0 = a 40-row chunk, then
    a 30-row chunk that starts from the state and the tail the first left
    (each in a 64-row bucket: the padding is the identity); other slots'
    leaves and the other layers' are untouched bitwise."""
    conv, xbc, dt, A, D = _mixer_rows(128, 3)
    rec0 = _leaves()

    def rows(lo, hi):       # rows [lo, hi) in a 64-row bucket
        return [jnp.pad(a[:, lo:hi], [(0, 0), (0, 64 - (hi - lo))]
                        + [(0, 0)] * (a.ndim - 2)) for a in (xbc, dt)]

    @jax.jit
    def span(rec, start, n, xbc, dt):
        return la.make_recur_span(1, start, n).ssm(
            CFG, conv, xbc, dt, A, D, (rec, jnp.int32(2)))

    with jax.default_matmul_precision("highest"):
        whole, rec_w = span(rec0, 0, 70, xbc, dt)
        first, rec = span(rec0, 0, 40, *rows(0, 40))
        second, rec = span(rec, 40, 30, *rows(40, 70))
        zero, _ = jax.jit(lambda *a: la.recur_from_zero.ssm(
            CFG, conv, *a, A, D, (rec0,)))(xbc, dt)
    whole, zero = whole[:, :70], zero[:, :70]
    assert float(jnp.abs(first[:, :40] - whole[:, :40]).max()) < 1e-5
    assert float(jnp.abs(second[:, :30] - whole[:, 40:]).max()) < 1e-5
    assert float(jnp.abs(zero - whole).max()) < 1e-5
    for name in ("ssm_state", "ssm_conv"):
        at = (2, 0, 1) if name == "ssm_state" else (2, 1)
        assert float(jnp.abs(rec[name][at] - rec_w[name][at]).max()) < 1e-5
        untouched = rec[name].at[at].set(rec0[name][at])
        assert bool(jnp.all(untouched == rec0[name]))
    # the tail is the last K - 1 rows of x | B | C before the convolution
    assert bool(jnp.all(rec["ssm_conv"][2, 1] == xbc[0, 67:70]))
    assert bool(jnp.all(rec_w["ssm_conv"][2, 1] == xbc[0, 67:70]))


def test_a_mixed_steps_dead_rows_leave_state_and_tail_bitwise():
    """mixed_step's packed rows: 3 decode rows (slot 1 idle, slot 2 the
    chunking slot's own row: both dead) then a 16-row chunk of slot 2, 11
    rows valid, from position 0. The dead slots' leaves stay BITWISE; the
    live row advances as ``decode`` alone does; the chunk as a span."""
    B, Cn = 3, 16
    conv, xbc, dt, A, D = _mixer_rows(B + Cn, 4)
    rec0, at = _leaves(), jnp.int32(1)
    live = jnp.asarray([True, False, False])
    out, rec = jax.jit(lambda rec: la.make_recur_mixed(B, live, 2, 0, 11).ssm(
        CFG, conv, xbc, dt, A, D, (rec, at)))(rec0)
    assert out.shape == (1, B + Cn, CFG.ssm_num_heads, CFG.ssm_head_dim)
    for name in ("ssm_state", "ssm_conv"):
        s1 = (1, 0, 1) if name == "ssm_state" else (1, 1)
        assert bool(jnp.all(rec[name][s1] == rec0[name][s1])), name
    dec, rec_d = jax.jit(lambda rec: la.make_recur_decode(live).ssm(
        CFG, conv, xbc[0, :B, None], dt[0, :B, None], A, D, (rec, at)))(rec0)
    assert bool(jnp.all(dec[:, 0] == out[0, :B]))
    assert bool(jnp.all(rec_d["ssm_state"][1, 0, 0]
                        == rec["ssm_state"][1, 0, 0]))
    span, rec_s = jax.jit(lambda rec: la.make_recur_span(2, 0, 11).ssm(
        CFG, conv, xbc[:, B:], dt[:, B:], A, D, (rec, at)))(rec0)
    assert float(jnp.abs(span - out[:, B:]).max()) < 1e-6
    assert float(jnp.abs(rec_s["ssm_state"][1, 0, 2]
                         - rec["ssm_state"][1, 0, 2]).max()) < 1e-6
    # a dead decode row's output is the D skip alone over a decayed state:
    # it is never read; what matters is that nothing of it is KEPT
    assert bool(jnp.all(rec["ssm_conv"][1, 1] == rec0["ssm_conv"][1, 1]))


# -- (d) config, plan, leaves --------------------------------------------------


def test_the_kind_attends_and_recurs():
    assert BIG.layer_list and BIG.recurrent and BIG.recurrent_kinds == "SSM"
    assert BIG.num_attn_layers == BIG.num_recurrent_layers == 9
    assert (BIG.ssm_size, BIG.ssm_conv_size, BIG.ssm_in_size) \
        == (4096, 5120, 9248)
    assert BIG.num_heads // BIG.num_kv_heads == 5 and BIG.kv_lane_pack == 1
    # nine equal layers are ONE run: one scan body in every step program
    assert L.layer_plan(BIG) == [("h", None, 0, 0, 0, 9)]
    shapes = la._state_shapes(BIG, 64, jnp.bfloat16)
    assert shapes["ssm_state"] == ((9, 1, 64, 32, 256, 128), jnp.float32)
    assert shapes["ssm_conv"] == ((9, 64, 3, 5120), jnp.float32)
    # 4 MiB of state and 60 KiB of tail a slot and layer
    assert la.state_bytes(BIG, 64) == 9 * 64 * (4 * 2**20 + 61_440)
    assert la.is_state("ssm_state") and la.is_state("ssm_conv")


def test_the_recount_is_the_published_34b_and_the_stages_6_5b():
    mc = dataclasses.asdict(BIG)
    assert MAKER.param_counts(mc, 72)["total"] == 33_642_516_224
    assert MAKER.param_counts(mc)["total"] == 6_544_954_208
    assert MAKER.param_counts(mc)["layer"] == 430_120_032
    # the program's own tree holds as many
    shapes = jax.eval_shape(
        lambda: L.init_params(BIG, jax.random.key(0), jnp.bfloat16))
    assert L.param_count(shapes) == 6_544_954_208


@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bf16"])
def test_seeded_tree_has_the_programs_layout(quant):
    def theirs():
        p = L.init_params(BIG, jax.random.key(0), jnp.bfloat16)
        return quantize_params(p, BIG) if quant else p

    def flat(t):
        return {tuple(k.key for k in path): (tuple(leaf.shape),
                                             str(leaf.dtype))
                for path, leaf in jax.tree_util.tree_flatten_with_path(t)[0]}

    want = flat(jax.eval_shape(theirs))
    spec = {k: (tuple(s), d) for k, (s, d)
            in MAKER.tree_spec(dataclasses.asdict(BIG), quant).items()}
    assert spec == want
    made = flat(MAKER.make(dataclasses.asdict(CFG), 3, quant))
    assert made == {k: (tuple(s), d) for k, (s, d) in MAKER.tree_spec(
        dataclasses.asdict(CFG), quant).items()}


def test_the_parts_table_weighs_the_new_leaves():
    tree = MAKER.make(dataclasses.asdict(CFG), 3, True)
    w = parts.param_weights(tree, CFG)
    n, H = CFG.num_layers, CFG.hidden_size
    assert set(w) == {"embed", "norm", "attn.proj", "attn.out", "mlp",
                      "recur", "head"}
    # the SSM's in-projection streams with wq / wk / wv, its out-projection
    # with wo: the parts the blocks' scopes name them under
    assert w["attn.proj"][1] == n * H * (CFG.q_size + 2 * CFG.kv_size
                                         + CFG.ssm_in_size)
    assert w["attn.out"][1] == n * H * (CFG.q_size + CFG.ssm_size)
    assert w["recur"][1] == 0 and w["recur"][0] > 0
    assert len(parts.PARTS) == 12


@pytest.mark.parametrize("over,match", [
    (dict(layer_pattern="hhg"), "list of their own"),
    (dict(layer_pattern="hh"), "list of their own"),
    (dict(ssm_num_groups=3), "whole number"),
    (dict(ssm_state_size=0), "ssm_state_size"),
    (dict(conv_taps=1), "conv_taps"),
    (dict(ssm_multipliers=(1.0, 2.0)), "five segments"),
    (dict(mlp_multipliers=(1.0,)), "gate and"),
])
def test_config_refuses_what_the_kind_cannot_be(over, match):
    with pytest.raises(ValueError, match=match):
        tiny_falcon_h1(**over)


def test_a_config_from_json_is_hashable_and_equal():
    import json

    mc = json.loads(json.dumps(dataclasses.asdict(CFG)))
    assert isinstance(mc["ssm_multipliers"], list)
    again = ModelConfig(**mc)
    assert again == CFG and hash(again) == hash(CFG)
    # 1e11 from JSON is an int past int32: the rotary tables take it
    cos, _ = L.rope_cos_sin(jnp.arange(4), 16, 100000000000)
    assert bool(jnp.all(jnp.isfinite(cos)))


def test_the_ragged_entry_pads_a_group_of_five():
    """20 query heads in groups of 5 (a tile's head axis is sliced in whole
    sublane tiles on the chip): the ragged entry gives every KV head's group
    a dead sixth head and drops it again — the same numbers as the XLA
    attention — and leaves a power-of-two group's call as it was."""
    r = np.random.default_rng(6)
    hkv, g, d, ps, pages = 4, 5, 16, 8, 6
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa: E731
    pool_k, pool_v = f(2, pages + 1, hkv, ps, d), f(2, pages + 1, hkv, ps, d)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    N = 16
    row_map = jnp.asarray([0] * 8 + [1] * 8, jnp.int32)
    limits = jnp.asarray([20] * 7 + [0] + list(range(3, 11)), jnp.int32)
    q = f(N, hkv * g, d)
    got = pa.ragged_attend_pallas_paged(
        q, pool_k, pool_v, limits, jnp.int32(1), table, row_map,
        interpret=True, bblock=8)
    assert got.shape == q.shape
    # every row's keys: its slot's pages in order, [N, hkv, 3 * ps, d]
    k, v = (pool[1, table[row_map]].transpose(0, 2, 1, 3, 4
                                              ).reshape(N, hkv, -1, d)
            for pool in (pool_k, pool_v))
    s = jnp.einsum("nhgd,nhsd->nhgs", q.reshape(N, hkv, g, d), k) / d ** 0.5
    s = jnp.where(jnp.arange(3 * ps)[None, None, None]
                  < limits[:, None, None, None], s, -1e30)
    want = jnp.einsum("nhgs,nhsd->nhgd", jax.nn.softmax(s, -1), v)
    want = jnp.where(limits[:, None, None] > 0,
                     want.reshape(N, hkv * g, d), 0.0)    # a dead row: zeros
    assert float(jnp.abs(got - want).max()) < 1e-4
    # no pad where the heads fill whole tiles, or fit one: no pad op is traced
    for heads in (hkv * 4, hkv * 1):
        text = str(jax.make_jaxpr(
            lambda q: pa.ragged_attend_pallas_paged(
                q, pool_k, pool_v, limits, jnp.int32(1), table, row_map,
                interpret=True, bblock=8))(f(N, heads, d)))
        assert " pad[" not in text.split("pallas_call")[0]


# -- (e) the served path -------------------------------------------------------


def _engine(params, cfg=CFG, **over):
    kw = dict(max_decode_slots=4, max_cache_len=256, prefill_buckets=(16, 32),
              dtype="float32", weights_dtype="bf16", prefix_cache=True,
              decode_horizon=2, page_size=PS, decode_pipeline=1,
              ragged_attention=1, attention_impl="xla",
              kv_host_tier_bytes=0, derived_seed=7, prefill_chunk=CHUNK)
    kw.update(over)
    return Engine(cfg, params, ServingConfig(**kw))


def _drain(eng):
    for _ in range(10000):
        if not eng.step():
            return
    raise AssertionError("engine did not drain")


def _submit(eng, n, s, max_tokens=12):
    return eng.submit(Request(prompt_ids=_ids(n, s), ignore_eos=True,
                              max_tokens=max_tokens, logprobs=0))


def _streams(eng):
    """A 90-token prompt (three chunks of 32 through ``mixed_step``, the
    later ones from a carried state and tail) arrives under a live stream,
    and two short prompts behind it; two more queue together on the idle
    engine (a packed batch); then a request takes a slot another left."""
    a = _submit(eng, 20, 3, 40)
    for _ in range(3):
        eng.step()
    reqs = [a] + [_submit(eng, n, s) for n, s in ((90, 4), (9, 5), (11, 6))]
    _drain(eng)
    reqs += [_submit(eng, n, s) for n, s in ((10, 8), (12, 9))]
    _drain(eng)
    reqs.append(_submit(eng, 13, 7, 6))
    _drain(eng)
    return reqs


def _ref_logprobs(cfg, params, r):
    ids = r.prompt_ids + r.generated
    rows = REF.logprobs(dataclasses.asdict(cfg), params, ids,
                        len(r.generated))
    return rows, rows[np.arange(len(r.generated)), r.generated]


@pytest.fixture(scope="module", params=["xla", "pallas"])
def served(request):
    params = _params()
    eng = _engine(params, attention_impl=request.param)
    seen, orig = [], flightrec.record

    def tap(*a, **rec):
        if a[0] == "dispatch":
            seen.append(dict(rec))
        return orig(*a, **rec)

    flightrec.record = tap
    try:
        reqs = _streams(eng)
    finally:
        flightrec.record = orig
    return params, eng, reqs, seen


def test_prefill_then_decode_through_cache_and_state_is_the_references(
        served):
    """prefill_step, prefill_batch_step, three chunks of mixed_step beside a
    live row and two one-chunk walks behind them, decode steps, a reused
    slot: every stream's rows are the reference's full pass — the served
    token's log-softmaxed LOGIT and its distance from the row's maximum
    (with ``pallas`` through the paged kernels in interpret mode, the ragged
    entry at the padded group and the decode kernel over [32, 16] tiles)."""
    params, eng, reqs, seen = served
    mixed = [r for r in seen if r["program"] == "mixed_step"]
    assert [r["chunk_n"] for r in mixed] == [32, 32, 26, 9, 11]
    assert "prefill_batch_step" in {r["program"] for r in seen}
    for r in reqs:
        rows, ref_lp = _ref_logprobs(CFG, params, r)
        got = np.asarray([lp[0] for lp in r.logprob_data], np.float32)
        assert np.abs(got - ref_lp).max() < TOL_F32
        assert (rows.max(-1) - ref_lp).max() < TOL_F32
    assert len({t for r in reqs for t in r.generated}) > 20   # no echo


def test_dispatch_records_and_metrics_carry_the_new_names(served):
    params, eng, reqs, seen = served
    dec = [r for r in seen if r["program"] == "decode_steps"]
    mix = [r for r in seen if r["program"] == "mixed_step"]
    assert dec and mix
    for r in dec + mix:
        assert r["state_kind"] == "SSM" and "kda_rows" not in r
        assert r["ssm_slots"] == r["state_slots"] == r["active"]
    assert all(r["ssm_span_rows"] == 0 and "attn_pages_live" in r
               for r in dec)
    assert [(r["ssm_span_rows"], r["ssm_span_blocks"]) for r in mix] \
        == [(32, 1), (32, 1), (26, 1), (9, 1), (11, 1)]
    text = eng.metrics.registry.render()
    state, tail = eng.cache["ssm_state"], eng.cache["ssm_conv"]
    assert state.shape == (3, 1, 4, 4, 32, 16) and state.dtype == jnp.float32
    assert tail.shape == (3, 4, 3, CFG.ssm_conv_size)
    assert eng.cache["k"].shape[0] == 3        # a pool leaf a layer, too
    n = state.nbytes + tail.nbytes
    assert f"tpu_serve_ssm_state_bytes {float(n)}" in text \
        or f"tpu_serve_ssm_state_bytes {n}" in text
    assert "tpu_serve_ssm_span_rows_total 110" in text
    assert 'tpu_serve_state_rows_total{kind="SSM",program="decode_steps"}' \
        in text
    assert 'tpu_serve_recurrent_state_bytes{kind="SSM"}' in text
    # a recurrent model consults no prefix index, and says so
    assert 'tpu_serve_prefix_lookups_skipped_total{reason="recurrent_state"}' \
        in text


def test_the_start_up_log_states_the_states_bytes(caplog):
    import logging

    with caplog.at_level(logging.INFO):
        eng = _engine(_params())
    line = next(r.getMessage() for r in caplog.records
                if "SSM state" in r.getMessage())
    n = eng.cache["ssm_state"].nbytes
    assert f"SSM state {n} bytes (3 layers x 4 slots x 4 heads x [32, 16]" \
        in line and "beside the KV pool's" in line
    assert f"{4 * 32 * 16 * 4} bytes a slot and layer" in line


def test_a_reused_slot_starts_from_zeros():
    """One slot, two requests one after the other: the second reads zeros
    before its position 0 — state AND tail — not what the first left."""
    params = _params()
    eng = _engine(params, max_decode_slots=1)
    first = _submit(eng, 25, 8, 8)
    _drain(eng)
    assert float(jnp.abs(eng.cache["ssm_state"]).max()) > 0
    assert float(jnp.abs(eng.cache["ssm_conv"]).max()) > 0
    second = _submit(eng, 9, 9, 8)
    _drain(eng)
    for r in (first, second):
        _, ref_lp = _ref_logprobs(CFG, params, r)
        got = np.asarray([lp[0] for lp in r.logprob_data], np.float32)
        assert np.abs(got - ref_lp).max() < TOL_F32
    # and the comparison would see a state that was kept
    rows = REF.logprobs(dataclasses.asdict(CFG), params,
                        second.prompt_ids + second.generated, 8,
                        wrong="stale_state")
    got = np.asarray([lp[0] for lp in second.logprob_data], np.float32)
    assert np.abs(got - rows[np.arange(8), second.generated]).max() > 0.02


REFUSED = {
    "spec": (dict(spec_decode=True), "speculative decoding"),
    "host-tier": (dict(kv_host_tier_bytes=1 << 20), "host KV tier"),
    "int8-kv": (dict(kv_dtype="int8"), "int8 KV"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_start_up_refuses_what_it_refuses_for_the_other_recurrent_kinds(
        what):
    over, sentence = REFUSED[what]
    with pytest.raises(ValueError, match=sentence) as e:
        _engine(_params(), **over)
    assert "recurrent (SSM) layers" in str(e.value)


def test_the_dry_run_server_knows_the_list():
    from aws_k8s_ansible_provisioner_tpu.serving import server

    args = server.build_parser().parse_args(
        ["--model", "tiny-falcon-h1", "--max-decode-slots", "2",
         "--max-cache-len", "128", "--kv-host-tier-bytes", "0"])
    state = server.build_state(server.serving_config_from_args(args))
    assert state.engine.cfg.layer_pattern == "hhh"
    assert "ssm_state" in state.engine.cache


# -- (f) the checkpoint's names ------------------------------------------------


def test_hf_loader_maps_the_checkpoints_names_onto_the_tree():
    """A seeded state dict under the names ``_convert_falcon_h1`` expects
    (from memory of the family's code: untested against a checkpoint) comes
    back as the tree it was made from."""
    from aws_k8s_ansible_provisioner_tpu.models.hf_loader import (
        convert_state_dict)

    tree = L.init_params(CFG, jax.random.key(2), jnp.float32)
    par = tree["layers"]["par"]
    sd = {"model.embed_tokens.weight": tree["embed"]["weight"],
          "model.final_layernorm.weight": tree["final_norm"]["weight"],
          "lm_head.weight": tree["lm_head"]["kernel"].T}
    names = {"wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
             "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
             "w_gate": "feed_forward.gate_proj",
             "w_up": "feed_forward.up_proj",
             "w_down": "feed_forward.down_proj"}
    for i in range(CFG.num_layers):
        pre = f"model.layers.{i}."
        for ours, theirs in names.items():
            sd[pre + theirs + ".weight"] = par[ours]["kernel"][i].T
        s = par["ssm"]
        sd[pre + "mamba.in_proj.weight"] = s["w_in"]["kernel"][i].T
        sd[pre + "mamba.out_proj.weight"] = s["wo"]["kernel"][i].T
        sd[pre + "mamba.conv1d.weight"] = s["conv"]["weight"][i].T[:, None]
        sd[pre + "mamba.conv1d.bias"] = s["conv"]["bias"][i]
        sd[pre + "mamba.norm.weight"] = s["o_norm"]["weight"][i]
        for name in ("dt_bias", "A_log", "D"):
            sd[pre + "mamba." + name] = s[name][i]
        sd[pre + "input_layernorm.weight"] = par["input_norm"]["weight"][i]
        sd[pre + "pre_ff_layernorm.weight"] = par["post_norm"]["weight"][i]
    got = convert_state_dict(CFG, {k: np.asarray(v) for k, v in sd.items()},
                             jnp.float32)

    def flat(t):
        return {jax.tree_util.keystr(p): leaf for p, leaf
                in jax.tree_util.tree_flatten_with_path(t)[0]}

    a, b = flat(got), flat(tree)
    assert set(a) == set(b)
    for k in a:
        assert a[k].shape == b[k].shape and bool(jnp.all(a[k] == b[k])), k
