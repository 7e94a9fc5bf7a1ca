"""The model's parts (models/parts.py): every heavy operation a step program
writes carries a name of the closed set into the compiled HLO, and the
start-up gauge weighs the served tree by the same names.

``jax.named_scope`` puts the part into each operation's ``op_name``
(``jit(decode_steps)/while/body/.../attn.proj/dot_general``); the device
trace's reader (benchmark/benchlib/op_parts.py) names an operation by the
innermost part there, a fusion without one by what it holds. Checked here on
the CPU, for each model kind at tiny size and each step function
``aot.enumerate_programs`` yields, in the COMPILED module: every fusion,
matmul, convolution and custom call with a float result that still carries
the program's ``op_name`` names a part — its own or one it holds — or only
moves data (a parameter copy, the layer walk's slices); the compiler's own
rewrites carry no ``op_name`` at all and are not the program's to name.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu import config as C
from aws_k8s_ansible_provisioner_tpu.config import ServingConfig
from aws_k8s_ansible_provisioner_tpu.models import layers as L
from aws_k8s_ansible_provisioner_tpu.models import parts
from aws_k8s_ansible_provisioner_tpu.models.quant import quantize_params
from aws_k8s_ansible_provisioner_tpu.serving import aot
from aws_k8s_ansible_provisioner_tpu.serving import metrics as _metrics
from aws_k8s_ansible_provisioner_tpu.serving import programs as _programs

MODELS = {"tiny-qwen3": C.tiny_qwen3, "tiny-olmoe": C.tiny_olmoe,
          "tiny-qwen3-moe": C.tiny_qwen3_moe, "tiny-solar": C.tiny_solar,
          "tiny-sala": C.tiny_sala}
EVERY = {parts.EMBED, parts.NORM, parts.ATTN_PROJ, parts.ATTN_CORE,
         parts.ATTN_OUT, parts.HEAD, parts.SAMPLE}
# the parts each model kind shows, in every step program: a dense model
# shows no experts, no recurrence, no selection
SHOWS = {
    "tiny-qwen3": EVERY | {parts.MLP},
    "tiny-olmoe": EVERY | {parts.ROUTER, parts.EXPERTS},
    "tiny-qwen3-moe": EVERY | {parts.ROUTER, parts.EXPERTS},
    "tiny-solar": EVERY | {parts.ROUTER, parts.EXPERTS, parts.MLP,
                           parts.RECUR},
    "tiny-sala": EVERY | {parts.MLP, parts.RECUR, parts.SELECT},
}
# opcodes that only move data or count: a fusion of nothing else is a
# parameter copy, a scan's slice or a loop counter
MOVES = {"parameter", "constant", "bitcast", "copy", "transpose", "reshape",
         "broadcast", "slice", "dynamic-slice", "dynamic-update-slice",
         "concatenate", "pad", "iota", "tuple", "get-tuple-element",
         "convert", "compare", "select", "and", "or", "not"}
# the layer walk's own slices of the stacked weights and the scan's outputs
SLICES = {"dynamic_slice", "dynamic_update_slice", "squeeze"}
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = (\S+) ([\w\-]+)\((.*)$", re.M)


def _plan(model: str):
    cfg = MODELS[model]()
    page = cfg.sparse_block_size if cfg.selects else 16
    serving = ServingConfig(
        weights_dtype="int8", model=model, max_decode_slots=4,
        max_cache_len=16 * page if cfg.selects else 128, page_size=page,
        prefill_buckets=(16, 32), dtype="float32", prefix_cache=False,
        decode_horizon=4, decode_pipeline=1, ragged_attention=1,
        spec_decode=not cfg.recurrent and not cfg.selects, spec_k=3,
        kv_host_tier_bytes=0, attention_impl="xla")
    return aot.ProgramPlan(cfg, serving)


def _step_functions(model: str):
    """One (function name, lowered module) a step FUNCTION the plan can
    dispatch: the first variant the enumeration yields of each."""
    plan = _plan(model)
    params, cache = aot._abstract_state(plan, None)
    seen = {}
    for _, fn, args, kwargs in aot.enumerate_programs(plan, None, params,
                                                      cache):
        if fn.__name__ not in seen:
            seen[fn.__name__] = (fn, args, kwargs)
    return seen


CASES = [(m, p) for m in MODELS for p in _programs.STEP_PROGRAMS
         if not (p == "spec_decode_step"
                 and (MODELS[m]().recurrent or MODELS[m]().selects))]


def _part(op_name: str):
    return next((s for s in reversed(op_name.split("/"))
                 if s in parts.PARTS), None)


def _compiled(text: str):
    """[(instruction, result type, own part or None, opcodes it holds)] of
    the executable computations' fusions, dots, convolutions and custom
    calls; a fusion holds its fused computation's opcodes and parts."""
    comps = {}
    for comp in text.split("\n\n"):
        m = re.match(r"\s*(?:ENTRY )?%([\w.\-]+) ", comp)
        if m:
            comps[m.group(1)] = comp
    fused = set(re.findall(r"fusion\([^\n]*calls=%([\w.\-]+)", text))

    def held(name, depth=0):
        ops, named = set(), set()
        for _, _, opcode, rest in _INSTR.findall(comps.get(name, "")):
            ops.add(opcode)
            om = re.search(r'op_name="([^"]*)"', rest)
            if om and _part(om.group(1)):
                named.add(_part(om.group(1)))
            cm = re.search(r"calls=%([\w.\-]+)", rest)
            if cm and depth < 4:
                o, n = held(cm.group(1), depth + 1)
                ops |= o
                named |= n
        return ops, named

    out = []
    for name, comp in comps.items():
        if name in fused:
            continue
        for iname, shape, opcode, rest in _INSTR.findall(comp):
            if opcode not in ("dot", "convolution", "fusion", "custom-call"):
                continue
            om = re.search(r'op_name="([^"]*)"', rest)
            op_name = om.group(1) if om else ""
            cm = re.search(r"calls=%([\w.\-]+)", rest)
            ops, named = held(cm.group(1)) if cm else ({opcode}, set())
            out.append((iname, shape, op_name,
                        _part(op_name) or (sorted(named) or [None])[0], ops))
    return out


@pytest.fixture(scope="module")
def lowered():
    cache = {}

    def get(model, program):
        if model not in cache:
            cache[model] = _step_functions(model)
        fn, args, kwargs = cache[model][program]
        return fn.lower(*args, **kwargs)

    return get


@pytest.mark.parametrize("model,program", CASES)
def test_compiled_operations_name_a_part_or_only_move_data(lowered, model,
                                                           program):
    text = lowered(model, program).compile().as_text()
    shown = {p for p in parts.PARTS
             if re.search(rf'op_name="[^"]*/{re.escape(p)}/', text)}
    assert shown == SHOWS[model], sorted(shown)
    rows = _compiled(text)
    # a matmul of every part that multiplies by a kernel is there to be named
    assert {r[3] for r in rows if r[4] & {"dot", "convolution"}} \
        >= SHOWS[model] & {parts.ATTN_PROJ, parts.ATTN_OUT, parts.MLP,
                           parts.EXPERTS, parts.ROUTER, parts.HEAD}
    bare = [r[:3] for r in rows
            if r[3] is None and r[2]                # the program's, unnamed
            and re.match(r"\(?(f|bf)\d", r[1])      # with a float result
            and r[2].rsplit("/", 1)[-1] not in SLICES
            and not r[4] <= MOVES]
    assert not bare, bare[:8]


# -- the gauge: what each part weighs in the tree as it is served ------------


def _served(model: str, **over):
    cfg = MODELS[model](**over)
    return cfg, quantize_params(
        L.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16), cfg)


def _kernels(tree) -> int:
    return sum(int(leaf.size) for path, leaf in
               jax.tree_util.tree_flatten_with_path(tree)[0]
               if path[-1].key == "kernel")


# int8 dense, an MoE, a KDA share with a shared expert, the list hybrid
@pytest.mark.parametrize("model", ["tiny-qwen3", "tiny-olmoe", "tiny-solar",
                                   "tiny-sala"])
def test_parts_weights_sum_to_the_served_tree(model):
    cfg, params = _served(model)
    w = parts.param_weights(params, cfg)
    assert sum(b for b, _ in w.values()) \
        == sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    # the parts that hold parameters: no kernel call, no sampler
    assert set(w) == SHOWS[model] - {parts.ATTN_CORE, parts.SAMPLE,
                                     parts.SELECT} \
        - ({parts.EMBED} if cfg.tie_embeddings else set())
    tied = int(params["embed"]["weight"].size) if cfg.tie_embeddings else 0
    assert sum(n for _, n in w.values()) == _kernels(params) + tied
    assert list(w) == [p for p in parts.PARTS if p in w]


def test_a_tied_table_is_the_heads_and_counted_once():
    cfg, params = _served("tiny-qwen3")
    assert cfg.tie_embeddings
    w = parts.param_weights(params, cfg)
    table = params["embed"]
    assert parts.EMBED not in w
    assert w[parts.HEAD][1] == cfg.vocab_size * cfg.hidden_size
    assert w[parts.HEAD][0] == table["weight"].nbytes \
        + table["scale"].nbytes + params["final_norm"]["weight"].nbytes
    cfg, params = _served("tiny-qwen3", tie_embeddings=False)
    w = parts.param_weights(params, cfg)
    assert w[parts.EMBED] == (sum(leaf.nbytes for leaf in
                                  jax.tree.leaves(params["embed"])), 0)
    assert w[parts.HEAD][1] == int(params["lm_head"]["kernel"].size)


def test_expert_stacks_and_a_shared_expert_are_told_apart():
    cfg, params = _served("tiny-solar")
    w = parts.param_weights(params, cfg)
    stacks = shared = 0
    for kind in params["layers"].values():
        stacks += sum(_kernels(kind[n]) for n in ("w_gate", "w_up", "w_down"))
        shared += _kernels(kind["shared"])
    assert w[parts.EXPERTS][1] == stacks and w[parts.MLP][1] == shared
    assert w[parts.ROUTER][1] == sum(
        _kernels(kind["router"]) for kind in params["layers"].values())
    # the state's convolution taps and output norm are the recurrence's
    assert w[parts.RECUR] == (sum(
        leaf.nbytes for n in ("conv", "o_norm")
        for leaf in jax.tree.leaves(params["layers"]["kda"][n])), 0)


def test_a_sharded_tree_weighs_what_one_chip_holds():
    from aws_k8s_ansible_provisioner_tpu.config import MeshConfig
    from aws_k8s_ansible_provisioner_tpu.parallel.mesh import make_mesh
    from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
        shard_params)

    cfg, params = _served("tiny-qwen3")
    whole = parts.param_weights(params, cfg)
    mesh = make_mesh(MeshConfig(tp=2))
    w = parts.param_weights(shard_params(params, mesh, cfg), cfg)
    for part in (parts.ATTN_PROJ, parts.ATTN_OUT, parts.MLP):
        assert w[part][1] * 2 == whole[part][1]
    assert w[parts.NORM] == whole[parts.NORM]       # replicated


def test_the_engine_publishes_the_gauge_and_logs_it(caplog):
    import logging

    from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine

    gauge = _metrics.params_by_part
    gauge.publish({parts.EXPERTS: (7, 7)})          # another tree's
    cfg = C.tiny_qwen3()
    params = L.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    with caplog.at_level(logging.INFO):
        eng = Engine(cfg, params, ServingConfig(
            max_decode_slots=4, max_cache_len=64, prefill_buckets=(16, 32),
            dtype="float32", weights_dtype="int8", page_size=8,
            attention_impl="xla"))
    assert eng.param_weights == parts.param_weights(eng.params, cfg)
    assert gauge.by_part() == {p: (float(b), float(n))
                               for p, (b, n) in eng.param_weights.items()}
    text = gauge.registry.render()
    assert 'tpu_serve_param_bytes{part="attn.proj"}' in text
    assert 'tpu_serve_param_elements{part="head"}' in text
    assert "experts" not in text
    assert any(r.getMessage().startswith("params: ")
               and "attn.proj" in r.getMessage() for r in caplog.records)
