"""AOT compiled-program registry: deviceless compilation + HBM fit ledger.

Replica respawn is the common case (drain, failover, rolling restarts,
reconciler repair), and every respawned engine pays 20-40 s of serial XLA
compile per program before ``/readyz`` flips. This module compiles the FULL
program set a serving config can dispatch — the same enumeration
``EnginePrograms.warmup`` walks (``serving/programs.py``) — **ahead of time
and deviceless**, then writes a committed manifest recording per-program
compile seconds and ``memory_analysis()`` bytes, summed into an HBM ledger
(params + KV pages + max temp) with an explicit fit/no-fit verdict against
per-chip capacity. An over-budget config fails fast at deploy time (non-zero
exit) instead of OOMing on the first burst.

Compilation target, best available first:

1. ``jax.experimental.topologies`` — an abstract TPU topology (default
   ``v5e:2x4`` = v5e-8) when libtpu is importable AND the process's own
   backend is a TPU: real Mosaic/XLA-TPU lowering of the chip's branch, for
   chips that need not be attached. On a host-only process the attention
   layer would trace its host branch, so ``--platform tpu`` refuses there
   and ``auto`` takes target 2. The GCE metadata probe is skipped explicitly
   (``TPU_SKIP_MDS_QUERY``) — without it the topology lookup hangs on
   non-GCE hosts.
2. An 8-device host-platform mesh of identical axis shapes otherwise
   (``--xla_force_host_platform_device_count``): identical program
   *structure* and exact params/KV ledger bytes; temp bytes become a
   host-backend proxy (recorded as such in the manifest).

Programs compile through ``jax.jit(...).lower(abstract args).compile()`` —
operands are ``ShapeDtypeStruct``s built by ``jax.eval_shape`` over the same
init/quantize functions the engine calls, so nothing model-sized is ever
materialized (Qwen3-8B AOT runs in megabytes of host RAM).

Usage::

    python -m aws_k8s_ansible_provisioner_tpu.serving.aot \
        --model Qwen/Qwen3-8B --tp 8 --out AOT_QWEN3_8B_v5e8.json

At serve time the engine consumes the manifest (``--aot-manifest`` on the
server CLI → ``EnginePrograms.load_aot_manifest``): the config fingerprint
is re-checked, the ledger lands on ``tpu_serve_hbm_compiled_bytes``, and
warmup compiles through the persistent compilation cache the AOT run
populated (``--cache-dir`` / ``JAX_COMPILATION_CACHE_DIR``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import Optional

MANIFEST_SCHEMA = "tpu-serve-aot/v1"
V5E_HBM_GIB_PER_CHIP = 16.0
# Fields every program entry must carry (schema check + tests).
PROGRAM_FIELDS = ("name", "compile_seconds", "argument_bytes",
                  "output_bytes", "temp_bytes", "generated_code_bytes")
LEDGER_FIELDS = ("capacity_bytes_per_chip", "params_bytes_per_chip",
                 "kv_bytes_per_chip", "max_temp_bytes", "total_bytes",
                 "headroom_bytes", "fit")


# ---------------------------------------------------------------------------
# Sizing plan (mirrors Engine.__init__ / EnginePrograms._init_params_and_cache
# arithmetic; tests/test_aot.py pins the two against each other)
# ---------------------------------------------------------------------------


class ProgramPlan:
    """The derived sizes every program's operand shapes hang off."""

    def __init__(self, cfg, serving, dp: int = 1, tp: int = 1):
        self.cfg, self.serving = cfg, serving
        self.dp, self.tp = dp, tp
        self.num_slots = serving.max_decode_slots
        if self.num_slots % dp:
            raise ValueError(f"max_decode_slots={self.num_slots} must be "
                             f"divisible by dp={dp}")
        max_len = -(-serving.max_cache_len // 256) * 256 \
            if serving.max_cache_len > 256 else serving.max_cache_len
        self.max_len = min(max_len, cfg.max_seq_len)
        self.buckets = tuple(b for b in serving.prefill_buckets
                             if b <= self.max_len)
        if not self.buckets:
            raise ValueError("no prefill bucket fits the cache window")
        self.kv_quant = serving.kv_dtype == "int8"
        self.weights_quant = serving.weights_dtype == "int8"
        self.pages_per_slot = -(-self.max_len // serving.page_size)
        pool_pages = serving.kv_pool_pages \
            or self.num_slots * self.pages_per_slot
        if serving.kv_pool_pages and pool_pages % dp:
            raise ValueError(f"kv_pool_pages={pool_pages} must be "
                             f"divisible by dp={dp}")
        # +1 scratch page per dp group (engine layout)
        self.total_pages = dp * (pool_pages // dp + 1)
        # batched-prefill row bucket: the engine rounds the live batch up to
        # a power of two, warmup fills min(max_prefill_batch, num_slots)
        nb = max(1, min(serving.max_prefill_batch, self.num_slots))
        self.batch_rows = 1 << (nb - 1).bit_length()
        # chunk program width: configured chunk, else the largest bucket
        # (the prefix-cache suffix path dispatches it even when plain
        # chunked prefill is off) — Engine._chunk_size
        self.chunk = serving.prefill_chunk if serving.prefill_chunk > 0 \
            else self.buckets[-1]
        self.horizon = max(1, serving.decode_horizon)
        # the window layers' inventory of a list that also holds full ones
        from aws_k8s_ansible_provisioner_tpu.ops.kv_pool import (
            window_inventory)

        self.win_pages = window_inventory(
            cfg, self.num_slots, self.pages_per_slot, serving.page_size,
            self.horizon, self.chunk)[1]
        self.spec_rows = serving.spec_k + 1 if serving.spec_decode else 0

    def fingerprint(self) -> dict:
        """The config facts a consuming engine must match."""
        return {
            "model": self.cfg.name,
            "num_slots": self.num_slots,
            "max_len": self.max_len,
            "page_size": self.serving.page_size,
            "buckets": list(self.buckets),
            "weights_dtype": self.serving.weights_dtype,
            "kv_dtype": self.serving.kv_dtype,
            "dp": self.dp, "tp": self.tp,
        }


# ---------------------------------------------------------------------------
# Abstract operands
# ---------------------------------------------------------------------------


def _mesh_for(devices, dp: int, tp: int):
    from aws_k8s_ansible_provisioner_tpu.config import MeshConfig
    from aws_k8s_ansible_provisioner_tpu.parallel.mesh import make_mesh

    need = dp * tp
    if len(devices) < need:
        raise RuntimeError(f"need {need} devices for dp={dp} tp={tp}, "
                           f"have {len(devices)}")
    return make_mesh(MeshConfig(dp=dp, tp=tp), devices=list(devices)[:need])


def _with_sharding(sds_tree, pspec_tree, mesh, device=None):
    """Attach shardings to a ShapeDtypeStruct pytree: NamedShardings under a
    mesh; without one, every leaf is PLACED on ``device`` (the described
    chip) — an unplaced operand would lower the program for the process's
    default backend, i.e. compile it for the host under the chip's name."""
    import jax
    from jax.sharding import NamedSharding, SingleDeviceSharding

    if mesh is None:
        if device is None:
            return sds_tree
        one = SingleDeviceSharding(device)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            sds_tree)
    return jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        sds_tree, pspec_tree)


def _abstract_state(plan, mesh, device=None):
    """(params, cache) as ShapeDtypeStruct pytrees with the engine's
    shardings — via eval_shape over the engine's own init/quantize fns, so
    shapes can never drift from what the engine dispatches."""
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
    from aws_k8s_ansible_provisioner_tpu.models.quant import quantize_params
    from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
        param_pspecs, pool_pspecs)
    from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp

    cfg, serving = plan.cfg, plan.serving
    dtype = jnp.bfloat16 if serving.dtype == "bfloat16" else jnp.float32
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), dtype))
    if plan.weights_quant:
        params = jax.eval_shape(lambda p: quantize_params(p, cfg), params)
    # one chip holds every leaf whole: no spec is read (and a model with a
    # layer pattern, which the engine refuses a mesh, has no rule table)
    params = _with_sharding(
        params, None if mesh is None
        else param_pspecs(cfg, quant_weights=plan.weights_quant), mesh,
        device)
    cache = jax.eval_shape(
        lambda: kvp.init_pool(cfg, plan.total_pages, serving.page_size,
                              dtype, quant=plan.kv_quant,
                              win_pages=plan.win_pages))
    cache = _with_sharding(cache, pool_pspecs(plan.kv_quant), mesh, device)
    if cfg.recurrent:
        # the per-slot recurrent state beside the pool (one chip: the
        # engine refuses a mesh for such a model)
        from aws_k8s_ansible_provisioner_tpu.ops import linear_attention

        state = jax.eval_shape(lambda: linear_attention.init_state(
            cfg, plan.num_slots, dtype))
        cache.update(_with_sharding(
            state, jax.tree.map(lambda _: None, state), None, device))
    return params, cache


def _sharded_bytes(sds_tree, pspec_tree, mesh) -> int:
    """Exact per-chip bytes of a sharded pytree: each leaf's bytes divided
    by the product of the mesh-axis sizes its PartitionSpec names
    (replicated leaves count whole — every chip holds them)."""
    import jax

    if mesh is None:
        return sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(sds_tree))
    total = 0
    leaves = zip(jax.tree.leaves(sds_tree),
                 jax.tree.leaves(pspec_tree, is_leaf=lambda x: x is None
                                 or isinstance(x, tuple)))
    for leaf, spec in leaves:
        shards = 1
        if spec is not None:
            for axes in spec:
                for ax in ((axes,) if isinstance(axes, str)
                           else (axes or ())):
                    shards *= mesh.shape.get(ax, 1)
        size = 1
        for d in leaf.shape:
            size *= d
        total += (size * leaf.dtype.itemsize) // max(1, shards)
    return total


# ---------------------------------------------------------------------------
# Program enumeration (mirrors EnginePrograms.warmup scope="full")
# ---------------------------------------------------------------------------


def enumerate_programs(plan, mesh, params, cache, bblock: int = 1):
    """Full program set for the config: one (name, jit_fn, args, kwargs)
    per distinct compiled executable the engine can dispatch. Mirrors
    ``warmup(scope="full")``: every prefill bucket, batched prefill, the
    chunk program, the fused decode (one program for every substep count),
    its penalties and logprobs variants, and the spec-verify program when
    speculation is on."""
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.serving.programs import (
        BAN_K, BIAS_K, decode_steps, mixed_step, prefill_batch_step,
        prefill_chunk_step, prefill_step, spec_decode_step)

    cfg, serving = plan.cfg, plan.serving
    B, pps = plan.num_slots, plan.pages_per_slot

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    scalar = sds((), i32)

    # a model with recurrent layers: the prefill programs are told whose
    # per-slot state they build (EnginePrograms._state_kw)
    slot_kw = dict(slot=scalar) if cfg.recurrent else {}

    def win_kw(name: str, *lead):
        """The window layers' table operand (EnginePrograms._win_kw)."""
        return {name: sds(lead + (pps,), i32)} if cfg.windowed else {}

    def prefill_kwargs(n: Optional[int] = None):
        """Per-request operand rows; ``n`` rows for the batch program, the
        single-prompt scalar layout otherwise."""
        if n is None:
            return dict(
                **slot_kw, **win_kw("wpages"), pages=sds((pps,), i32),
                seed=sds((), u32), ban_ids=sds((BAN_K,), i32),
                ban_until=scalar, bias_ids=sds((BIAS_K,), i32),
                bias_vals=sds((BIAS_K,), f32), rep=sds((), f32))
        return dict(
            **({"slots": sds((n,), i32)} if cfg.recurrent else {}),
            **win_kw("wtables", n), tables=sds((n, pps), i32),
            seeds=sds((n,), u32), ban_ids=sds((n, BAN_K), i32),
            ban_until=sds((n,), i32), bias_ids=sds((n, BIAS_K), i32),
            bias_vals=sds((n, BIAS_K), f32), reps=sds((n,), f32))

    programs = []
    for b in plan.buckets:
        programs.append((
            f"prefill_b{b}", prefill_step,
            (cfg, params, cache, sds((1, b), i32), scalar, rng,
             sds((), f32), scalar, sds((), f32)),
            prefill_kwargs()))
    # logprobs variants compile against the smallest bucket (any bucket
    # proves the variant; warmup uses an isolated small request too)
    b0 = plan.buckets[0]
    programs.append((
        f"prefill_b{b0}_logprobs", prefill_step,
        (cfg, params, cache, sds((1, b0), i32), scalar, rng,
         sds((), f32), scalar, sds((), f32)),
        dict(prefill_kwargs(), logprobs=True, prompt_logprobs=True)))
    n = plan.batch_rows
    programs.append((
        f"prefill_batch_n{n}_b{b0}", prefill_batch_step,
        (cfg, params, cache, sds((n, b0), i32), sds((n,), i32), rng,
         sds((n,), f32), sds((n,), i32), sds((n,), f32)),
        prefill_kwargs(n)))
    programs.append((
        f"prefill_chunk_c{plan.chunk}", prefill_chunk_step,
        (cfg, params, cache, sds((1, plan.chunk), i32), scalar, scalar,
         rng, sds((), f32), scalar, sds((), f32)),
        dict(pages=sds((pps,), i32),
             seed=sds((), u32), ban_ids=sds((BAN_K,), i32),
             ban_until=scalar, bias_ids=sds((BIAS_K,), i32),
             bias_vals=sds((BIAS_K,), f32), rep=sds((), f32),
             rep_seen=sds((cfg.vocab_size,), jnp.bool_), **slot_kw,
             **win_kw("wpages"))))

    # an MoE model's decode and mixed programs take the live-slot mask
    # (EnginePrograms._live_rows); a dense model's take no such operand
    live = sds((B,), jnp.bool_) \
        if cfg.num_experts > 0 or cfg.recurrent else None

    def decode_kwargs(penalties=False, logprobs=False):
        kw = dict(
            mesh=mesh, impl=serving.attention_impl, logprobs=logprobs,
            penalties=penalties,
            table=sds((B, pps), i32),
            seeds=sds((B,), u32), ban_ids=sds((B, BAN_K), i32),
            ban_until=sds((B,), i32), bias_ids=sds((B, BIAS_K), i32),
            bias_vals=sds((B, BIAS_K), f32), bblock=bblock, live=live,
            steps=scalar, **win_kw("wtable", B))
        if penalties:
            kw.update(counts=sds((B, cfg.vocab_size), i32),
                      presence=sds((B,), f32), frequency=sds((B,), f32),
                      repetition=sds((B,), f32),
                      prompt_mask=sds((B, cfg.vocab_size), jnp.bool_))
        return kw

    decode_args = (cfg, plan.horizon, params, cache, sds((B,), i32),
                   sds((B,), i32), rng, sds((B,), f32), sds((B,), i32),
                   sds((B,), f32))
    # ONE program whatever the substeps a dispatch runs: the count is the
    # ``steps`` operand, the horizon only sizes the outputs
    programs.append((f"decode_fused_h{plan.horizon}", decode_steps,
                     decode_args, decode_kwargs()))
    programs.append((f"decode_fused_h{plan.horizon}_penalties", decode_steps,
                     decode_args, decode_kwargs(penalties=True)))
    programs.append((f"decode_fused_h{plan.horizon}_logprobs", decode_steps,
                     decode_args, decode_kwargs(logprobs=True)))
    if (serving.ragged_attention > 0 and serving.decode_pipeline > 0
            and (serving.ragged_features > 0 or not serving.spec_decode)):
        # Ragged mixed-batch program (ISSUE 14): one dispatch serves a
        # prefill chunk packed alongside every decode row. Operand layout
        # mirrors EnginePrograms._mixed_dispatch exactly. With
        # ragged_features (ISSUE 16) the spec-decode clause relaxes —
        # verify now hands the carry off instead of forcing a pre-spec
        # drain, so a spec-enabled engine still runs the mixed program.
        mixed_args = (cfg, params, cache, sds((B,), i32), sds((B,), i32),
                      sds((1, plan.chunk), i32), scalar, scalar, scalar,
                      sds((), f32), sds((cfg.vocab_size,), jnp.bool_),
                      sds((), u32), sds((), f32), scalar, sds((), f32), rng,
                      sds((B,), f32), sds((B,), i32), sds((B,), f32))
        mixed_kwargs = dict(
            mesh=mesh, impl=serving.attention_impl,
            table=sds((B, pps), i32), seeds=sds((B,), u32),
            ban_ids=sds((B, BAN_K), i32), ban_until=sds((B,), i32),
            bias_ids=sds((B, BIAS_K), i32),
            bias_vals=sds((B, BIAS_K), f32), bblock=bblock, live=live,
            **win_kw("wtable", B))
        programs.append((f"mixed_c{plan.chunk}", mixed_step,
                         mixed_args, mixed_kwargs))
        if serving.ragged_features > 0:
            # Guided variant (ISSUE 16): decode-row allow bitset + the
            # chunking request's own grammar row — the per-row mask
            # operands _mixed_dispatch passes when any guided slot is
            # active. Proven once here so the first guided admission on a
            # manifest-adopted replica never compiles.
            W = (cfg.vocab_size + 31) // 32
            programs.append((
                f"mixed_c{plan.chunk}_guided", mixed_step, mixed_args,
                dict(mixed_kwargs, allow=sds((B, W), u32),
                     pallow=sds((1, W), u32))))
    if plan.spec_rows:
        R = plan.spec_rows
        programs.append((
            f"spec_verify_r{R}", spec_decode_step,
            (cfg, R, params, cache, sds((B, R), i32), sds((B,), i32), rng,
             sds((B,), f32), sds((B,), i32), sds((B,), f32)),
            dict(impl=serving.attention_impl, mesh=mesh,
                 table=sds((B, pps), i32),
                 seeds=sds((B,), u32), bblock=bblock)))
    return programs


# ---------------------------------------------------------------------------
# Deviceless compile + ledger
# ---------------------------------------------------------------------------


def _memory_entry(compiled) -> dict:
    """memory_analysis() bytes, zero-filled where the backend reports none
    (the host platform's analysis is partial — flagged via ``platform``)."""
    ma = None
    try:
        ma = compiled.memory_analysis()
    except Exception:            # tpulint: disable=R3 backend-optional API — CPU executables may not implement memory stats; zeros are the documented degraded value
        ma = None
    get = (lambda k: int(getattr(ma, k, 0) or 0)) if ma is not None \
        else (lambda k: 0)
    return {
        "argument_bytes": get("argument_size_in_bytes"),
        "output_bytes": get("output_size_in_bytes"),
        "temp_bytes": get("temp_size_in_bytes"),
        "generated_code_bytes": get("generated_code_size_in_bytes"),
    }


def compile_programs(programs, progress=None) -> list:
    entries = []
    for name, fn, args, kwargs in programs:
        t0 = time.perf_counter
        start = t0()
        compiled = fn.lower(*args, **kwargs).compile()
        dt = t0() - start
        entry = {"name": name, "compile_seconds": round(dt, 3)}
        entry.update(_memory_entry(compiled))
        entries.append(entry)
        if progress:
            progress(f"  {name}: {dt:.2f}s compile, "
                     f"temp {entry['temp_bytes'] / 2**20:.1f} MiB")
    return entries


def build_ledger(plan, mesh, params, cache, entries,
                 hbm_gib: float = V5E_HBM_GIB_PER_CHIP) -> dict:
    from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
        param_pspecs, pool_pspecs)

    capacity = int(hbm_gib * 2**30)
    pspecs = None if mesh is None \
        else param_pspecs(plan.cfg, quant_weights=plan.weights_quant)
    params_bytes = _sharded_bytes(params, pspecs, mesh)
    # ``cache``: the pool and, beside it, a recurrent model's per-slot state
    kv_bytes = _sharded_bytes(cache, pool_pspecs(plan.kv_quant), mesh)
    max_temp = max((e["temp_bytes"] for e in entries), default=0)
    total = params_bytes + kv_bytes + max_temp
    return {
        "capacity_bytes_per_chip": capacity,
        "params_bytes_per_chip": params_bytes,
        "kv_bytes_per_chip": kv_bytes,
        "max_temp_bytes": max_temp,
        "total_bytes": total,
        "headroom_bytes": capacity - total,
        "fit": total <= capacity,
        # Tier-2 KV (ISSUE 20): the host-RAM prefix-page budget the engine
        # will pin. Informational — host DRAM, NOT counted against the HBM
        # capacity above — but part of the fit story: a pod spec must
        # reserve it on top of the process's baseline RSS. Absent from
        # LEDGER_FIELDS so pre-tier manifests still verify.
        "host_tier_bytes": int(getattr(plan.serving,
                                       "kv_host_tier_bytes", 0)),
    }


def verify_manifest(m: dict) -> None:
    """Schema check shared by tests, ``make aot-smoke``, and the engine's
    load path. Raises ValueError on any structural problem."""
    if m.get("schema") != MANIFEST_SCHEMA:
        raise ValueError(f"manifest schema {m.get('schema')!r} != "
                         f"{MANIFEST_SCHEMA!r}")
    for key in ("platform", "config", "programs", "hbm_ledger",
                "total_compile_seconds"):
        if key not in m:
            raise ValueError(f"manifest missing {key!r}")
    if not m["programs"]:
        raise ValueError("manifest has no programs")
    for p in m["programs"]:
        for f in PROGRAM_FIELDS:
            if f not in p:
                raise ValueError(f"program entry missing {f!r}: {p}")
    for f in LEDGER_FIELDS:
        if f not in m["hbm_ledger"]:
            raise ValueError(f"hbm_ledger missing {f!r}")


def build_manifest(cfg, serving, dp: int = 1, tp: int = 1,
                   devices=None, platform: str = "host",
                   topology: str = "", bblock: int = 1,
                   hbm_gib: float = V5E_HBM_GIB_PER_CHIP,
                   progress=None) -> dict:
    """Compile the full program set for (cfg, serving) over ``devices`` and
    return the manifest dict. ``devices`` defaults to the current backend's
    (the host-platform path)."""
    import jax

    if devices is None:
        devices = jax.devices()
    if platform == "tpu":
        from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

        if not pallas_attention.supported():
            # The attention layer picks its branch from the process's
            # backend: on a host-only process it traces the XLA fallback and
            # interpret-mode kernels, which is not the program the chip runs.
            raise RuntimeError(
                "aot: refusing to compile under the name 'tpu' — this "
                "process's JAX backend is "
                f"{jax.default_backend()!r}, so the programs would take the "
                "host branch (XLA attention, interpret-mode kernels). Run "
                "on a TPU host, or pass --platform host for the host proxy")
    plan = ProgramPlan(cfg, serving, dp=dp, tp=tp)
    mesh = _mesh_for(devices, dp, tp) if dp * tp > 1 else None
    if mesh is not None and cfg.num_experts > 0 and cfg.moe_impl != "gshard":
        plan.cfg = cfg = cfg.scaled(moe_impl="gshard")  # engine mesh path
    params, cache = _abstract_state(plan, mesh, devices[0])
    programs = enumerate_programs(plan, mesh, params, cache, bblock=bblock)
    if progress:
        progress(f"compiling {len(programs)} programs for "
                 f"{cfg.name} dp={dp} tp={tp} on {platform}...")
    entries = compile_programs(programs, progress=progress)
    ledger = build_ledger(plan, mesh, params, cache, entries,
                          hbm_gib=hbm_gib)
    manifest = {
        "schema": MANIFEST_SCHEMA,
        "platform": platform,
        "topology": topology,
        "jax_version": jax.__version__,
        "bblock": bblock,
        "config": plan.fingerprint(),
        "programs": entries,
        "hbm_ledger": ledger,
        "total_compile_seconds": round(
            sum(e["compile_seconds"] for e in entries), 3),
    }
    verify_manifest(manifest)
    return manifest


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _acquire_devices(args):
    """(devices, platform, topology): abstract TPU topology devices when
    libtpu imports (and --platform allows), else host-platform devices."""
    if args.platform in ("auto", "tpu"):
        from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

        try:
            import libtpu  # noqa: F401
            have_libtpu = True
        except ImportError:
            have_libtpu = False
        # 'auto' takes the described chip only where the programs would
        # trace the chip's own branch (build_manifest refuses otherwise)
        if have_libtpu and (args.platform == "tpu"
                            or pallas_attention.supported()):
            # Without the skip flag the topology lookup queries the GCE
            # metadata server and hangs (effectively) forever off-GCE.
            os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
            os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-8")
            os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
            from jax.experimental import topologies

            topo = topologies.get_topology_desc(args.topology, "tpu")
            return list(topo.devices), "tpu", args.topology
        if args.platform == "tpu":
            raise RuntimeError("--platform tpu requires libtpu")
    import jax

    # Exactly dp*tp host devices: the persistent-cache key covers the
    # compile options (device count included), so an 8-device AOT run would
    # never produce cache hits for a single-device consumer engine.
    need = max(1, args.dp * args.tp)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={need}").strip()
    devices = jax.devices("cpu")
    return devices, "host", f"host:{len(devices)}"


def _resolve_model(name: str, serving):
    from aws_k8s_ansible_provisioner_tpu.config import (
        MODEL_REGISTRY, tiny_qwen3)

    if name in MODEL_REGISTRY:
        return MODEL_REGISTRY[name]
    if name == "tiny-qwen3":
        return tiny_qwen3()
    raise SystemExit(f"aot: unknown model {name!r}; registered: "
                     f"{sorted(MODEL_REGISTRY)} or tiny-qwen3")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m aws_k8s_ansible_provisioner_tpu.serving.aot",
        description="AOT-compile the full serving program set deviceless "
                    "and write the compile/HBM manifest.")
    ap.add_argument("--model", default="Qwen/Qwen3-8B")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=8)
    ap.add_argument("--topology", default="v5e:2x4",
                    help="jax.experimental.topologies descriptor")
    ap.add_argument("--platform", choices=("auto", "tpu", "host"),
                    default="auto")
    ap.add_argument("--out", default="",
                    help="manifest path (default: stdout)")
    ap.add_argument("--cache-dir", default="",
                    help="populate this persistent compilation cache "
                         "(what serve-time warmup then hits)")
    ap.add_argument("--hbm-gib", type=float, default=V5E_HBM_GIB_PER_CHIP,
                    help="per-chip HBM capacity for the fit verdict")
    ap.add_argument("--bblock", type=int, default=0,
                    help="decode batch block to compile (0: the config's "
                         "pin, else 1 — runtime autotune may still pick "
                         "another and warm-compile it)")
    ap.add_argument("--max-cache-len", type=int, default=0,
                    help="override ServingConfig.max_cache_len")
    ap.add_argument("--slots", type=int, default=0,
                    help="override ServingConfig.max_decode_slots")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    devices, platform, topology = _acquire_devices(args)

    import dataclasses

    import jax

    from aws_k8s_ansible_provisioner_tpu.config import ServingConfig

    if args.cache_dir:
        os.makedirs(args.cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", args.cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    serving = ServingConfig(model=args.model)
    overrides = {}
    if args.max_cache_len:
        overrides["max_cache_len"] = args.max_cache_len
    if args.slots:
        overrides["max_decode_slots"] = args.slots
    if overrides:
        serving = dataclasses.replace(serving, **overrides)
    cfg = _resolve_model(args.model, serving)
    bblock = args.bblock or (serving.decode_bblock
                             if serving.decode_bblock > 0 else 1)
    progress = None if args.quiet else \
        (lambda msg: print(msg, file=sys.stderr))
    manifest = build_manifest(cfg, serving, dp=args.dp, tp=args.tp,
                              devices=devices, platform=platform,
                              topology=topology, bblock=bblock,
                              hbm_gib=args.hbm_gib, progress=progress)
    text = json.dumps(manifest, indent=1)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text + "\n")
    else:
        print(text)
    ledger = manifest["hbm_ledger"]
    verdict = "FIT" if ledger["fit"] else "NO-FIT"
    print(f"aot: {len(manifest['programs'])} programs, "
          f"{manifest['total_compile_seconds']:.1f}s total compile "
          f"[{platform}/{topology}]; HBM {ledger['total_bytes'] / 2**30:.2f}"
          f" / {ledger['capacity_bytes_per_chip'] / 2**30:.0f} GiB per chip"
          f" -> {verdict}", file=sys.stderr)
    return 0 if ledger["fit"] else 2


if __name__ == "__main__":
    sys.exit(main())
