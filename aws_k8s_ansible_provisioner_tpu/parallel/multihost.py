"""Multi-host (DCN) support: process-spanning meshes + per-process data feed.

The reference's scale-out story is NCCL inside vLLM — which it never actually
configures (SURVEY.md §2.3: single L4). Here multi-host is first-class and
TPU-native: on a multi-host slice (v5e-16+) or across slices, every host runs
the SAME program, ``jax.distributed.initialize`` wires the processes together
(TPU pods auto-detect coordinator/count from the metadata server; explicit
args cover CPU rigs and tests), the mesh simply spans ``jax.devices()`` —
which after initialization enumerates ALL hosts' chips — and XLA routes
collectives over ICI within a host/slice and DCN across (the compiler knows
the topology; nothing to install or configure, deleting the reference's
implicit NCCL layer entirely).

Data feeding is the one part that is per-process: a host may only materialize
the shards its own devices own. ``device_put_global`` builds a global array
from a (deterministically generated) global numpy batch by asking the
sharding which index-slices this process's devices hold — every host computes
the same cheap synthetic/tokenized batch and materializes only its slice, so
no host ever holds the global batch on device and no host-to-host data
exchange happens at feed time.

Self-test (run one per process, any machine, no TPUs needed):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    python -m aws_k8s_ansible_provisioner_tpu.parallel.multihost \\
        --coordinator localhost:9955 --num-processes 2 --process-id <i>

It builds a (dp=4, tp=2) process-spanning mesh over all 8 global devices,
runs two sharded training steps with per-process feeding, and prints the
loss — which must be identical on every process AND equal to a
single-process run on the same seed (tests/test_multihost.py asserts both).
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

log = logging.getLogger("tpu_serve.multihost")


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> dict:
    """Initialize the JAX distributed runtime (idempotent).

    With no arguments on a TPU pod, coordinator/count/id auto-detect from the
    TPU metadata environment. Explicit args are for DCN rigs without metadata
    (and for multi-process CPU tests). Returns a summary dict.
    """
    if not jax.distributed.is_initialized():
        if coordinator_address is None:
            jax.distributed.initialize()
        else:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
    info = {
        "process_id": jax.process_index(),
        "num_processes": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
    }
    log.info("distributed: %s", info)
    return info


def device_put_global(global_np: np.ndarray, mesh, pspec: P) -> jax.Array:
    """Materialize a globally-sharded array from a host-replicated numpy batch.

    Every process passes the SAME ``global_np`` (deterministic generation is
    the contract — e.g. training/loop.synthetic_data_fn keyed on (seed,
    step)); each materializes only the slices its own devices hold, so the
    per-host device footprint is the shard, not the batch.
    """
    sharding = NamedSharding(mesh, pspec)
    return jax.make_array_from_callback(
        global_np.shape, sharding, lambda idx: global_np[idx],
        dtype=global_np.dtype)


def _selftest(args) -> None:
    import optax

    jax.config.update("jax_platforms", "cpu")
    init_distributed(args.coordinator, args.num_processes, args.process_id)

    from aws_k8s_ansible_provisioner_tpu.config import MeshConfig, tiny_qwen3
    from aws_k8s_ansible_provisioner_tpu.parallel import make_mesh
    from aws_k8s_ansible_provisioner_tpu.parallel.sharding import tokens_pspec
    from aws_k8s_ansible_provisioner_tpu.training import (init_train_state,
                                                          make_train_step)

    cfg = tiny_qwen3()
    mesh_cfg = MeshConfig(dp=args.dp, tp=args.tp)
    if mesh_cfg.num_devices != jax.device_count():
        raise ValueError(
            f"selftest mesh dp*tp={mesh_cfg.num_devices} must span ALL "
            f"{jax.device_count()} global devices — a smaller mesh would "
            f"leave some processes without addressable shards")
    # jax.devices() now spans every process — the mesh is the multi-host mesh
    mesh = make_mesh(mesh_cfg, devices=jax.devices())
    opt = optax.adamw(1e-3)
    state = init_train_state(cfg, mesh, opt, seed=args.seed)
    step = make_train_step(cfg, mesh, opt)
    # the SAME deterministic stream the training loop uses — every process
    # generates identical batches and materializes only its own shards
    from aws_k8s_ansible_provisioner_tpu.training import synthetic_data_fn
    data = synthetic_data_fn(cfg, 4 * mesh_cfg.dp, 16, args.seed)
    loss = None
    for s in range(2):
        tokens, mask = data(s)
        g_tok = device_put_global(tokens, mesh, tokens_pspec())
        g_mask = device_put_global(mask, mesh, tokens_pspec())
        state, loss = step(state, g_tok, g_mask)
    # every process prints the (replicated) loss; the test asserts equality
    print(f"MULTIHOST_SELFTEST process={jax.process_index()}/"
          f"{jax.process_count()} devices={jax.device_count()} "
          f"loss={float(loss):.6f}", flush=True)


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description="multi-host self-test")
    p.add_argument("--coordinator", default=None)
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--dp", type=int, default=4)
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    _selftest(args)


if __name__ == "__main__":
    main()
