"""Pipeline parallelism: GPipe-style microbatched layer stages over a pp axis.

The reference has no parallelism of any kind in-repo (SURVEY.md §2.3) — pp is
net-new capability, TPU-first: stages are a mesh axis, the inter-stage hop is
a single ``lax.ppermute`` over ICI neighbors per pipeline tick, and the whole
schedule is one ``lax.scan`` inside ``shard_map`` — XLA sees a static loop of
(stage compute, neighbor permute) and overlaps the DMA with compute. No
microbatch queues, no send/recv runtime, no NCCL groups: the schedule IS the
program.

Design:
- The stacked layer params ``[L, ...]`` reshape to ``[PP, L/PP, ...]`` and
  shard ``P("pp", ...)`` — each device holds one stage's contiguous layer
  block (`to_pipeline_params`). Embedding/final-norm/head replicate (small
  next to the layer stack).
- GPipe schedule: M microbatches flow through PP stages in M + PP - 1 ticks.
  Stage 0 ingests microbatch t at tick t; the last stage computes the
  masked-CE partial sums for microbatch t - (PP-1) at tick t. Bubble ticks
  compute on zeros and are masked out of the loss — SPMD requires uniform
  compute, so the bubble costs time, not correctness (bubble fraction
  (PP-1)/(M+PP-1): pick M >= 4*PP in practice).
- Loss accumulates as (masked nll sum, mask count) pairs and divides once at
  the end, then psums over pp (only the last stage holds nonzero partials)
  and dp — so the result equals the NON-pipelined ``trainer.lm_loss`` on the
  same batch exactly, which is what the parity tests assert.
- Backward: ``shard_map``/``ppermute``/``scan`` are all differentiable (the
  transpose of a ppermute is the reverse ppermute — backward activations hop
  stage s → s-1 exactly like GPipe's backward phase). ``jax.checkpoint`` on
  the stage body gives the standard remat-per-stage memory profile.

Composition: pp × dp in one mesh (batch microbatches shard over dp). tp/sp
compose with dp/ep via GSPMD in the non-pipelined path (trainer.py); stacking
them inside the pp shard_map would need hand-written collectives per matmul
and is out of scope — at v5e-8 scale, tp×dp covers the model sizes this repo
ships, and pp exists for the depth-bound regime beyond them.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig
from aws_k8s_ansible_provisioner_tpu.models.layers import (
    _embed_inputs,
    _final_logits,
    decoder_block,
    make_default_attend,
)


def check_pp_divisibility(cfg: ModelConfig, pp: int) -> None:
    if cfg.num_layers % pp != 0:
        raise ValueError(f"pp={pp} does not divide num_layers="
                         f"{cfg.num_layers} for model {cfg.name}")


def to_pipeline_params(params: Any, pp: int) -> Any:
    """Reshape stacked layer leaves [L, ...] → [PP, L/PP, ...] (stage-major)."""
    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda x: x.reshape((pp, x.shape[0] // pp) + x.shape[1:]),
        params["layers"])
    return out


def from_pipeline_params(params: Any) -> Any:
    """Inverse of to_pipeline_params (for checkpoint export / parity tests)."""
    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]),
        params["layers"])
    return out


def pipeline_param_pspecs(cfg: ModelConfig, params: Any) -> Any:
    """Layer leaves shard on the stage axis; everything else replicates."""
    specs = jax.tree.map(lambda _: P(), params)
    specs["layers"] = jax.tree.map(
        lambda x: P("pp", *([None] * (x.ndim - 1))), params["layers"])
    return specs


def make_pipeline_lm_loss(cfg: ModelConfig, mesh: Mesh, n_microbatches: int,
                          remat: bool = True) -> Callable:
    """Build ``loss(params, tokens, loss_mask) -> scalar`` pipelined over the
    mesh's ``pp`` axis (and data-parallel over ``dp`` when present).

    ``params`` must be in pipeline form (to_pipeline_params); tokens/loss_mask
    are the full [B, T] batch — B must split into n_microbatches (times dp).
    """
    M = n_microbatches
    has_dp = "dp" in mesh.axis_names

    # honors cfg.sliding_window — the pipelined loss must match
    # model_forward's mask exactly (the parity tests' whole point)
    attend = make_default_attend(cfg)

    def stage_fwd(p_stage, x, cos, sin):
        """Run this device's layer block over activation x [mb, T, H]."""
        def body(x, p_l):
            x, _ = decoder_block(cfg, p_l, x, cos, sin, attend, None)
            return x, None
        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, p_stage)
        return x

    def shard_body(params, tokens, loss_mask):
        # tokens: [M, mb, T] (this dp shard's microbatches)
        pp_idx = jax.lax.axis_index("pp")
        # static stage count from the mesh: PP feeds range()/arange(), so it
        # must be a Python int
        PP = int(mesh.shape["pp"])
        p_stage = jax.tree.map(lambda x: x[0], params["layers"])  # [Lpp, ...]
        _, mb, T = tokens.shape
        H = cfg.hidden_size

        def tick(carry, t):
            x_in, nll_sum, cnt_sum = carry
            mb_t = jnp.clip(t, 0, M - 1)
            toks_t = tokens[mb_t]                               # [mb, T]
            positions = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None], (mb, T))
            x0, cos, sin = _embed_inputs(params, cfg, toks_t, positions)
            # stage 0 ingests microbatch t; later stages take the permuted
            # activation from their left neighbor (zeros during fill bubbles)
            x = jnp.where(pp_idx == 0, x0.astype(jnp.float32),
                          x_in).astype(x0.dtype)
            y = stage_fwd(p_stage, x, cos, sin)
            # last stage: masked-CE partials for microbatch t - (PP-1).
            # lax.cond, not a mask: the [H, V] head matmul is often the
            # largest matmul per tick and SPMD stages CAN branch on their
            # own axis index — only the last stage pays for it.
            out_mb = t - (PP - 1)
            tgt_toks = tokens[jnp.clip(out_mb, 0, M - 1)]
            tgt_mask = loss_mask[jnp.clip(out_mb, 0, M - 1)]

            def ce_partials(y):
                logits = _final_logits(params, cfg, y).astype(jnp.float32)
                logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
                nll = -jnp.take_along_axis(
                    logp, tgt_toks[:, 1:][..., None], axis=-1)[..., 0]
                m = tgt_mask[:, 1:].astype(jnp.float32)
                return (nll * m).sum(), m.sum()

            valid = (pp_idx == PP - 1) & (out_mb >= 0)
            d_nll, d_cnt = jax.lax.cond(
                valid, ce_partials,
                lambda y: (jnp.float32(0.0), jnp.float32(0.0)), y)
            nll_sum = nll_sum + d_nll
            cnt_sum = cnt_sum + d_cnt
            # hand the activation to the right neighbor for the next tick
            y_next = jax.lax.ppermute(
                y.astype(jnp.float32), "pp",
                [(i, (i + 1) % PP) for i in range(PP)])
            return (y_next, nll_sum, cnt_sum), None

        init = (jnp.zeros((mb, T, H), jnp.float32), jnp.float32(0.0),
                jnp.float32(0.0))
        (_, nll_sum, cnt_sum), _ = jax.lax.scan(
            tick, init, jnp.arange(M + PP - 1))
        # only the last stage holds partials; dp shards hold their slice
        nll_sum = jax.lax.psum(nll_sum, "pp")
        cnt_sum = jax.lax.psum(cnt_sum, "pp")
        if has_dp:
            nll_sum = jax.lax.psum(nll_sum, "dp")
            cnt_sum = jax.lax.psum(cnt_sum, "dp")
        return nll_sum / jnp.maximum(cnt_sum, 1.0)

    def loss(params, tokens, loss_mask):
        B, T = tokens.shape
        dp = mesh.shape.get("dp", 1)
        pp = mesh.shape["pp"]
        stage_dim = jax.tree.leaves(params["layers"])[0].shape[0]
        if stage_dim != pp:
            # A mismatch would silently shard stage_dim over pp devices and
            # shard_body's x[0] would DROP layers — wrong loss, no error.
            raise ValueError(f"params are staged for pp={stage_dim} but the "
                             f"mesh has pp={pp} (to_pipeline_params mismatch)")
        if B % (M * dp):
            raise ValueError(f"batch {B} must split into {M} microbatches "
                             f"x dp={dp}")
        mb = B // M
        tokens_m = tokens.reshape(M, mb, T)
        mask_m = loss_mask.reshape(M, mb, T)
        specs = pipeline_param_pspecs(cfg, params)
        data_spec = P(None, "dp", None) if has_dp else P(None, None, None)
        fn = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(specs, data_spec, data_spec),
            out_specs=P(),
            check_vma=False)
        return fn(params, tokens_m, mask_m)

    return loss


def init_pipeline_params(cfg: ModelConfig, mesh: Mesh, pp: int,
                         seed: int = 0, dtype=jnp.float32) -> Any:
    """Init params directly in pipeline form, stage-sharded over the mesh."""
    from aws_k8s_ansible_provisioner_tpu.models.layers import init_params

    check_pp_divisibility(cfg, pp)

    def build():
        return to_pipeline_params(init_params(cfg, jax.random.PRNGKey(seed),
                                              dtype), pp)

    shapes = jax.eval_shape(build)
    specs = pipeline_param_pspecs(cfg, shapes)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(build, out_shardings=shardings)()


def make_pipeline_train_step(cfg: ModelConfig, mesh: Mesh, optimizer,
                             n_microbatches: int, remat: bool = True):
    """(params, opt_state, tokens, mask) -> (params, opt_state, loss), jitted
    with donated state. Params in pipeline form (init_pipeline_params)."""
    loss_fn = make_pipeline_lm_loss(cfg, mesh, n_microbatches, remat)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens, loss_mask
             ) -> Tuple[Any, Any, jnp.ndarray]:
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, loss_mask)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step
