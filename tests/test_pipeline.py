"""Pipeline parallelism: GPipe schedule parity vs the non-pipelined path.

The load-bearing property: the pipelined loss (and its gradients, via one
optimizer step) EXACTLY equals trainer.lm_loss on the same params/batch — the
microbatch accumulation is masked-sum/count, not mean-of-means, so no
weighting skew; the ppermute schedule must be pure plumbing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aws_k8s_ansible_provisioner_tpu.config import MeshConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.parallel import (
    check_pp_divisibility,
    from_pipeline_params,
    init_pipeline_params,
    make_mesh,
    make_pipeline_lm_loss,
    make_pipeline_train_step,
    to_pipeline_params,
)
from aws_k8s_ansible_provisioner_tpu.training import make_train_step
from aws_k8s_ansible_provisioner_tpu.training.trainer import lm_loss


def _data(cfg, B, T, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
    mask = np.ones_like(tokens)
    mask[:, : T // 4] = 0  # ragged mask exercises the masked-sum path
    return jnp.asarray(tokens), jnp.asarray(mask)


def test_round_trip_params():
    cfg = tiny_qwen3(num_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    pp = to_pipeline_params(params, 2)
    assert pp["layers"]["wq"]["kernel"].shape[0] == 2
    back = from_pipeline_params(pp)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 params, back)


def test_pp_divisibility_error():
    with pytest.raises(ValueError, match="pp=3"):
        check_pp_divisibility(tiny_qwen3(num_layers=4), 3)


@pytest.mark.parametrize("pp,M", [(2, 2), (2, 4), (4, 4)])
def test_pipeline_loss_matches_lm_loss(cpu_devices, pp, M):
    cfg = tiny_qwen3(num_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    tokens, mask = _data(cfg, B=M * 2, T=16)
    ref = lm_loss(params, cfg, tokens, mask, remat=False)

    mesh = make_mesh(MeshConfig(pp=pp), devices=cpu_devices[:pp])
    loss_fn = make_pipeline_lm_loss(cfg, mesh, n_microbatches=M, remat=False)
    got = loss_fn(to_pipeline_params(params, pp), tokens, mask)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)


def test_pipeline_dp_composition(cpu_devices):
    """pp=2 x dp=2: microbatches shard over dp; loss still matches exactly."""
    cfg = tiny_qwen3(num_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    tokens, mask = _data(cfg, B=8, T=12, seed=3)
    ref = lm_loss(params, cfg, tokens, mask, remat=False)
    mesh = make_mesh(MeshConfig(dp=2, pp=2), devices=cpu_devices[:4])
    loss_fn = make_pipeline_lm_loss(cfg, mesh, n_microbatches=2, remat=False)
    got = loss_fn(to_pipeline_params(params, 2), tokens, mask)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)


def test_pipeline_remat_parity(cpu_devices):
    cfg = tiny_qwen3(num_layers=4)
    params = to_pipeline_params(
        init_params(cfg, jax.random.PRNGKey(4), jnp.float32), 2)
    tokens, mask = _data(cfg, B=4, T=12, seed=5)
    mesh = make_mesh(MeshConfig(pp=2), devices=cpu_devices[:2])
    l0 = make_pipeline_lm_loss(cfg, mesh, 2, remat=False)(params, tokens, mask)
    l1 = make_pipeline_lm_loss(cfg, mesh, 2, remat=True)(params, tokens, mask)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)


def test_pipeline_train_step_matches_nonpipelined(cpu_devices):
    """One optimizer step through the pipeline == one step of the standard
    GSPMD train step: gradients through scan+ppermute are exact."""
    cfg = tiny_qwen3(num_layers=4)
    tokens, mask = _data(cfg, B=4, T=16, seed=6)
    opt = optax.sgd(0.1)  # stateless-ish: no moment rescaling noise

    # reference: single-device mesh train step
    mesh1 = make_mesh(MeshConfig(), devices=cpu_devices[:1])
    from aws_k8s_ansible_provisioner_tpu.training import init_train_state
    state = init_train_state(cfg, mesh1, opt, seed=7)
    ref_step = make_train_step(cfg, mesh1, opt, remat=False)
    ref_state, ref_loss = ref_step(state, tokens, mask)

    # pipelined: same init (seed 7), pp=2
    mesh = make_mesh(MeshConfig(pp=2), devices=cpu_devices[:2])
    p = init_pipeline_params(cfg, mesh, pp=2, seed=7)
    opt_state = opt.init(p)
    step = make_pipeline_train_step(cfg, mesh, opt, n_microbatches=2,
                                    remat=False)
    p2, _, loss = step(p, opt_state, tokens, mask)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
        from_pipeline_params(p2), ref_state.params)
