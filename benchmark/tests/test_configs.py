"""The configuration files equal MODEL_REGISTRY's entries; the benchmark's
seeded tree has the layout the program's own quantizer gives."""

import os

import pytest

from benchlib import files
from benchlib import server_under_test as sut

CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(files.BENCH_DIR,
                                                         "configs")))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_equals_the_registry(name):
    cfg = files.load_json(os.path.join(files.BENCH_DIR, "configs",
                                       name + ".json"))
    mc = sut.model_config_of(cfg)      # raises SystemExit on any difference
    assert mc.name == cfg["registry_name"]
    bench = files.load_json(os.path.join(files.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == name)
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == []


@pytest.mark.parametrize("quant", [True, False])
def test_seeded_tree_has_the_programs_layout(quant):
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.config import tiny_qwen3
    from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
    from aws_k8s_ansible_provisioner_tpu.models.quant import quantize_params

    for tie in (True, False):
        cfg = tiny_qwen3(tie_embeddings=tie)
        mc = {"num_layers": cfg.num_layers, "hidden_size": cfg.hidden_size,
              "vocab_size": cfg.vocab_size, "head_dim": cfg.head_dim,
              "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
              "intermediate_size": cfg.intermediate_size,
              "tie_embeddings": tie}

        def theirs():
            p = init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
            return quantize_params(p, cfg) if quant else p

        want = jax.eval_shape(theirs)
        maker = files.load_module("weight_makers", "qwen3_dense")
        got = maker.make(mc, 5, quant)
        flat = lambda t: {jax.tree_util.keystr(k): (tuple(v.shape),
                                                    str(v.dtype))
                          for k, v in jax.tree_util.tree_leaves_with_path(t)}
        assert flat(got) == flat(want)
        spec = maker.tree_spec(mc, quant)
        assert {"".join(f"['{p}']" for p in k): v
                for k, v in spec.items()} == flat(want)
        # same seed, same tree; int8 kernels use the whole range
        again = maker.make(mc, 5, quant)
        assert all(bool((a == b).all()) for a, b in zip(
            jax.tree.leaves(got), jax.tree.leaves(again)))
