"""Parallelism: device mesh, sharding rules, ring attention, pipeline stages.

The reference implements no parallelism of its own (SURVEY.md §2.3); everything
here is net-new TPU-first design: XLA-collective backend over ICI, Megatron TP
via PartitionSpecs, ring attention for sequence/context parallelism, expert
parallelism for MoE (ops/moe.py + sharding specs), and GPipe-style pipeline
stages over ppermute.
"""

from aws_k8s_ansible_provisioner_tpu.parallel.mesh import (  # noqa: F401
    auto_mesh_config,
    make_mesh,
)
from aws_k8s_ansible_provisioner_tpu.parallel.pipeline import (  # noqa: F401
    check_pp_divisibility,
    from_pipeline_params,
    init_pipeline_params,
    make_pipeline_lm_loss,
    make_pipeline_train_step,
    to_pipeline_params,
)
from aws_k8s_ansible_provisioner_tpu.parallel.ring_attention import (  # noqa: F401
    make_ring_attend,
    ring_attend_local,
)
from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (  # noqa: F401
    check_tp_divisibility,
    param_pspecs,
    param_shardings,
    shard_params,
    tokens_pspec,
)
