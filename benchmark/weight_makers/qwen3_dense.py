"""Seeded weights for the dense Qwen3 block (RMSNorm, q/k-norm, GQA, SwiGLU),
made ON THE DEVICE in ONE jitted call, directly in the dtype they are served
in — the tree ``models/quant.py::quantize_params`` gives (int8 kernels with a
float32 per-out-channel ``scale`` sibling) or plain bf16 kernels.

Why not the server's own seeded path: ``build_state`` without a checkpoint
materialises the whole bf16 tree unsharded (16.4 GB for Qwen3-8B on a 16 GB
chip). ``build_state(params=...)`` takes a tree, and the engine skips
quantization for one that already carries scales.

How activations stay O(1) through every layer: int8 kernels are uniform on
[-127, 127] (std 73.6), and the per-channel scale sets the real-valued std of
each matrix: ``sigma`` (0.02, the program's own init) for wq/wk/wv/w_gate/
w_up, ``sigma / sqrt(2 L)`` for the two matrices that write to the residual
stream (wo, w_down; the GPT-2 rule, so the stream's variance does not grow
with depth), and ``logit_sigma / sqrt(H)`` for the embedding and the output
head, so logits have std ~ ``logit_sigma`` (0.64 = what 0.02 gives at H=1024)
at every width. Every projection reads an RMS-normed input, so no scale
compounds. Norm weights are 1 +- 5%, scales vary +- 5% across channels, so
neither multiply is a no-op.

Large leaves are generated per layer (``lax.map`` over the layer axis) so the
random bits in flight stay a layer's worth, not the model's.
"""

from __future__ import annotations

import math

STD_Q = math.sqrt((255 ** 2 - 1) / 12.0)     # uniform integers on [-127, 127]


def tree_spec(mc: dict, quant: bool) -> dict:
    """{path: (shape, dtype name)} of the served tree for ModelConfig fields
    ``mc`` — the benchmark's statement of the layout, compared with the
    program's own in benchmark/tests."""
    L, H, V = mc["num_layers"], mc["hidden_size"], mc["vocab_size"]
    D = mc["head_dim"]
    q, kv = mc["num_heads"] * D, mc["num_kv_heads"] * D
    inter = mc["intermediate_size"]
    kd = "int8" if quant else "bfloat16"
    spec = {("embed", "weight"): ((V, H), kd),
            ("final_norm", "weight"): ((H,), "bfloat16")}
    if quant:
        spec[("embed", "scale")] = ((V,), "float32")
    for name, din, dout in (("wq", H, q), ("wk", H, kv), ("wv", H, kv),
                            ("wo", q, H), ("w_gate", H, inter),
                            ("w_up", H, inter), ("w_down", inter, H)):
        spec[("layers", name, "kernel")] = ((L, din, dout), kd)
        if quant:
            spec[("layers", name, "scale")] = ((L, dout), "float32")
    for name, width in (("input_norm", H), ("post_norm", H), ("q_norm", D),
                        ("k_norm", D)):
        spec[("layers", name, "weight")] = ((L, width), "bfloat16")
    if not mc.get("tie_embeddings", False):
        spec[("lm_head", "kernel")] = ((H, V), kd)
        if quant:
            spec[("lm_head", "scale")] = ((V,), "float32")
    return spec


def make(mc: dict, seed: int, quant: bool, sigma: float = 0.02,
         logit_sigma: float = 0.64, out_shardings=None):
    """The served tree, on the device(s), from ``seed``. One jitted call."""
    import jax
    import jax.numpy as jnp

    L, H, V = mc["num_layers"], mc["hidden_size"], mc["vocab_size"]
    spec = tree_spec(mc, quant)
    resid = sigma / math.sqrt(2.0 * L)
    head = logit_sigma / math.sqrt(H)
    sig = {"wq": sigma, "wk": sigma, "wv": sigma, "w_gate": sigma,
           "w_up": sigma, "wo": resid, "w_down": resid}

    def int8(key, shape):
        bits = jax.random.bits(key, shape, jnp.uint8)
        return jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8),
                           jnp.int8(-127))

    def chan_scale(key, n, s):
        u = jax.random.uniform(key, (n,), jnp.float32, 0.95, 1.05)
        return u * (s / STD_Q)

    def blocked(key, shape, s, chan_axis):
        """Kernel [blocks..., din, dout] generated one leading block at a
        time; returns (kernel, scale) with scale over the ``chan_axis`` of
        each block (1 = out channels of [din, dout]; 0 = rows of [V, H])."""
        nb = shape[0]

        def one(k):
            kq, ks = jax.random.split(k)
            qk = int8(kq, shape[1:])
            sc = chan_scale(ks, shape[1:][chan_axis], s)
            if quant:
                return qk, sc
            bshape = [1, 1]
            bshape[chan_axis] = -1
            return (qk.astype(jnp.float32) * sc.reshape(bshape)
                    ).astype(jnp.bfloat16), sc

        return jax.lax.map(one, jax.random.split(key, nb))

    def norm(key, shape):
        return jax.random.uniform(key, shape, jnp.float32, 0.95,
                                  1.05).astype(jnp.bfloat16)

    def build(key):
        keys = iter(jax.random.split(key, 32))
        tree: dict = {"layers": {}}
        # embedding rows: V splits into `nb` row blocks
        nb = next(b for b in (16, 8, 4, 2, 1) if V % b == 0)
        w, sc = blocked(next(keys), (nb, V // nb, H), head, 0)
        tree["embed"] = {"weight": w.reshape(V, H)}
        if quant:
            tree["embed"]["scale"] = sc.reshape(V)
        for name, s in sig.items():
            shape = spec[("layers", name, "kernel")][0]
            w, sc = blocked(next(keys), shape, s, 1)
            tree["layers"][name] = {"kernel": w}
            if quant:
                tree["layers"][name]["scale"] = sc
        for name in ("input_norm", "post_norm", "q_norm", "k_norm"):
            tree["layers"][name] = {"weight": norm(
                next(keys), spec[("layers", name, "weight")][0])}
        tree["final_norm"] = {"weight": norm(next(keys), (H,))}
        if not mc.get("tie_embeddings", False):
            # [H, V] as nb column blocks [H, V/nb], scale per out column
            w, sc = blocked(next(keys), (nb, H, V // nb), head, 1)
            tree["lm_head"] = {"kernel": jnp.moveaxis(w, 0, 1).reshape(H, V)}
            if quant:
                tree["lm_head"]["scale"] = sc.reshape(V)
        return tree

    key = jax.random.key(int(seed) % (2 ** 31), impl="rbg")
    fn = jax.jit(build) if out_shardings is None \
        else jax.jit(build, out_shardings=out_shardings)
    return fn(key)
