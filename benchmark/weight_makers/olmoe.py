"""Seeded weights for the OLMoE block (RMSNorm, q/k RMSNorm over the whole
projection, MHA, a router over E SwiGLU experts), made ON THE DEVICE in ONE
jitted call, directly in the dtype they are served in: the tree
``models/quant.py::quantize_params`` gives for an MoE model (int8 kernels
with a float32 per-out-channel ``scale`` sibling, expert stacks
``[L, E, in, out]`` with ``[L, E, out]`` scales, the router left in bf16) or
plain bf16 kernels. Nothing is imported from the program or from the other
makers; the int8 and scale rules are ``qwen3_dense.py``'s, repeated here.

How activations stay O(1): int8 kernels are uniform on [-127, 127] (std
73.6) and the per-channel scale sets each matrix's real std: ``sigma``
(0.02) for wq/wk/wv and every expert's gate/up, ``sigma / sqrt(2 L)`` for
wo (the GPT-2 rule for what writes to the residual stream), and
``logit_sigma / sqrt(H)`` for the embedding and the output head. Every
projection reads an RMS-normed input, so no scale compounds.

The router and the experts' down projections are what this maker adds:

- **Router spread.** The router kernel has std ``router_spread / sqrt(H)``,
  so a token's 64 router logits have std ``router_spread`` = 2.0. With the
  program's own init (0.02 * sqrt(2048) = 0.9) the softmax is nearly flat:
  every top-8 weight sits near 1/64, OLMoE does not renormalise them, and
  the whole expert layer writes a twentieth of what attention writes — a
  dropped or wrong expert would then hide inside any tolerance. At 2.0 the
  top-8 hold about two thirds of the mass (largest weight ~0.25, eighth
  ~0.02) and sum_k w_k^2 ~ 0.12: eight experts of clearly different weight,
  as a trained router gives (a flatter router hides the experts; a sharper
  one, spread 3.0, makes two experts carry the layer and triples the bf16
  path's distance from the reference).
- **Down projections** have std ``moe_gain * sigma / sqrt(2 L)`` with
  ``moe_gain`` = 1.5: with sum_k w_k^2 ~ 0.12 the weighted sum of eight
  experts then writes about half of what a dense MLP writes under the GPT-2
  rule — a third of every layer's update, so a wrong expert path moves
  logprobs by tenths of a nat. Not more, because the bf16 served path's
  distance from the float32 reference grows faster than the gain: over 8
  cases each on the chip (PERF.md, PR 26) the program's logprob of the next
  token sat up to 0.21 nats off at gain 3.0 (the comparison's limit is
  0.25), up to 0.42 at gain 3.0 with spread 3.0, and up to 0.042 at 1.5.
- **Near ties.** With random weights the 8th and 9th largest of 64 logits
  are ~0.15 apart on average (spread / (64 * pdf at the 87.5 % quantile)),
  exponentially distributed. A bf16 served path carries activations that
  differ from a float32 reference by ~0.5 % after a few layers, which moves
  a logit DIFFERENCE by ~0.014: about one routed choice in ten per token
  and layer is expected to pick the 9th expert where the reference picks
  the 8th. Such a flip swaps two experts whose weights are both ~0.02 (a
  tie is a tie of the weights too) against a layer whose weights have
  root-sum-square 0.35: a ~8 % change of one layer's expert write, a
  hundredth of a nat or two at the logprobs; the flips and the rounding of
  a token's 16 layers together make the 0.01-0.04 read on the chip.

Expert stacks are generated one layer at a time (``lax.map`` over the layer
axis: 64 x 2048 x 1024 random bytes in flight, not 16 times that).
"""

from __future__ import annotations

import math

STD_Q = math.sqrt((255 ** 2 - 1) / 12.0)     # uniform integers on [-127, 127]


def tree_spec(mc: dict, quant: bool) -> dict:
    """{path: (shape, dtype name)} of the served tree for ModelConfig fields
    ``mc`` — the benchmark's statement of the layout, compared with the
    program's own in the tests."""
    L, H, V = mc["num_layers"], mc["hidden_size"], mc["vocab_size"]
    D = mc["head_dim"]
    q, kv = mc["num_heads"] * D, mc["num_kv_heads"] * D
    E, inter = mc["num_experts"], mc["moe_intermediate_size"]
    kd = "int8" if quant else "bfloat16"
    spec = {("embed", "weight"): ((V, H), kd),
            ("final_norm", "weight"): ((H,), "bfloat16"),
            ("layers", "router", "kernel"): ((L, H, E), "bfloat16")}
    if quant:
        spec[("embed", "scale")] = ((V,), "float32")
    for name, din, dout in (("wq", H, q), ("wk", H, kv), ("wv", H, kv),
                            ("wo", q, H)):
        spec[("layers", name, "kernel")] = ((L, din, dout), kd)
        if quant:
            spec[("layers", name, "scale")] = ((L, dout), "float32")
    for name, din, dout in (("w_gate", H, inter), ("w_up", H, inter),
                            ("w_down", inter, H)):
        spec[("layers", name, "kernel")] = ((L, E, din, dout), kd)
        if quant:
            spec[("layers", name, "scale")] = ((L, E, dout), "float32")
    # q/k RMSNorm over the whole projection, not per head
    for name, width in (("input_norm", H), ("post_norm", H), ("q_norm", q),
                        ("k_norm", kv)):
        spec[("layers", name, "weight")] = ((L, width), "bfloat16")
    if not mc.get("tie_embeddings", False):
        spec[("lm_head", "kernel")] = ((H, V), kd)
        if quant:
            spec[("lm_head", "scale")] = ((V,), "float32")
    return spec


def make(mc: dict, seed: int, quant: bool, sigma: float = 0.02,
         logit_sigma: float = 0.64, router_spread: float = 2.0,
         moe_gain: float = 1.5, out_shardings=None):
    """The served tree, on the device(s), from ``seed``. One jitted call."""
    import jax
    import jax.numpy as jnp

    L, H, V = mc["num_layers"], mc["hidden_size"], mc["vocab_size"]
    spec = tree_spec(mc, quant)
    resid = sigma / math.sqrt(2.0 * L)
    head = logit_sigma / math.sqrt(H)
    sig = {"wq": sigma, "wk": sigma, "wv": sigma, "wo": resid,
           "w_gate": sigma, "w_up": sigma, "w_down": moe_gain * resid}

    def kernel(key, shape, s):
        """One block [..., din, dout]: int8 bits, a scale per out channel
        (the last axis; every leading axis but din keeps its own)."""
        kq, ks = jax.random.split(key)
        bits = jax.random.bits(kq, shape, jnp.uint8)
        qk = jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8),
                         jnp.int8(-127))
        sc = jax.random.uniform(ks, shape[:-2] + shape[-1:], jnp.float32,
                                0.95, 1.05) * (s / STD_Q)
        if quant:
            return qk, sc
        return (qk.astype(jnp.float32) * sc[..., None, :]
                ).astype(jnp.bfloat16), sc

    def blocked(key, shape, s):
        """[blocks, ...] generated one leading block at a time."""
        return jax.lax.map(lambda k: kernel(k, shape[1:], s),
                           jax.random.split(key, shape[0]))

    def norm(key, shape):
        return jax.random.uniform(key, shape, jnp.float32, 0.95,
                                  1.05).astype(jnp.bfloat16)

    def build(key):
        keys = iter(jax.random.split(key, 32))
        tree: dict = {"layers": {}}
        # embedding [V, H], scale per ROW: made as [nb, H, V/nb] column
        # blocks of its transpose
        nb = next(b for b in (16, 8, 4, 2, 1) if V % b == 0)
        w, sc = blocked(next(keys), (nb, H, V // nb), head)
        tree["embed"] = {"weight": jnp.swapaxes(w, 1, 2).reshape(V, H)}
        if quant:
            tree["embed"]["scale"] = sc.reshape(V)
        for name, s in sig.items():
            w, sc = blocked(next(keys),
                            spec[("layers", name, "kernel")][0], s)
            tree["layers"][name] = {"kernel": w}
            if quant:
                tree["layers"][name]["scale"] = sc
        tree["layers"]["router"] = {"kernel": (
            jax.random.normal(next(keys), (L, H, mc["num_experts"]),
                              jnp.float32)
            * (router_spread / math.sqrt(H))).astype(jnp.bfloat16)}
        for name in ("input_norm", "post_norm", "q_norm", "k_norm"):
            tree["layers"][name] = {"weight": norm(
                next(keys), spec[("layers", name, "weight")][0])}
        tree["final_norm"] = {"weight": norm(next(keys), (H,))}
        if not mc.get("tie_embeddings", False):
            w, sc = blocked(next(keys), (nb, H, V // nb), head)
            tree["lm_head"] = {"kernel": jnp.moveaxis(w, 0, 1).reshape(H, V)}
            if quant:
                tree["lm_head"]["scale"] = sc.reshape(V)
        return tree

    key = jax.random.key(int(seed) % (2 ** 31), impl="rbg")
    fn = jax.jit(build) if out_shardings is None \
        else jax.jit(build, out_shardings=out_shardings)
    return fn(key)
