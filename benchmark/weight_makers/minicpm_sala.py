"""Seeded weights for the MiniCPM-SALA hybrid (selecting attention layers and
Lightning linear-attention layers in a LIST, dense SwiGLU FFNs, muP scales),
made ON THE DEVICE in ONE jitted call, directly in the dtype they are served
in: the tree ``models/quant.py::quantize_params`` gives for a model whose
layer kinds are a list — ``layers = {attn: [n_a, ...], lightning: [n_l,
...]}``, int8 kernels with a float32 per-out-channel ``scale`` sibling for
every projection, the gates and the FFN; the norms in bf16. Nothing is
imported from the other makers; the int8 and scale rules are
``qwen3_dense.py``'s. The first thing it does is build the program's
``ModelConfig`` from the fields: a program that does not know them (the
parent commit of the PR that brought this file) fails there, at once.

How activations stay O(1) under muP. int8 kernels are uniform on [-127, 127]
(std 73.6) and the per-channel scale sets each matrix's real std, every one a
multiple of 1 / sqrt(fan-in) so that the same rules hold at a test's width:

- the embedding rows have std 1 / ``scale_emb``: ``h0`` has std 1;
- every projection that reads an RMS-normed input (q, k, v, the gates, the
  FFN's gate and up) 1.28 / sqrt(H) (0.02 at 4,096);
- what writes to the residual stream is sized so that a block adds std
  ~0.5 AFTER the muP factor r = scale_depth / sqrt(mup_depth) (0.2475): the
  FFN's down projection 2.67 / (r' sqrt(I)), a Lightning layer's output
  projection 3.6 / (r' sqrt(H d)), a selecting layer's 3.1 / (r' sqrt(Hq
  D)), with r' = r / 0.2475 — so the sixteen branches of an 8-layer stage
  together outweigh the embedding about 2:1 and no kind is a bystander (with
  the GPT-2 rule and r on top, h0 would be twenty times any branch and a
  layer left out would move no logit);
- the head 0.64 * (H / dim_model_base) / sqrt(H): logits of std 0.64 after
  the muP division, as the other configurations' have.

**q/k gains of the selecting layers: 3.0 each** (the ``q_norm`` / ``k_norm``
weights, x U(0.95, 1.05)). After the per-head RMSNorm a q.k / sqrt(D) logit
then has std ~9 and a POOLED-key logit (the mean of 32 keys) std ~1.6: the
selector's softmax over several hundred windows is far from uniform and a
block's score (the best of its five windows, summed over the group's 16
heads) differs from its neighbours' by tens of per cent — block scores are
SPREAD. With unit gains the pooled logits have std 0.18, every window gets
nearly the same probability, and which 31 of ~160 unforced blocks a query
reads is a coin toss that bfloat16 and float32 call differently: the
comparison with the reference would then measure ties, not the program
(PERF.md, PR 32, lesson 3; measured for this configuration by chip_smoke.py's
``check_selection_cause``, numbers in PERF.md, PR 34). The Lightning layers'
q/k norms keep gain 1.

Layer stacks are generated one layer at a time (``lax.map``).
"""

from __future__ import annotations

import math

STD_Q = math.sqrt((255 ** 2 - 1) / 12.0)     # uniform integers on [-127, 127]
QK_GAIN = 3.0


def _dims(mc: dict) -> dict:
    pat = mc["layer_pattern"]
    return dict(
        na=sum(pat.count(c) for c in "gs"), nl=pat.count("l"),
        H=mc["hidden_size"], I=mc["intermediate_size"], V=mc["vocab_size"],
        q=mc["num_heads"] * mc["head_dim"],
        kv=mc["num_kv_heads"] * mc["head_dim"], hd=mc["head_dim"],
        D=mc.get("lightning_num_heads", 0) * mc.get("lightning_head_dim", 0),
        d=mc.get("lightning_head_dim", 0))


def _kind_spec(mc: dict, kind: str) -> dict:
    """{path under layers/<kind>: (trailing shape, quantised?)}; what is
    not quantised is a bf16 norm weight."""
    n = _dims(mc)
    H, I = n["H"], n["I"]
    spec = {("input_norm", "weight"): ((H,), False),
            ("post_norm", "weight"): ((H,), False),
            ("w_gate", "kernel"): ((H, I), True),
            ("w_up", "kernel"): ((H, I), True),
            ("w_down", "kernel"): ((I, H), True)}
    if kind == "attn":
        spec.update({("wq", "kernel"): ((H, n["q"]), True),
                     ("wk", "kernel"): ((H, n["kv"]), True),
                     ("wv", "kernel"): ((H, n["kv"]), True),
                     ("wg", "kernel"): ((H, n["q"]), True),
                     ("wo", "kernel"): ((n["q"], H), True),
                     ("q_norm", "weight"): ((n["hd"],), False),
                     ("k_norm", "weight"): ((n["hd"],), False)})
    else:
        D = n["D"]
        spec.update({("wq", "kernel"): ((H, D), True),
                     ("wk", "kernel"): ((H, D), True),
                     ("wv", "kernel"): ((H, D), True),
                     ("wg", "kernel"): ((H, D), True),
                     ("wo", "kernel"): ((D, H), True),
                     ("q_norm", "weight"): ((n["d"],), False),
                     ("k_norm", "weight"): ((n["d"],), False),
                     ("o_norm", "weight"): ((n["d"],), False)})
    return spec


def tree_spec(mc: dict, quant: bool) -> dict:
    """{path: (shape, dtype name)} of the served tree for ModelConfig fields
    ``mc`` — the benchmark's statement of the layout, compared with the
    program's own in the tests."""
    n = _dims(mc)
    kd = "int8" if quant else "bfloat16"
    spec = {("embed", "weight"): ((n["V"], n["H"]), kd),
            ("final_norm", "weight"): ((n["H"],), "bfloat16"),
            ("lm_head", "kernel"): ((n["H"], n["V"]), kd)}
    if quant:
        spec[("embed", "scale")] = ((n["V"],), "float32")
        spec[("lm_head", "scale")] = ((n["V"],), "float32")
    for kind, count in (("attn", n["na"]), ("lightning", n["nl"])):
        if not count:
            continue
        for path, (shape, q) in _kind_spec(mc, kind).items():
            spec[("layers", kind) + path] = ((count,) + shape,
                                             kd if q else "bfloat16")
            if q and quant:
                spec[("layers", kind) + path[:-1] + ("scale",)] = (
                    (count,) + shape[-1:], "float32")
    return spec


def param_counts(mc: dict) -> dict:
    """Parameters by part, norms left out (the recount the tests compare
    with the configuration file's)."""
    n = _dims(mc)
    H, I = n["H"], n["I"]
    ffn = 3 * H * I
    attn = 2 * H * n["q"] + 2 * H * n["kv"] + n["q"] * H + ffn
    lightning = 4 * H * n["D"] + n["D"] * H + ffn
    return {"attn_layer": attn, "lightning_layer": lightning,
            "embedding_and_head": 2 * n["V"] * H,
            "total": n["na"] * attn + n["nl"] * lightning + 2 * n["V"] * H}


def make(mc: dict, seed: int, quant: bool, logit_sigma: float = 0.64,
         qk_gain: float = QK_GAIN, branch: float = 1.0, out_shardings=None):
    """The served tree, on the device(s), from ``seed``. One jitted call.
    ``branch`` scales everything that writes to the residual stream."""
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.config import ModelConfig

    cfg = ModelConfig(**mc)      # a program without these fields stops here
    n = _dims(mc)
    H, V = n["H"], n["V"]
    r = cfg.residual_scale / 0.2475
    proj = 1.28 / math.sqrt(H)
    std = {"wq": proj, "wk": proj, "wv": proj, "wg": proj, "w_gate": proj,
           "w_up": proj,
           "w_down": branch * 2.67 / (r * math.sqrt(n["I"])),
           "attn/wo": branch * 3.1 / (r * math.sqrt(n["q"])),
           "lightning/wo": branch * 3.6 / (r * math.sqrt(max(n["D"], 1)))}
    head = logit_sigma / cfg.logit_scale / math.sqrt(H)
    embed = 1.0 / cfg.scale_emb

    def kernel(key, shape, s):
        """One block [din, dout]: int8 bits, a scale per out channel."""
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

    def table(key, rows, cols, s, scale_axis, name):
        """The embedding [V, H] (a scale a ROW) or the head [H, V] (a scale a
        column): int8 bits made in blocks of ROWS and put together along
        the leading axis (a transpose of a [.., H, V / 8] block to get
        there costs the TPU compiler three minutes at V / 8 = 9,181:
        deviceless compile, PR 34)."""
        kq, ks = jax.random.split(key)
        # blocks of a whole number of int8 tiles (32 rows): merging blocks
        # of 9,181 rows is the same three minutes
        nb, per = 16, -(-rows // (16 * 32)) * 32
        bits = jax.lax.map(
            lambda k: jax.random.bits(k, (per, cols), jnp.uint8),
            jax.random.split(kq, nb)).reshape(nb * per, cols)[:rows]
        qk = jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8),
                         jnp.int8(-127))
        sc = jax.random.uniform(ks, ((rows, cols)[scale_axis],), jnp.float32,
                                0.95, 1.05) * (s / STD_Q)
        if quant:
            return {name: qk, "scale": sc}
        wide = sc[:, None] if scale_axis == 0 else sc[None, :]
        return {name: (qk.astype(jnp.float32) * wide).astype(jnp.bfloat16)}

    def norm(key, shape, gain=1.0):
        return (gain * jax.random.uniform(key, shape, jnp.float32, 0.95,
                                          1.05)).astype(jnp.bfloat16)

    def put(tree, path, value):
        for part in path[:-1]:
            tree = tree.setdefault(part, {})
        tree[path[-1]] = value

    def kind_tree(key, kind, count):
        out: dict = {}
        keys = iter(jax.random.split(key, 32))
        for path, (shape, q) in _kind_spec(mc, kind).items():
            k, full, name = next(keys), (count,) + shape, path[0]
            if q:
                w, sc = blocked(k, full, std.get(f"{kind}/{name}",
                                                 std.get(name)))
                put(out, path, w)
                if quant:
                    put(out, path[:-1] + ("scale",), sc)
            else:
                gain = qk_gain if kind == "attn" \
                    and name in ("q_norm", "k_norm") else 1.0
                put(out, path, norm(k, full, gain))
        return out

    def build(key):
        keys = iter(jax.random.split(key, 8))
        tree: dict = {"layers": {}}
        tree["embed"] = table(next(keys), V, H, embed, 0, "weight")
        for kind, count in (("attn", n["na"]), ("lightning", n["nl"])):
            k = next(keys)
            if count:
                tree["layers"][kind] = kind_tree(k, kind, count)
        tree["final_norm"] = {"weight": norm(next(keys), (H,))}
        tree["lm_head"] = table(next(keys), H, V, head, 1, "kernel")
        return tree

    key = jax.random.key(int(seed) % (2 ** 31), impl="rbg")
    fn = jax.jit(build) if out_shardings is None \
        else jax.jit(build, out_shardings=out_shardings)
    return fn(key)
