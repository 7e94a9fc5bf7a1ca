"""Seeded weights for a Falcon-H1 list (every layer a Mamba-2 state-space
mixer AND GQA attention on one normed input, then a SwiGLU; an untied head),
made ON THE DEVICE in ONE jitted call, directly in the dtype they are served
in: the tree ``models/quant.py::quantize_params`` gives for the list —
``layers = {par: [n, ...]}`` with the state-space mixer's leaves under
``ssm`` — int8 kernels with a float32 per-out-channel ``scale`` sibling for
the attention's four projections, the SSM's two, the FFN's three, the
embedding (a scale a row) and the head; norms, taps and their bias in bf16;
``dt_bias``, ``A_log`` and ``D`` in float32. Nothing is imported from the
other makers. The first thing it does is build the program's ``ModelConfig``
from the fields: a program that does not know them (the parent commit of the
PR that brought this file) fails there, at once.

**The published multipliers stay as published; the WEIGHTS are sized around
them.** int8 kernels are uniform on [-127, 127] (std ``STD_Q``) and the
per-channel scale sets each matrix's real std, so that with the multipliers
in place every pre-activation is of order one and every branch adds a stated
share of the stream:

- the embedding's rows have std 1 / ``embedding_multiplier``: the stream
  starts at unit std a channel, the UNIT of what follows;
- W_in's five segments: z, x, B and C come out unit normal (std 1 /
  (``ssm_in_multiplier`` x the segment's entry of ``ssm_multipliers`` x
  sqrt(hidden))), dt with std ``DT_SPREAD`` (0.5), so the step size — and
  with it the decay — moves with the token by a factor of e^+-0.5;
- the taps are normal with std 1 / sqrt(K); their bias is near 0 on the x
  channels and near ``BC_BIAS`` (-0.75) on B's and C's, where SiLU of a unit
  normal has mean ~0: with the +0.2 mean an unbiased SiLU leaves on all three,
  ``S`` and its read-out are dominated by a constant that carries nothing of
  the sequence;
- **decays from forgetting in a few tokens to remembering across hundreds**:
  head h of 32 decays with a time constant ``TAU_MIN`` x (``TAU_MAX`` /
  ``TAU_MIN``)^(h / 31) tokens (3 ... 600) at its nominal step size, the
  step sizes log-spaced on [0.02, 0.2] in an order of their own (``dt_bias``
  the inverse softplus, ``A_log`` = -log(tau x step)); ``D`` uniform on
  [0.5, 1.5]: a wrong carried state still shows at position 300;
- q and k at ``QK_GAIN`` (1.7) each — Wk divided by ``key_multiplier`` — so
  a q.k / sqrt(D) logit has std ~2.9 and which keys a query sees matters;
- **each of the three branches adds ``gain`` (0.3) units a channel**: W_out
  ``ssm_gain`` / (``ssm_out_multiplier`` sqrt(d_ssm)) behind the gated norm
  (unit RMS), Wo ``attn_gain`` / (``attention_out_multiplier`` x CTX_STD x
  sqrt(Hq D)) (a softmax average of unit values has std ~0.35 at these
  gains), W_down ``mlp_gain`` / (``mlp_multipliers[1]`` x 0.597 sqrt(I))
  behind a gate sized through ``mlp_multipliers[0]``. With 0.02-std weights
  and ``attention_out_multiplier`` 0.0375 a dropped attention branch would
  hide inside the comparison's tolerance; here a dropped branch is a tenth
  of the stream's variance a layer, nine layers deep;
- the head has std ``logit_sigma`` / (``lm_head_multiplier`` sqrt(hidden)):
  logits of std 0.64, as the other configurations'. It is untied, so a
  token's own embedding is not read back by it.
"""

from __future__ import annotations

import math

STD_Q = math.sqrt((255 ** 2 - 1) / 12.0)     # uniform integers on [-127, 127]
QK_GAIN = 1.7
SWIGLU_STD = 0.597      # std of silu(g) * u for unit normals g, u
CTX_STD = 0.35          # std of a softmax average of unit values
DT_SPREAD = 0.5
BC_BIAS = -0.75
TAU_MIN, TAU_MAX = 3.0, 600.0
STEP_MIN, STEP_MAX = 0.02, 0.2


def _dims(mc: dict) -> dict:
    Hs, P, G, N = (mc["ssm_num_heads"], mc["ssm_head_dim"],
                   mc["ssm_num_groups"], mc["ssm_state_size"])
    return dict(
        L=mc["num_layers"], H=mc["hidden_size"], I=mc["intermediate_size"],
        V=mc["vocab_size"], K=mc["conv_taps"], Hs=Hs, P=P, G=G, N=N,
        ssm=Hs * P, conv=Hs * P + 2 * G * N, w_in=2 * Hs * P + 2 * G * N + Hs,
        q=mc["num_heads"] * mc["head_dim"],
        kv=mc["num_kv_heads"] * mc["head_dim"])


def _layer_spec(mc: dict) -> dict:
    """{path under layers/par: (trailing shape, quantised?, dtype)}."""
    n = _dims(mc)
    H = n["H"]
    return {
        ("input_norm", "weight"): ((H,), False, "bfloat16"),
        ("post_norm", "weight"): ((H,), False, "bfloat16"),
        ("wq", "kernel"): ((H, n["q"]), True, None),
        ("wk", "kernel"): ((H, n["kv"]), True, None),
        ("wv", "kernel"): ((H, n["kv"]), True, None),
        ("wo", "kernel"): ((n["q"], H), True, None),
        ("w_gate", "kernel"): ((H, n["I"]), True, None),
        ("w_up", "kernel"): ((H, n["I"]), True, None),
        ("w_down", "kernel"): ((n["I"], H), True, None),
        ("ssm", "w_in", "kernel"): ((H, n["w_in"]), True, None),
        ("ssm", "conv", "weight"): ((n["K"], n["conv"]), False, "bfloat16"),
        ("ssm", "conv", "bias"): ((n["conv"],), False, "bfloat16"),
        ("ssm", "dt_bias"): ((n["Hs"],), False, "float32"),
        ("ssm", "A_log"): ((n["Hs"],), False, "float32"),
        ("ssm", "D"): ((n["Hs"],), False, "float32"),
        ("ssm", "o_norm", "weight"): ((n["ssm"],), False, "bfloat16"),
        ("ssm", "wo", "kernel"): ((n["ssm"], H), True, None)}


def tree_spec(mc: dict, quant: bool) -> dict:
    """{path: (shape, dtype name)} of the served tree for ModelConfig fields
    ``mc`` — the benchmark's statement of the layout, compared with the
    program's own in the tests."""
    n = _dims(mc)
    kd = "int8" if quant else "bfloat16"
    spec = {("embed", "weight"): ((n["V"], n["H"]), kd),
            ("lm_head", "kernel"): ((n["H"], n["V"]), kd),
            ("final_norm", "weight"): ((n["H"],), "bfloat16")}
    if quant:
        spec[("embed", "scale")] = ((n["V"],), "float32")
        spec[("lm_head", "scale")] = ((n["V"],), "float32")
    for path, (shape, q, dt) in _layer_spec(mc).items():
        spec[("layers", "par") + path] = ((n["L"],) + shape, kd if q else dt)
        if q and quant:
            spec[("layers", "par") + path[:-1] + ("scale",)] = (
                (n["L"],) + shape[-1:], "float32")
    return spec


def param_counts(mc: dict, layers: int = 0) -> dict:
    """Parameters by part from the fields, for ``layers`` layers (0 = the
    ``num_layers`` held): the recount the tests compare with the
    configuration file's."""
    n = _dims(mc)
    H = n["H"]
    attn = 2 * H * n["q"] + 2 * H * n["kv"]
    ssm = H * n["w_in"] + n["K"] * n["conv"] + n["conv"] + 3 * n["Hs"] \
        + n["ssm"] + n["ssm"] * H
    mlp = 3 * H * n["I"]
    layer = attn + ssm + mlp + 2 * H
    emb = n["V"] * H
    return {"attention_mixer": attn, "ssm_mixer": ssm, "swiglu": mlp,
            "layer": layer, "embedding": emb, "head": emb,
            "total": (layers or n["L"]) * layer + 2 * emb + H}


def head_schedule(mc: dict):
    """(nominal step size [Hs], decay time constant in tokens [Hs]) a head:
    the time constants log-spaced over [TAU_MIN, TAU_MAX] in head order, the
    step sizes log-spaced over [STEP_MIN, STEP_MAX] in an order of their own
    (h x 7 mod Hs), so that neither follows the other."""
    Hs = mc["ssm_num_heads"]
    span = max(Hs - 1, 1)
    tau = [TAU_MIN * (TAU_MAX / TAU_MIN) ** (h / span) for h in range(Hs)]
    step = [STEP_MIN * (STEP_MAX / STEP_MIN) ** (((h * 7) % Hs) / span)
            for h in range(Hs)]
    return step, tau


def make(mc: dict, seed: int, quant: bool, logit_sigma: float = 0.64,
         qk_gain: float = QK_GAIN, ssm_gain: float = 0.3,
         attn_gain: float = 0.3, mlp_gain: float = 0.3,
         out_shardings=None):
    """The served tree, on the device(s), from ``seed``. One jitted call."""
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.config import ModelConfig

    ModelConfig(**mc)            # a program without these fields stops here
    n = _dims(mc)
    H, V, L = n["H"], n["V"], n["L"]
    m_ssm = tuple(mc.get("ssm_multipliers") or (1.0,) * 5)
    m_gate, m_down = tuple(mc.get("mlp_multipliers") or (1.0, 1.0))
    in_mul = mc.get("ssm_in_multiplier", 1.0)
    a_in = mc.get("attention_in_multiplier", 1.0)
    # (weight std) x sqrt(fan-in) of each kernel: a number, or one a column
    seg = [1.0, 1.0, 1.0, 1.0, DT_SPREAD]
    widths = (n["ssm"], n["ssm"], n["G"] * n["N"], n["G"] * n["N"], n["Hs"])
    w_in_gain = [g / (in_mul * m) for g, m, w in zip(seg, m_ssm, widths)
                 for _ in range(w)]
    gain_of = {
        ("wq",): qk_gain / a_in,
        ("wk",): qk_gain / (a_in * mc.get("key_multiplier", 1.0)),
        ("wv",): 1.0 / a_in,
        ("wo",): attn_gain / (mc.get("attention_out_multiplier", 1.0)
                              * CTX_STD),
        ("w_gate",): 1.0 / m_gate,
        ("w_up",): 1.0,
        ("w_down",): mlp_gain / (m_down * SWIGLU_STD),
        ("ssm", "w_in"): w_in_gain,
        ("ssm", "wo"): ssm_gain / mc.get("ssm_out_multiplier", 1.0)}
    step, tau = head_schedule(mc)

    def kernel(key, shape, gain):
        """One block [din, dout]: int8 bits, a scale per out channel (std
        ``gain`` / sqrt(din))."""
        kq, ks = jax.random.split(key)
        bits = jax.random.bits(kq, shape, jnp.uint8)
        qk = jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8),
                         jnp.int8(-127))
        sc = jax.random.uniform(ks, shape[-1:], jnp.float32, 0.95, 1.05) \
            * (jnp.asarray(gain, jnp.float32)
               / (math.sqrt(shape[-2]) * STD_Q))
        if quant:
            return qk, sc
        return (qk.astype(jnp.float32) * sc).astype(jnp.bfloat16), sc

    def blocked(key, shape, gain):
        """[layers, din, dout] generated one layer at a time."""
        return jax.lax.map(lambda k: kernel(k, shape[1:], gain),
                           jax.random.split(key, shape[0]))

    def table(key, rows, cols, std, by_row: bool):
        """[rows, cols] int8 bits made in 16 blocks of whole int8 tiles (32
        rows) along the leading axis; the scale a ROW (the embedding) or a
        COLUMN (the head)."""
        kq, ks = jax.random.split(key)
        nb, per = 16, -(-rows // (16 * 32)) * 32
        bits = jax.lax.map(
            lambda k: jax.random.bits(k, (per, cols), jnp.uint8),
            jax.random.split(kq, nb)).reshape(nb * per, cols)[:rows]
        qk = jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8),
                         jnp.int8(-127))
        sc = jax.random.uniform(ks, (rows if by_row else cols,), jnp.float32,
                                0.95, 1.05) * (std / STD_Q)
        if quant:
            return qk, sc
        return (qk.astype(jnp.float32)
                * (sc[:, None] if by_row else sc[None, :])
                ).astype(jnp.bfloat16), None

    def norm(key, shape):
        return jax.random.uniform(key, shape, jnp.float32, 0.95, 1.05
                                  ).astype(jnp.bfloat16)

    def put(tree, path, value):
        for part in path[:-1]:
            tree = tree.setdefault(part, {})
        tree[path[-1]] = value

    def small(key, path, full):
        """The leaves that are no kernel."""
        name = path[-1]
        if path[-2:] == ("conv", "weight"):
            return (jax.random.normal(key, full, jnp.float32)
                    / math.sqrt(n["K"])).astype(jnp.bfloat16)
        if path[-2:] == ("conv", "bias"):
            centre = jnp.where(jnp.arange(n["conv"]) < n["ssm"], 0.0, BC_BIAS)
            return (centre + 0.1 * jax.random.normal(key, full, jnp.float32)
                    ).astype(jnp.bfloat16)
        if name == "dt_bias":       # softplus(dt_bias) = the nominal step
            s = jnp.asarray(step, jnp.float32)
            return jnp.broadcast_to(s + jnp.log(-jnp.expm1(-s)), full)
        if name == "A_log":         # exp(-step x A) = exp(-1 / tau)
            return jnp.broadcast_to(-jnp.log(
                jnp.asarray(tau, jnp.float32) * jnp.asarray(step,
                                                            jnp.float32)),
                full)
        if name == "D":
            return jax.random.uniform(key, full, jnp.float32, 0.5, 1.5)
        return norm(key, full)

    def build(key):
        keys = iter(jax.random.split(key, 40))
        tree: dict = {"layers": {"par": {}}}
        w, sc = table(next(keys), V, H,
                      1.0 / mc.get("embedding_multiplier", 1.0), True)
        tree["embed"] = {"weight": w, **({"scale": sc} if quant else {})}
        for path, (shape, q, _) in _layer_spec(mc).items():
            k, full = next(keys), (L,) + shape
            if q:
                w, sc = blocked(k, full, gain_of[path[:-1]])
                put(tree["layers"]["par"], path, w)
                if quant:
                    put(tree["layers"]["par"], path[:-1] + ("scale",), sc)
            else:
                put(tree["layers"]["par"], path, small(k, path, full))
        tree["final_norm"] = {"weight": norm(next(keys), (H,))}
        # [H, V]: the bits of a [V, H] table would need a transpose of 1.3 GB
        w, sc = table(next(keys), H, V,
                      logit_sigma / (mc.get("lm_head_multiplier", 1.0)
                                     * math.sqrt(H)), False)
        tree["lm_head"] = {"kernel": w, **({"scale": sc} if quant else {})}
        return tree

    key = jax.random.key(int(seed) % (2 ** 31), impl="rbg")
    fn = jax.jit(build) if out_shardings is None \
        else jax.jit(build, out_shardings=out_shardings)
    return fn(key)
