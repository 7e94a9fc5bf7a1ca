"""Seeded weights for the LFM2-MoE list (gated short convolutions and GQA
layers, leading dense SwiGLU FFNs, then a sigmoid router with a selection
bias over ``num_experts`` SwiGLU experts, no shared expert, tied embeddings),
made ON THE DEVICE in ONE jitted call, directly in the dtype they are served
in: the tree ``models/quant.py::quantize_params`` gives for a model whose
layer kinds are a list and whose FFN differs by layer — ``layers = {attn:
[n_a, ...], conv: [n_c, ...], ffn_dense: [n_d, ...], ffn_moe: [L - n_d,
...]}``, int8 kernels with a float32 per-out-channel ``scale`` sibling for
every projection, the FFNs and the expert stacks; the norms, the
convolution's taps, the router and its bias in bf16 / float32. Nothing is
imported from the other makers; the int8 and scale rules are
``qwen3_dense.py``'s. The first thing it does is build the program's
``ModelConfig`` from the fields: a program that does not know them (the
parent commit of the PR that brought this file) fails there, at once.

How the stream is sized. This model has NO norm behind a branch, so what a
branch ADDS is set by its last matrix, and its head is TIED: the embedding
is also the unembedding. int8 kernels are uniform on [-127, 127] (std 73.6)
and the per-channel scale sets each matrix's real std:

- the embedding rows have std ``logit_sigma`` / sqrt(H): the logits have
  std 0.64 as the other configurations';
- every INPUT projection (W_in, q, k, v, every FFN's gate and up) 1 /
  sqrt(fan-in): its output has std ~1 for a normed input; the
  convolution's K taps are normal with std 1 / sqrt(K), so ``C * conv(B *
  X)`` has std ~1 too;
- **layer 0's operator adds ``first_gain`` (16) embedding stds a channel**,
  and that is the stream's unit from there on. Without it the tied head
  reads the token's own embedding back: the logit of the INPUT token is
  sqrt(H) x 0.64 / (stream RMS in embedding stds) = 29 at a stream the
  embedding dominates, every served token repeats the prompt's last one
  with logprob 0, and the comparison sees nothing (the first round of this
  PR: the oldest tap dropped moved a logprob by 0.01 nats; my chip run, PR
  42). At 16 the input token's logit is 1.1, under the 2.7 the largest of
  65,536 random logits reads;
- every later branch's LAST matrix is sized so that the branch adds a vector
  of RMS ``gain`` x that unit: ``conv_gain`` / ``attn_gain`` / ``mlp_gain``
  0.2, the routed FFNs' ``expert_gain`` 0.02 A CHOSEN EXPERT: 0.08 at the
  served top-4 (below). W_out ``gain`` / sqrt(H); the attention's
  ``gain`` / (CTX_STD sqrt(Hq D)) — a softmax average of ~unit values has
  std CTX_STD ~0.35 at these q/k gains and lengths; a SwiGLU's down
  projection ``gain`` / (0.6 sqrt(I)) (``silu(g) * u`` of unit normals has
  std 0.597), a routed one's further / ROUTED_RMS (0.7: sqrt of the sum of
  the squared weights below). 47 branches of a fifth of the stream each:
  larger ones make the depth chaotic — every branch multiplies a
  perturbation of its input, and with branches as large as the stream the
  bfloat16 program sits 0.5 nats from the float32 reference with the
  experts switched off (CPU, a quarter-width copy of all 24 layers, 316
  tokens); at a fifth, 0.07.

**Anchor channels.** One embedding channel in ``ANCHOR_SHARE`` (64 of
2,048) holds +127 in every row, and every branch's last matrix has a ZERO
scale on those out-channels, so no branch writes them: a constant direction
of the residual stream, which is what a model without biases has for one.
Only the routers give it a weight (below); every other matrix reads it as
one more input.

**Router: top-heavy, so that the top-4 is no tie.** The reference routes on
float32 activations, the program on bfloat16 ones; where a token's 4th and
5th of 32 scores + bias are nearer than that noise the two choose another
set (2 % of tokens at the first routed layer, 9 % at the nineteenth, over
512 tokens at the served size; my chip run, PR 42, chip_smoke.py). A zero-mean sigmoid router gives its four choices
nearly EQUAL weights after renormalisation, and a flip then swaps a quarter
of a routed sum. Here the router's kernel has std ``router_spread`` /
sqrt(H) (3.0) and its anchor rows add the same negative number for every
expert, so that a token's 32 logits are normal around -7.2 and the largest
sits near ``router_top`` (-1.0): the chosen scores fall off as 0.34, 0.14,
0.08, 0.05 (the fifth 0.03; CPU, the quarter-width copy: the centre lands
at -6.9) and the weights as 0.56, 0.23, 0.13, 0.08. The anchor rows are
sized from the stream's RMS at each routed layer's FFN, RECKONED from the
gains above (no norm pins it: a reckoning off by 10 % moves every logit of
a token alike by 0.7 and no ratio between them). Even so the flips are
what the comparison's noise is made of: with the routed branches at the
others' 0.2 the worst of 300 windows of 16 positions read 0.39 nats and
the median window 0.17 (same copy), against 0.07 / 0.05 with the experts
off — so the routed branch is 0.08, and the served model reads 0.03-0.12 a
window on the chip (my chip runs, PR 42; limit 0.25). The gain is stated
per chosen expert because a flip swaps 1 / k of a routed sum: the CPU
rehearsals' tiny list chooses 2 of 8 and takes 0.04. The price: a fault
of ONE expert is not reliably outside the limit — the busiest expert
computed as its neighbour reads 0.15-0.71 over three prompt lengths and
three bias sizes, refused at 5 windows of 9, and float8 inside the experts
alone 0.06-0.14, never refused (``CONTROLS_REPORTED`` of the reference,
through benchmark/controls_lfm2.py; my chip run, PR 42). Flips cannot be
cut by a wider gap: the router's logits and their bfloat16 noise scale
together, so the share of near-ties is the spread's own.

**The selection bias decides the near-ties, on all 32 experts.**
``expert_bias`` is one of ``BIAS_LEVELS`` stratified normal quantiles x
``bias_spread`` per expert, permuted: every expert has one and none is held
out of the routing. ``bias_spread`` is 0.003, under the gap between a
token's 4th and 5th score (0.039 and 0.025 on average, the median gap
0.0065): the chosen set changes in 22 % of rows, and where it does the
expert replaced held 0.08 of the row's weight (simulated from the router's
own score distribution, 200,000 rows). Larger is not free: a routing flip
between the float32 reference and the bfloat16 program swaps two experts
whose scores differ by as much as their biases do, so what a flip costs
grows with the spread. At 0.015 (the choice changes in 68 % of rows) the
plain reference read 0.040-0.187 nats over 27 windows, four of them over
0.15 (my chip run, PR 42, call 8: too near the 0.25 limit for a
comparison that every later check repeats on fresh seeds), where near-ties
alone read 0.029-0.120 over 30 windows (calls 3 and 6). The price is the
control: with the bias left out of the choice (the reference's
"no_expert_bias") the comparison read 0.16 / 0.39 / 0.21 nats at 4, 63 and
300 tokens at a spread of 0.01, 0.29 / 0.35 / 0.24 at 0.015 and 0.28 / 0.41
/ 0.39 at 0.02 (call 7, seed 3600000011; limit 0.25) — at 0.003 it is a
fault of the fourth expert in a fifth of the rows, and PERF.md section 6
says what the limits make of it. The first round of this PR had 0.05 on
every expert (the choice changed in 92-97 % of rows and a flip cost three
times as much); the second held 4 of 32 experts out by a bias of -1, which
no published bias does and which left an eighth of every stack dead (the
review's finding).

**q/k gains 1.7 each** (the ``q_norm`` / ``k_norm`` weights): a q.k /
sqrt(D) logit has std ~2.9, so a query's softmax is far from uniform and
WHICH keys it sees, and how they are rotated, matters (RoPE over half of
the head reads 1.15-1.92 nats on the chip).

Expert stacks are generated one layer at a time (``lax.map``), the
embedding in blocks of whole int8 tiles.
"""

from __future__ import annotations

import math
from statistics import NormalDist

STD_Q = math.sqrt((255 ** 2 - 1) / 12.0)     # uniform integers on [-127, 127]
QK_GAIN = 1.7
BIAS_LEVELS = 32
ANCHOR_SHARE = 32       # one embedding channel in 32 is an anchor
SWIGLU_STD = 0.597      # std of silu(g) * u for unit normals g, u
CTX_STD = 0.35          # std of a softmax average of unit values (see above)
ROUTED_RMS = 0.7        # sqrt(sum of squared routing weights), top-heavy


def _dims(mc: dict) -> dict:
    L, nd, pat = mc["num_layers"], mc.get("num_dense_layers", 0), \
        mc["layer_pattern"]
    return dict(
        L=L, nd=nd, nm=L - nd, na=pat.count("g"), nc=pat.count("c"),
        H=mc["hidden_size"], I=mc["intermediate_size"],
        Im=mc["moe_intermediate_size"], E=mc["num_experts"],
        V=mc["vocab_size"], K=mc["conv_taps"],
        q=mc["num_heads"] * mc["head_dim"],
        kv=mc["num_kv_heads"] * mc["head_dim"], hd=mc["head_dim"])


def _stack_spec(mc: dict, stack: str) -> dict:
    """{path under layers/<stack>: (trailing shape, quantised?, dtype)}."""
    n = _dims(mc)
    H = n["H"]
    norms = {("input_norm", "weight"): ((H,), False, "bfloat16"),
             ("post_norm", "weight"): ((H,), False, "bfloat16")}
    if stack == "attn":
        return {**norms,
                ("wq", "kernel"): ((H, n["q"]), True, None),
                ("wk", "kernel"): ((H, n["kv"]), True, None),
                ("wv", "kernel"): ((H, n["kv"]), True, None),
                ("wo", "kernel"): ((n["q"], H), True, None),
                ("q_norm", "weight"): ((n["hd"],), False, "bfloat16"),
                ("k_norm", "weight"): ((n["hd"],), False, "bfloat16")}
    if stack == "conv":
        return {**norms,
                ("w_in", "kernel"): ((H, 3 * H), True, None),
                ("conv", "weight"): ((n["K"], H), False, "bfloat16"),
                ("wo", "kernel"): ((H, H), True, None)}
    if stack == "ffn_dense":
        return {("w_gate", "kernel"): ((H, n["I"]), True, None),
                ("w_up", "kernel"): ((H, n["I"]), True, None),
                ("w_down", "kernel"): ((n["I"], H), True, None)}
    E, Im = n["E"], n["Im"]
    return {("router", "kernel"): ((H, E), False, "bfloat16"),
            ("router", "bias"): ((E,), False, "float32"),
            ("w_gate", "kernel"): ((E, H, Im), True, None),
            ("w_up", "kernel"): ((E, H, Im), True, None),
            ("w_down", "kernel"): ((E, Im, H), True, None)}


def _stacks(mc: dict):
    n = _dims(mc)
    return [(s, c) for s, c in (("attn", n["na"]), ("conv", n["nc"]),
                                ("ffn_dense", n["nd"]),
                                ("ffn_moe", n["nm"])) if c]


def tree_spec(mc: dict, quant: bool) -> dict:
    """{path: (shape, dtype name)} of the served tree for ModelConfig fields
    ``mc`` — the benchmark's statement of the layout, compared with the
    program's own in the tests."""
    n = _dims(mc)
    kd = "int8" if quant else "bfloat16"
    spec = {("embed", "weight"): ((n["V"], n["H"]), kd),
            ("final_norm", "weight"): ((n["H"],), "bfloat16")}
    if quant:
        spec[("embed", "scale")] = ((n["V"],), "float32")
    for stack, count in _stacks(mc):
        for path, (shape, q, dt) in _stack_spec(mc, stack).items():
            spec[("layers", stack) + path] = ((count,) + shape,
                                              kd if q else dt)
            if q and quant:
                spec[("layers", stack) + path[:-1] + ("scale",)] = (
                    (count,) + shape[:-2] + shape[-1:], "float32")
    return spec


def param_counts(mc: dict) -> dict:
    """Parameters by part, norms and the router's bias left out (the
    recount the tests compare with the configuration file's)."""
    n = _dims(mc)
    H = n["H"]
    conv = H * 3 * H + H * H + n["K"] * H
    attn = 2 * H * n["q"] + 2 * H * n["kv"]
    expert = 3 * H * n["Im"]
    routed = n["E"] * expert + H * n["E"]
    dense = 3 * H * n["I"]
    return {"conv_mixer": conv, "attention_mixer": attn, "expert": expert,
            "routed_ffn": routed, "dense_ffn": dense,
            "embedding": n["V"] * H,
            "total": n["V"] * H + n["nc"] * conv + n["na"] * attn
            + n["nd"] * dense + n["nm"] * routed,
            "active": n["V"] * H + n["nc"] * conv + n["na"] * attn
            + n["nd"] * dense + n["nm"] * (
                mc["num_experts_per_tok"] * expert + H * n["E"])}


def anchor_channels(mc: dict) -> int:
    """Leading embedding channels that every token holds at +127."""
    return max(1, mc["hidden_size"] // ANCHOR_SHARE)


def make(mc: dict, seed: int, quant: bool, logit_sigma: float = 0.64,
         qk_gain: float = QK_GAIN, first_gain: float = 16.0,
         conv_gain: float = 0.2, attn_gain: float = 0.2,
         mlp_gain: float = 0.2, expert_gain: float = 0.02,
         router_spread: float = 3.0,
         router_top: float = -1.0, bias_spread: float = 0.003,
         out_shardings=None):
    """The served tree, on the device(s), from ``seed``. One jitted call."""
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.config import ModelConfig

    ModelConfig(**mc)            # a program without these fields stops here
    n = _dims(mc)
    H, V, nd, A = n["H"], n["V"], n["nd"], anchor_channels(mc)
    pat = mc["layer_pattern"]
    moe_gain = expert_gain * mc["num_experts_per_tok"]
    levels = [NormalDist().inv_cdf((i + 0.5) / BIAS_LEVELS)
              for i in range(BIAS_LEVELS)]
    # a token's logits are normal around this centre: the largest of E
    # (Blom's expected maximum) then sits near ``router_top``
    router_centre = router_top - router_spread * NormalDist().inv_cdf(
        (n["E"] - 0.375) / (n["E"] + 0.25)) if n["E"] else 0.0
    # (weight std) x sqrt(fan-in) of each kernel; a branch's last matrix
    # writes no anchor channel
    # the stream's unit: what layer 0's operator adds, ``first_gain``
    # embedding stds a channel; every other branch adds its gain x that
    unit = first_gain * logit_sigma / math.sqrt(H)
    first_conv = [1.0 / conv_gain if i == 0 else 1.0
                  for i, k in enumerate(k for k in pat if k == "c")] \
        if pat[0] == "c" else None
    first_attn = [1.0 / attn_gain if i == 0 else 1.0
                  for i, k in enumerate(k for k in pat if k == "g")] \
        if pat[0] == "g" else None
    gain_of = {
        ("attn", "wo"): (attn_gain * unit / CTX_STD, True, first_attn),
        ("conv", "wo"): (conv_gain * unit, True, first_conv),
        ("ffn_dense", "w_down"): (mlp_gain * unit / SWIGLU_STD, True, None),
        ("ffn_moe", "w_down"): (moe_gain * unit / SWIGLU_STD / ROUTED_RMS,
                                True, None)}

    def kernel(key, shape, gain, last):
        """One block [..., din, dout]: int8 bits, a scale per out channel
        (std ``gain`` / sqrt(din); zero on the anchor channels of a
        branch's ``last`` matrix)."""
        kq, ks = jax.random.split(key)
        bits = jax.random.bits(kq, shape, jnp.uint8)
        qk = jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8),
                         jnp.int8(-127))
        sc = jax.random.uniform(ks, shape[:-2] + shape[-1:], jnp.float32,
                                0.95, 1.05) \
            * (gain / math.sqrt(shape[-2]) / STD_Q)
        if last:
            sc = jnp.where(jnp.arange(shape[-1]) < A, 0.0, sc)
        if quant:
            return qk, sc
        return (qk.astype(jnp.float32) * sc[..., None, :]
                ).astype(jnp.bfloat16), sc

    def blocked(key, shape, gain=1.0, last=False, by_layer=None):
        """[layers, ...] generated one layer at a time; ``by_layer``: a
        further factor a layer on the scales."""
        w, sc = jax.lax.map(lambda k: kernel(k, shape[1:], gain, last),
                            jax.random.split(key, shape[0]))
        if by_layer is None:
            return w, sc
        f = jnp.asarray(by_layer, jnp.float32).reshape(
            (-1,) + (1,) * (sc.ndim - 1))
        if quant:
            return w, sc * f
        return (w.astype(jnp.float32) * f[..., None]).astype(w.dtype), sc * f

    def table(key, rows, cols, s):
        """The embedding [V, H] (a scale a ROW; also the head): int8 bits
        made in blocks of a whole number of int8 tiles (32 rows) and put
        together along the leading axis. The first ``A`` columns hold +127
        in every row."""
        kq, ks = jax.random.split(key)
        nb, per = 16, -(-rows // (16 * 32)) * 32
        bits = jax.lax.map(
            lambda k: jax.random.bits(k, (per, cols), jnp.uint8),
            jax.random.split(kq, nb)).reshape(nb * per, cols)[:rows]
        qk = jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8),
                         jnp.int8(-127))
        qk = jnp.where(jnp.arange(cols)[None, :] < A, jnp.int8(127), qk)
        sc = jax.random.uniform(ks, (rows,), jnp.float32, 0.95, 1.05) \
            * (s / STD_Q)
        if quant:
            return {"weight": qk, "scale": sc}
        return {"weight": (qk.astype(jnp.float32) * sc[:, None]
                           ).astype(jnp.bfloat16)}

    def norm(key, shape, gain=1.0):
        return (gain * jax.random.uniform(key, shape, jnp.float32, 0.95,
                                          1.05)).astype(jnp.bfloat16)

    def bias(key, count):
        """Selection bias [count, E]: every block of BIAS_LEVELS ids holds
        the same stratified normal quantiles, permuted."""
        E = n["E"]
        nb = -(-E // BIAS_LEVELS)
        lv = jnp.asarray(levels, jnp.float32) * bias_spread
        perm = jax.vmap(lambda k: jax.random.permutation(k, lv))(
            jax.random.split(key, nb * count))
        return perm.reshape(count, nb * BIAS_LEVELS)[:, :E]

    def put(tree, path, value):
        for part in path[:-1]:
            tree = tree.setdefault(part, {})
        tree[path[-1]] = value

    def centre(count):
        """[count, H, 1]: what the anchor channels' rows of each routed
        layer's router add, the same for every expert, so that a token's
        logits are centred on ``router_centre``: the anchor channels hold
        127 / STD_Q x the embedding's std and nothing adds to them, and the
        FFN's input norm divides by the stream's RMS, which grows by a
        branch's gain squared with each branch before it."""
        e2 = 1.0 + (A / H) * ((127.0 / STD_Q) ** 2 - 1.0)
        live = 1.0 - A / H                     # the channels a branch writes
        seen = []               # (in units of an embedding channel's std)
        for i in range(nd, nd + count):
            ops = 1.0 + sum((conv_gain if k == "c" else attn_gain) ** 2
                            for k in pat[1:i + 1])
            ffns = min(i, nd) * mlp_gain ** 2 + max(i - nd, 0) * moe_gain ** 2
            seen.append(127.0 / STD_Q / math.sqrt(
                e2 + live * first_gain ** 2 * (ops + ffns)))
        rows = (jnp.arange(H) < A).astype(jnp.float32)[None, :, None]
        return rows * (router_centre / A
                       / jnp.asarray(seen, jnp.float32)[:, None, None])

    def stack_tree(key, stack, count):
        out: dict = {}
        keys = iter(jax.random.split(key, 32))
        for path, (shape, q, dt) in _stack_spec(mc, stack).items():
            k, full, name = next(keys), (count,) + shape, path[0]
            if q:
                w, sc = blocked(k, full, *gain_of.get((stack, name), ()))
                put(out, path, w)
                if quant:
                    put(out, path[:-1] + ("scale",), sc)
            elif path[-1] == "bias":
                put(out, path, bias(k, count))
            elif name == "router":
                put(out, path, (jax.random.normal(k, full, jnp.float32)
                                * (router_spread / math.sqrt(H))
                                + centre(count)).astype(jnp.bfloat16))
            elif name == "conv":
                put(out, path, (jax.random.normal(k, full, jnp.float32)
                                / math.sqrt(n["K"])).astype(jnp.bfloat16))
            else:
                put(out, path, norm(k, full, {"q_norm": qk_gain,
                                              "k_norm": qk_gain}.get(name,
                                                                     1.0)))
        return out

    def build(key):
        keys = iter(jax.random.split(key, 8))
        tree: dict = {"layers": {}}
        tree["embed"] = table(next(keys), V, H, logit_sigma / math.sqrt(H))
        for stack, count in _stacks(mc):
            tree["layers"][stack] = stack_tree(next(keys), stack, count)
        tree["final_norm"] = {"weight": norm(next(keys), (H,))}
        return tree

    key = jax.random.key(int(seed) % (2 ** 31), impl="rbg")
    fn = jax.jit(build) if out_shardings is None \
        else jax.jit(build, out_shardings=out_shardings)
    return fn(key)
