"""The comparison that decides ``correct``, outside the measured window.

Two seeded prompts (lengths in the configuration file; 63 and 300 tokens in
this PR's cells, so one sits below and one above a 64-token page and both
below and above the smallest prefill buckets), 16 greedy tokens each with
chosen-token logprobs, streamed through the same HTTP path. Each generated
position is compared with the plain float32 reference
(benchmark/reference/<name>.py) teacher-forced over prompt + served tokens,
on the very weights the server holds.

Tolerances (chip_smoke.py's, and its reasons):
- NEAR_MAX_NATS 0.35: how far the served token's reference logprob may sit
  below the reference's own maximum. Weights are random, so the top two
  logits are often a few hundredths of a nat apart and bf16 matmuls
  legitimately flip the argmax, so raw token equality would be brittle; a
  broken cache, kernel or position lands ~3 nats down (logit std ~0.64 over
  152k entries).
- LOGPROB_NATS 0.25: served chosen-token logprob against the reference's
  logprob of that same token. The served path computes in bf16 with int8
  weights upcast exactly; the reference is float32 throughout, so this also
  bounds what the serving precision costs — dropping a layer, a norm or
  RoPE, or computing in a coarser type than stated, moves logprobs by far
  more than 0.25.
"""

from __future__ import annotations

from benchlib import client as cl
from benchlib import files
from benchlib.trafficgen import prompt_text

NEAR_MAX_NATS = 0.35
LOGPROB_NATS = 0.25
N_GEN = 16


def check(port: int, model: str, cfg_file: dict, tree, seed: int,
          say) -> bool:
    import numpy as np

    ref = files.load_module("reference", cfg_file["reference"])
    mc = cfg_file["model_config"]
    ok = True
    for n_prompt in cfg_file.get("correctness_prompt_lens", [63, 300]):
        prompt = prompt_text(seed, 10_000_000 + n_prompt, n_prompt)
        res = cl.Result(measured=True)
        cl.stream_completion(
            port, cl.completion_body(model, prompt, N_GEN, logprobs=0), res,
            parse=True, timeout=600.0)
        if not (res.ok and len(res.token_ids) == N_GEN
                and len(res.logprobs) == N_GEN):
            say(f"correctness[{n_prompt}]: malformed response: status "
                f"{res.status} done {res.done} tokens {len(res.token_ids)} "
                f"logprobs {len(res.logprobs)} error {res.error!r}")
            ok = False
            continue
        ids = list(prompt.encode("ascii")) + [int(t) for t in res.token_ids]
        rows = ref.logprobs(mc, tree, ids, N_GEN)
        served_ref = rows[np.arange(N_GEN), np.asarray(res.token_ids)]
        gap = float(np.max(rows.max(axis=-1) - served_ref))
        agree = float(np.max(np.abs(np.asarray(res.logprobs) - served_ref)))
        finite = bool(np.all(np.isfinite(rows)))
        say(f"correctness[{n_prompt}]: {N_GEN} positions; served token below "
            f"the reference maximum by <= {gap:.4f} nats (tol "
            f"{NEAR_MAX_NATS}); served vs reference logprob differ <= "
            f"{agree:.4f} nats (tol {LOGPROB_NATS}); finite {finite}")
        ok = ok and finite and gap <= NEAR_MAX_NATS and agree <= LOGPROB_NATS
    return ok
