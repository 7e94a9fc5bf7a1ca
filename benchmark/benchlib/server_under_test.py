"""Starts the system under test: the server's own entry points, in this
process, on a worker thread (a copy of chip_smoke.py's start sequence).

    build_parser().parse_args(flags) -> serving_config_from_args
    -> build_state(model_cfg=..., params=<the benchmark's seeded tree>)
    -> serve(...) on a daemon thread

No ``engine.warmup()``: the run warms what its cell dispatches through the
HTTP path (benchlib/warmup.py). The configuration file gives the flags, the
ModelConfig fields and what the resolved engine must look like (``expect``).
"""

from __future__ import annotations

import os
import socket
import subprocess
import threading
import time

from benchlib import files

# published config.json key -> ModelConfig field (what a new model's file
# has to give; checked against the file's own ``model_config``)
HF_TO_MODEL_CONFIG = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "hidden_act": "act", "attention_bias": "attention_bias",
    "bos_token_id": "bos_token_id", "eos_token_id": "eos_token_id",
}


def check_config_file(cfg_file: dict) -> None:
    """The file is consistent with itself: every published key that maps to a
    ModelConfig field carries the same value there."""
    mc, hf = cfg_file["model_config"], cfg_file["hf_config"]
    for hk, mk in HF_TO_MODEL_CONFIG.items():
        if hf.get(hk) is None:
            continue
        if mk not in mc or mc[mk] != hf[hk]:
            raise SystemExit(
                f"configuration {cfg_file['name']}: published {hk}="
                f"{hf[hk]!r} but model_config {mk}={mc.get(mk)!r}")


def model_config_of(cfg_file: dict):
    """The program's ModelConfig from the file's fields; where the program
    registers the model under the same name, the two must be equal."""
    import dataclasses

    from aws_k8s_ansible_provisioner_tpu.config import (MODEL_REGISTRY,
                                                        ModelConfig)

    check_config_file(cfg_file)
    mc = ModelConfig(**cfg_file["model_config"])
    reg = MODEL_REGISTRY.get(cfg_file.get("registry_name", ""))
    if reg is not None:
        a, b = dataclasses.asdict(mc), dataclasses.asdict(reg)
        a.pop("hf_repo", None), b.pop("hf_repo", None)
        if a != b:
            diff = {k: (a[k], b[k]) for k in a if a[k] != b[k]}
            raise SystemExit(f"configuration {cfg_file['name']} differs from "
                             f"MODEL_REGISTRY[{reg.name!r}]: {diff}")
    return mc


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_native_scheduler(say) -> None:
    """The native scheduler is the intended one; make_scheduler picks up the
    library it finds. Built from the committed sources inside the checkout
    (native/build/, git-ignored); ``make`` is a no-op once it is built."""
    t0 = time.monotonic()
    subprocess.run(["make", "-C", os.path.join(files.ROOT, "native"),
                    "runtime"], check=True, stdout=subprocess.DEVNULL)
    say(f"native scheduler: make took {time.monotonic() - t0:.1f}s")


class Server:
    def __init__(self, cfg_file: dict, params, say, rehearsal: bool):
        from aws_k8s_ansible_provisioner_tpu.ops.attention import resolve_impl
        from aws_k8s_ansible_provisioner_tpu.serving import server

        self.port = _free_port()
        argv = list(cfg_file["server_flags"]) + [
            "--host", "127.0.0.1", "--port", str(self.port)]
        args = server.build_parser().parse_args(argv)
        serving = server.serving_config_from_args(args)
        t0 = time.monotonic()
        self.state = server.build_state(
            serving, model_cfg=model_config_of(cfg_file), params=params)
        self.build_s = time.monotonic() - t0
        self.engine = eng = self.state.engine
        self.served_model = serving.model
        got = {"attention_impl": resolve_impl(eng.serving.attention_impl),
               "paged": bool(eng.paged),
               "decode_bblock": int(eng.decode_bblock),
               "scheduler": type(eng.sched).__name__,
               "slots": int(eng.num_slots), "window": int(eng.max_len),
               "weights_dtype": eng.serving.weights_dtype,
               "kv_int8": bool(eng.kv_quant),
               "pipeline": int(eng.serving.decode_pipeline),
               "ragged": int(eng.serving.ragged_attention)}
        say(f"server: flags {cfg_file['server_flags']}; resolved {got}; "
            f"build_state {self.build_s:.1f}s")
        want = dict(cfg_file.get("expect", {}))
        if rehearsal:
            want.update(cfg_file.get("expect_rehearsal", {}))
        bad = {k: (got.get(k), v) for k, v in want.items() if got.get(k) != v}
        if bad:
            raise SystemExit(f"the engine did not resolve as the "
                             f"configuration states: {bad}")
        ready = threading.Event()
        # daemon: a failed phase must end the process, not leave it serving
        self._thread = threading.Thread(
            target=server.serve, name="serve", daemon=True,
            args=(self.state, "127.0.0.1", self.port, ready))
        self._thread.start()
        if not ready.wait(60):
            raise SystemExit("server did not come up")

    def wait_idle(self, timeout: float = 60.0) -> bool:
        eng = self.engine
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if (not any(r is not None for r in eng.slot_req)
                    and eng.sched.stats().queue_depth == 0
                    and eng._inflight is None):
                return True
            time.sleep(0.02)
        return False

    def drain(self) -> None:
        from benchlib.client import http_json

        http_json(self.port, "POST", "/admin/drain", {})
        self._thread.join(60)
        if self._thread.is_alive():
            raise SystemExit("server did not stop after drain")
