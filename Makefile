# Developer/CI entry points. The heavy lifting lives in bench.py /
# bench_sweep.py / deploy/*; these targets pin the hardware-free invocations
# so CI and laptops run the same commands. chip-smoke, ttft-sweep and a bare
# `python bench.py` need a TPU and fail without one.

PY ?= python

.PHONY: test bench-smoke bench-dry ttft-sweep chaos-smoke validate-manifests \
	overload-smoke resume-smoke reconcile-smoke trace-smoke lint \
	locksan-smoke aot-smoke pipeline-smoke ragged-smoke flight-smoke \
	devmon-smoke capacity-smoke bench-diff bench-ragged bench-mixedfeat \
	bench-prefixtier autoscale-smoke chip-smoke chip-smoke-4

# The served path end to end ON THE CHIP (chip_smoke.py): the default server
# at Qwen3-0.6B width and depth, random seeded weights, real HTTP requests,
# kernel parity and end-to-end logprob checks. One process holds the chip;
# exits non-zero without a TPU. The last stdout line is the verdict.
chip-smoke:
	$(PY) chip_smoke.py

# The sharded path (--tp 4), its answers checked against the same weights held
# whole on one device; four chips.
chip-smoke-4:
	$(PY) chip_smoke.py --chips 4

# The tier-1 gate's shape (serial, CPU, slow tests excluded).
test:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		-p no:cacheprovider

# One decode step through the SHIPPED bench program family (paged pool +
# double-buffered bblock Pallas kernels + int8 weights) under
# JAX_PLATFORMS=cpu: catches program-construction regressions in seconds,
# no hardware. Tier-1 also runs these tests; this target is the focused
# pre-push check after touching the kernel/engine decode path.
bench-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m bench_smoke \
		-p no:cacheprovider

# Fault-injection suite on CPU (serving/chaos.py + tests/test_chaos.py):
# every injected fault — connect refused, stalled decode, page-pool
# exhaustion, slow client, mid-stream disconnect, deadline expiry — must
# produce its documented degradation behavior. Tier-1 also runs these; this
# target is the focused pre-push check after touching the robustness layer.
chaos-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py -q \
		-p no:cacheprovider

# Overload BENCH on CPU (ROADMAP robustness follow-on): offered load through
# the REAL router past the replicas' admission limits; writes the
# shed-rate-vs-offered-load curve to OVERLOAD_BENCH.json. Expected shape:
# ~0 shed while offered <= capacity, rising shed rate with completed
# throughput holding — overload degrades by policy, not collapse.
overload-smoke:
	env JAX_PLATFORMS=cpu $(PY) bench_sweep.py --overload \
		--overload-requests 24 --overload-levels 1,4,16

# Self-healing deploy smoke (r9): kill a hermetic rehearse-style deploy
# mid-L3 with injected FATAL chaos -> the journal classifies the failure and
# `deploy --resume` completes from exactly that layer (L1/L2 not re-run);
# inject TRANSIENT chaos into L2 -> the executor retries with deterministic
# capped jittered exponential backoff and the deploy succeeds. Tier-1 runs
# these tests too (marker resume_smoke); this is the focused driver.
resume-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m resume_smoke \
		-p no:cacheprovider

# Reconciler smoke (r9): per-layer health probes (VM READY / nodes Ready /
# per-replica /readyz / gateway smoke / collector), first-broken repair
# (in-place undrain before playbook re-run, honest non-zero exit when the
# probe still fails), and the rolling-restart-under-load scenario — every
# serving replica restarted behind the real router under live seeded load,
# zero non-2xx and byte-identical streams. Tier-1 runs these too (marker
# reconcile_smoke).
reconcile-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m reconcile_smoke \
		-p no:cacheprovider

# Tracing smoke (serving/tracing.py): a hermetic in-process fake OTLP
# collector receives the full span tree from REAL router→server→engine
# requests (streamed + unary) — root span, per-hop dispatch spans
# (failover/429-retry included), server request span, five monotonic
# non-overlapping phase children — and a killed exporter changes no request
# outcome. Tier-1 runs these too (marker trace_smoke).
trace-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m trace_smoke \
		-p no:cacheprovider

# kubeconform (when installed) + structural validation over every rendered
# deploy/manifests template; rehearse-kind.sh runs the same validator on the
# exact bytes it applies.
validate-manifests:
	$(PY) deploy/validate_manifests.py

# Project-native static analysis (tools/tpulint, rules R1-R7: clock
# discipline, metric registration/rendering, broad excepts, page-release,
# lock discipline, chaos-fault test coverage, manifest-flag/CLI coherence)
# + manifest validation + a NON-STRICT mypy pass over the typed serving/
# deploy modules. mypy is a dev-extra (pip install -e .[dev]); the gate
# skips it with a notice when not installed — tpulint itself is
# dependency-free and always runs. Exit 0 == zero unsuppressed findings.
# Tier-1 runs the same rules via tests/test_tpulint.py (marker `lint`).
lint:
	$(PY) -m tools.tpulint aws_k8s_ansible_provisioner_tpu deploy
	$(PY) deploy/validate_manifests.py
	@if $(PY) -c "import mypy" >/dev/null 2>&1; then \
		$(PY) -m mypy --ignore-missing-imports --no-strict-optional \
			--follow-imports=silent \
			aws_k8s_ansible_provisioner_tpu/serving/tracing.py \
			aws_k8s_ansible_provisioner_tpu/serving/metrics.py \
			aws_k8s_ansible_provisioner_tpu/serving/programs.py \
			aws_k8s_ansible_provisioner_tpu/serving/aot.py \
			deploy/state.py; \
	else \
		echo "lint: mypy not installed (pip install -e .[dev]) — type check skipped"; \
	fi

# Deterministic lock/race sanitizer (serving/locksan.py) over the sanitizer
# unit tests PLUS the thread-heaviest e2e subsets (drain, chaos, router e2e)
# with TPU_LOCKSAN=1: every serving/ lock is order-tracked, a lock-order
# cycle or cross-thread unguarded write fails the session (see the
# _locksan_gate fixture), and seeded responses stay byte-identical with the
# sanitizer on vs off. Tier-1 runs tests/test_locksan.py (marker
# locksan_smoke) without the env; this target is the full instrumented run.
locksan-smoke:
	env JAX_PLATFORMS=cpu TPU_LOCKSAN=1 $(PY) -m pytest \
		tests/test_locksan.py tests/test_drain.py tests/test_chaos.py \
		tests/test_router_e2e.py -q -p no:cacheprovider

# Decode-pipeline smoke (serving/programs.py one-deep async pipeline):
# seeded golden streams byte-identical pipeline on vs off, lifecycle edges
# (cancel/deadline/chunk/drain), injected fetch failure recovery — run
# LockSan-instrumented, since the pipeline adds engine-thread state
# (_inflight/_pipe_carry) whose single-writer contract LockSan verifies at
# runtime. Tier-1 runs the same tests (marker pipeline_smoke) without the
# env.
pipeline-smoke:
	env JAX_PLATFORMS=cpu TPU_LOCKSAN=1 $(PY) -m pytest \
		tests/test_decode_pipeline.py -q -p no:cacheprovider

# Ragged mixed-batch attention smoke (ops/pallas_attention.py ragged paged
# kernel + serving/programs.py mixed_step): interleaved chunked-prefill
# admissions must hold the pipeline open (zero admission-edge drains on
# tpu_serve_pipeline_drains_total), seeded streams byte-identical ragged vs
# legacy across sampled/logprobs/penalties, and the injected
# ragged_dispatch_error fault drops the dispatch without killing the
# engine. LockSan-instrumented for the same single-writer reason as
# pipeline-smoke; tier-1 runs the same tests (marker ragged_smoke) bare.
ragged-smoke:
	env JAX_PLATFORMS=cpu TPU_LOCKSAN=1 $(PY) -m pytest tests/ -q \
		-m ragged_smoke -p no:cacheprovider

# Chip-free ragged A/B (bench.py --ragged): chunked-prefill-heavy mixed
# load, ragged_attention=1 vs the sync fallback in one process. Asserts the
# ragged pass matches-or-beats sync tok/s with ZERO admission-edge drains
# and writes BENCH_ragged_r01.json.
bench-ragged:
	env JAX_PLATFORMS=cpu $(PY) bench.py --ragged

# Feature-vs-plain A/B on the ragged pipeline (ISSUE 16): spec + guided +
# LoRA + chunked prefill concurrently must hold >= 0.9x plain tok/s with
# zero feature-reason pipeline drains. Writes BENCH_mixedfeat_r01.json.
bench-mixedfeat:
	env JAX_PLATFORMS=cpu $(PY) bench.py --mixed-features

# Warm-host-tier TTFT vs cold-re-prefill A/B (ISSUE 20): after LRU eviction
# spills a long prompt's prefix pages to host RAM, re-serving it must beat
# a full re-prefill by >= 3x TTFT. Writes BENCH_prefixtier_r01.json.
bench-prefixtier:
	env JAX_PLATFORMS=cpu $(PY) bench.py --prefix-tier

# AOT registry smoke (serving/aot.py): deviceless host-platform compile of
# the full tiny-config program set through build_manifest — manifest schema
# checked, per-program compile seconds recorded, HBM fit verdict asserted
# both ways. Tier-1 runs the same tests (marker aot_smoke); the committed
# Qwen3-8B v5e-8 artifact (AOT_QWEN3_8B_v5e8.json) is regenerated with
#   python -m aws_k8s_ansible_provisioner_tpu.serving.aot --model Qwen/Qwen3-8B --tp 8
aot-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m aot_smoke \
		-p no:cacheprovider

# Flight-recorder smoke (serving/flightrec.py + serving/slo.py): a chaos-
# injected deadline expiry must yield a spooled black-box dump with the
# complete admit -> deadline_reap -> finish timeline and trace ids via
# /debug/flight/<id>; seeded streams stay byte-identical recorder on vs
# off; an injected spool fault (flight_dump_error) is counted, never felt
# by a request. Tier-1 runs the same tests (marker flight_smoke).
flight-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m flight_smoke \
		-p no:cacheprovider

# Device-telemetry smoke (serving/devmon.py): golden /debug/roofline
# arithmetic under a fake clock, HBM drift warn-never-kill, byte-identical
# streams devmon on/off, OpenMetrics exemplar/escaping goldens. Tier-1 runs
# the same tests (marker devmon_smoke).
devmon-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m devmon_smoke \
		-p no:cacheprovider

# Capacity-observatory smoke (serving/capacity.py): golden headroom-forecast
# arithmetic under a fake clock, the OVERLOAD_BENCH.json replay (the
# forecast must cross saturation at or below the measured shed knee),
# byte-identical seeded streams estimator on/off, drop-not-fail export
# chaos, and the router's /debug/capacity fleet aggregation. Tier-1 runs
# the same tests (marker capacity_smoke).
capacity-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m capacity_smoke \
		-p no:cacheprovider

# Fleet actuation (serving/autoscaler.py): ramp e2e through real servers,
# scale-to-zero cold start, flap suppression, launch-failure backoff.
autoscale-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m autoscale_smoke \
		-p no:cacheprovider

# Artifact regression differ (tools/benchdiff.py): compare a fresh bench
# run against the committed baseline before replacing it. Usage:
#   make bench-diff A=OVERLOAD_BENCH.json B=/tmp/OVERLOAD_BENCH.json
# Non-zero exit when a known metric moved the bad way past --threshold
# (tok/s and speedups down, TTFT/bubble/ready-time up, shed knee earlier).
bench-diff:
	$(PY) -m tools.benchdiff $(A) $(B)

# Full bench field-plumbing proof on CPU (tiny model, ~15 s): one JSON line
# with every real-run field (bblock, weights_dtype, dma_steps_per_substep,
# device_kind), labelled "dry": true — never a device number.
bench-dry:
	$(PY) bench.py --dry

# TTFT prefill-lever curve ON THE CHIP (prefill batch x chunked interleave;
# see bench_sweep.TTFT_GRID). Each config is one `bench.py --measure`
# process, which exits non-zero without a TPU.
ttft-sweep:
	$(PY) bench_sweep.py --ttft
