# Unified build/test/bench entry point (parity role: the reference's
# top-level Bazel workspace + CI scripts — SURVEY §2.1 row "Build").
#
#   make native     build the C++ object-store runtime (.so)
#   make cpp        build the C++ client API (+ demo binary)
#   make sanitize   build + run the TSAN/ASAN store-chaos harnesses
#   make test       full pytest suite (virtual 8-device CPU mesh)
#   make test-fast  the quick core slice (smoke for iteration)
#   make bench      the flagship MFU benchmark (one JSON line)
#   make ci         everything CI runs: native + cpp + sanitize + test

PY ?= python
# deterministic chaos schedules: export CHAOS_SEED=<n> (or set here) to
# reproduce a failing chaos run kill-for-kill
CHAOS_SEED ?= 1729

.PHONY: all native cpp sanitize test test-fast chaos chaos-serve bench bench-isolation bench-trace trace-demo train-obs-demo bench-train-obs bench-net bench-launch bench-incidents bench-gate ci clean

all: native cpp

native:
	$(MAKE) -C ray_tpu/native

cpp:
	$(MAKE) -C ray_tpu/cpp

sanitize:
	$(MAKE) -C ray_tpu/native tsan asan
	./ray_tpu/native/store_chaos_tsan /dev/shm/ray_tpu_chaos_tsan 8 200
	./ray_tpu/native/store_chaos_asan /dev/shm/ray_tpu_chaos_asan 8 200

test: native
	$(PY) -m pytest tests/ -x -q -m "not slow"

test-fast: native
	$(PY) -m pytest tests/test_core_basic.py tests/test_actors.py \
		tests/test_direct_actor.py tests/test_data.py -q

# slow-marked fault-injection suite: worker/node SIGKILLs mid-run, elastic
# resume convergence, priority-preemption resume. Excluded from tier-1;
# seeded via CHAOS_SEED.
chaos:
	CHAOS_SEED=$(CHAOS_SEED) $(PY) -m pytest tests/test_chaos.py \
		tests/test_elastic_chaos.py tests/test_preempt_chaos.py \
		tests/test_serve_chaos.py tests/test_llm_chaos.py \
		tests/test_incident_chaos.py -m slow -q

# serve-plane churn suite: replica + controller SIGKILLs under sustained
# mixed unary/streaming load, graceful-redeploy zero-drop proof — plus the
# LLM variant with live decode streams (kills mid-decode fail typed or
# pre-first-token; drain finishes in-flight decodes). Seeded via
# CHAOS_SEED like the rest of the chaos group; on-demand for CI.
chaos-serve:
	CHAOS_SEED=$(CHAOS_SEED) $(PY) -m pytest tests/test_serve_chaos.py \
		tests/test_llm_chaos.py -m slow -q

bench:
	$(PY) bench.py

# request-tracing plane smoke: nested task graph + streaming serve request
# reconstructed via ray_tpu.trace (stage sum within 10% of wall, TTFT span
# present), plus a profiler flame-graph export. Fails non-zero on any
# violation.
trace-demo:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/trace_demo.py

# tracing/profiler overhead: same-box alternating on/off pairs; the
# recorded acceptance signal is the per-call ratio (budget <= 1.05).
# --append writes the rows to BENCH_CORE.jsonl
bench-trace:
	JAX_PLATFORMS=cpu $(PY) bench_trace.py

# training step-plane smoke: 2-rank run with throttled ingest + per-step
# checkpoints -> per-rank step waterfall (stage sums within 10% of wall),
# then a seeded-kill rerun whose goodput gap must be attributed by the
# downtime ledger. Fails non-zero on any coverage/attribution violation.
train-obs-demo:
	JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/train_obs_demo.py

# step-plane overhead: alternating fresh-cluster on/off pairs over a tight
# report loop; the recorded acceptance signal is the per-step ratio
# (budget <= 1.05). --append writes the row to BENCH_CORE.jsonl
bench-train-obs:
	JAX_PLATFORMS=cpu $(PY) bench_train_obs.py --append

# transfer-plane overhead + per-path GiB/s: socket-plane broadcast with the
# plane toggled in alternating pairs (median per-pair ratio, budget <= 1.05)
# plus the link ledger's per-path EWMAs and the stage-coverage ratio.
# --append writes the rows to BENCH_SCALE.jsonl. Fails non-zero on budget
# violation.
bench-net:
	JAX_PLATFORMS=cpu $(PY) bench_netplane.py --append

# control-plane (actor-launch) observability: launch-rate overhead with the
# plane toggled in alternating pairs (budget <= 1.05) plus the 1000-actor
# per-stage launch decomposition and stage-coverage ratio. --append writes
# the rows to BENCH_SCALE.jsonl. Fails non-zero on budget violation.
bench-launch:
	JAX_PLATFORMS=cpu $(PY) bench_launch_obs.py --append

# incident/alerting-plane overhead: small-task rate with the plane (1 Hz
# SLO scan + event intake) toggled live in alternating pairs, 3 SLOs
# registered while ON (budget <= 1.05). --append writes the row to
# BENCH_CORE.jsonl.
bench-incidents:
	JAX_PLATFORMS=cpu $(PY) bench_incidents.py --append

# bench regression gate: re-reads the BENCH_*.jsonl ledgers and fails
# non-zero if the newest row of any *_overhead_ratio metric exceeds its
# budget (default 1.05), any *_stage_coverage row is below 0.9, any
# *_ttft_p99_ms row exceeds its budget (default 5000 ms), any
# *_floor_ratio row is below its floor (default 1.0), or any
# *_untyped_failures row exceeds its budget (default 0).
bench-gate:
	$(PY) tools/bench_check.py

# multi-tenant acceptance: a noisy-neighbor job (task spam + large puts)
# must not degrade a high-priority job's p99 probe latency beyond 2x its
# calm baseline. Slow; excluded from tier-1.
bench-isolation:
	$(PY) bench_isolation.py

ci: native cpp sanitize test

clean:
	$(MAKE) -C ray_tpu/native clean
	$(MAKE) -C ray_tpu/cpp clean
