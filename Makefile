PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
ARTIFACTS := artifacts

.PHONY: test lint bench-smoke bench trace-demo

# tier-1 verify (see ROADMAP.md); on the CPU, kernels in interpret mode
test:
	PYTHONPATH=$(PYTHONPATH) JAX_PLATFORMS=cpu python -m pytest -x -q

# ruff (pinned in requirements-dev.txt; config in ruff.toml)
lint:
	ruff check src tests benchmarks examples

# one registered config per family — a reintroduced family gate in the
# serving plane fails this sweep fast
BENCH_FAMILY_ARCHS := qwen3-4b mixtral-8x7b mamba2-2.7b zamba2-2.7b seamless-m4t-large-v2

# CI-friendly benchmark smoke: colocated-vs-disaggregated serving latency
# (small shapes, swept over one config per family: dense, moe, ssm,
# hybrid, encdec) + the paged-vs-dense decode step-time gate (native
# paged step must be <= 1.0x the dense-cache step; skipped for
# non-pageable families) + the telemetry-overhead gate (flight recorder
# on <= 1.05x off on the decode step) + the daemon-driven elastic
# scheduling trace (short) + the prefix-cache cold/warm gate — paged
# (warm TTFT < 0.6x cold, kv bytes saved) AND snapshot ssm/hybrid (warm
# TTFT < 0.7x cold, snapshot bytes saved, warm channel bytes < cold).
# Every run's CSV is captured under $(ARTIFACTS)/ and folded into one
# bench_smoke.json for the CI artifact upload.
bench-smoke:
	mkdir -p $(ARTIFACTS)
	for arch in $(BENCH_FAMILY_ARCHS); do \
		PYTHONPATH=$(PYTHONPATH) python benchmarks/disagg_serving.py --smoke --arch $$arch > $(ARTIFACTS)/disagg_serving_$$arch.csv || exit 1; \
		cat $(ARTIFACTS)/disagg_serving_$$arch.csv; \
	done
	PYTHONPATH=$(PYTHONPATH) python -m benchmarks.elastic_sched --smoke > $(ARTIFACTS)/elastic_sched.csv
	cat $(ARTIFACTS)/elastic_sched.csv
	PYTHONPATH=$(PYTHONPATH) python benchmarks/prefix_cache.py --smoke > $(ARTIFACTS)/prefix_cache.csv
	cat $(ARTIFACTS)/prefix_cache.csv
	PYTHONPATH=$(PYTHONPATH) python benchmarks/prefix_cache.py --smoke --arch mamba2-2.7b > $(ARTIFACTS)/prefix_cache_mamba2.csv
	cat $(ARTIFACTS)/prefix_cache_mamba2.csv
	PYTHONPATH=$(PYTHONPATH) python benchmarks/prefix_cache.py --smoke --arch zamba2-2.7b > $(ARTIFACTS)/prefix_cache_zamba2.csv
	cat $(ARTIFACTS)/prefix_cache_zamba2.csv
	PYTHONPATH=$(PYTHONPATH) python benchmarks/multitenant.py --smoke > $(ARTIFACTS)/multitenant.csv
	cat $(ARTIFACTS)/multitenant.csv
	PYTHONPATH=$(PYTHONPATH) python benchmarks/cluster_cache.py --smoke > $(ARTIFACTS)/cluster_cache.csv
	cat $(ARTIFACTS)/cluster_cache.csv
	python benchmarks/smoke_json.py $(ARTIFACTS)/*.csv -o $(ARTIFACTS)/bench_smoke.json

# Perfetto-openable demo trace: the closed-loop serving example
# (autoscale + kill-column self-heal) exports its flight-recorder state
# + daemon decision audit as Chrome trace-event JSON
trace-demo:
	mkdir -p $(ARTIFACTS)
	PYTHONPATH=$(PYTHONPATH) python examples/serve_disagg.py --trace-out $(ARTIFACTS)/serve_disagg_trace.json

# full benchmark harness (paper tables/figures)
bench:
	PYTHONPATH=$(PYTHONPATH) python benchmarks/run.py
