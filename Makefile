# One memorable entry point per CI stage.
#   make test-fast   - tier-1: every test not marked `slow` (<~90s on CPU);
#                      this is what .github/workflows/ci.yml runs per push
#   make test        - tier-2: the full suite (the ROADMAP.md verify command)
#   make bench-smoke - fast estimator-sweep + fused-runtime + serving +
#                      stage-breakdown benchmarks on CPU (interpret-mode
#                      kernels), driven by the shared `bench-smoke` spec
#                      preset; writes BENCH_fused.json, BENCH_serving.json,
#                      BENCH_step.json (+ a sample obs span trace) and
#                      gates every artifact's tripwires via run.py --check;
#                      then exercises the run registry end to end: a short
#                      `launch train` with health telemetry on writes
#                      artifacts/runs/<run_id>/, `launch report` renders
#                      its health report, and `launch replay` re-executes
#                      the run and verifies every recorded scalar bitwise
#   make swarm-smoke - distributed-swarm gate: the scaling/bytes-per-step
#                      benchmark with its tripwires (BENCH_dist.json), then
#                      a 2-worker swarm run that hard-kills a worker
#                      mid-run (chaos_crash) and recovers through the
#                      elastic-rejoin path, verified bit-for-bit by
#                      `launch replay`
#   make specs       - dump every repro.api preset to artifacts/specs/
#                      (the serialized experiment-spec surface CI archives)
#   make docs        - regenerate the generated docs (docs/cli.md and the
#                      serving spec table in docs/serving.md) from the live
#                      spec schema; idempotent, and CI fails on any diff
#   make lint        - bytecode-compile everything (+ ruff when installed)

PY ?= python
export PYTHONPATH := src

.PHONY: test test-fast bench-smoke swarm-smoke specs docs lint

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q

test-fast:
	JAX_PLATFORMS=cpu $(PY) -m pytest -q -m "not slow"

bench-smoke:
	$(PY) benchmarks/estimator_sweep.py --smoke --preset bench-smoke
	$(PY) benchmarks/fused_forward.py --smoke --preset bench-smoke --json BENCH_fused.json --check
	$(PY) benchmarks/serving.py --smoke --preset bench-smoke --json BENCH_serving.json --check
	$(PY) benchmarks/step_time.py --smoke --preset bench-smoke --json BENCH_step.json --jsonl BENCH_step_trace.jsonl --check
	$(PY) benchmarks/run.py --collect-only --check
	$(PY) -m repro.launch train --preset tiny-smoke --telemetry true \
		--set run.eval_every=0 --set telemetry.health_norms=true
	$(PY) -m repro.launch report --out artifacts/runs/report.md
	$(PY) -m repro.launch replay

swarm-smoke:
	$(PY) benchmarks/distributed.py --smoke --json BENCH_dist.json --check
	$(PY) -m repro.launch swarm --preset swarm-smoke \
		--set run.steps=30 --set run.ckpt_every=10 \
		--set run.ckpt_dir=artifacts/swarm-ckpt \
		--set swarm.chaos_crash=1:3 --set swarm.chaos_seed=7 \
		--out artifacts/swarm.json
	$(PY) -m repro.launch replay
	$(PY) benchmarks/run.py --collect-only --check

specs:
	$(PY) -m repro.launch specs --out artifacts/specs

docs:
	$(PY) -m repro.launch specs --out artifacts/specs --markdown docs

lint:
	$(PY) -m compileall -q src tests benchmarks examples
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check src tests benchmarks examples; \
	else \
		echo "lint: compileall passed (ruff not installed)"; \
	fi
