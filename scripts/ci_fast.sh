#!/usr/bin/env bash
# Fast tier-1 selection: everything except the @pytest.mark.slow
# end-to-end tests (offline-phase training + long missions), so CI gets a
# signal in minutes. The full suite remains the default `pytest` run.
# Finishes with an engine smoke: a short serve through the AveryEngine
# front door (random-init weights) so the fast path exercises prompt
# gating -> policy -> channel -> batched cloud serving end to end.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
echo "[ci_fast] averylint (static invariants + runtime sanitizer smoke)"
# repo-aware lints first: recompile/host-sync/future/refcount/determinism
# findings fail fast before the test suite spends minutes compiling, then
# a short serve under the recompile + transfer sanitizers proves the
# steady-state decode pump stays churn-free (see docs/analysis.md)
python -m repro.analysis.lint src/
python -m repro.analysis.sanitizers --smoke
python -m pytest -q -m "not slow" "$@"
echo "[ci_fast] engine smoke (microbatch + inflight)"
python -m repro.launch.serve --duration 2 --smoke --max-batch 4
python -m repro.launch.serve --duration 2 --smoke --max-batch 4 --batching inflight
echo "[ci_fast] paged shared-prefix serving smoke"
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_serving --paged-smoke
echo "[ci_fast] speculative decoding smoke"
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_serving --spec-smoke
echo "[ci_fast] sharded serving smoke (8-device host-platform mesh)"
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_serving --sharded-smoke
echo "[ci_fast] chaos storm smoke (retry/downshift/deadline, zero leaks)"
# chaos_rows asserts the fault-tolerance contract itself: every future
# resolves, >=1 successful downshifted retry, >=1 deadline cancel, and
# zero leaked KV pages — a broken engine fails this step, not just a row
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_serving --chaos-smoke
echo "[ci_fast] fleet storm smoke (QoS scheduling vs FIFO)"
# fleet_storm_rows asserts the multi-tenant scheduling contract: Context
# p99 strictly beats FIFO on the same trace, Jain >= 0.9, >=1 preemption
# with token-exact resume, >=1 rate-limit rejection, zero leaked pages
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_serving --fleet-storm-smoke
echo "[ci_fast] full-size chaos + fleet storm rows (the baseline gates them)"
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_serving --chaos
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_serving --fleet-storm
echo "[ci_fast] trace smoke (span tracer + flight recorder)"
# a traced profiled-path serve through a blackout window: the retry spans
# must pass the lifecycle validator, the Perfetto export must round-trip,
# and the flight-recorder ring must dump the journey — all on the
# LUT-profiled engine, no executor/model (observability itself stays
# jax-free: averylint AV201 + test_host_only_modules_have_no_jax_imports)
python - <<'EOF'
import glob, json, os
from repro.core.lut import paper_lut
from repro.engine import (AveryEngine, FaultInjector, LoopbackTransport,
                          RetryPolicy)
from repro.engine.observability import validate_chrome_trace, validate_traces
art = os.path.join("benchmarks", "artifacts")
engine = AveryEngine(
    lut=paper_lut(), trace=True,
    flight_dir=os.path.join(art, "flight_ci_smoke"),
    transport=FaultInjector(LoopbackTransport(20.0), blackouts=[(0.0, 30.0)]),
    retry=RetryPolicy(max_attempts=3, backoff_base_s=1.0))
sess = engine.session("uav-ci")
res = sess.submit_frame(0.0)
assert res.feasible and res.attempts == 2, res
problems = validate_traces(engine.tracer)
assert not problems, problems
path = engine.dump_trace(os.path.join(art, "trace_ci_smoke.json"))
problems = validate_chrome_trace(json.load(open(path)))
assert not problems, problems
dump = engine.dump_flight(os.path.join(art, "flight_ci_smoke", "manual.json"))
assert dump and json.load(open(dump))["events"], dump
for f in glob.glob(os.path.join(art, "flight_ci_smoke", "*.json")):
    os.remove(f)
os.rmdir(os.path.join(art, "flight_ci_smoke"))
print("trace smoke ok:", path)
EOF
echo "[ci_fast] perf gate (bench counts vs committed baseline)"
# the smoke rows above merged into benchmarks/artifacts/BENCH_serving.json;
# the gate compares their counts, quality rates and mission-clock
# latencies with BENCH_baseline.json (wall-clock fields are never gated:
# a CPU run cannot show speed)
python scripts/perf_gate.py
