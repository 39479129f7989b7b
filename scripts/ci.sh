#!/usr/bin/env bash
# Tier-1 CI entry point: install dev deps (best effort — the container may be
# offline, in which case hypothesis-only modules skip themselves), run the
# pass-registry consistency check and the quickstart smoke (registry API +
# tiny P->L->Q pipeline through int8 export), then the canonical test
# command from ROADMAP.md.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m pip install -q -r requirements-dev.txt 2>/dev/null \
    || echo "ci.sh: pip install failed (offline?); property tests will skip"

export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

# every registered pass must carry (kind, granularity) ranks the planner
# knows, and a defaulted hp dataclass — a bad registration fails CI here
python - <<'PY'
import repro.core  # populates the registry (D/P/Q/E + L)
from repro.core import registry
keys = registry.check_consistency()
print('registry consistent:', ''.join(keys))
PY

python examples/quickstart.py --smoke

# serving-benchmark smoke: times the fake-quant / dynamic-int8 /
# int8-resident paths (incl. the fused low-rank variant) on a tiny batch —
# catches export-plan regressions that only bite at serve time.  Also
# runs the analyzer's int8-residency and launch-budget rules over the
# exports (mobilenet must have no needless fallback; a measure-mode export
# never records a fused/chained choice its own timings say is slower).
# Writes no BENCH file (the committed BENCH_serving.json comes from a full
# run).
python benchmarks/serving_int8.py --smoke

# serving-runtime smoke: a tiny Poisson trace through the continuous-
# batching scheduler — asserts the queue drains and every request's answer
# is bit-exact vs the monolithic model serving it alone at the same slot
# geometry (the early-exit compaction contract).  Writes no BENCH file.
python benchmarks/serving_load.py --smoke

# resilience smoke: the same trace (plus arrival bursts) through the
# replica pool under a seeded chaos plan — a replica killed mid-batch and
# a straggler slowdown.  Asserts the pool drains with zero lost requests,
# fails over through the registry restore path, and every completion is
# bit-exact vs the undisturbed run; the chaos+SLO leg asserts no admitted
# request ever finishes past its deadline.  Writes no BENCH file.
python benchmarks/serving_load.py --smoke --chaos

# trace smoke: the chaos smoke again with --trace — the run must emit a
# valid Chrome-trace JSON whose spans pass the strict invariant check
# (nesting, per-replica serial execution, latency == span extent) with
# the kill + failover story visible on the replica tracks; then the
# validator itself is proven live by mutating a span (tearing t1 < t0)
# and requiring check_trace to go red on the mutated file.
python benchmarks/serving_load.py --smoke --chaos \
    --trace /tmp/trace_smoke.json
python - <<'PY'
import json
from repro.obs import check_trace, load_chrome_trace

spans = load_chrome_trace('/tmp/trace_smoke.json')
assert not check_trace(spans, strict=False), 'smoke trace has violations'
assert any(s.name == 'stage.exec' and s.args.get('killed') for s in spans)
assert any(s.name == 'failover.restore' for s in spans)

with open('/tmp/trace_smoke.json') as f:
    doc = json.load(f)
for ev in doc['traceEvents']:          # tear one stage.exec span
    if ev.get('ph') == 'X' and ev.get('name') == 'stage.exec':
        ev['dur'] = -ev['dur'] - 1
        break
torn = check_trace(load_chrome_trace(doc), strict=False)
assert torn, 'check_trace stayed green on a torn span'
print(f'trace smoke OK: {len(spans)} spans valid, '
      f'torn-span mutation caught ({len(torn)} violation(s))')
PY

# pipeline-parallel smoke, on 8 forced host devices (JAX_PLATFORMS=cpu
# lets the benchmark force the count in-process; on an accelerator it
# fails with fewer devices instead): serves the same tiny trace through the
# single-device scheduler and the placed pipeline, asserting every
# request bit-exact vs the monolithic oracle and the recorded spans
# (incl. transfer.carry) strictly valid.  Then the placement-consistency
# rule is proven live: green on the pipeline's placed export, red on the
# stage-assignment-dropping mutant.  Writes no BENCH file.
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/serving_pipeline.py --smoke
python - <<'PY'
import jax
from repro.analysis import check
from repro.analysis.mutations import MUTANTS, _resnet_export

model, _, _, x = _resnet_export(use_pallas=False, exits=True)
placed = model.place_stages((jax.devices()[0],) * model.n_stages)
clean = check(model=placed, x=x, rules=('placement-consistency',),
              target='ci:placed-export')
assert not any(f.severity == 'error' for f in clean.findings), clean
red = check(**MUTANTS['placement-consistency']())
errs = [f for f in red.findings if f.severity == 'error']
assert errs, 'placement-consistency stayed green on its mutant'
print(f'placement-consistency OK: clean export green, '
      f'mutant red ({len(errs)} error finding(s))')
PY

# static-analysis gate (repro/analysis): every rule must be green on the
# shipped exports of all three CNN kinds (both backends + the theoretical
# sequence) AND red on its deliberately-mutated export — a rule that stops
# firing on its own mutant fails CI even while everything stays green.
# Any error-severity finding on a clean export exits non-zero here.
python -m repro.analysis.gate

exec python -m pytest -x -q "$@"
