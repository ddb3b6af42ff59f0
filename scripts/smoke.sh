#!/usr/bin/env bash
# PR smoke gate: tier-1 tests + the runner-driven table1 path end-to-end
# + serve, profile, tuning, trace, history and fleet gates.
#
#     bash scripts/smoke.sh [--fast-only]
#
# Fails on the first nonzero exit.  --fast-only skips the pytest tier
# (useful while iterating on the benchmark harness itself).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" != "--fast-only" ]]; then
    echo "== tier-1 tests =="
    python -m pytest -x -q
fi

echo "== runner path: table1_suite --fast =="
python -m benchmarks.run --fast --only table1_suite

echo "== serve smoke: one continuous-batching cell through the runner =="
python - <<'EOF'
from repro.runner import BenchmarkRunner, Scenario

sc = Scenario(arch="gemma-2b", task="serve", batch=4, seq=8, slots=2,
              trace="bursty")
runner = BenchmarkRunner()
rr = runner.run(sc, record=False)
print(f"  {rr.name}: {rr.status} "
      f"({rr.extra.get('tok_per_s', 0):.1f} tok/s, "
      f"ttft_p50={rr.extra.get('ttft_p50', 0):.0f}us)")
assert rr.status == "ok", rr.error
for key in ("ttft_p50", "ttft_p95", "ttft_p99", "tok_lat_p50", "tok_lat_p95",
            "tok_lat_p99", "tok_per_s", "trace", "slots", "tokens_digest"):
    assert key in rr.extra, key
assert len(rr.extra["tokens"]) == 4
print("serve smoke OK")
EOF

echo "== mixed-prompt serve + capture->replay round-trip =="
python - <<'EOF'
import json
import os
import tempfile

from repro.runner import BenchmarkRunner, Scenario

# a bimodal trace: 4 requests spanning 2 distinct prompt lengths in one
# continuous-batching replay (per-slot KV positions)
sc = Scenario(arch="gemma-2b", task="serve", batch=4, seq=8, slots=2,
              trace="bursty+bimodal")
runner = BenchmarkRunner()
rr = runner.run(sc, record=False)
assert rr.status == "ok", rr.error
cap = rr.extra["capture"]
lens = set(cap["prompt_lens"])
print(f"  {rr.name}: {rr.status} prompt_lens={sorted(lens)}")
assert len(lens) >= 2, f"want >= 2 distinct prompt lengths, got {lens}"
assert len(cap["prompt_lens"]) == 4

# round-trip: replay the captured spec via trace="file:..." and demand
# byte-identical tokens
path = os.path.join(tempfile.mkdtemp(prefix="smoke_capture_"), "cap.json")
with open(path, "w") as f:
    json.dump({"trace_spec": 1, **cap}, f)
rr2 = runner.run(Scenario(arch="gemma-2b", task="serve", batch=4, seq=8,
                          slots=2, trace=f"file:{path}"), record=False)
assert rr2.status == "ok", rr2.error
assert rr2.extra["tokens_digest"] == rr.extra["tokens_digest"], \
    (rr.extra["tokens_digest"], rr2.extra["tokens_digest"])
print(f"  capture replay digest match: {rr2.extra['tokens_digest'][:16]}")
print("capture smoke OK")
EOF

echo "== batched admission: digest equality vs single-prefill baseline =="
python - <<'EOF'
from repro.runner import BenchmarkRunner, Scenario

# a queue-forming cell (compressed bursty bimodal arrivals) replayed
# under both admission policies: batched wave prefill must generate the
# byte-identical token streams of the one-prefill-per-request baseline
runner = BenchmarkRunner()
cell = dict(arch="gemma-2b", task="loadgen", batch=6, seq=8, slots=3,
            trace="bursty+bimodal", load=8.0)
rb = runner.run(Scenario(**cell), record=False)
rs = runner.run(Scenario(**cell, admission="single"), record=False)
assert rb.status == "ok", rb.error
assert rs.status == "ok", rs.error
print(f"  batched: {rb.extra['admit_calls']} prefill calls "
      f"(batch max {rb.extra['admit_batch_max']}), "
      f"single: {rs.extra['admit_calls']} calls")
assert rb.extra["tokens_digest"] == rs.extra["tokens_digest"], \
    (rb.extra["tokens_digest"], rs.extra["tokens_digest"])
assert rb.extra["admit_batch_max"] >= 2, rb.extra["admit_batch_max"]
assert rb.extra["admit_calls"] < rs.extra["admit_calls"]
print(f"  admission digest match: {rb.extra['tokens_digest'][:16]}")
print("admission smoke OK")
EOF

echo "== profiled cell: measured timeline + attribution through the runner =="
python - <<'EOF'
from repro.runner import BenchmarkRunner, Scenario

runner = BenchmarkRunner(runs=2)
rr = runner.run(Scenario(arch="gemma-2b", task="train", batch=1, seq=8),
                profile=True, record=False)
assert rr.status == "ok", rr.error
fracs = {k: v for k, v in rr.extra.items() if k.startswith("prof_frac_")}
total = sum(fracs.values())
assert abs(total - 1.0) < 0.05, fracs
assert rr.extra["prof_steps"] == 2 and rr.extra["prof_flops"] > 0
print("  " + rr.name + ": " +
      " ".join(f"{k.replace('prof_frac_', '')}={v:.2f}"
               for k, v in sorted(fracs.items())) +
      f" (sum {total:.3f})")
print("profiled smoke OK")
EOF

echo "== tuning gate: 2-candidate sweep, DB hit on next trace =="
python - <<'EOF'
import os
import tempfile

tmp = tempfile.mkdtemp(prefix="smoke_tuning_")
os.environ["REPRO_TUNING_DB"] = os.path.join(tmp, "tuning_db.json")

from repro.runner import BenchmarkRunner
from repro.tuning import make_case, run_sweep
from repro.kernels.flash_attention import ops as fops
import jax
import jax.numpy as jnp

case = make_case("flash_attention", B=1, S=64, H=2, K=2, D=32)
runner = BenchmarkRunner(runs=1, warmup=0, compile_warmup=0)
summary = run_sweep([case], runner, max_candidates=2)
row = summary["cases"][0]
assert row["status"] == "ok", row
assert os.path.exists(summary["db_path"]), summary["db_path"]
print(f"  {row['case']}: winner={row['winner']} "
      f"({row['ratio']:.2f}x vs default)")

# a blocks-unspecified trace must now serve the recorded winner
served = {}
orig = fops.flash_attention_bh
def spy(*a, **kw):
    served.update({k: kw[k] for k in ("block_q", "block_k")})
    return orig(*a, **kw)
fops.flash_attention_bh = spy
ks = jax.random.split(jax.random.key(0), 3)
q = jax.random.normal(ks[0], (1, 64, 2, 32), jnp.float32)
k = jax.random.normal(ks[1], (1, 64, 2, 32), jnp.float32)
v = jax.random.normal(ks[2], (1, 64, 2, 32), jnp.float32)
fops.flash_attention(q, k, v)
fops.flash_attention_bh = orig
assert served == dict(row["winner"]), (served, row["winner"])
print("tuning smoke OK")
EOF

echo "== tuning queue drain: profile_report --drain-queue (serial) =="
python -m benchmarks.profile_report --drain-queue

echo "== trace gate: 1-cell profiled run exports a well-formed Chrome trace =="
python -m benchmarks.run --fast --only table1_suite \
    --filter '^gemma-2b/train/' --profile \
    --trace-out results/smoke_trace.json
python - <<'EOF'
import json

with open("results/smoke_trace.json") as f:
    trace = json.load(f)
events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
assert events, "no complete events in trace"
by_id = {e["args"]["span_id"]: e for e in events}
cells = [e for e in events if e["args"].get("kind") == "cell"]
assert cells, "no cell spans in trace"
for cell in cells:
    kids = [e for e in events
            if e["args"].get("parent") == cell["args"]["span_id"]
            and e["args"].get("kind") == "phase"]
    assert kids, f"cell {cell['name']} has no phase children"
    cover = sum(k["dur"] for k in kids) / cell["dur"]
    print(f"  {cell['name']}: {len(kids)} phases cover {cover:.1%}")
    assert cover >= 0.95, f"{cell['name']}: phases cover only {cover:.1%}"
print("trace gate OK")
EOF

echo "== history gate: two nightly probes -> 2-point provenance series =="
python - <<'EOF'
import os
import tempfile

from repro.core.ci import run_nightly
from repro.core.regression import MetricStore
from repro.runner import BenchmarkRunner
from repro.telemetry.history import series

store = MetricStore(os.path.join(tempfile.mkdtemp(prefix="smoke_hist_"),
                                 "metrics.json"))
probe = dict(archs=["gemma-2b"], tasks=("train",), batches=(1,), seqs=(8,),
             runs=2)
runner = BenchmarkRunner(runs=2)
run_nightly(store, update_baseline=True, runner=runner, **probe)
run_nightly(store, runner=runner, **probe)
two_point = {k: pts for k, pts in series(store).items() if len(pts) >= 2}
assert two_point, "no 2-point provenance series after two nights"
for (name, prov), pts in sorted(two_point.items()):
    print(f"  {name} [{prov}]: {len(pts)} points")
print("history gate OK")
EOF

echo "== fleet gate: 2 supervised ticks, injected regression -> confirm + bisect =="
python scripts/fleet.py --ticks 2 --fast --results-dir results
python - <<'EOF'
import json
import re

# status heartbeat: schema-tagged, fresh per tick, counters monotonic
with open("results/fleet_status.json") as f:
    status = json.load(f)
assert status.get("fleet_status") == 1, status.keys()
ticks = status["ticks"]
assert len(ticks) == 2, [t["tick"] for t in ticks]
for t in ticks:
    assert t["ts"] > 0 and t["cells"] >= 2 and "counters" in t, t
c0, c1 = ticks[0]["counters"], ticks[1]["counters"]
for key, v0 in c0.items():
    assert c1.get(key, 0) >= v0, (key, v0, c1.get(key))
assert c1["fleet_ticks_total"] == 2, c1
assert status["open_findings"] >= 1, status["open_findings"]
print(f"  status: {len(ticks)} ticks, counters monotonic "
      f"({len(c1)} tracked), open={status['open_findings']}")

# triage report: the injected tick-2 regression was re-measured,
# confirmed, and bisected to the synthetic culprit
with open("results/fleet_report.json") as f:
    report = json.load(f)
rules = [fd["rule"] for fd in report["findings"]]
assert "regression_confirmed" in rules, rules
bisected = [fd for fd in report["findings"]
            if fd["rule"] == "regression_bisected"]
assert bisected, rules
for fd in bisected:
    assert fd["evidence"]["culprit"] == "c08", fd["evidence"]
print(f"  report: {rules.count('regression_confirmed')} confirmed, "
      f"{len(bisected)} bisected to c08")

# the stride-gated autotuner drain emptied the seeded queue
with open("results/tuning_queue.json") as f:
    queue = json.load(f)
assert queue["jobs"] == [], queue["jobs"]
assert c1.get("fleet_drained_jobs_total", 0) >= 1, c1

# Prometheus exposition parses line-by-line
sample = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? '
                    r'[-+0-9.eE]+(nan|inf)?$')
with open("results/fleet_metrics.prom") as f:
    lines = [ln for ln in f.read().splitlines() if ln]
values = {}
for ln in lines:
    if ln.startswith("#"):
        continue
    assert sample.match(ln), f"bad prometheus line: {ln!r}"
    name = ln.split("{")[0].split(" ")[0]
    values.setdefault(name, float(ln.rsplit(" ", 1)[1]))
assert values.get("fleet_cells_total", 0) > 0, values
print(f"  prometheus: {len(lines)} lines, "
      f"{len(values)} series, cells={values['fleet_cells_total']:.0f}")
print("fleet gate OK")
EOF

echo "smoke OK"
