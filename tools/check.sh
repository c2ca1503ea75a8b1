#!/usr/bin/env bash
# One-command local gate: static analysis + bytecode compile + quick tests.
# Usable as a pre-push hook or CI entrypoint:
#   ln -s ../../tools/check.sh .git/hooks/pre-push
set -euo pipefail

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO_ROOT"

echo "== graftcheck =="
python tools/graftcheck.py progen_tpu tools benchmarks \
    train.py sample.py bench.py generate_data.py

echo "== graftcheck injected-leak gate =="
# the analyzer itself is gated the way benchdiff is: a fixture with a
# page allocation that returns before releasing MUST exit 1, proving the
# resource-linearity pass still bites (not just that the repo is clean)
LEAK_DIR="$(mktemp -d)"
cat > "$LEAK_DIR/leak.py" <<'EOF'
def admit(pool, n, ok):
    pages = pool.allocate(n)
    if pages is None:
        return None
    if not ok:
        return None          # injected: early return, pages never freed
    for p in pages:
        pool.release(p)
    return n
EOF
if python tools/graftcheck.py --no-baseline --rules resource-leak \
        "$LEAK_DIR/leak.py" > /dev/null; then
    echo "graftcheck FAILED to flag an injected page leak" >&2
    rm -rf "$LEAK_DIR"
    exit 1
fi
rm -rf "$LEAK_DIR"

echo "== compileall =="
python -m compileall -q progen_tpu tools benchmarks tests train.py sample.py bench.py

echo "== quick tier-1 subset =="
# the fast, single-host slice of tier-1: analyzer suite + core numerics.
# The full tier-1 sweep (ROADMAP.md) still runs in CI.
JAX_PLATFORMS=cpu python -m pytest \
    tests/test_graftcheck.py tests/test_ops.py tests/test_loss.py \
    tests/test_decode.py tests/test_observe.py \
    -q -m 'not slow' -p no:cacheprovider

echo "== paged-serving smoke =="
# tiny paged run on CPU: page pool + ragged paged mix + paged engine end
# to end, one parseable JSON record (full comparison: benchmarks/paged.md)
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --requests 4 --rate 50 --slots 2 --chunk 4 \
    --max-new 6 --prime-min 4 --prime-max 12 \
    --paged --page-size 8

echo "== chaos-serving smoke =="
# seeded fault plan over four serving points + --verify: asserts every
# non-shed completion is token-identical to a fault-free rerun AND that
# snapshot -> restore -> replay reproduces the straight run exactly
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --requests 4 --rate 50 --slots 2 --chunk 4 \
    --max-new 6 --prime-min 4 --prime-max 12 \
    --chaos --verify --ttl 60

echo "== disagg-serving smoke =="
# disaggregated serving on CPU with --verify: asserts the disagg output
# is token-identical to the plain engine in the same process
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --requests 4 --rate 50 --slots 2 --chunk 4 \
    --max-new 6 --prime-min 4 --prime-max 12 \
    --disagg --verify

echo "== multiproc-serving smoke =="
# real 2-process disaggregated cluster (prefill worker + decode replica
# subprocesses behind the router) with --verify: asserts the cluster's
# completions are token-identical to the in-process engine AND that a
# fresh cluster replay reproduces them exactly (docs/SERVING.md §7)
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --requests 4 --rate 50 --slots 2 --chunk 4 \
    --max-new 6 --prime-min 4 --prime-max 12 \
    --serve-procs --verify

echo "== trace smoke =="
# 2-process cluster with tracing on: every process dumps its span ring,
# the driver merges them with clock-offset correction into ONE
# Perfetto-loadable trace.json, and traceview must find + summarize the
# spans (exit 0).  docs/OBSERVABILITY.md has the design.
TRACE_DIR="$(mktemp -d)"
BENCH_DIR="$(mktemp -d)"
trap 'rm -rf "$TRACE_DIR" "$BENCH_DIR"' EXIT
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --requests 4 --rate 50 --slots 2 --chunk 4 \
    --max-new 6 --prime-min 4 --prime-max 12 \
    --serve-procs --trace --trace-out "$TRACE_DIR"
python tools/traceview.py --summarize "$TRACE_DIR/trace.json"

echo "== statusz smoke =="
# real 2-process cluster with the live introspection plane on: every
# process (driver + prefill worker + decode replica) serves /healthz and
# /metricsz on a loopback port and the bench self-checks each endpoint
# mid-run — 200, parseable JSON health, strict Prometheus exposition
# (docs/OBSERVABILITY.md §statusz)
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --requests 4 --rate 50 --slots 2 --chunk 4 \
    --max-new 6 --prime-min 4 --prime-max 12 \
    --serve-procs --statusz

echo "== benchdiff regression gate =="
# compare the superstep quick-bench record against itself (must pass),
# then against a synthetically degraded copy (must FAIL nonzero) — the
# gate that catches a perf regression before it ships
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --requests 4 --rate 50 --slots 2 --chunk 4 \
    --max-new 6 --prime-min 4 --prime-max 12 \
    --out "$BENCH_DIR/base.jsonl"
python tools/benchdiff.py "$BENCH_DIR/base.jsonl" "$BENCH_DIR/base.jsonl"
python - "$BENCH_DIR" <<'EOF'
import json, sys
d = sys.argv[1]
rec = json.loads(open(f"{d}/base.jsonl").readline())
rec["tokens_per_sec"] = rec["tokens_per_sec"] * 0.2   # -80%: regression
rec["p95_latency_s"] = rec.get("p95_latency_s", 1.0) * 5 + 1.0
rec["wall_time"] = rec.get("wall_time", 0) + 1
open(f"{d}/bad.jsonl", "w").write(json.dumps(rec) + "\n")
EOF
if python tools/benchdiff.py "$BENCH_DIR/base.jsonl" "$BENCH_DIR/bad.jsonl"; then
    echo "benchdiff FAILED to flag an injected regression" >&2
    exit 1
fi

echo "== qos-overload smoke =="
# replay the committed 2x-overload trace (benchmarks/traces/) on virtual
# time with --verify: priority preemption fires, shed-oldest and
# deadline sheds are typed completions, every non-shed completion is
# token-identical to an uncontended rerun, the high class's p95 beats a
# FIFO rerun of the same trace, and no nonzero-weight tenant starves
# (docs/SERVING.md §10)
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --slots 2 --chunk 4 --max-new 6 \
    --trace-file benchmarks/traces/overload_2x.jsonl \
    --verify --out "$BENCH_DIR/qos.jsonl"
# virtual-time determinism makes the QoS fields exact: the self-diff
# must pass, and an injected fairness/priority regression must FAIL —
# the gate that catches a scheduling regression before it ships
python tools/benchdiff.py --metric serving_qos \
    "$BENCH_DIR/qos.jsonl" "$BENCH_DIR/qos.jsonl"
python - "$BENCH_DIR" <<'EOF'
import json, sys
d = sys.argv[1]
rec = json.loads(open(f"{d}/qos.jsonl").readline())
rec["qos_fairness_index"] = rec["qos_fairness_index"] * 0.5  # starved tenant
rec["hi_p95_latency_v"] = rec["hi_p95_latency_v"] * 5 + 1.0  # class inversion
rec["wall_time"] = rec.get("wall_time", 0) + 1
open(f"{d}/qos_bad.jsonl", "w").write(json.dumps(rec) + "\n")
EOF
if python tools/benchdiff.py --metric serving_qos \
        "$BENCH_DIR/qos.jsonl" "$BENCH_DIR/qos_bad.jsonl"; then
    echo "benchdiff FAILED to flag an injected QoS regression" >&2
    exit 1
fi

echo "== quant smoke =="
# int8 weights + 8-bit gate pages on the committed CPU fixture schedule
# (benchmarks/quant.jsonl uses the same seed/args), with the accuracy
# tier live: --verify fails the run if the greedy token-match rate vs
# the in-process full-precision engine drops below the 0.98 gate
# (docs/SERVING.md §12)
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --requests 6 --rate 50 --slots 3 --chunk 8 \
    --max-new 8 --prime-min 4 --prime-max 16 --seed 9 \
    --paged --page-size 8 --budget-slots 8 \
    --quantize weights+pages --verify --out "$BENCH_DIR/quant.jsonl"
# floor-gate the deterministic fields against the committed baseline:
# token_match_rate (zero band — any drop is a real accuracy regression)
# and equal_hbm_inflight (closed-form pool capacity).  Wall-clock
# throughput/latency fields get throwaway bands here: this leg runs on
# arbitrary CI hardware
python tools/benchdiff.py benchmarks/quant.jsonl "$BENCH_DIR/quant.jsonl" \
    --band tokens_per_sec=100 --band quant_decode_tok_s=100 \
    --band p50_latency_s=100 --band p95_latency_s=100 --band wall_s=100
# injected token-match regression MUST fail the gate: a quantization
# change that flips even one greedy token cannot ship silently
python - "$BENCH_DIR" <<'EOF'
import json, sys
d = sys.argv[1]
recs = [json.loads(ln) for ln in open(f"{d}/quant.jsonl")]
for rec in recs:
    if "token_match_rate" in rec:
        rec["token_match_rate"] -= 0.05   # one flipped token's worth
        rec["wall_time"] = rec.get("wall_time", 0) + 1
open(f"{d}/quant_bad.jsonl", "w").write(
    "".join(json.dumps(r) + "\n" for r in recs))
EOF
if python tools/benchdiff.py "$BENCH_DIR/quant.jsonl" \
        "$BENCH_DIR/quant_bad.jsonl" \
        --band tokens_per_sec=100 --band quant_decode_tok_s=100 \
        --band p50_latency_s=100 --band p95_latency_s=100 \
        --band wall_s=100; then
    echo "benchdiff FAILED to flag an injected token-match regression" >&2
    exit 1
fi

echo "== fleetcache smoke =="
# fleet prefix cache on a real cluster (prefill worker + 2 decode
# replicas): a Zipf popular-prompt schedule runs cache-aware vs
# cache-blind on the SAME arrivals under a tight page pool; --verify
# asserts both clusters are token-identical to the in-process engine
# (placement is a perf hint, never a correctness input); the record's
# fleet_prefix_hit_rate / ttft_p95 feed the benchdiff gate
# (docs/SERVING.md §11)
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --requests 8 --rate 50 --slots 2 --chunk 4 \
    --max-new 6 --prime-min 8 --prime-max 12 \
    --paged --page-size 4 --num-pages 24 \
    --serve-procs --replicas 2 --zipf 1.1 --zipf-pool 4 \
    --verify --out "$BENCH_DIR/fleetcache.jsonl"
# self-diff must pass; an injected cache regression (hit rate collapse
# + TTFT blowup) must FAIL — the gate that catches a routing or
# digest-plumbing regression before it ships
python tools/benchdiff.py --metric serving_fleetcache \
    "$BENCH_DIR/fleetcache.jsonl" "$BENCH_DIR/fleetcache.jsonl"
python - "$BENCH_DIR" <<'EOF'
import json, sys
d = sys.argv[1]
rec = json.loads(open(f"{d}/fleetcache.jsonl").readline())
rec["fleet_prefix_hit_rate"] = rec["fleet_prefix_hit_rate"] * 0.3  # cache miss storm
rec["ttft_p95"] = rec["ttft_p95"] * 5 + 1.0                        # first-token blowup
rec["wall_time"] = rec.get("wall_time", 0) + 1
open(f"{d}/fleetcache_bad.jsonl", "w").write(json.dumps(rec) + "\n")
EOF
if python tools/benchdiff.py --metric serving_fleetcache \
        "$BENCH_DIR/fleetcache.jsonl" "$BENCH_DIR/fleetcache_bad.jsonl"; then
    echo "benchdiff FAILED to flag an injected fleetcache regression" >&2
    exit 1
fi

echo "== elastic-serving smoke =="
# elastic control plane on a real cluster: a bursty schedule forces a
# scale-up (warm-before-routable), plus a rolling LoRA hot-swap mid-run;
# --verify asserts every non-shed completion is token-identical to the
# max-size fixed fleet and the swap window dropped zero requests
# (docs/SERVING.md §9).  fixed_small is skipped: the verify oracle is
# fixed_big, and the autoscale + swap phases are the paths under test.
JAX_PLATFORMS=cpu python benchmarks/bench_elastic.py \
    --config default --requests 8 --rate 4 --slots 2 --chunk 4 \
    --max-new 6 --prime-min 4 --prime-max 12 \
    --swap-at 2 --swap-requests 6 --skip-modes fixed_small \
    --verify --out "$BENCH_DIR/elastic.jsonl"
# self-diff on the elastic record must pass (same gate family as the
# quick-bench: shed_rate and swap_dropped are watched fields)
python tools/benchdiff.py --metric serving_elastic \
    "$BENCH_DIR/elastic.jsonl" "$BENCH_DIR/elastic.jsonl"

echo "== mesh smoke =="
# process-spanning meshes end to end (docs/TRAINING.md mesh topology,
# docs/SERVING.md §13): a REAL 2-process jax.distributed training job
# whose tensor axis spans the processes must write a cooperative
# checkpoint bit-identical to a single-process run of the same mesh
# (mesh_ckpt_parity), and a 2-process tensor-parallel decode group
# behind the real cluster must be token-identical to the in-process
# engine with zero transport CRC failures/desyncs (--smoke implies
# --verify)
JAX_PLATFORMS=cpu python benchmarks/bench_mesh.py \
    --smoke --out "$BENCH_DIR/mesh.jsonl"
# floor-gate parity against the committed full-sweep baseline: the
# zero band on mesh_ckpt_parity means ANY bit divergence fails; the
# wall-clock fields get throwaway bands (arbitrary CI hardware, and
# the smoke sweep is smaller than the committed one)
python tools/benchdiff.py benchmarks/mesh.jsonl "$BENCH_DIR/mesh.jsonl" \
    --band wall_s=100 --band tp_group_decode_tok_s=100
# injected parity break MUST fail the gate: a partitioning change that
# flips even one checkpoint bit across a process boundary cannot ship
python - "$BENCH_DIR" <<'EOF'
import json, sys
d = sys.argv[1]
recs = [json.loads(ln) for ln in open(f"{d}/mesh.jsonl")]
for rec in recs:
    if "mesh_ckpt_parity" in rec:
        rec["mesh_ckpt_parity"] = 0.0     # injected: ckpt bit divergence
        rec["wall_time"] = rec.get("wall_time", 0) + 1
open(f"{d}/mesh_bad.jsonl", "w").write(
    "".join(json.dumps(r) + "\n" for r in recs))
EOF
if python tools/benchdiff.py --metric training_mesh \
        "$BENCH_DIR/mesh.jsonl" "$BENCH_DIR/mesh_bad.jsonl"; then
    echo "benchdiff FAILED to flag an injected mesh-parity break" >&2
    exit 1
fi

echo "== scenario-mix smoke =="
# all four workload classes (generate / constrained infill / embeddings /
# multi-tenant LoRA) through ONE engine run with --verify: asserts rerun
# identity (tokens AND embedding bytes), that constrained positions never
# emit a masked token, that tenant-0 rows match a bankless engine, and
# that snapshot -> restore -> replay reproduces the run (docs/SERVING.md §8)
JAX_PLATFORMS=cpu python benchmarks/bench_serving.py \
    --config default --requests 8 --rate 50 --slots 2 --chunk 4 \
    --max-new 6 --prime-min 4 --prime-max 12 \
    --scenario-mix "generate=0.4,infill=0.2,embed=0.2,lora=0.2" \
    --lora-tenants 4 --lora-rank 4 --verify

echo "== superstep quick-bench smoke =="
# tiny-shape K-sweep on CPU: proves the fused dispatch path runs end to
# end and emits parseable JSON (full sweep: benchmarks/superstep.md)
JAX_PLATFORMS=cpu python benchmarks/bench_superstep.py \
    --steps 8 --reps 1 --ks 1,8 --batch 2

echo "== all checks passed =="
