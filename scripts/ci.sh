#!/usr/bin/env bash
# Tier-1 gate + serving smoke. Run from anywhere; no PYTHONPATH needed
# (pyproject.toml sets pythonpath=src for pytest; the serve smoke exports
# it for the module launch).
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tier-1: full test suite =="
python -m pytest -x -q

echo "== serving smoke: continuous batching + bitmap-compressed head =="
PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
    --sparsity 0.5 --slots 2 --requests 6 --max-len 64

echo "== bench smoke: whole-stack bitmap streaming (attn/MLP + MoE + jamba hybrid) -> BENCH_serve.json =="
PYTHONPATH=src python benchmarks/bitmap_streaming.py --smoke \
    --archs olmo-1b granite-moe-3b-a800m jamba-v0.1-52b \
    --sparsities 0.0 0.75 --slots 2 --requests 8 --max-len 32 --repeats 2 \
    --out BENCH_serve.json

echo "== spmd smoke: sharded serving on 8 fake devices (mp=4 vs mp=1 bit-identical, per-device weight stream) =="
PYTHONPATH=src python scripts/spmd_smoke.py --arch olmo-1b --mp 4

echo "== bench smoke: sharded serving cell -> BENCH_serve.json (model_parallel) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
    python benchmarks/bitmap_streaming.py --smoke \
    --archs olmo-1b granite-moe-3b-a800m --sparsities 0.75 \
    --requests 6 --max-len 32 --repeats 1 --model-parallel 4 \
    --out BENCH_serve.json

echo "== manifest coverage report (MoE expert stacks + SSM mixers packed) =="
PYTHONPATH=src python scripts/manifest_report.py \
    --archs granite-moe-3b-a800m jamba-v0.1-52b

echo "== bench smoke: paged KV cache -> BENCH_serve.json (paging) =="
PYTHONPATH=src python benchmarks/paged_serving.py --smoke \
    --page-lens 8 --slots 2 --requests 8 --max-len 128 --repeats 2 \
    --out BENCH_serve.json

echo "== bench smoke: chunked prefill -> BENCH_serve.json (prefill) =="
PYTHONPATH=src python benchmarks/prefill.py --smoke \
    --chunks 8 --slots 2 --requests 6 --max-len 64 --repeats 2 \
    --out BENCH_serve.json

echo "== bench smoke: shared-prefix COW reuse + preemption -> BENCH_serve.json (prefix_reuse) =="
PYTHONPATH=src python benchmarks/prefix_reuse.py --smoke \
    --requests 10 --max-len 64 --repeats 2 \
    --out BENCH_serve.json

echo "== chaos smoke: seeded fault injection under audit (two archs) =="
PYTHONPATH=src python scripts/chaos_smoke.py --archs olmo-1b gemma3-4b

echo "== bench smoke: overload goodput / shed rate -> BENCH_serve.json (overload) =="
PYTHONPATH=src python benchmarks/overload.py --smoke \
    --requests 16 --max-len 48 --out BENCH_serve.json

echo "== telemetry smoke: trace/events/metrics artifacts + on==off token identity =="
PYTHONPATH=src python scripts/telemetry_smoke.py --arch olmo-1b

echo "CI OK"
