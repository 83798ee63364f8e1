#!/bin/bash
# Test tiers (parity: unittests/CMakeLists.txt labels + parallel_UT_rule):
#   fast    - op/autograd/layer units
#   dist    - virtual-mesh SPMD engines + multi-process launch (EXCLUSIVE)
#   native  - C++ runtime through ctypes
#   e2e     - convergence/book tests (slow)
#   --comm-selftest - 2-rank sharded-vs-replicated weight-update
#                     equivalence + comm-gauge CLI smoke (ISSUE 4)
#   --serve-selftest - serving engine end-to-end on the CPU fallback
#                      path + serve-gauge/percentile CLI smoke, request
#                      trace export, stalled-request watchdog (ISSUE 5/6),
#                      COW prefix-cache invariants + speculative-decode
#                      equivalence and hit/acceptance rendering (ISSUE 9)
#   --quant-selftest - quantization subsystem: fake-quant op numerics,
#                      int8-KV serving parity + capacity, weight-only-
#                      quantized Predictor decode, int8 comm gauge
#                      breakdown (ISSUE 7)
#   --pallas-selftest - fused Pallas primitives library: interpret-mode
#                      parity for the fused optimizer step / LayerNorm /
#                      bias+GELU / dropout+residual kernels vs jnp
#                      references, fused-vs-unfused engine equivalence,
#                      routing-counter CLI smoke (ISSUE 8)
#   --overlap-selftest - comm/compute overlap (ISSUE 10): 2-rank
#                      overlap==barrier bit-level fp32 + compressed-wire
#                      tolerance + deferred-gather memory win, chunked
#                      collectives, layer grouping, dp=1 no-op
#                      invariant, exposed/hidden comm gauge rendering
#   --cluster-selftest - disaggregated serving cluster (ISSUE 11):
#                      prefix-affinity router placement units, true
#                      2-replica subprocess cluster (token-identity +
#                      affinity > round-robin + forced-hang drain),
#                      prefill->decode page-stream bit-equivalence,
#                      mp-sharded engine equivalence, router counter
#                      rendering + cross-replica trace merge
#   --remat-selftest - activation economy (ISSUE 12): remat-policy
#                      loss bit-identity (TrainStep/hybrid/pipeline) +
#                      resolution units, sequence-parallel LayerNorm/
#                      dropout sharding == replicated on the 8-dev
#                      mesh, dropout-fused flash fwd+VJP parity vs the
#                      dense reference, activation-byte census drop,
#                      mem/pallas CLI smokes
#   --async-selftest - async step pipeline (ISSUE 13): DeviceLoader
#                      sharded prefetch + staging-ring no-aliasing,
#                      windowed-dispatch loss bit-identity on all three
#                      engines + zero-host-sync assertion, on-device LR
#                      schedule equivalence incl. mid-schedule resume,
#                      GradScaler deferred found-inf accounting,
#                      host-gap gauge rendering
#   --pp-selftest - interleaved virtual-stage pipeline schedule
#                      (ISSUE 14): round-robin chunk partition units,
#                      interleaved v2 == 1F1B bit-identity (pp2 +
#                      dp2xpp2, stash/recompute memory modes, scaler
#                      found-inf path, remat composition, sync_model
#                      cross-restore), bubble-model census + ptpu_pp_*
#                      gauge rendering, true 2-rank subprocess leg
#   --tenant-selftest - multi-tenant SLO-aware serving (ISSUE 15):
#                      priority/quota/deadline admission units over a
#                      deterministic clock, charged-preemption
#                      accounting, degradation-ladder hysteresis with
#                      stage-transition trace events, weighted prefix
#                      eviction, no-tenant token-identity, adversarial
#                      heavy+light mix, per-tenant SLO rendering
#   --ledger-selftest - step-time ledger & MFU observatory (ISSUE 16):
#                      wall decomposition reconciliation, analytic
#                      FLOPs/MFU with remat recompute factor, all-
#                      engine gauge wiring, 2-rank injected-slow-rank
#                      straggler detection, histogram percentile
#                      edges, metrics-docs registry consistency,
#                      ledger CLI
#   --serve-ledger-selftest - serving goodput ledger & decode roofline
#                      (ISSUE 17): iteration-wall decomposition with
#                      ordered clamps, goodput identity across
#                      preemption / spec rejection / degrade shed /
#                      cluster drain, trace-v4 delivered/wasted parity,
#                      HBM roofline table, zero-extra-host-sync budget,
#                      then the serve + bench-compare CLI smokes
#   --fused-selftest - fused decode windows (ISSUE 19): k-iteration
#                      scan dispatch token-identity vs serial (greedy
#                      + sampled, eos-mid-window, page boundaries,
#                      preempt/resume, budget cuts), quiescence-gate
#                      units, one-fetch-per-window sync budget,
#                      per-iteration timeline/ledger attribution,
#                      wall-clock publish cadence, trace-v5 roundtrip,
#                      mp2 sharded identity, then the serve CLI smoke
#   --kvtier-selftest - tiered KV cache (ISSUE 20): host-RAM spill
#                      tier allocator invariants (exactly-once release
#                      across tiers, COW + int8 scale siblings bit-
#                      identical over spill/resurrect, LRU subtree
#                      ordering), preempt->spill->resume token
#                      identity, fused try_reserve vs in-flight spill
#                      pins, router prefetch-hint warming a replica's
#                      host tier end-to-end, no-spill configs keeping
#                      PR-19 shapes/syncs/gauges, then the serve CLI
#                      smoke (renders the host-tier lines)
#   --alerts-selftest - telemetry time axis (ISSUE 18): history-ring
#                      sampling/wraparound + derived views on injected
#                      clocks, alert state machine fire -> sustain ->
#                      hysteretic clear with artifact/journal/gauge
#                      emissions, 2-replica federation (one scrape,
#                      replica labels, heartbeat-staleness precedes
#                      the watchdog drain), registry concurrency,
#                      zero-sync budget, then the alerts CLI smoke
set -e
cd "$(dirname "$0")/.."
TIER="${1:-all}"
case "$TIER" in
  fast)   python -m pytest tests/test_ops.py tests/test_autograd.py \
            tests/test_layers_optim.py tests/test_controlflow_dist.py \
            tests/test_profiler_trace.py tests/test_diagnostics.py \
            tests/test_numerics.py tests/test_bucketing.py \
            tests/test_fused_primitives.py tests/test_overlap.py \
            tests/test_serving.py tests/test_serving_trace.py \
            tests/test_serving_cluster.py tests/test_serving_tenants.py \
            tests/test_serving_fused.py tests/test_serving_kvtier.py \
            tests/test_remat.py \
            tests/test_async_step.py tests/test_pipeline_schedule.py \
            tests/test_ledger.py tests/test_monitor.py \
            tests/test_serving_ledger.py \
            tests/test_timeseries.py tests/test_alerts.py \
            tests/test_metrics_docs.py -q
          # observability tooling smoke: tracer -> export -> summary CLI
          python tools/trace_summary.py --selftest
          # diagnostics smoke: flight recorder -> hang/OOM reports -> CLI
          python tools/health_dump.py --selftest
          # numerics smoke: fused stats -> guard trip -> artifact render
          python tools/health_dump.py numerics --selftest
          # comm smoke: bucket gauges -> snapshot -> render
          python tools/health_dump.py comm --selftest
          # serving smoke: engine -> serve gauges -> render
          python tools/health_dump.py serve --selftest
          # cluster smoke: 2-replica router -> placement counters
          python tools/health_dump.py cluster --selftest
          # tenancy smoke: quota/priority engine -> tenant SLO table
          python tools/health_dump.py tenants --selftest
          # pallas smoke: fused primitives -> route counters -> render
          python tools/health_dump.py pallas --selftest
          # async smoke: windowed loop -> host-gap gauges -> render
          python tools/health_dump.py host --selftest
          # pipeline smoke: schedule model -> pp gauges -> render
          python tools/health_dump.py pp --selftest
          # ledger smoke: TrainStep loop -> ledger gauges -> render
          python tools/health_dump.py ledger --selftest
          # alerts smoke: history ring -> rule fire/clear -> render
          python tools/health_dump.py alerts --selftest ;;
  dist)   python -m pytest tests/test_distributed.py \
            tests/test_launch_elastic.py tests/test_bert_zero_asp.py -q ;;
  native) python -m pytest tests/test_native.py tests/test_ps.py -q ;;
  e2e)    python -m pytest tests/test_e2e_train.py tests/test_static.py \
            tests/test_checkpoint_book.py tests/test_inference_dy2static.py -q ;;
  --comm-selftest)
          # true 2-rank mesh: bucketed sharded update must be
          # bit-identical (fp32) to the replicated one, bf16 wire within
          # tolerance (docs/performance.md)
          python tests/dist_models/dist_bucket_equiv.py
          python tools/health_dump.py comm --selftest ;;
  --quant-selftest)
          # dormant-op numerics (STE grads vs finite differences,
          # moving-average scale state, int8 round-trip), the int8
          # KV-pool + weight-only-quantized decode paths, and the
          # wire-byte breakdown rendering
          python -m pytest tests/test_quantization.py -q
          python -m pytest tests/test_serving.py -q \
            -k 'int8 or quant'
          python tools/health_dump.py comm --selftest ;;
  --pallas-selftest)
          # fused-primitive parity (interpret-mode kernels vs jnp
          # references, incl. grad checks and the engine-step
          # equivalences) + routing-counter rendering
          python -m pytest tests/test_fused_primitives.py -q
          python tools/health_dump.py pallas --selftest ;;
  --overlap-selftest)
          # true 2-rank mesh: overlapped schedule bit-identical to the
          # barrier path (fp32, chunked too), compressed wires within
          # tolerance, deferred-gather resident-param-memory win
          # (census-measured) + the in-process overlap units and the
          # exposed/hidden comm rendering
          python tests/dist_models/dist_bucket_equiv.py --leg overlap
          python -m pytest tests/test_overlap.py -q
          python tools/health_dump.py comm --selftest ;;
  --serve-selftest)
          # serving engine end to end on the CPU fallback path (paged
          # pool + continuous batching + COW prefix caching +
          # speculative decoding + request observatory), then the CLI
          # smokes: serve gauges/percentiles incl. prefix hit-rate and
          # spec acceptance + trace export + stalled-request watchdog
          # (health_dump) and the per-request SLO table with
          # cached/spec columns from an exported trace (trace_summary)
          python -m pytest tests/test_serving.py \
            tests/test_serving_trace.py -q
          python tools/health_dump.py serve --selftest
          python tools/trace_summary.py --selftest ;;
  --cluster-selftest)
          # the disaggregated cluster end to end: router placement
          # units, 2-replica subprocess cluster with forced-hang
          # drain, page-stream equivalence, mp-sharded engine, then
          # the CLI smokes (placement-counter rendering + the
          # cross-replica serve-trace merge)
          python -m pytest tests/test_serving_cluster.py -q
          python tools/health_dump.py cluster --selftest
          python tools/trace_summary.py --selftest ;;
  --remat-selftest)
          # tuned remat + sequence-parallel activations + dropout-fused
          # flash (ISSUE 12), then the census/routing CLI smokes
          XLA_FLAGS="--xla_force_host_platform_device_count=8" \
          python -m pytest tests/test_remat.py -q
          python tools/health_dump.py mem --selftest
          python tools/health_dump.py pallas --selftest ;;
  --async-selftest)
          # the async step pipeline end to end (ISSUE 13): DeviceLoader
          # prefetch/sharding, windowed-dispatch bit-identity + the
          # zero-host-sync harness, on-device LR schedules, deferred
          # GradScaler accounting, then the host-gap CLI smoke
          XLA_FLAGS="--xla_force_host_platform_device_count=8" \
          python -m pytest tests/test_async_step.py -q
          python tools/health_dump.py host --selftest ;;
  --pp-selftest)
          # the interleaved schedule end to end (ISSUE 14): partition/
          # bubble-model units, v2==v1 bit-identity legs incl. the
          # true 2-rank subprocess leg, then the census CLI smoke
          XLA_FLAGS="--xla_force_host_platform_device_count=8" \
          python -m pytest tests/test_pipeline_schedule.py -q
          python tools/health_dump.py pp --selftest ;;
  --tenant-selftest)
          # the multi-tenant SLO scheduler end to end (ISSUE 15):
          # admission/quota/deadline units, charged preemption,
          # ladder hysteresis, weighted eviction, token-identity and
          # the adversarial mix, then the tenant SLO CLI smokes
          python -m pytest tests/test_serving_tenants.py -q
          python tools/health_dump.py tenants --selftest
          python tools/trace_summary.py --selftest ;;
  --ledger-selftest)
          # the step-time ledger end to end (ISSUE 16): decomposition
          # + FLOPs/MFU units, engine wiring, the 2-rank straggler
          # subprocess leg, percentile edges, docs-registry
          # consistency, then the ledger + bench-compare CLI smokes
          XLA_FLAGS="--xla_force_host_platform_device_count=8" \
          python -m pytest tests/test_ledger.py tests/test_monitor.py \
            tests/test_metrics_docs.py -q
          python tools/health_dump.py ledger --selftest ;;
  --serve-ledger-selftest)
          # the serving goodput ledger end to end (ISSUE 17): serve-
          # wall decomposition + goodput identity + roofline units,
          # trace-v4 pricing parity, sync-budget harness, then the
          # serve-gauge + bench-compare CLI smokes
          python -m pytest tests/test_serving_ledger.py \
            tests/test_metrics_docs.py -q
          python tools/health_dump.py serve --selftest ;;
  --fused-selftest)
          # fused decode windows end to end (ISSUE 19): token-identity
          # vs serial across every truncation edge, quiescence gate,
          # sync-budget and per-iteration observability, then the
          # serve-gauge CLI smoke (renders the fused-window line)
          python -m pytest tests/test_serving_fused.py \
            tests/test_metrics_docs.py -q
          python tools/health_dump.py serve --selftest ;;
  --kvtier-selftest)
          # tiered KV cache end to end (ISSUE 20): cross-tier
          # allocator invariants, spill/resurrect token identity,
          # in-flight pins vs fused reservations, cluster prefetch
          # hints, tierless-inertness guards, then the serve-gauge
          # CLI smoke (renders the host-tier section)
          python -m pytest tests/test_serving_kvtier.py \
            tests/test_metrics_docs.py -q
          python tools/health_dump.py serve --selftest ;;
  --alerts-selftest)
          # the telemetry time axis end to end (ISSUE 18): history-
          # ring + derived-view units, alert state-machine legs on
          # injected clocks, the 2-replica federation / forced-
          # overload / injected-hang acceptance tests, registry
          # concurrency, docs-registry consistency, then the
          # alerts CLI smoke
          python -m pytest tests/test_timeseries.py tests/test_alerts.py \
            tests/test_monitor.py tests/test_metrics_docs.py -q
          python tools/health_dump.py alerts --selftest ;;
  all)    python -m pytest tests/ -q
          python tools/trace_summary.py --selftest
          python tools/health_dump.py --selftest
          python tools/health_dump.py numerics --selftest
          python tools/health_dump.py comm --selftest
          python tools/health_dump.py serve --selftest
          python tools/health_dump.py tenants --selftest
          python tools/health_dump.py cluster --selftest
          python tools/health_dump.py pallas --selftest
          python tools/health_dump.py mem --selftest
          python tools/health_dump.py host --selftest
          python tools/health_dump.py pp --selftest
          python tools/health_dump.py ledger --selftest
          python tools/health_dump.py alerts --selftest ;;
  *) echo "usage: $0 [fast|dist|native|e2e|all|--comm-selftest|--serve-selftest|--quant-selftest|--pallas-selftest|--overlap-selftest|--cluster-selftest|--remat-selftest|--async-selftest|--pp-selftest|--tenant-selftest|--ledger-selftest|--serve-ledger-selftest|--alerts-selftest|--fused-selftest|--kvtier-selftest]"; exit 1 ;;
esac
