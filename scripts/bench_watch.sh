#!/bin/bash
# Persistent accelerator watcher: probe the backend in a short-lived child
# process (this shell never holds the chip, so bench.py can); on every
# success, run the full bench with per-phase partials written into the
# repo (BENCH_PARTIAL.json), snapshot the result to a round-stamped
# artifact, and COMMIT it.  Then re-arm, so evidence lands in git the
# moment it exists.
set -u
cd "$(dirname "$0")/.."
mkdir -p logs
PROBE_S="${PENROZ_WATCH_PROBE_S:-120}"
SLEEP_S="${PENROZ_WATCH_SLEEP_S:-60}"
RESLEEP_S="${PENROZ_WATCH_RESLEEP_S:-1800}"   # between successful re-runs
ROUND="${PENROZ_ROUND:-05}"
SNAP="BENCH_MIDROUND_r${ROUND}.json"

# Soak-run serving observability: with PENROZ_WATCH_SERVING_URL pointing at
# a live server (e.g. http://127.0.0.1:8000), poll /serving_stats/ in the
# background and append timestamped JSON lines to logs/serving_stats.jsonl —
# continuous-batching occupancy/throughput regressions become visible in
# the same artifact stream as the bench captures.
SERVING_URL="${PENROZ_WATCH_SERVING_URL:-}"
SERVING_POLL_S="${PENROZ_WATCH_SERVING_POLL_S:-60}"
if [ -n "$SERVING_URL" ]; then
  (
    while true; do
      if out=$(curl -fsS --max-time 10 "${SERVING_URL%/}/serving_stats/" \
                 2>>logs/bench_watch.log); then
        printf '{"t":"%s","serving":%s}\n' "$(date -u +%FT%TZ)" "$out" \
          >> logs/serving_stats.jsonl
      fi
      sleep "$SERVING_POLL_S"
    done
  ) &
  SERVING_POLL_PID=$!
  trap '[ -n "${SERVING_POLL_PID:-}" ] && kill "$SERVING_POLL_PID" 2>/dev/null' EXIT
  echo "$(date -u +%FT%TZ) polling ${SERVING_URL%/}/serving_stats/ every ${SERVING_POLL_S}s (pid $SERVING_POLL_PID)" >> logs/bench_watch.log
fi

attempt=0
while true; do
  if timeout "$PROBE_S" python -c \
      "import jax; d=jax.devices(); print('BACKEND_OK', d[0].device_kind, len(d), flush=True)" \
      >> logs/bench_watch.log 2>&1; then
    attempt=$((attempt + 1))
    echo "$(date -u +%FT%TZ) backend up -> running bench (attempt $attempt)" >> logs/bench_watch.log
    PENROZ_BENCH_PARTIAL=BENCH_PARTIAL.json \
      timeout 3600 python bench.py > BENCH_MIDROUND.out 2>> logs/bench_watch.log
    rc=$?
    echo "$(date -u +%FT%TZ) bench rc=$rc" >> logs/bench_watch.log
    if [ "$rc" -ne 0 ]; then
      # Even a died/timed-out run leaves per-phase metrics in the
      # partial — commit the evidence rather than waiting for a clean
      # pass that may never come.
      git add -- BENCH_PARTIAL.json >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: partial capture (rc=$rc)" \
          -- BENCH_PARTIAL.json >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) partial committed (rc=$rc)" >> logs/bench_watch.log
    fi
    # Serving-stack capture alongside the training bench: the shared-prefix
    # workload (chunked prefill + radix prefix cache) emits its own JSON
    # artifact via PENROZ_BENCH_JSON_OUT.  Opt-in (adds minutes per pass);
    # failures must not block the main capture.
    if [ "${PENROZ_WATCH_SHARED_PREFIX:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_SHARED_PREFIX_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --shared-prefix \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_SHARED_PREFIX_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: shared-prefix serving capture" \
          -- "BENCH_SHARED_PREFIX_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) shared-prefix capture committed" >> logs/bench_watch.log
    fi
    # Speculative-decoding capture (same shape as the shared-prefix hook):
    # tokens/decode-step + accept rate with spec on vs off.  Opt-in;
    # failures must not block the main capture.
    if [ "${PENROZ_WATCH_SPEC:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_SPEC_DECODE_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --speculative \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_SPEC_DECODE_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: speculative-decoding capture" \
          -- "BENCH_SPEC_DECODE_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) speculative capture committed" >> logs/bench_watch.log
    fi
    # Compiled multi-step decode capture (same shape as the shared-prefix
    # hook): single-row mean ITL + tokens/dispatch at superstep 1 vs 4 vs 8
    # with greedy parity.  Opt-in; failures must not block the main capture.
    if [ "${PENROZ_WATCH_MULTISTEP:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_MULTISTEP_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --multistep \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_MULTISTEP_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: multi-step decode capture" \
          -- "BENCH_MULTISTEP_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) multi-step capture committed" >> logs/bench_watch.log
    fi
    # SLO-tiered QoS capture (same shape as the shared-prefix hook):
    # interactive p99 TTFT under a batch flood, FIFO vs WFQ+preemption,
    # plus the tenant-quota offender/victim split.  Opt-in; failures must
    # not block the main capture.
    if [ "${PENROZ_WATCH_QOS:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_QOS_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --mixed-slo \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_QOS_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: mixed-SLO QoS capture" \
          -- "BENCH_QOS_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) mixed-SLO QoS capture committed" >> logs/bench_watch.log
    fi
    # Ragged unified-attention capture (same shape as the shared-prefix
    # hook): mixed-traffic ITL + tokens/dispatch, paged-unified vs
    # contiguous-phased, with greedy parity.  Opt-in; failures must not
    # block the main capture.
    if [ "${PENROZ_WATCH_RAGGED:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_RAGGED_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --ragged \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_RAGGED_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: ragged unified-attention capture" \
          -- "BENCH_RAGGED_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) ragged capture committed" >> logs/bench_watch.log
    fi
    # Disaggregated-prefill capture (same shape as the shared-prefix
    # hook): decode ITL + long-prompt TTFT + hand-off latency with
    # PENROZ_DISAGG_PREFILL off vs on over a 2-replica group, greedy
    # parity gated.  Opt-in; failures must not block the main capture.
    if [ "${PENROZ_WATCH_DISAGG:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_DISAGG_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --disagg \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_DISAGG_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: disaggregated-prefill capture" \
          -- "BENCH_DISAGG_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) disaggregated-prefill capture committed" >> logs/bench_watch.log
    fi
    # D2D hand-off + elastic-roles capture (same shape as the
    # shared-prefix hook): hand-off p50/p99 host vs d2d transport, plus
    # prefill-burst -> decode-burst ITL elastic vs pinned with role-flip
    # evidence.  Opt-in; failures must not block the main capture.
    if [ "${PENROZ_WATCH_D2D:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_D2D_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --disagg-elastic \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_D2D_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: d2d hand-off + elastic-roles capture" \
          -- "BENCH_D2D_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) d2d hand-off capture committed" >> logs/bench_watch.log
    fi
    # Capacity-ledger capture (same shape as the shared-prefix hook):
    # ledger on/off ITL delta + mixed-tenant /memory/ attribution under
    # PENROZ_MEMLEDGER_STRICT=1.  Opt-in; failures must not block the
    # main capture.
    if [ "${PENROZ_WATCH_MEMORY:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_MEM_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --memory \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_MEM_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: capacity-ledger capture" \
          -- "BENCH_MEM_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) capacity-ledger capture committed" >> logs/bench_watch.log
    fi
    # Replica-router capture (same shape as the shared-prefix hook):
    # goodput-vs-replicas curve under overload (shed rate, per-wave
    # goodput, prefix-affinity hit rate) with greedy parity across
    # widths.  Opt-in; failures must not block the main capture.
    if [ "${PENROZ_WATCH_REPLICAS:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_SHARD_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --replicas \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_SHARD_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: replica-router goodput capture" \
          -- "BENCH_SHARD_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) replica-router capture committed" >> logs/bench_watch.log
    fi
    # Session hibernation / KV tiering capture (same shape as the
    # shared-prefix hook): resume TTFT per tier (hbm radix hit, host blob
    # import, disk blob import) vs cold re-prefill, with greedy parity
    # across all placements and the promotion hit rate.  Opt-in; failures
    # must not block the main capture.
    if [ "${PENROZ_WATCH_SESSIONS:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_TIER_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --sessions \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_TIER_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: session-tiering resume capture" \
          -- "BENCH_TIER_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) session-tiering capture committed" >> logs/bench_watch.log
    fi
    # Crash-durability capture: journal replay ms, sessions restored
    # across a simulated kill -9, post-restart resume TTFT vs the
    # in-run warm-disk reference, and stream reconnect-gap p99.
    # Opt-in; failures must not block the main capture.
    if [ "${PENROZ_WATCH_RESTART:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_RESTART_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --restart \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_RESTART_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: restart-durability capture" \
          -- "BENCH_RESTART_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) restart-durability capture committed" >> logs/bench_watch.log
    fi
    # Multi-tenant LoRA capture (same shape as the shared-prefix hook):
    # mixed-adapter ITL/wall vs per-adapter serial groups + parity.
    # Opt-in; failures must not block the main capture.
    if [ "${PENROZ_WATCH_LORA:-0}" = "1" ]; then
      PENROZ_BENCH_JSON_OUT="$PWD/BENCH_LORA_r${ROUND}.json" \
        timeout 1800 python scripts/bench_serving.py --multi-adapter \
          >> logs/bench_watch.log 2>&1 \
        && git add -- "BENCH_LORA_r${ROUND}.json" \
          >> logs/bench_watch.log 2>&1 \
        && git commit -m "bench watcher: multi-adapter LoRA capture" \
          -- "BENCH_LORA_r${ROUND}.json" >> logs/bench_watch.log 2>&1 \
        && echo "$(date -u +%FT%TZ) multi-adapter capture committed" >> logs/bench_watch.log
    fi
    if [ "$rc" -eq 0 ]; then
      python - "$SNAP" "$attempt" <<'EOF' 2>> logs/bench_watch.log
import json, sys, time
snap, attempt = sys.argv[1], int(sys.argv[2])
with open("BENCH_PARTIAL.json") as fh:
    partial = json.load(fh)
out = {"rc": 0,
       "captured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
       "round": int(snap.split("_r")[1].split(".")[0]),
       "attempt": f"watcher run {attempt}",
       "metric": "gpt2-124M train tokens/sec/chip",
       "unit": "tokens/sec/chip"}
out.update(partial)
with open(snap, "w") as fh:
    json.dump(out, fh, indent=1)
EOF
      # Commit ONLY the bench artifacts.  `git add` first: the
      # round-stamped snapshot starts untracked and a pathspec-mode
      # commit of an untracked file fails outright.  Retry covers a
      # foreground git operation holding the lock at this instant.
      committed=0
      for _ in 1 2; do
        if git add -- "$SNAP" BENCH_PARTIAL.json BENCH_MIDROUND.out \
              >> logs/bench_watch.log 2>&1 \
            && git commit -m "bench watcher: on-chip capture (attempt $attempt, rc=0)" \
              -- "$SNAP" BENCH_PARTIAL.json BENCH_MIDROUND.out >> logs/bench_watch.log 2>&1; then
          committed=1
          break
        fi
        sleep 10
      done
      if [ "$committed" -eq 1 ]; then
        echo "$(date -u +%FT%TZ) snapshot committed -> $SNAP; re-arming in ${RESLEEP_S}s" >> logs/bench_watch.log
      else
        echo "$(date -u +%FT%TZ) COMMIT FAILED for $SNAP (left in worktree); re-arming in ${RESLEEP_S}s" >> logs/bench_watch.log
      fi
      sleep "$RESLEEP_S"
      continue
    fi
  fi
  sleep "$SLEEP_S"
done
