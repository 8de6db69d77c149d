#!/usr/bin/env bash
# One-entry-point CI gate: static analysis first (cheap, catches the
# jit-discipline regressions gsc-lint encodes), then the report selftest,
# then the tier-1 pytest command from ROADMAP.md.  A new unsuppressed
# gsc-lint finding fails the gate BEFORE any test compiles — suppress it
# in tools/gsc_lint_baseline.json (with a written reason) only when it is
# an accepted trace-time case, otherwise fix it.
#
# Usage: bash tools/ci_check.sh [--lint-only]
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== gsc-lint (rules R1-R10, baseline: tools/gsc_lint_baseline.json) =="
# the summary line carries a stale-suppression count when the baseline
# has drifted — `python tools/gsc_lint.py --prune-stale` clears it
python tools/gsc_lint.py gsc_tpu/ tools/

echo "== gsc-lint self-check (concurrency rules must catch a seeded inversion) =="
# negative control: a throwaway ABBA lock-order fixture MUST fail the
# linter — if it passes, the R6-R10 pass is wired out of the gate and
# the green lint stage above is meaningless.  Explicit rm (not a trap:
# the tier-1 EXIT trap below would override it).
SELFCHECK_DIR=$(mktemp -d /tmp/gsc_lint_selfcheck.XXXXXX)
cat > "$SELFCHECK_DIR/inversion.py" <<'PYEOF'
import threading


class Inverted:
    def __init__(self):
        self.a_lock = threading.Lock()
        self.b_lock = threading.Lock()

    def fwd(self):
        with self.a_lock:
            with self.b_lock:
                pass

    def rev(self):
        with self.b_lock:
            with self.a_lock:
                pass
PYEOF
if python tools/gsc_lint.py --no-baseline -q "$SELFCHECK_DIR/inversion.py" \
        >/dev/null 2>&1; then
    rm -rf "$SELFCHECK_DIR"
    echo "ci_check: FAIL — gsc-lint passed a seeded lock-order inversion" >&2
    exit 1
fi
rm -rf "$SELFCHECK_DIR"
echo "ci_check: self-check OK (seeded inversion rejected)"

echo "== obs_report selftest (event-schema smoke) =="
python tools/obs_report.py --selftest

if [[ "${1:-}" == "--lint-only" ]]; then
    echo "ci_check: lint-only pass OK"
    exit 0
fi

echo "== serve smoke (AOT policy serving: cold compile -> cache-hit restart) =="
# tiny checkpoint -> in-process server -> N requests twice: run 1 must
# write the compiled-policy artifacts and record p99; run 2 must hit the
# cache on every bucket (tools/serve_smoke.py asserts rc, events, hits)
env JAX_PLATFORMS=cpu python tools/serve_smoke.py

echo "== multihost smoke (pjit carving bit-equality + tp envelope) =="
# three fresh-subprocess carving legs — replicated and sharded must land
# BIT-identical final learner states over the same 8 virtual CPU
# devices, and the 1x2 tp leg (true tensor-parallel compute, psum
# partial products) must land inside the bench_diff curve-envelope
# bands vs those controls (tp never joins the digest set — banded
# acceptance IS its contract).  The tool exits nonzero on digest
# divergence, an out-of-band tp leg, a failed leg, or a wedged backend,
# with structured {"status":"failed","reason":...} rows, never a bare
# tail
env JAX_PLATFORMS=cpu python tools/dryrun_multihost.py --mesh-matrix \
    --legs "8x1:replicated,4x2:sharded,1x2:tp" --leg-timeout 420

echo "== tp smoke (tensor-parallel CLI run -> collectives in perf.json + curve gate) =="
# a tiny real-CLI train run on a 1x2 mesh with --partition-rules tp must
# rc=0 with run_start recording the tp book, perf.json carrying the
# partitioned executable's all-reduce count/bytes next to the
# carving-comparable plain capture, and the curves envelope gating
# through bench_diff (self-compare rc 0, injected regression rc 1) —
# tools/tp_smoke.py asserts all of it
env JAX_PLATFORMS=cpu python tools/tp_smoke.py

echo "== mixtopo smoke (mixed-topology batch: 2 networks, one dispatch) =="
# a tiny 2-episode train run with --topo-mix "schedule,line3" must exit 0
# with per-topology return gauges in metrics.json and per_topology_return
# on every harness_episode event (tools/mixtopo_smoke.py asserts both
# plus the run_end status and the run_start topo_mix tag)
env JAX_PLATFORMS=cpu python tools/mixtopo_smoke.py

echo "== perfobs smoke (cost ledger -> perf.json + trace export + bench_diff) =="
# a tiny train run must write a complete perf.json cost ledger (FLOPs/
# bytes/fusions/MFU for episode_step), its rotated events stream must
# export as VALID trace-event JSON, and bench_diff must self-compare
# clean while failing an injected synthetic regression
# (tools/perfobs_smoke.py asserts all three)
env JAX_PLATFORMS=cpu python tools/perfobs_smoke.py

echo "== learnobs smoke (learn ledger -> curves.json + /metrics + bench_diff gate) =="
# a tiny mixed-topology train run must write a complete curves.json
# (return/TD series + per-topology coverage of both mixture members +
# envelope summary), land learn_signal events + td/grad/topology gauges,
# scrape cleanly over the /metrics endpoint, and gate through bench_diff
# (self-compare rc 0, injected curve regression rc 1) —
# tools/learnobs_smoke.py asserts all of it
env JAX_PLATFORMS=cpu python tools/learnobs_smoke.py

echo "== serveobs smoke (request tracing + SLO engine -> slo.json + trace + gate) =="
# a tiny SPR-tier serve run with --trace-sample 1 and a deliberately low
# --slo-p99-ms must write a complete slo.json (attainment/burn/deadline-
# miss/pad-waste/decomposition), leave sampled request spans that export
# as a VALID trace with request->flush flow arrows, scrape cleanly over
# /metrics (live queue-depth probe current), and gate through bench_diff
# (self-compare rc 0, injected SLO regression rc 1) —
# tools/serveobs_smoke.py asserts all of it
env JAX_PLATFORMS=cpu python tools/serveobs_smoke.py

echo "== fleet smoke (continuous batching + hot-swap under load, 2 workers) =="
# a 2-worker SPR-tier real-CLI run with --continuous and one forced
# hot-swap must rc=0 with ZERO dropped requests, policy_version on every
# serve_flush event, per-worker queue gauges in the /metrics exposition,
# weight_swap events from both workers, and the fleet-merged slo.json
# gating through bench_diff (self-compare rc 0, injected p99 regression
# rc 1) — tools/fleet_smoke.py asserts all of it
env JAX_PLATFORMS=cpu python tools/fleet_smoke.py

echo "== scenario smoke (on-device factory + auto-curriculum, zero retraces) =="
# a tiny 3-episode factory train run (--topo-mix factory:... --no-perf)
# must rc=0 with EXACTLY one trace each for factory_sample/reset_all/
# chunk_step across the randomized scenario stream, one curriculum event
# per episode with floored weights, curriculum_weight{family=} gauges in
# metrics.json AND over a live /metrics scrape, and a SCEN-shaped row
# gating through bench_diff (self-compare rc 0, injected env-steps/s
# regression rc 1) — tools/scenario_smoke.py asserts all of it
env JAX_PLATFORMS=cpu python tools/scenario_smoke.py

echo "== async smoke (decoupled actor/learner through the real CLI) =="
# a tiny 3-episode --async run (2 replicas, 2 actors, --no-perf) must
# rc=0 with EXACTLY one trace each for rollout_episodes/reset_all/
# learn_burst/replay_ingest across every actor/learner interleaving,
# the drain-proved async_train tail (produced == ingested, zero lost),
# policy_lag/replay_lag/learner_idle_frac gauges + actor/learner phase
# histograms in metrics.json, and an ASYNC-shaped row gating through
# bench_diff (self-compare rc 0, injected env-steps/s regression rc 1)
# — tools/async_smoke.py asserts all of it.  Its second stage forces 4
# host devices in a fresh subprocess and proves the --async --mesh 4x1
# composition: ring dp-sharded over all 4 devices, ZERO collectives on
# the compiled ingest, one trace per entry point, a published version
# adopted by an actor AND a serve VersionWatcher off --hot-swap-dir,
# tp-only (1x4) refused with recarve instructions
env JAX_PLATFORMS=cpu python tools/async_smoke.py

echo "== flight smoke (series rings + async trace + black-box post-mortem) =="
# the same tiny --async run with the series recorder on must leave a
# schema-versioned series.json whose last ring points equal the final
# metrics.json gauges, an event stream that reconstructs a STRICT-
# validator-clean trace (per-actor tracks, channel residency, balanced
# publish->adopt flows), and a deliberately wedged fleet thread must
# stall BY NAME then escalate into blackbox.json; the ASYNC row's new
# policy_lag_p99/actor_idle_frac fields gate through bench_diff
# (self-compare rc 0, injected staleness blow-up rc 1) —
# tools/flight_smoke.py asserts all of it
env JAX_PLATFORMS=cpu python tools/flight_smoke.py

echo "== chaos smoke (resilience: injected faults must self-heal) =="
# two legs: a tiny CPU train run under an injected prefetcher death +
# NaN episode, then a fresh-subprocess real-CLI `train --async` run
# under actor_die@a0:1;ring_poison@2;learner_transient@3 — both must
# exit 0 with matching structured `recovery` events in events.jsonl;
# the async leg additionally proves the drain accounting (produced ==
# ingested, zero transitions lost past the quarantined block) and that
# no poisoned version was ever adopted (tools/chaos_smoke.py asserts
# all of it; `--round` banks the CHAOS_r* bench row with the mid-run
# SIGTERM + --resume auto continuation)
env JAX_PLATFORMS=cpu python tools/chaos_smoke.py

echo "== tier-1 tests (ROADMAP.md verify command) =="
# per-invocation log: concurrent ci_check runs must not interleave tees
# and corrupt each other's DOTS_PASSED tally
T1LOG=$(mktemp /tmp/ci_check_t1.XXXXXX.log)
trap 'rm -f "$T1LOG"' EXIT
# `|| rc=$?` keeps set -e from aborting at a red pytest pipeline — the
# DOTS_PASSED tally must print precisely on failing runs
rc=0
timeout -k 10 1200 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee "$T1LOG" || rc=$?
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$T1LOG" \
    | tr -cd . | wc -c)
exit $rc
