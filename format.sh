#!/usr/bin/env bash
# Lint/format gate (reference: format.sh — yapf+flake8, diff-vs-merge-base
# or --all).  This build standardizes on flake8 only; CI runs the same
# invocation (.github/workflows/test.yaml lint job).
#
# Usage:
#   ./format.sh                  # lint files changed vs the merge-base with main
#   ./format.sh --all            # lint the whole tree
#   ./format.sh --check [--all]  # explicit non-mutating check mode for CI:
#                                # guaranteed to touch no files, exits nonzero
#                                # on findings (same lint; the flag exists so
#                                # CI stays correct if a mutating formatter is
#                                # ever added to the default path)

set -euo pipefail
cd "$(dirname "$0")"

FLAKE8_ARGS=(--max-line-length=88 --extend-ignore=E203,W503)

CHECK=0
ALL=0
for arg in "$@"; do
    case "$arg" in
        --check) CHECK=1 ;;
        --all)   ALL=1 ;;
        *) echo "usage: $0 [--check] [--all]" >&2; exit 2 ;;
    esac
done
# --check is non-mutating by construction: only checks run below.
if [[ "$CHECK" == 1 ]]; then
    # metrics-name lint: every instrument registered anywhere in the
    # package must be Prometheus-clean — rlt_ prefix + a unit suffix
    # (_bytes/_seconds/_total) — so the driver's /metrics exposition
    # never emits an unscrapable series (telemetry/metrics.py).
    # (-c entry, not -m: the telemetry package imports the module at
    # init, and runpy would re-execute it with a RuntimeWarning)
    python -c 'import sys; from ray_lightning_tpu.telemetry.metrics \
        import _main; sys.exit(_main(["--check-names"]))'
    # compile-plane selfcheck: env knobs round-trip through worker_env,
    # the cache-seeding pack/unpack round-trips, and every metric the
    # compile plane publishes is covered by the name lint above
    # (ray_lightning_tpu/compile/selfcheck.py; no jax backend touched)
    python -c 'import sys; from ray_lightning_tpu.compile.selfcheck \
        import _main; sys.exit(_main([]))'
    # comm-plane selfcheck: the compression policy resolves correctly on
    # every built-in strategy, RLT_COMM* env knobs round-trip, and the
    # compressed collectives lower without error on a small virtual CPU
    # mesh (ray_lightning_tpu/comm/selfcheck.py)
    python -c 'import sys; from ray_lightning_tpu.comm.selfcheck \
        import _main; sys.exit(_main([]))'
    # ops-plane selfcheck: decode-impl resolution precedence, the
    # flash-decode grid-skip invariant (the index-map clamp and the
    # kernel's compute guard must agree on every block), geometry
    # gating, interpreter lowering parity vs the dense einsum, and the
    # identity-page-table round-trip (ray_lightning_tpu/ops/selfcheck.py)
    python -c 'import sys; from ray_lightning_tpu.ops.selfcheck \
        import _main; sys.exit(_main([]))'
    # serve-plane selfcheck: bucket resolution + padding, scheduler
    # invariants (slot uniqueness, tenant quota, fair-share progress)
    # under a simulated multi-tenant run, serve metric names, and the
    # prefill/decode programs lowering on a CPU mesh
    # (ray_lightning_tpu/serve/selfcheck.py)
    python -c 'import sys; from ray_lightning_tpu.serve.selfcheck \
        import _main; sys.exit(_main([]))'
    # fleet-plane selfcheck: FleetConfig/PageConfig validation +
    # RLT_FLEET*/RLT_SERVE_PAGED* env round-trip, page free-list
    # accounting, prefix-hash round-trip (collision-verified), the
    # autoscaler patience/cooldown state machine, router least-loaded/
    # sticky/affinity/quota invariants, the federation directory
    # (register/lookup/invalidate round-trip, liveness expiry,
    # collision-proof routing, retained-page size bound),
    # rlt_fleet_* metric names
    # (ray_lightning_tpu/serve/fleet/selfcheck.py)
    python -c 'import sys; from ray_lightning_tpu.serve.fleet.selfcheck \
        import _main; sys.exit(_main([]))'
    # elastic-plane selfcheck: ElasticConfig validation + RLT_ELASTIC*
    # env round-trip, fault-spec parsing, elastic metric names, and the
    # residual re-bucket's injected-error invariant on a CPU array
    # (ray_lightning_tpu/elastic/selfcheck.py)
    python -c 'import sys; from ray_lightning_tpu.elastic.selfcheck \
        import _main; sys.exit(_main([]))'
    # planner-plane selfcheck: PlanConfig validation + RLT_PLAN* env
    # round-trip, enumeration coverage/pruning reasons, byte→seconds
    # score monotonicity, PlanReport schema, plan metric names
    # (ray_lightning_tpu/plan/selfcheck.py)
    python -c 'import sys; from ray_lightning_tpu.plan.selfcheck \
        import _main; sys.exit(_main([]))'
    # mpmd-plane selfcheck: schedule invariants (every microbatch F
    # before its B, 1F1B depth <= stages x virtual, the plain-1F1B
    # bubble tie + interleaved win), RLT_MPMD* env round-trip, channel
    # codec round-trip / out-of-order / dead-peer timeout, stage-cut
    # resolution, metric names (ray_lightning_tpu/mpmd/selfcheck.py)
    python -c 'import sys; from ray_lightning_tpu.mpmd.selfcheck \
        import _main; sys.exit(_main([]))'
    # trace-plane selfcheck: span-record schema, trace-context
    # round-trip (driver + worker spans reassemble one request tree),
    # flight-recorder bounded-size invariant, profile-controller state
    # machine, trace-plane + anatomy metric names, the anatomy parser
    # on the golden synthetic fixture (exposed-comm overlap math + the
    # wall = compute + exposed + host identity), and the
    # TelemetryConfig anatomy knobs round-tripping through
    # worker_env/RLT_ANATOMY* (ray_lightning_tpu/telemetry/selfcheck.py)
    python -c 'import sys; from ray_lightning_tpu.telemetry.selfcheck \
        import _main; sys.exit(_main([]))'
fi

if [[ "$ALL" == 1 ]]; then
    exec flake8 "${FLAKE8_ARGS[@]}" ray_lightning_tpu tests __graft_entry__.py
fi

MERGEBASE="$(git merge-base origin/main HEAD 2>/dev/null \
             || git merge-base main HEAD 2>/dev/null \
             || git rev-parse HEAD~1)"
FILES="$(git diff --name-only --diff-filter=ACRM "$MERGEBASE" -- '*.py')"
if [[ -z "$FILES" ]]; then
    echo "No changed python files."
    exit 0
fi
# shellcheck disable=SC2086
exec flake8 "${FLAKE8_ARGS[@]}" $FILES
