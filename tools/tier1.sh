#!/usr/bin/env bash
# Tier-1 verification gate: a fast syntax sweep, then the exact ROADMAP.md
# tier-1 test command.  CI (.github/workflows/tier1.yml) and humans run the
# same script, so "tier-1 green" means one thing.
set -o pipefail

cd "$(dirname "$0")/.."

echo "== compileall gate =="
python -m compileall -q pbccs_tpu tools || exit 1

echo "== static analysis (ccs analyze: conc / jax / registry / exsafe / leases / proto) =="
# clean vs the committed baseline, <60s analyzer-runtime budget, and
# every rule still fires on its positive fixture; runtime is printed
# by the smoke itself
timeout -k 10 180 python tools/analyze_smoke.py || exit 1

echo "== ruff (style gate; import order advisory) =="
if command -v ruff >/dev/null 2>&1; then
    ruff check . || exit 1
    # import-block ordering: reported, not yet enforced (ruff.toml)
    ruff check --select I001 --exit-zero --statistics . 2>/dev/null || true
else
    echo "ruff not installed; skipping (CI installs and enforces it)"
fi

echo "== kernel smoke (dense interior + edge kernels vs the f64 dense oracle) =="
# interpret mode, fixed seed, prebaked-layout path; ~30 s budget
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/kernel_smoke.py || exit 1

echo "== observability smoke (trace schema) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/obs_smoke.py || exit 1

echo "== chaos smoke (fault injection / quarantine / watchdog) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/chaos_smoke.py || exit 1

echo "== fuzz smoke (hostile-input hardening: BAM salvage / wire armor / drain) =="
# deterministic: any finding reproduces with --seed 0 --only <CLASS>
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/fuzz_inputs.py --smoke --seed 0 || exit 1

echo "== sched smoke (device-fleet scheduler: 8-device scaling + benched-device chaos) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/sched_smoke.py || exit 1

echo "== fleet smoke (serve replicas behind ccs router: kill -9 + drain, zero lost/dup) =="
timeout -k 10 900 env JAX_PLATFORMS=cpu python tools/fleet_smoke.py || exit 1

echo "== autopilot smoke (ccs fleet supervisor: respawn, quarantine, autoscale, rolling restart) =="
timeout -k 10 900 env JAX_PLATFORMS=cpu python tools/autopilot_smoke.py || exit 1

echo "== tenant smoke (TLS fleet: auth on every edge, noisy-neighbor fairness, SLO shed) =="
timeout -k 10 900 env JAX_PLATFORMS=cpu python tools/tenant_smoke.py || exit 1

echo "== endurance smoke (scaled full-cell stream: OOM + ENOSPC + kill -9, zero loss) =="
# the scaled run itself is budgeted <= 120 s warm (the smoke prints its
# runtime); the wrapper allows cold-compile headroom
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/endurance_smoke.py || exit 1

echo "== perf smoke (ledger schema + counter determinism + perf_gate vs PERF_BASELINE) =="
# two fresh-process runs of a fixed workload: CPU-deterministic ledger
# counters must be identical, the gate must pass the clean ledger in
# counters-only mode and reject a perturbed one with a structured diff
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/perf_smoke.py || exit 1

echo "== tune smoke (ccs tune: output-change rejection, profile ship, loader ladder, attribution) =="
# one real search over a loaded band-width grid: the output-changing
# candidate must be rejected, the profile must ship + apply + stamp
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/tune_smoke.py || exit 1

echo "== tier-1 tests =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
