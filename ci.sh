#!/bin/sh
# CI gate (reference: Jenkinsfile stages 'verify' + 'test', build-tools
# checkstyle, githooks-plugin): refuses a dirty exit. Run before every
# end-of-round snapshot — and from .githooks/pre-commit for the fast lint.
#
#   ./ci.sh          lint + tier-1 test suite + chaos smoke + pallas parity
#   ./ci.sh fast     lint only (pre-commit speed)
#   ./ci.sh slow     tier-2 only: volume pins, randomized chaos sweeps,
#                    device-engine cluster suites (pytest -m slow)
set -e
cd "$(dirname "$0")"

echo "== zblint (project lint suite: undefined names, discarded actor"
echo "   futures, blocking calls on actors, metrics hot loops + doc drift,"
echo "   dirty-family coverage, swallowed excepts, unregistered jax.jit;"
echo "   docs/operations/lint.md) =="
python -m tools.zblint

echo "== compileall (syntax gate) =="
python -m compileall -q zeebe_tpu tests benchmarks tools __graft_entry__.py

echo "== zbaudit (IR-level audit of every registered jit entry point:"
echo "   HBM model, dtype flow, host boundary + donation, collective"
echo "   volume, recompile signatures, op census; docs/operations/iraudit.md) =="
python -m tools.zbaudit

if [ "$1" = "fast" ]; then
  echo "CI GATE (fast) GREEN"
  exit 0
fi

if [ "$1" = "slow" ]; then
  echo "== tier-2: volume pins, randomized chaos sweeps, device clusters =="
  python -m pytest tests/ -q -m "slow"
  echo "CI GATE (slow tier) GREEN"
  exit 0
fi

echo "== chaos smoke (fixed-seed fault schedule; tier-1, <60s) =="
python -m pytest tests/test_chaos.py -q -m "not slow"

echo "== exporter plane (director/compaction gating/sinks; tier-1) =="
python -m pytest tests/test_exporters.py -q -m "not slow"

echo "== JSONL exporter smoke (boot broker, run a workflow, replay audit) =="
python tools/exporter_smoke.py

echo "== state lifecycle smoke (delta takes, crash-restore, replay parity) =="
python tools/state_smoke.py

echo "== trace smoke (sample_rate=1.0: every lifecycle stage present +"
echo "   monotonic, wave timelines, trace_report round-trips valid JSON) =="
JAX_PLATFORMS=cpu python tools/trace_smoke.py

echo "== wave-scheduler smoke (skewed-traffic fill >= 2x per-partition"
echo "   baseline, per-partition logs bit-identical, overload sheds) =="
JAX_PLATFORMS=cpu python tools/scheduler_smoke.py

echo "== sharded-mesh dry run (8-device partition mesh: all_to_all"
echo "   exchange + psum aggregates, message-correlation drive) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8); print('dryrun_multichip(8) OK')"

echo "== full test suite (tier-1; run './ci.sh slow' for the slow tier) =="
python -m pytest tests/ -x -q -m "not slow" --ignore=tests/test_chaos.py --ignore=tests/test_exporters.py

# The on-chip legs need a TPU and exit non-zero without one, so they run
# only where one is attached. The probe is a child process: this shell
# never holds the chip, and each leg below is one process on it.
if python -c "import jax, sys; sys.exit(jax.default_backend() != 'tpu')" 2>/dev/null; then
  echo "== served path on the chip (chip_smoke.py: boot, parity, 2,048"
  echo "   instances through the socket client, restart) =="
  python chip_smoke.py

  echo "== pallas ops + mega-pass parity =="
  python benchmarks/pallas_ops_check.py

  echo "== autotune dispatch self-check =="
  python -m zeebe_tpu.tpu.autotune
else
  echo "== no TPU attached: chip_smoke.py, pallas parity and the autotune"
  echo "   self-check NOT run =="
fi

echo "CI GATE GREEN"
