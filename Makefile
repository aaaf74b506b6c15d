# Developer entry points.  Targets that set JAX_PLATFORMS=cpu run on the
# host on purpose; `make smoke` needs a chip and fails without one.  The
# benchmark is BENCHMARK.json: python3 benchmarks/run.py (PERF.md).

PY ?= python

.PHONY: test lint smoke chaos chaos-query-storm chaos-device-ooo chaos-device chaos-merge chaos-store chaos-push chaos-exchange chaos-ha chaos-stream chaos-slo-burn soak docs doctor top metrics-smoke

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

# static analysis gate (docs/static_analysis.md): exit 0 clean,
# 1 = findings outside tez_tpu/tools/graftlint_baseline.json, 2 = error
lint:
	$(PY) -m tez_tpu.tools.graftlint

# the quickest proof the main path still runs on the chip (chip_smoke.py)
smoke:
	$(PY) chip_smoke.py

chaos:
	$(PY) -m tez_tpu.tools.chaos --trials 3

chaos-device-ooo:
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --device-ooo --trials 3

# failure-containment soak: hung dispatch + OOM storm + reorder, all bit-exact
chaos-device:
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --device-ooo --device-hang --device-oom-storm --trials 3

# reduce-side merge-lane containment: OOM storm on async merge dispatches,
# breaker trip + short-circuit + half-open recovery, drained output bit-exact
chaos-merge:
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --merge-storm --trials 3

# buffer-store eviction storm: wide shuffle through deliberately tiny store
# tiers forces demotion/eviction mid-merge, output bit-exact vs store-off
chaos-store:
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --store-pressure --trials 3

# push-transport kill storm: eager pushes die mid-map-wave (seeded
# shuffle.push.send faults); the pull backstop must keep the output
# bit-exact vs a fault-free pull-only baseline
chaos-push:
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --push-storm --trials 3

# AM crash survival: SIGKILL the session AM with one DAG mid-run and two
# parked in the admission queue, reattach, replay — every DAG bit-exact,
# parked losses typed, zombies fenced; plus the coded push-replica
# failover leg (store.replica.lost, zero producer re-execution)
chaos-ha:
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --am-kill --trials 3

# streaming crash survival: 3 resident streams on one session AM under
# seeded mid-window task kills, then an AM crash mid-stream with sealed
# uncommitted windows + a half-filled open spool on disk; the successor
# window-exact replays from the commit ledger — committed windows
# bit-exact vs a fault-free feed, zero duplicate commits, bounded lag
chaos-stream:
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --stream-kill --trials 3

# burn-before-breach SLO alerting: one resident stream ramping toward a
# window-p95 target; the telemetry sampler's multi-window burn evaluation
# must journal SLO_BURN_ALERT strictly before TENANT_SLO_BREACH, fsck's
# SLO ledger and the doctor's alert->breach join must both agree
chaos-slo-burn:
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --slo-burn --trials 3

# multi-tenant session soak: one resident session AM under barrier-synced
# recurring DAGs from 3 tenants, forced am.admit.shed / am.queue.delay
# faults plus seeded task faults — every accepted DAG bit-exact, shed
# submissions the only (typed) losses, store bytes tenant-attributed,
# zero epoch fences, per-tenant p95 bounded
soak:
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --tenant-storm --trials 3

# query kill storm: the whole deterministic corpus suite twice per trial
# (seed parity picks uniform vs zipf) under seeded task/fetch kills with
# the result cache on — every run bit-exact vs the numpy oracle, kills
# confirmed in the journal, round 2 must serve lineage cache hits
chaos-query-storm:
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --query-storm --trials 3

# skewed hot-key exchange with one delayed chip (mesh.exchange.delay):
# the splitter must hold the round count down and coded r2 must mask the
# straggler, output bit-exact vs the fault-free padded baseline
chaos-exchange:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 $(PY) -m tez_tpu.tools.chaos --exchange-skew --trials 3

# live terminal view of one AM's GET /doctor/live (docs/telemetry.md);
# the AM must run with tez.am.web.enabled=true (make soak does)
URL ?= http://127.0.0.1:8080
top:
	$(PY) -m tez_tpu.tools.top $(URL)

# tier-1 scrape smoke: boot an AM with the web UI on, then validate
# /metrics via the strict golden parser, /metrics.json structurally,
# and /doctor/live through graft top's renderer
metrics-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_metrics_smoke.py -q

docs:
	$(PY) -m tez_tpu.tools.gen_config_docs > docs/configuration.md

# causal auto-triage (docs/doctor.md): one flight-armed tenant-storm run,
# then the doctor's cross-plane blame waterfall over its history journals
# + flight dumps.  DOCTOR_DIR is kept so the artifacts can be re-examined
# (doctor runs on the storm session's journals; the tsbase* warmup
# baselines would otherwise dominate the straggler ranking).
DOCTOR_DIR ?= /tmp/tez-doctor
doctor:
	rm -rf $(DOCTOR_DIR) && mkdir -p $(DOCTOR_DIR)
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.chaos --tenant-storm --trials 1 --dump-flight --workdir $(DOCTOR_DIR)
	JAX_PLATFORMS=cpu $(PY) -m tez_tpu.tools.doctor $(DOCTOR_DIR)/tenantstorm0 $(DOCTOR_DIR)/flight_*.json
