# Developer entry points (reference parity: the reference ships a Makefile
# driving tests and its four docker images).

.PHONY: lint test testfast chip-smoke metrics-smoke chaos-smoke store-fsck perf-smoke trace-smoke coldstart-smoke megabatch-smoke router-smoke slo-smoke quant-smoke autopilot-smoke capacity-smoke mesh-smoke telemetry-smoke qos-smoke reconcile-smoke layout-smoke incident-smoke smoke images builder-image server-image watchman-image

# invariant linter (docs/ARCHITECTURE.md §17/§21): lock discipline
# against the declared hierarchy, blocking-calls-under-hot-locks,
# guarded-state ownership (GUARDED_FIELDS only under their lock),
# wire contracts (routes / X-Gordo-* headers / smoke-asserted series
# cross-referenced producer↔consumer), fault-seam coverage, exception
# hygiene (counterless broad swallows), unbound span seams, gordo_*
# metric conventions, GORDO_* knob registry + generated README table
# sync. Pure stdlib — runs in seconds, no jax (--jobs N parallelizes,
# --format json for CI). The gate is "no NEW violations"
# (lint_baseline.json grandfathers the deliberate keeps, each with a
# reason — empty reasons expire).
lint:
	python -m gordo_components_tpu.analysis

test:
	python -m pytest tests/ -q

testfast:
	python -m pytest tests/ -q -x -m "not slow"

# needs a TPU (exit 2 without one): fleet-build -> store -> run-server ->
# second boot -> Pallas flash kernel, one process; stdout ends with a JSON
# report line and then the {"ok", "device"} result line.
# `python chip_smoke.py --rehearse` runs the same stages tiny on the CPU
chip-smoke:
	python chip_smoke.py

# end-to-end exposition check: build a throwaway model, serve it, warm it,
# scrape /metrics?format=prometheus, fail on malformed output or missing
# standard series
metrics-smoke:
	JAX_PLATFORMS=cpu python tools/scrape_metrics.py --spawn

# end-to-end resilience check: boot a fleet server with injected faults
# (one slow dispatch, one dead artifact) and assert degraded-but-alive:
# healthy 200s, 503/504 + Retry-After on the sick machines, /healthz
# degraded naming them, gordo_resilience_* series in the exposition
chaos-smoke:
	JAX_PLATFORMS=cpu python tools/chaos_smoke.py

# end-to-end model-store integrity check: build a throwaway models tree
# with a torn CURRENT generation, an unrecoverable machine, and crash
# debris; assert fsck detects everything, repairs via rollback +
# quarantine, and sweeps the debris (tools/store_fsck.py --selftest)
store-fsck:
	JAX_PLATFORMS=cpu python tools/store_fsck.py --selftest

# serving data-plane check: two-format (npz/JSON) parity, pipelined-vs-
# serial dispatch bit-identity, and a short saturation sweep that must
# not collapse under concurrency (CPU backend; no absolute-RPS gates)
perf-smoke:
	JAX_PLATFORMS=cpu python tools/perf_smoke.py

# span-timeline attribution check: drive a request through a
# fault-injected 200ms dispatch delay and assert the flight recorder
# shows the delay in the dispatch stage, the Chrome trace export is
# Perfetto-valid JSON, `gordo trace dump` works, exemplars link
# histograms to the trace, and watchman surfaces the slow request
trace-smoke:
	JAX_PLATFORMS=cpu python tools/trace_smoke.py

# persistent-compile-cache check: a warm boot pays zero fresh XLA
# compiles (load-not-compile), /reload and rollback adopt generations
# recompile-free, and corrupt/stale/torn cache entries fall back to JIT
# with bit-identical scores
coldstart-smoke:
	JAX_PLATFORMS=cpu python tools/coldstart_smoke.py

# cross-machine megabatching check: the fused stacked program is
# bit-identical to the per-machine path at matched batches, 12 threads
# spread over 8 machines fuse into fewer device dispatches than requests
# (fusion ratio > 1.5), and shard mode falls back cleanly
megabatch-smoke:
	JAX_PLATFORMS=cpu python tools/megabatch_smoke.py

# horizontal serving tier check: 3 real worker processes behind the
# router — consistent-hash placement (X-Gordo-Worker echo), SIGKILL one
# worker mid-traffic (re-route, no 5xx burst beyond the breaker budget,
# eject + respawn), graceful SIGTERM drain (zero dropped requests), and
# a canary → sweep generation rollout plus fleet rollback paying zero
# fresh XLA compiles via the shared compile-cache store
router-smoke:
	JAX_PLATFORMS=cpu python tools/router_smoke.py

# fleet observability check: 2 real worker processes behind the router —
# a routed request renders ONE merged two-lane Perfetto trace (router +
# placed worker, clock-aligned, pull fallback for truncated stitches),
# the aggregate scrape parses with worker labels + merged buckets, and
# injected dispatch latency trips the fast-window burn-rate crossing
# (quiet without faults)
slo-smoke:
	JAX_PLATFORMS=cpu python tools/slo_smoke.py

# precision-ladder check (§19): a mixed f32/bf16/int8 fleet scores
# within each rung's declared parity budget of the all-f32 reference
# (f32 bit-identical; threshold-flip drift reported), the fused
# megabatch path never mixes dtypes, a warm boot of the quantized fleet
# pays zero fresh XLA compiles, and --precision pins survive the
# build → manifest → /healthz round trip
quant-smoke:
	JAX_PLATFORMS=cpu python tools/quant_smoke.py

# closed-loop autopilot check (§20): scripted-signal convergence under
# a step load change (bounded ticks, ≤1 direction flip per window —
# the oscillation guard), injected dispatch latency driving a journaled
# downscale on a real server (flight-recorder event + gordo_autopilot_*
# series + runtime kill switch), and the elastic tier retiring a worker
# on sustained idle (drain-before-retire, ZERO dropped requests) and
# spawning one on sustained burn, with /autopilot ↔ CLI parity
autopilot-smoke:
	JAX_PLATFORMS=cpu python tools/autopilot_smoke.py

# fleet-scale hot-path check (§22): a 2k-machine synthetic fleet —
# FLEET_INDEX lazy boot ≥5x faster than the full scan, the host-RAM
# spill tier serving a demoted machine ≥3x faster than the store path,
# placement candidate lookups in the microsecond regime at a 64-worker
# ring (incremental join beats full rebuild), production-shaped load
# through 2 lazy workers at zero failures / zero SLO breaches, and the
# Prometheus exposition size-bounded (top-K + `other` machine labels)
# at any fleet size. GORDO_CAPACITY_MACHINES/SECONDS resize; the 10k+
# sweep is `tools/capacity_harness.py full` and the `slow` test
capacity-smoke:
	JAX_PLATFORMS=cpu python tools/capacity_smoke.py

# multi-host mesh serving check (§23): a 6-machine fleet sharded across
# a 2-process serving mesh — layout-routed scoring byte-identical (f32)
# to the single-host reference, SIGKILL of one shard host degrading to
# the surviving shard's spill fallback rung with ZERO client-visible
# errors, and a warm re-boot of the same layout paying ZERO fresh XLA
# compiles through the shared compile-cache store
mesh-smoke:
	JAX_PLATFORMS=cpu python tools/mesh_smoke.py

# telemetry warehouse check (§24): Zipf load through 2 shard workers —
# the merged /telemetry traffic sketch ranks machines exactly as the
# load generator sent them, the measured-cost ledger reports nonzero
# device bytes per precision rung and nonzero host-tier bytes, the
# ?view=export layout-input document schema-validates and reproduces
# the Zipf head, and a paired noise-floored gate holds the accounting
# overhead <= 3% of request throughput
telemetry-smoke:
	JAX_PLATFORMS=cpu python tools/telemetry_smoke.py

# multi-tenant QoS check (§25): the three-principal mix (premium
# interactive + saturating bulk + over-quota abuser) through 2 router
# workers against a small admission gate — premium p99 holds with ZERO
# sheds while the bulk tenant saturates at 12 threads and is actually
# shed, quota exhaustion answers 429 + Retry-After (never an
# overload-shaped 503), and scores stay byte-identical bare vs
# tenant-stamped vs the forced-bulk endpoint
qos-smoke:
	JAX_PLATFORMS=cpu python tools/qos_smoke.py

# declarative fleet reconciler check (§26): a 6-machine tier with three
# seeded divergences — SIGKILLed worker, stale CURRENT pointer, machine
# declared bf16 while built f32 — self-heals to the journaled spec
# through the real seams (respawn / pin / precision rebuild /
# canary→sweep reload) with ZERO client-visible errors under trickle
# traffic; then two mid-sweep kill drills assert the WAL's exactly-once
# contract (crashed step re-executes, landed-but-unmarked step resumes
# without re-running)
reconcile-smoke:
	JAX_PLATFORMS=cpu python tools/reconcile_smoke.py

# fleet layout compiler check (§27): a skewed-Zipf 48-machine fleet
# through the real 2-worker router tier — the live telemetry export
# compiles into a deterministic plan whose cost block beats the uniform
# name-hash baseline, the plan applied live through the journaled spec
# at ZERO client-visible errors and ZERO fresh XLA compiles for
# rung-unchanged machines, the re-run Zipf schedule lands a lower
# measured p99 than name-hash, the parity-budgeted variant projects
# more machines-per-GiB, and /fleet/rollback converges the plan away
# cleanly. GORDO_LAYOUT_SMOKE_MACHINES/SECONDS resize
layout-smoke:
	JAX_PLATFORMS=cpu python tools/layout_smoke.py

# fleet black box check (§28): kill -9 a ledger writer mid-append and
# assert the reload contract (torn tail truncated, contiguous seq
# prefix, zero pre-tail loss); then the full 2-worker tier with an
# activated GORDO_FAULTS dispatch stall AND a planted innocent
# autopilot downscale — within 3 scrape ticks a DURABLE incident
# report's TOP ranked candidate names the injected fault seam; every
# control loop's ledger events schema-validate in the same run.
# GORDO_INCIDENT_SMOKE_MACHINES/SECONDS resize
incident-smoke:
	JAX_PLATFORMS=cpu python tools/incident_smoke.py

# the full smoke battery: invariant lint + exposition + resilience +
# store integrity + serving data plane + span attribution + cold-start
# economics + cross-machine megabatching + the horizontal serving tier
# + the fleet observability plane (stitching / aggregation / SLO)
# + the precision ladder (parity budgets / dtype routing / warm boots)
# + the closed-loop autopilot (convergence / journal / elastic tier)
# + the fleet-scale hot paths (index boot / spill tier / placement /
#   bounded scrape)
# + multi-host mesh serving (layout routing / fallback rung / warm boots)
# + the telemetry warehouse (traffic top-K / cost ledger / export /
#   accounting overhead)
# + multi-tenant QoS (quotas / priority classes / class-ordered sheds)
# + the declarative fleet reconciler (journaled specs / self-healing
#   convergence / WAL exactly-once disaster drills)
# + the fleet layout compiler (measured-cost plans / zero-compile live
#   apply / p99 + density gates / rollback)
# + the fleet black box (crash-safe control ledger / incident
#   root-cause attribution)
smoke: lint metrics-smoke chaos-smoke store-fsck perf-smoke trace-smoke coldstart-smoke megabatch-smoke router-smoke slo-smoke quant-smoke autopilot-smoke capacity-smoke mesh-smoke telemetry-smoke qos-smoke reconcile-smoke layout-smoke incident-smoke

images: builder-image server-image watchman-image

builder-image:
	docker build -t gordo-tpu-builder --build-arg ROLE=builder -f Dockerfile .

server-image:
	docker build -t gordo-tpu-server --build-arg ROLE=server -f Dockerfile .

watchman-image:
	docker build -t gordo-tpu-watchman --build-arg ROLE=watchman -f Dockerfile .
