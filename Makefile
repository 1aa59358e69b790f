# zkp2p_tpu build/verification entry points.
#
# Tests run on the CPU backend (tests/conftest.py: JAX_PLATFORMS=cpu, 8
# virtual devices, interpret-mode kernel differentials).  The chip is
# reached by `make chip-smoke` — one process per chip.

.PHONY: native native-asan native-tsan lint circuit-audit test test-slow metrics-smoke precomp-smoke precomp-cache chaos-smoke loadgen-smoke nonmsm-smoke prove-floor-smoke fleet-smoke fleet-obs-smoke fleet-chaos sched-smoke tune-smoke tpu-shard-smoke warm-cache doctor chip-smoke rehearsal-dryrun fullsize-proof

native:
	$(MAKE) -C csrc

# Static invariant checks (tier-1 resident; docs/STATIC_ANALYSIS.md):
# knob/gate discipline, csrc StatSlot vs STATS_FIELDS ABI drift, metric
# naming, spool durability, clock rules, and the pyflakes-tier baseline
# (an installed ruff is grafted on automatically).  Pure AST — runs in
# seconds with NO native build, NO jax import; exits nonzero on any
# finding.  This is the pre-commit gate: run it before every push.
lint:
	python -m tools.lint

# Circuit soundness audit — the registry admission gate (tier-1 resident
# via tests/test_circuit_audit.py; docs/STATIC_ANALYSIS.md §circuit
# audit): build every registered circuit and run the static R1CS
# auditor — unconstrained wires, the determinism fixpoint, bool/width
# demands, dead/duplicate rows, hook coverage, public-layout parity.
# Jax-free like `make lint` (gadgets/models need only numpy); reports
# cached under .bench_cache keyed by structural circuit digest, so an
# unchanged tree re-audits in seconds.  The 4.9M-wire flagship audit
# runs under the slow tier (ZKP2P_RUN_SLOW=1 pytest
# tests/test_circuit_audit.py -k flagship).
circuit-audit:
	python -m tools.lint --circuits

# Sanitizer smoke: build the ASan+UBSan library and run the MSM parity
# check against it (tests/test_native_asan.py LD_PRELOADs libasan into a
# python subprocess — the interpreter itself is uninstrumented).  Green
# means the batch-affine fill / batch-inversion buffers ran clean.
native-asan:
	$(MAKE) -C csrc libzkp2p_native_asan.so
	ZKP2P_RUN_SLOW=1 python -m pytest tests/test_native_asan.py -q

# Race-detector smoke (slow tier; mirrors the native-asan layout): build
# the TSan-instrumented library and drive the native CONCURRENCY surface
# — the WorkPool MPMC queue from two submitter threads, the
# relaxed-atomics stats block under a concurrent reader, pool-parallel
# NTT stages, segmented matvec and the multi-column MSM at threads=2 —
# with parity asserts against the host oracle.  Suppressions:
# csrc/tsan.supp (currently empty; policy in docs/STATIC_ANALYSIS.md).
# First green run caught a real race: the ifma_enabled plain-int cache.
native-tsan:
	$(MAKE) -C csrc libzkp2p_native_tsan.so
	ZKP2P_RUN_SLOW=1 python -m pytest tests/test_native_tsan.py -q

# Observability smoke (fast; also a tier-1 resident): a tiny prove with
# the JSONL sink + Prometheus endpoint enabled must yield nonzero native
# MSM fill/suffix + pool counters, request records carrying
# run_id/request_id/knob manifest, and a trace_report table that parses.
# See docs/OBSERVABILITY.md.
metrics-smoke: native
	python -m pytest tests/test_metrics_smoke.py -q

# Fixed-base precomputed-table smoke (fast; tier-1 resident): build ->
# persist -> reload -> identical proof on a tiny key, plus stale-cache
# rejection — the cheap proof that the precomp cache layer works before
# a cold service start spends minutes building bench-shape tables.
precomp-smoke: native
	python -m pytest \
	  tests/test_msm_precomp.py -q -k "cache or stale or partial"

# Pre-build the fixed-base tables for the bench-shape venmo key into
# .bench_cache/ (same spirit as the .jax_cache pre-warm): ~50 s per G1
# family cold, a no-op warm — run it before a bench so the first prove
# loads tables instead of building them.
precomp-cache: native
	JAX_PLATFORMS=cpu python -c "\
	import bench; \
	from zkp2p_tpu.prover.precomp import precomputed_for, precomp_manifest; \
	cs, lay, make_input = bench._build_venmo(); \
	dpk, vk = bench.build_keys(cs); \
	pk = precomputed_for(dpk); \
	import json; print(json.dumps(precomp_manifest(), indent=1))"

# Chaos smoke (fast; tier-1 resident): 2 subprocess workers on one
# spool, 1 SIGKILL landed mid-prove (victim chosen by reading the pid
# out of a live .claim file), faults injected at 4 sites — then the
# global invariant is asserted: every request in exactly one terminal
# state, every proof pairing-verifies, no duplicate terminal records.
# See docs/ROBUSTNESS.md §chaos harness; ~25 s on the 2-core box.
chaos-smoke: native
	python -m pytest tests/test_chaos.py -q

# Load-generator smoke (fast; tier-1 resident): a 2-second open-loop
# Poisson burst against the stub-speed toy prover on a temp spool —
# the capacity JSON must parse with scored ramp steps, /status must
# scrape 200 mid-run, and trace_report must render the sink's request
# waterfalls (Chrome-trace export) + time-series lines.  The real
# measurement is `python tools/loadgen.py --circuit venmo` — see
# docs/OBSERVABILITY.md §loadgen; ~20 s on the 2-core box.
loadgen-smoke: native
	python -m pytest tests/test_loadgen.py -q

# Fleet smoke (tier-1 resident): the supervised-fleet machinery end to
# end — drain semantics (SIGTERM mid-batch: in-flight -> done, no new
# claims, heartbeat keeps held claims out of peer-takeover range, exit
# codes split clean drain from escalation), supervisor restart/backoff/
# circuit-breaker/governor, a 2-worker toy fleet with one SIGKILL and
# one SIGTERM drain under the PR-7 global invariant with /status
# reachable on both auto-bound metrics ports, and the flock'd
# one-cold-build-per-key contract across two processes.  The N=3
# chaos acceptance + the --fleet loadgen scaling arm are the slow tier
# (`make fleet-chaos`).  See docs/ROBUSTNESS.md §fleet; ~2 min.
fleet-smoke: native
	python -m pytest tests/test_fleet.py -q

# Fleet observability plane smoke (fast; tier-1 resident): federation
# aggregation rules (counter sum / per-worker gauge labels / histogram
# bucket-merge with mismatch refusal), merged-window SLO pinned against
# a pooled oracle, alert rules + hysteresis on synthetic time-series,
# fleet /status fail-closed, chrome-trace flow events across pids, and
# the 2-worker toy-fleet smoke: fleet /metrics + /status scrape 200,
# merged request counters equal the per-worker sums AND the proof
# artifacts, trace_report --fleet-dir renders valid JSON.  See
# docs/OBSERVABILITY.md §fleet plane; ~15 s on the 2-core box.
fleet-obs-smoke: native
	python -m pytest tests/test_fleet_obs.py -q

# Adaptive-scheduler smoke (tier-1 resident; docs/SCHEDULING.md):
# deterministic controller units (amortization model, EWMA, SLO-driven
# sizing monotone-in-load + clamped, expected-deadline-miss shed that
# never sheds a feasible request, interactive-first lanes, autoscale
# hysteresis that cannot flap on an oscillating signal), the toy-circuit
# mini-trace through the REAL service (adaptive sheds/lanes/targets vs
# the byte-for-byte static off arm, digest-distinguishable), and the
# 1->2->1 fleet autoscale demo with the PR-7 zero-lost invariant green.
# ~40 s on the 2-core box (the autoscale demo is most of it).
sched-smoke: native
	python -m pytest tests/test_sched.py -q

# Host-profile + `zkp2p-tpu tune` smoke (fast; tier-1 resident;
# docs/TUNING.md §host profiles): atomic profile round-trip, tampered /
# foreign-fingerprint rejection to the fallback arm, byte-exact
# geometry fallback parity (no profile = the hand-picked c16/q2/L8
# oracle), profile-seeded AmortModel exiting warm-up with zero observed
# batches, tuned-vs-fallback digest distinguishability, and a real
# tiny-shape budgeted sweep end to end.  ~5 s on the 1-core box.
tune-smoke: native
	python -m pytest tests/test_tune.py -q

# Sharded-TPU-arm smoke (tier-1 resident; docs/TPU.md): the pjit
# batch-axis prover on the 8-virtual-device CPU mesh — toy-circuit
# byte parity (single + batch) vs the native-loop oracle under pinned
# (r, s), per-device bucket partial sums vs the unsharded arm, mesh-spec
# parsing + fallback arming, warm-cache round-trip with the >=10x
# second-run compile-span assertion, and heterogeneous-tier routing
# units.  Rides the persistent .jax_cache (run `make warm-cache` first
# on a cold checkout); ~1 min warm on the 1-core box.
tpu-shard-smoke: native
	python -m pytest tests/test_tpu_shard.py -q

# Pre-compile the batch prover (sharded arm included) into the
# persistent .jax_cache — the XLA analog of `make precomp-cache`: a
# cold pod-MSM shard_map executable compiles for MINUTES on a 1-core
# host, a warm one loads in milliseconds.  Run before a cold
# `make tpu-shard-smoke`.
warm-cache:
	JAX_PLATFORMS=cpu \
	  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  python -m zkp2p_tpu --circuit toy warm-cache --shard 2x4 --batch 4

# The full fleet acceptance (slow): N=3 supervised workers, seeded
# faults, worker SIGKILL + worker SIGTERM drain + supervisor
# kill/restart, plus the `--fleet 2` loadgen arm proving >=1.8x
# single-worker throughput at the same SLO objective.
fleet-chaos: native
	ZKP2P_RUN_SLOW=1 python -m pytest \
	  tests/test_fleet.py -q -k "acceptance or loadgen_fleet"

# Non-MSM floor smoke (fast; tier-1 resident): segmented-matvec byte
# parity vs the scatter oracle across {threads}x{tier}, pool-NTT and
# fused-ladder parity vs the knob-off arms (incl. the 2^19 bench-shape
# domain), plan-cache round-trip with tamper rejection, and the
# shared-executor churn regression.  The isolated perf read is
# `python tools/msm_hwbench.py --ladder --n 524288` — see
# docs/TUNING.md §non-MSM; ~15 s on the 2-core box.
nonmsm-smoke: native
	python -m pytest tests/test_nonmsm.py -q

# Single-prove floor smoke (fast; tier-1 resident): the PR-20 floor
# arms — interleaved+prefetched MSM apply, radix-8 fused NTT stages,
# witness-u64-at-builder — byte-identical to the committed-old arms
# across {knob on/off} x {threads 1,2} x {single, batch S=3}, with the
# execution digest separating every gate combination, plus the
# builder-u64 zero-copy hand-off and the radix-8 kernel parity vs the
# scalar fr_ntt oracle.  The isolated perf read is
# `python tools/msm_hwbench.py --apply-prof --glv --n 524288` — see
# docs/TUNING.md §prove floor; ~40 s on the 1-core box.
prove-floor-smoke: native
	python -m pytest -q \
	  tests/test_nonmsm.py -k "radix8 or witness_u64 or prove_floor" && \
	python -m pytest -q \
	  tests/test_msm_multi.py -k "floor_arms"

# Execution-path preflight (docs/OBSERVABILITY.md §execution audit):
# initialise the backend, arm EVERY gate through its real resolver,
# print the gate→arm table + execution digest, and warn loudly on
# mis-arms (e.g. pallas forced on a CPU host).  It takes the chip on a
# TPU host, so run it alone.  Machine output:
# `python -m zkp2p_tpu doctor --json`.
doctor:
	python -m zkp2p_tpu doctor

test:
	python -m pytest tests/ -x -q
	@echo "hint: 'make lint' (static invariants, seconds) and" \
	  "'make native-asan' / 'make native-tsan' (sanitizer tiers) are separate gates"

# THREE fresh pytest processes, unlimited stack, persistent cache OFF:
# long single-process runs segfault inside XLA:CPU on the biggest
# graphs (executable.serialize()/backend_compile stacks; the flake
# concentrates in the G2 MSM compiles of the test_m* files, so they get
# their own process).
test-slow:
	bash -c 'ulimit -s unlimited; \
	  ZKP2P_RUN_SLOW=1 ZKP2P_NO_CACHE=1 python -m pytest tests/test_[a-l]*.py -q && \
	  ZKP2P_RUN_SLOW=1 ZKP2P_NO_CACHE=1 python -m pytest tests/test_m*.py -q && \
	  ZKP2P_RUN_SLOW=1 ZKP2P_NO_CACHE=1 python -m pytest tests/test_[n-z]*.py -q'

# -- the chip and its CPU stand-in --------------------------------------
# chip-smoke: the served path end to end on the TPU (kernel differential
# compiled for the chip, a venmo batch through the service, pairing +
# byte-equality checks, a second process on the same compile cache).
# Needs a TPU and fails at once without one; from a CPU sandbox run it
# through the chip tool.  rehearsal-dryrun is the virtual-device CPU
# check of the sharded dataflow.
chip-smoke:
	python chip_smoke.py

rehearsal-dryrun:
	@echo "== dryrun_multichip(8) under timeout 600 =="
	timeout 600 python -c 'import __graft_entry__ as g; g.dryrun_multichip(8)'

# Full-size flagship proof with the native C++ runtime (caches under
# .bench_cache/; artifacts in docs/fullsize_proof/).
fullsize-proof:
	JAX_PLATFORMS=cpu python tools/prove_fullsize_native.py
