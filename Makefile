# One-command CI for the repo.
#
#   make check        the driver's tier-1 command (`pytest -m "not slow"
#                     tests/`, one call) after the native build, lint and
#                     three fast smokes, then the shm TSAN gate
#   make check-slow   the slow tier on top (XLA-fallback kernel variants,
#                     multi-process gang bootstraps — compile-bound)
#   make check-all    both tiers + TSAN
#
# check runs every test not marked slow; the marker targets below (chaos,
# health, fleet, ...) are for iterating on one subsystem. The driver adds
# six workers: `make check PYTEST="python -m pytest -q -n 6"`.
#
# Speed is not measured here: `python3 benchmark/run.py --workload <cell>`
# on a TPU v5e, cells in BENCHMARK.json, numbers in PERF_LEDGER.jsonl.

PYTEST ?= python -m pytest -q
FAST ?= -m "not slow"

.PHONY: check check-slow check-all chaos health pipeline profile memory \
	broadcast fleet rl ingest tsan shm lint spec-smoke shard-smoke scale \
	status

# cluster health at a glance (alerts, SLO digests, node liveness) from
# the in-process health plane; DASH=host:port reads a running head
status:
	python -c "import ray_tpu; ray_tpu.status(address='$(DASH)')"

shm:
	$(MAKE) -C ray_tpu/core/_shm

# static correctness gate: compileall as the syntax check, then raylint
# (ray_tpu.tools.raylint) over ray_tpu/ + tests/ — the rule catalog is in
# README "Correctness tooling"; suppress a deliberate pattern inline with
# `# raylint: disable=<rule>` plus a justification comment
lint:
	@echo "== lint: compileall =="
	python -m compileall -q ray_tpu tests
	@echo "== lint: raylint =="
	python -m ray_tpu.tools.raylint

# fast spec-decode smoke (<30s): greedy plain-vs-spec equivalence on the
# ngram proposer — a proposer regression fails here first
spec-smoke:
	@echo "== spec-decode smoke: greedy plain-vs-spec equivalence =="
	$(PYTEST) $(FAST) tests/test_spec_decode.py \
		-k "greedy_on_equals_off and ngram"

# fast federated-control-plane smoke (<30s): 32 simulated node agents
# over 2 KV shards with a primary SIGKILL'd mid-run — zero lost requests
# and bounded failover recovery or the harness exits nonzero
scale:
	@echo "== scale smoke: 32-node federation + shard kill ride-through =="
	python -m ray_tpu.util.scale_sim --nodes 32 --duration 4 --kill-shard

# fast 3D-parallelism smoke: one sharded-stage parity run (dp=2 submesh
# under the 2-stage pipeline) plus the schedule-generator units — seconds,
# not the full pipeline matrix
shard-smoke:
	@echo "== sharding smoke: sharded-stage parity + interleave units =="
	$(PYTEST) $(FAST) tests/test_pipeline_trainer.py \
		-k "TestInterleavedSchedule or (sharded_matches_replicated and dp)"

check: shm lint spec-smoke shard-smoke scale
	@echo "== tier-1: every test not marked slow =="
	$(PYTEST) $(FAST) tests/
	$(MAKE) tsan

check-slow:
	@echo "== slow tier =="
	$(PYTEST) -m slow tests/

# fault-injection tier (head/worker SIGKILLs, partitions). The chaos tests
# are also marked slow, so check-slow runs them in CI; this target runs
# JUST them for iterating on fault-tolerance work.
chaos:
	@echo "== chaos tier =="
	$(PYTEST) -m chaos tests/

# health-plane tier (digests, alert rules, quarantine, postmortems) for
# iterating on SLO/health work
health:
	@echo "== health tier =="
	$(PYTEST) -m health tests/

# MPMD pipeline-parallel trainer tier (stage gangs, 1F1B parity, ZeRO-1,
# channel backpressure) for iterating on pipeline work
pipeline:
	@echo "== pipeline tier =="
	$(PYTEST) -m pipeline tests/

# profiling-plane tier (stack dumps, sampling profiles, goodput ledger,
# hung-worker e2e) for iterating on profiler work
profile:
	@echo "== profile tier =="
	$(PYTEST) -m profile tests/

# object-plane tier (ledger metadata, flow accounting, leak sweep,
# dead-node locate) for iterating on object observability work
memory:
	@echo "== object plane tier =="
	$(PYTEST) -m objects tests/

# collective-broadcast tier (relay trees, partial hygiene, zero-socket
# shm handoff, api.broadcast e2e) for iterating on dissemination work
broadcast:
	@echo "== broadcast tier =="
	$(PYTEST) -m broadcast tests/

# fleet actuation tier (autoscale policy convergence, kill-resume chaos,
# adapter hot-swap, remediation pipeline) for iterating on fleet work
fleet:
	@echo "== fleet tier =="
	$(PYTEST) -m fleet tests/

# online-RL tier (fleet rollouts with logprobs, staleness bounds,
# no-drain weight re-sync, loop stop hygiene) for iterating on rl/online
# work
rl:
	@echo "== online RL tier =="
	$(PYTEST) -m rl tests/

# shared ingest-service tier (prefetch lifecycle, fair-share admission,
# repeat-epoch cache economics, pool autoscale) for iterating on
# data/ingest work
ingest:
	@echo "== shared ingest tier =="
	$(PYTEST) -m ingest tests/

check-all: check check-slow

# TSAN gate on the one concurrent native component (core/_shm). The
# CrossProcess tests fork, which TSAN cannot follow — excluded by design
# (see ray_tpu/core/_shm/Makefile header).
tsan:
	$(MAKE) -C ray_tpu/core/_shm tsan
	@echo "== TSAN: shm store concurrency tests =="
	env LD_PRELOAD=$$(g++ -print-file-name=libtsan.so) \
		RAY_TPU_SHM_LIB=$(CURDIR)/ray_tpu/core/_shm/libshm_store_tsan.so \
		$(PYTEST) tests/test_shm_store.py -k "not CrossProcess"
