# Test / chaos job targets.
#
#   make test         tier-1: fast deterministic suite (what the driver
#                     runs and .github/workflows/tier1.yml replicates);
#                     includes the deterministic subsets of
#                     tests/test_resilience.py and
#                     tests/test_coordination_durability.py
#   make chaos        slow probabilistic chaos job: fault injection armed
#                     on worker RPCs, heartbeats, and reconciles
#                     (tests/test_resilience.py -m slow)
#   make chaos-coord  slow coordination-durability chaos job: SIGKILL +
#                     restart of substrate members (subprocess
#                     coordinators) mid-traffic
#                     (tests/test_coordination_durability.py -m slow)
#   make chaos-replica  slow replication chaos job: kill -9 a worker
#                     subprocess mid-workload under churn, assert every
#                     in-flight and subsequent search returns the
#                     complete result set in exact parity with a
#                     single-node oracle; plus SIGKILL of the whole
#                     coordinator ensemble with the placement map
#                     intact (tests/test_replication.py -m slow)
#   make chaos-rebalance  slow elastic-data-plane chaos job: kill -9
#                     the migration SOURCE at leader.rebalance_copy and
#                     the TARGET at leader.rebalance_flip mid-drain,
#                     plus a hard leader kill mid-migration, all under
#                     a concurrent search workload asserting exact
#                     single-node-oracle merge parity on every response
#                     (tests/test_rebalance.py -m slow)
#   make chaos-overload  slow overload chaos job: 2x-overload zipfian
#                     closed loop against the admission front door with
#                     a real mid-run worker kill -9 AND a cache-
#                     invalidating upsert — shed rate rises, p99 of
#                     ADMITTED interactive queries stays bounded, every
#                     admitted result in exact single-node-oracle
#                     parity (tests/test_admission.py -m slow)
#   make chaos-autopilot  slow SLO-autopilot chaos job: step-change
#                     (1x -> 2x) zipfian closed loop with the
#                     autopilot enabled at fast cadence and a mid-run
#                     worker kill -9 — the control loop must make real
#                     adjustments, converge WITHOUT oscillation (no
#                     sign-flapping adjustments), keep admitted p99
#                     bounded, and revert exactly to static config on
#                     the kill switch (tests/test_autopilot.py -m slow)
#   make chaos-router  slow query-plane chaos job: 2x zipfian load
#                     through two stateless routers while a router AND
#                     the leader are killed -9 mid-workload — the
#                     surviving router keeps serving, every admitted
#                     read is exact single-node-oracle parity or
#                     honestly degraded (X-Scatter-Degraded), and the
#                     tier heals (tests/test_router.py -m slow)
#   make chaos-powerloss  slow whole-cluster power-loss chaos job: an
#                     upload/search workload with the DISK nemesis
#                     armed (torn writes on the document stores) while
#                     kill -9 hits EVERY node AND the coordinator at
#                     once; full restart on the same dirs must show
#                     zero acked-upload loss and exact single-node-
#                     oracle parity on every post-restart search
#                     (tests/test_storage.py -m slow)
#   make scrub        offline storage-integrity verification: every
#                     checkpoint version's manifest + the placed-docs
#                     CRC ledger (python -m tfidf_tpu scrub; exit 1 on
#                     corruption). POST /admin/scrub runs the same
#                     pass on a live node.
#   make chaos-partition  slow jepsen-style partition chaos job: a
#                     concurrent upsert/delete/search workload while
#                     the network nemesis (cluster/nemesis.py) deposes
#                     the node leader (control-plane cut, data plane
#                     intact — the split-brain fence case), splits the
#                     3-member coordinator ensemble, one-way-isolates
#                     a worker, and flaps the full mesh; after heal:
#                     exact single-node-oracle parity, zero acked-write
#                     loss, zero stale-epoch writes accepted
#                     (tests/test_partition.py -m slow)
#   make chaos-upgrade  slow zero-downtime-fleet-evolution chaos job:
#                     a rolling restart of the WHOLE fleet (workers ->
#                     router -> leader) onto a raised proto floor under
#                     live zipfian read + write load, with the version-
#                     skew nemesis stripping X-Proto-Version on one
#                     link, a partition, and an fsync-EIO storage fault
#                     mid-roll — zero acked-write loss, bounded shed,
#                     zero proto rejections for stamped clients, exact
#                     single-node-oracle parity at the end, and the
#                     upgraded fleet 426-rejects unstamped (implicit-v1)
#                     traffic (tests/test_upgrade.py -m slow)
#   make faults       list every registered fault point (chaos configs
#                     should be validated against this — see
#                     utils/faults.py)

#   make chaos-hybrid slow hybrid chaos job: zipfian hybrid/dense
#                     load with a worker's data plane killed
#                     mid-scatter — every reply exact or honestly
#                     X-Scatter-Degraded, never silently partial
#                     (tests/test_hybrid.py -m slow)
#   make chaos-tier   slow tiered-storage chaos job: the disk nemesis
#                     flips bytes in a cold spill file mid-query — the
#                     rotten spill must be quarantined, repaired from
#                     the host replica, and every search stays in
#                     exact untiered-oracle parity
#                     (tests/test_tiering.py -m slow)
#   make chaos-compute  slow compute-plane chaos job: zipfian load
#                     over a subprocess fleet while the device nemesis
#                     OOMs one worker's every dispatch (host-fallback
#                     degraded serving, honestly stamped
#                     X-Compute-Degraded), slow-wedges another, and
#                     poisons a query's rows on two replicas — every
#                     200 exact-parity-or-honestly-stamped, zero
#                     acked-write loss, the poison fingerprint
#                     quarantined (front-door 422) after exactly two
#                     distinct replica verdicts, full recovery after
#                     heal (tests/test_compute_chaos.py -m slow)

#   make trace-demo   zero-to-aha for the tracing layer: spin a small
#                     in-process cluster, kill a worker mid-request,
#                     print the rendered trace timeline showing the
#                     failed scatter.worker span and the scatter.slice
#                     failover that kept the results complete
#                     (tools/trace_demo.py)

#   make graftcheck   project-native static analysis (tools/graftcheck):
#                     lock-graph/deadlock, jit-purity, registry drift,
#                     resilience coverage, the wire-contract protocol
#                     passes (endpoint/header/status/seam drift), and
#                     the dead-symbol sweep — against the committed
#                     allowlist/baseline; new findings fail. Use
#                     `python -m tools.graftcheck --only protocol` for
#                     fast iteration on one analyzer.
#   make lockdep      the chaos/resilience/cluster suites under the
#                     runtime lockdep witness (instrumented Lock):
#                     fails on any inversion or any ordering the
#                     static lock graph cannot explain
#   make protocol-witness  the router + partition suites with the
#                     handler classes instrumented (runtime protocol
#                     witness): every observed (endpoint, method,
#                     status, headers) exchange must be explained by
#                     the static wire contract, and the core
#                     scatter/mutation surface must actually be
#                     exercised — lockdep-style mutual validation
#   make devicecheck  the device-hygiene static passes alone
#                     (tools/graftcheck/devicecheck.py): jit-cache
#                     discipline, transfer hygiene in the hot serving
#                     cone, donation audit — fast iteration target;
#                     `make graftcheck` runs them too
#   make device-witness  the engine/pipeline/tiering/hybrid suites
#                     under the runtime device witness (XLA compile
#                     events + instrumented np fetchers): every
#                     observed device->host transfer must be explained
#                     by the static devicecheck cone (named fetch/bulk
#                     stages or an allowlisted-with-reason site);
#                     vacuous runs fail (GRAFTCHECK_DEVICE_MIN)
#   make check        graftcheck + tier-1 in one shot

PYTEST_FLAGS := -q --continue-on-collection-errors -p no:cacheprovider

.PHONY: test chaos chaos-coord chaos-replica chaos-rebalance \
        chaos-overload chaos-partition chaos-autopilot chaos-router \
        chaos-powerloss chaos-upgrade chaos-hybrid chaos-tier \
        chaos-compute scrub \
        faults \
        graftcheck lockdep protocol-witness devicecheck \
        device-witness check trace-demo

test:
	JAX_PLATFORMS=cpu python -m pytest tests/ $(PYTEST_FLAGS) -m 'not slow'

graftcheck:
	python -m tools.graftcheck

# Suite choice: resilience + cluster + graftcheck cover every
# multi-lock ordering in the tree (the graftcheck suite drives a
# durable ensemble coordinator too) and are timing-stable under the
# instrumented Lock's overhead. test_coordination_durability's
# randomized-election Raft tests are NOT run instrumented — their 1s
# election margins flake under the added per-acquisition cost on
# 2-core CI runners; they still run uninstrumented in tier-1.
lockdep:
	JAX_PLATFORMS=cpu GRAFTCHECK_LOCKDEP=1 python -m pytest \
	  tests/test_resilience.py tests/test_cluster.py \
	  tests/test_replication.py tests/test_rebalance.py \
	  tests/test_admission.py tests/test_partition.py \
	  tests/test_observability.py tests/test_autopilot.py \
	  tests/test_router.py tests/test_storage.py \
	  tests/test_commit_stats.py tests/test_upgrade.py \
	  tests/test_graftcheck.py tests/test_hybrid.py \
	  tests/test_tiering.py tests/test_compute_chaos.py \
	  $(PYTEST_FLAGS) -m 'not slow'

# Suite choice: test_router drives the stateless-router tier (reads,
# proxied writes, sheds, downloads), test_partition drives the
# fence/nemesis wire surface, and test_hybrid drives the staged v3
# surface (mode/fusion fields, 2n replies, X-Search-Stages) — together
# they exercise the core scatter/mutation contract rows
# (CORE_EXERCISED in tools/graftcheck/protocol_witness.py) the
# witness requires.
protocol-witness:
	JAX_PLATFORMS=cpu GRAFTCHECK_PROTOCOL=1 python -m pytest \
	  tests/test_router.py tests/test_partition.py \
	  tests/test_graftcheck.py tests/test_hybrid.py \
	  $(PYTEST_FLAGS) -m 'not slow'

devicecheck:
	python -m tools.graftcheck --only devicecheck

# Suite choice: engine + pipeline + tiering + hybrid are the suites
# that drive the hot serving cone (searcher dispatch, pipeline
# dispatch/fetch, tiering upload ring, dense plane) — the paths whose
# transfers devicecheck reasons about statically. test_devicecheck's
# own steady-state gate additionally asserts zero post-warmup XLA
# recompiles; the suite-wide witness checks transfers only (per-test
# compile churn is expected across a suite).
device-witness:
	JAX_PLATFORMS=cpu GRAFTCHECK_DEVICE=1 GRAFTCHECK_DEVICE_MIN=1 \
	  python -m pytest \
	  tests/test_engine.py tests/test_pipeline.py \
	  tests/test_tiering.py tests/test_hybrid.py \
	  tests/test_compute_chaos.py \
	  $(PYTEST_FLAGS) -m 'not slow'

trace-demo:
	JAX_PLATFORMS=cpu python tools/trace_demo.py

check: graftcheck test

chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py $(PYTEST_FLAGS) -m slow

chaos-coord:
	JAX_PLATFORMS=cpu python -m pytest tests/test_coordination_durability.py $(PYTEST_FLAGS) -m slow

chaos-replica:
	JAX_PLATFORMS=cpu python -m pytest tests/test_replication.py $(PYTEST_FLAGS) -m slow

chaos-rebalance:
	JAX_PLATFORMS=cpu python -m pytest tests/test_rebalance.py $(PYTEST_FLAGS) -m slow

chaos-overload:
	JAX_PLATFORMS=cpu python -m pytest tests/test_admission.py $(PYTEST_FLAGS) -m slow

chaos-partition:
	JAX_PLATFORMS=cpu python -m pytest tests/test_partition.py $(PYTEST_FLAGS) -m slow

chaos-autopilot:
	JAX_PLATFORMS=cpu python -m pytest tests/test_autopilot.py $(PYTEST_FLAGS) -m slow

chaos-router:
	JAX_PLATFORMS=cpu python -m pytest tests/test_router.py $(PYTEST_FLAGS) -m slow

chaos-powerloss:
	JAX_PLATFORMS=cpu python -m pytest tests/test_storage.py $(PYTEST_FLAGS) -m slow

chaos-upgrade:
	JAX_PLATFORMS=cpu python -m pytest tests/test_upgrade.py $(PYTEST_FLAGS) -m slow

chaos-hybrid:
	JAX_PLATFORMS=cpu python -m pytest tests/test_hybrid.py $(PYTEST_FLAGS) -m slow

chaos-tier:
	JAX_PLATFORMS=cpu python -m pytest tests/test_tiering.py $(PYTEST_FLAGS) -m slow

chaos-compute:
	JAX_PLATFORMS=cpu python -m pytest tests/test_compute_chaos.py $(PYTEST_FLAGS) -m slow

scrub:
	python -m tfidf_tpu scrub

faults:
	python -m tfidf_tpu faults list
