# Repo-level developer entry points. The tier-1 gate is THE acceptance
# command (ROADMAP.md): the full CPU test run, collection errors
# surfaced — a PR that introduces a new collection error fails here even
# when every collected test passes.

SHELL := /bin/bash

.PHONY: tier1 quant-tests trace-tests overlap-tests doctor-tests \
	health-tests perf-tests traffic-tests hier-tests numerics-tests \
	reshard-tests analysis-tests ft-elastic-tests moe-tests \
	serve-tests decode-tests policy-tests fleet-tests request-tests \
	history-tests comm-lint

tier1:
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors \
	  -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
	  | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); \
	new_collect=$$(grep -ac 'ERROR collecting' /tmp/_t1.log || true); \
	if [ "$$new_collect" -gt 0 ]; then \
	  echo "tier1: $$new_collect collection error(s) — failing"; exit 1; \
	fi; \
	exit $$rc

# the quantized-tier suite alone (fast iteration on coll/quant work)
quant-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_quant_coll.py -q \
	  -p no:cacheprovider -p no:randomly

# the tracing + decision-audit suite alone (fast iteration on
# ompi_tpu/trace work: audit events, Chrome export, pvars, overflow)
trace-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_observability.py \
	  -q -k "trace or wire or handle" -p no:cacheprovider -p no:randomly

# the fleet flight-recorder suite: cross-rank merge, straggler doctor,
# mpisync, Prometheus exposition
doctor-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_doctor.py -q \
	  -p no:cacheprovider -p no:randomly

# the live-health suite: watchdog, desync sentinel, HTTP endpoint
health-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_health.py -q \
	  -p no:cacheprovider -p no:randomly

# the continuous-performance suite: cost model, goodput ledger, sentry
perf-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_perf.py -q \
	  -p no:cacheprovider -p no:randomly

# the topology-traffic suite: per-edge attribution, ICI/DCN plane
# ledger, hot-link sentry
traffic-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_traffic.py -q \
	  -p no:cacheprovider -p no:randomly

# the hierarchical multi-plane suite: hier/hier+quant decision arms,
# '<coll>@<plane>' rule rows, simulated-DCN classification
hier-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_hier.py -q \
	  -p no:cacheprovider -p no:randomly

# the numerics suite: probes, sentries, divergence auditor
numerics-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_numerics.py -q \
	  -p no:cacheprovider -p no:randomly

# the redistribution suite: plan compiler, executable cache, audit
reshard-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_reshard.py -q \
	  -p no:cacheprovider -p no:randomly

# the elastic fault-tolerance suite: cross-mesh reshard planner,
# peer-shadow ring, ElasticTrainer recovery loop, chaos injector
ft-elastic-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py -q \
	  -p no:cacheprovider -p no:randomly

# the token-proportional MoE suite: ragged dispatch/combine against the
# host oracle, moe_block_ep arms and conservation, hot-expert sentry
moe-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_moe_ep.py -q \
	  -p no:cacheprovider -p no:randomly

# the serving suite: paged-KV-cache accounting, prefill/decode greedy
# parity against the train forward(), convert_params, continuous-vs-
# static scheduler, decode_ag/decode_rs decision audit and conservation
serve-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q \
	  -p no:cacheprovider -p no:randomly

# the decode fast-path suite: fused collective-matmul decode program,
# speculative draft/verify windows, pad-past-native quant veto, learned
# decode arms, MoE decode parity
decode-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_decode.py -q \
	  -p no:cacheprovider -p no:randomly

# the policy-plane suite: verdict bus, statically pre-verified action
# space, fleet-consistent vote, audited observe->decide->act
policy-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_policy.py -q \
	  -p no:cacheprovider -p no:randomly

# the serving-fleet suite: KV-page migration round-trip, router,
# hot_replica sentry
fleet-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q \
	  -p no:cacheprovider -p no:randomly

# the request-plane suite: span-tree stitching, conservation, exemplar
# reservoir, SLO judge
request-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_requests.py -q \
	  -p no:cacheprovider -p no:randomly

# the history suite: the run ledger and the deterministic changepoint
# kernel
history-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_history.py -q \
	  -p no:cacheprovider -p no:randomly

# the static-analysis suite: jaxpr collective extraction, SPMD checks,
# comm-lint, DEVICE_RULES validator — plus the lint gate itself
analysis-tests: comm-lint
	env JAX_PLATFORMS=cpu python -m pytest tests/test_analysis.py -q \
	  -p no:cacheprovider -p no:randomly

# repo-invariant comm-lint (rules CL001-CL008, justified waivers only)
# plus the DEVICE_RULES grammar validator; nonzero on any unwaived
# finding — cheap enough to run on every edit
comm-lint:
	python -m ompi_tpu.analysis.lint ompi_tpu
	python -m ompi_tpu.analysis.rules DEVICE_RULES.txt

# the comm/compute overlap tier: bucketed grad sync + collective-matmul
# rings, INCLUDING the multi-device tests marked slow (excluded from
# tier-1 to keep its wall clock inside the 870 s budget)
overlap-tests:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_overlap.py \
	  tests/test_ops.py -k "CollectiveMatmul or overlap" -q \
	  -p no:cacheprovider -p no:randomly
