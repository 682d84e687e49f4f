# Developer entry points for the static verifier and the test suite.
#
#   make verify          analysis self-test + fast rule corpus + tier-1 tests
#   make analyze         fast rule corpus only (skips the compile-heavy hlo
#                        family) — the pre-push gate, ~1 min
#   make selftest        every seeded fixture / campaign / conformance /
#                        interleave arm must fire or run clean
#   make changed FILES="a.py b.py"
#                        run only the rule families gating the listed files
#                        (see conformance.FAMILY_MAP) — the pre-commit gate
#   make test            tier-1 pytest (not slow) as the driver runs it: xdist, six
#                        workers, --dist load, the junit file /tmp/_t1.xml
#                        (/root/TESTS_LAST_RUN.json: commands[0], cut at 1470 s)
#   make test-slow       the tests marked slow (-m slow); run it in a PR that
#                        adds a configuration or edits a reference
#   make distrib         distribution-plane gate: the distrib rule family
#                        (pinned tree campaigns + kill/delta models) plus the
#                        loopback fan-out bench arm (benchmarks/serving.py)
#   make loadgen         serve-traffic gate: the slo rule family (pinned
#                        Poisson campaigns + latency-sampler pins) plus the
#                        open-loop load bench arm (benchmarks/serving.py load)
#   make monitor         fleet-monitor gate: the monitor rule family
#                        (seeded-bug alert completeness + clean-twin
#                        false-alarm freedom + window coalescing)
#
# All targets force the CPU backend so they run on any host.

PY      ?= python
ENV     := JAX_PLATFORMS=cpu
PYTEST  := $(ENV) $(PY) -m pytest tests/ -q \
           --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
           --dist load -p no:randomly

.PHONY: verify analyze selftest changed test test-slow distrib loadgen monitor

verify: selftest analyze test

analyze:
	$(ENV) $(PY) -m bluefog_tpu.analysis --no-hlo

selftest:
	$(ENV) $(PY) -m bluefog_tpu.analysis --self-test

changed:
	@test -n "$(FILES)" || { echo "usage: make changed FILES=\"a.py b.py\""; exit 2; }
	$(ENV) $(PY) -m bluefog_tpu.analysis --changed-only $(FILES) --no-hlo

test:
	$(PYTEST) -m 'not slow' --junitxml=/tmp/_t1.xml

test-slow:
	$(PYTEST) -m slow

distrib:
	$(ENV) $(PY) -m bluefog_tpu.analysis --family distrib
	$(ENV) $(PY) benchmarks/serving.py distrib

loadgen:
	$(ENV) $(PY) -m bluefog_tpu.analysis --family slo
	$(ENV) $(PY) benchmarks/serving.py load

monitor:
	$(ENV) $(PY) -m bluefog_tpu.analysis --family monitor
