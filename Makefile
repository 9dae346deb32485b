# Test / drill entry points.  All CPU targets force JAX_PLATFORMS=cpu
# (tests/conftest.py pins it anyway; the env var keeps jax's platform
# probe from touching an attached accelerator during collection).

PYTEST := env JAX_PLATFORMS=cpu python -m pytest

.PHONY: tier1 tier1-budget faults chaos tpu chip-smoke perf-smoke kvcache obs overload lint lint-invariants elastic check

# The gating suite: everything not marked slow (the driver runs it with
# six xdist workers: add `-p xdist -n 6 --dist loadfile`).
tier1:
	$(PYTEST) tests/ -q -m 'not slow' --continue-on-collection-errors

# Tier-1 time budget report: the same gating run, ending with the 20
# slowest tests (pytest --durations; includes setup/teardown phases).
# The suite sits near its 870 s ceiling — run this before and after
# adding tier-1 tests, keep each new test to a few seconds, and push
# matrices behind @pytest.mark.slow (rebalance with in-test
# justification when a cell must move).
tier1-budget:
	$(PYTEST) tests/ -q -m 'not slow' --continue-on-collection-errors --durations=20

# Just the fault-injection / crash-recovery / degradation tests.
faults:
	$(PYTEST) tests/ -q -m faults

# Chaos smoke drill: the full fault matrix — every injection site
# (step / insert / suffix_insert / alloc and the kernel sites
# flash_kernel / paged_kernel / spec_decode, driven through
# `run.py --inject-faults`), kernel quarantine + XLA-fallback identity,
# non-finite-guard, and drain-on-signal.  Includes the slow drills that
# tier-1 excludes for time.
chaos:
	$(PYTEST) tests/ -q -m 'chaos or faults'

# Tier-1-safe perf guardrails (CPU, no accelerator needed): chunked
# decode's, chunked speculative serving's AND fused prefill-decode
# scheduling's host-boundary discipline — instrumented counter tests
# asserting <= 1 device->host sync and 0 steady-state host->device
# state uploads per fused dispatch (K decode iterations, R draft+verify
# rounds, or a prefill-carrying chunk), that decode rows keep emitting
# while a long prompt is mid-prefill (zero full-prefill stalls) with K
# un-collapsed — plus the K>1 vs K=1, spec_rounds>1 vs 1, and fused vs
# classic-admission token-identity matrices.  The KV-capacity subsystem
# owes the same discipline: ZERO decode-chunk stalls while a host-tier
# swap-in is in flight (every mid-swap dispatch keeps emitting at an
# un-collapsed K) and a radix/restored admission pays <= 1 state
# upload — the same budget as a fused admission.  Observability owes
# the strictest version: tracing is ALWAYS ON, so the same counters
# prove it adds zero device dispatches and zero extra host syncs per
# chunk (every dispatch span in the obs ring maps 1:1 onto a counted
# dispatch; the 1-fetch/0-upload steady state is unchanged).  These
# also run inside tier1; this target is the fast pre-push slice.
perf-smoke:
	$(PYTEST) tests/test_perf_smoke.py tests/test_serving_chunked.py tests/test_serving_spec.py tests/test_serving_fused.py tests/test_kvcache.py -q -m 'not slow'

# Just the KV-capacity subsystem (radix prefix index + host-DRAM tier).
kvcache:
	$(PYTEST) tests/ -q -m kvcache

# Observability layer (obs.py): request span timelines, dispatch
# spans, latency histograms, SLO accounting, Perfetto trace export,
# the /metrics registry exposition, and the /debug endpoints — the
# obs-marked suite plus the whole HTTP server suite (request-id
# plumbing and exposition live there), plus the control-plane layer
# (decision audit log, flight recorder, canary probes, health
# sentinel — tests/test_controlplane.py incl. the fleet drill).
obs:
	$(PYTEST) tests/test_obs.py tests/test_server.py tests/test_controlplane.py -q -m 'not slow'

# Overload control (overload.py): priority-class admission, the
# cost-based deadline refusal, the brownout ladder's transitions and
# hysteresis recovery, and the open-loop flood + ladder drills —
# including the slow-marked acceptance drill (Poisson mixed-class
# flood at >= 2x the sustainable rate: interactive attainment held,
# batch shed with clean 503 + Retry-After, zero hung clients, ladder
# stepped back to normal afterwards) that tier-1 excludes for time.
overload:
	$(PYTEST) tests/test_overload.py -q

# Elastic fleet (FleetController): autoscaler hysteresis, drain-by-
# migration (zero dropped sessions, token-identical), zero-downtime
# rollouts with the per-rung canary gate, and the scale_event /
# session_migrate chaos drills.
elastic:
	$(PYTEST) tests/test_elastic.py -q
	$(PYTEST) tests/test_faults.py -q -k 'migrate or scale_event'

# Invariant auditor (jax_llama_tpu/analysis): host-boundary lint,
# lowering-contract audit (donated args actually alias, host-fetch
# surface within budget, no full-pool-copy equations — all eight
# registered jitted programs lowered at a tiny geometry), the
# lock-discipline / thread-confinement check, the retrace auditor
# (bounded jit-cache-key domains statically + the admission-sweep
# cache drill), the comms-budget contracts (collective counts/bytes
# in the COMPILED sharded lowerings; full-pool collectives are hard
# findings), the schedule explorer (every racy-read/unguarded pragma
# backed by a passing interleaving model) and the metrics-registry
# lint — plus `ruff check` (pyflakes-class rules, [tool.ruff] in
# pyproject.toml) when ruff is installed in the environment.  Exit
# non-zero on any finding; the static layers also gate tier-1 via
# tests/test_analysis.py.
lint-invariants:
	env JAX_PLATFORMS=cpu python -m jax_llama_tpu.analysis
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check .; \
	else \
		echo "ruff not installed; skipping ruff check (pip install ruff)"; \
	fi

# THE single pre-PR gate: the full invariant audit (above, ruff
# included behind its command gate), the fast analysis tests, and the
# perf-smoke host-boundary drills.  Green `make check` = the static
# contracts, the thread-safety models, the jit-cache/comms budgets
# and the 1-fetch/0-upload discipline all hold — run it before every
# push; tier1 remains the full gating suite.
check: lint-invariants
	$(PYTEST) tests/test_analysis.py -q -m 'not slow'
	$(MAKE) perf-smoke

# The full lint gate (alias kept separate so CI can grow style/type
# layers here without slowing the invariant auditor).
lint: lint-invariants

# On-chip kernel regressions (run on a TPU host; self-skip elsewhere).
# ONE process: a chip belongs to one process at a time, so no xdist and
# no child that needs the chip.  chip-smoke's parent stays off jax and
# runs its children strictly one after another.
tpu:
	env JAX_PLATFORMS=tpu python -m pytest tests/test_tpu_compiled.py -q -m tpu -p no:xdist

chip-smoke:
	python chip_smoke.py
