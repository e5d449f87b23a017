PY ?= python
export PYTHONPATH := src

.PHONY: test test-fast install-dev service fleet inference

install-dev:
	$(PY) -m pip install -e ".[test]"

test:              ## tier-1 suite
	$(PY) -m pytest -x -q

test-fast:         ## tier-1 minus the slow end-to-end tests
	$(PY) -m pytest -x -q -m "not slow"

service:           ## RandService: 1024-tenant burst + replay check, then serve until SIGINT (graceful drain)
	$(PY) -m repro.service --burst 1024 --tenants 1024 --verify-replay --linger 600

fleet:             ## 2-shard wire fleet (pipelined binary clients, coalescing+pools on): kill-mid-burst failover, digest vs no-fault, union replay
	rm -rf /tmp/repro-fleet
	JAX_PLATFORMS=cpu $(PY) -m repro.service --fleet 2 --burst 256 --tenants 64 \
	    --journal-dir /tmp/repro-fleet --fault-plan kill@128 --verify-replay

inference:         ## continuous batcher: fused/xla parity run, then kill-mid-run + journal replay, digest vs no-fault
	rm -rf /tmp/repro-inference && mkdir -p /tmp/repro-inference
	$(PY) -m repro.inference --batch 16 --vocab 256 --sequences 48 --rate 4 \
	    --seed 7 --parity --digest-out /tmp/repro-inference/base.digest
	-$(PY) -m repro.inference --batch 16 --vocab 256 --sequences 48 --rate 4 \
	    --seed 7 --journal /tmp/repro-inference/journal.jsonl --fault-plan kill@40
	$(PY) -m repro.inference --batch 16 --vocab 256 --sequences 48 --rate 4 \
	    --seed 7 --journal /tmp/repro-inference/journal.jsonl \
	    --digest-out /tmp/repro-inference/replay.digest
	cmp /tmp/repro-inference/base.digest /tmp/repro-inference/replay.digest
	@echo "inference: kill-mid-run replay digest == no-fault digest"
