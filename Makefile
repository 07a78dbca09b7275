PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: test properties smoke smoke-router smoke-chunked smoke-steal \
	smoke-quant smoke-elastic smoke-prefix smoke-fleet-prefix \
	smoke-autotune ci

test:
	python -m pytest -x -q

# scheduler-policy invariants at a pinned hypothesis seed
properties:
	python -m pytest -q tests/test_scheduler_properties.py \
	    --hypothesis-seed=0

smoke:
	python -m repro.launch.serve --arch deepseek-7b --smoke \
	    --requests 6 --new-tokens 4 --slots 2
	python -m repro.launch.serve --arch dlrm --smoke --requests 6

# 2-replica ReplicaRouter smoke, both archs (priority policy on the LM)
smoke-router:
	python -m repro.launch.serve --arch deepseek-7b --smoke \
	    --requests 8 --new-tokens 4 --slots 2 --replicas 2 \
	    --policy priority --slo-ms 60000
	python -m repro.launch.serve --arch dlrm --smoke --requests 6 \
	    --replicas 2

# chunked-prefill smoke: serve a mixed trace with chunking on, then
# replay it monolithically and assert token-identical outputs — on the
# all-global arch AND on a stateful hybrid (RG-LRU + local ring), the
# stacks the SequenceStateManager (PR 5) opened to chunking
smoke-chunked:
	python -m repro.launch.serve --arch deepseek-7b --smoke \
	    --requests 8 --new-tokens 4 --slots 2 --max-len 64 \
	    --prefill-chunk 16 --verify-chunked
	python -m repro.launch.serve --arch recurrentgemma-9b --smoke \
	    --requests 8 --new-tokens 4 --slots 2 --max-len 64 \
	    --prefill-chunk 16 --verify-chunked

# work-stealing smoke: 2-replica fleet, every request hot-spotted onto
# replica 0, replica 0 killed mid-run — asserts nonzero telemetry.steals
# and a fault drain that loses zero tickets
smoke-steal:
	python -m repro.launch.serve --arch deepseek-7b --smoke \
	    --requests 8 --new-tokens 4 --slots 2 --replicas 2 \
	    --steal --verify-steal

# quantized-serving smoke (PR 6): single w8a8 engine replays its trace
# on fp32 and asserts the greedy-token-agreement guardrail; then a mixed
# fp32+w8a8 fleet (feedback routing + stealing) asserts every class-0
# request pinned to the fp32 replica with zero lost and zero downgrades
smoke-quant:
	python -m repro.launch.serve --arch deepseek-7b --smoke \
	    --requests 8 --new-tokens 4 --slots 3 --max-len 64 \
	    --prefill-chunk 16 --precision w8a8 --verify-quant
	python -m repro.launch.serve --arch deepseek-7b --smoke \
	    --requests 16 --new-tokens 4 --slots 3 --max-len 64 \
	    --replicas 2 --replica-precisions fp32,w8a8 --route feedback \
	    --steal --policy priority --verify-quant

# elastic-fleet smoke (PR 7): flash crowd + mid-crowd card freeze on the
# deterministic fleet sim — asserts scale-up, trough scale-down, exactly
# one missed-heartbeat fault drain, zero lost, and both wins vs a fixed
# fleet (less peak shedding, fewer replica-seconds)
smoke-elastic:
	python -m repro.launch.serve --elastic-smoke

# prefix-cache smoke (PR 8): populate the content-hash prefix cache
# with a hot-system-prompt trace, replay it through the warm cache, and
# assert nonzero hits with every output token-identical to a cold
# engine serving the same trace
smoke-prefix:
	python -m repro.launch.serve --arch deepseek-7b --smoke \
	    --requests 8 --new-tokens 4 --prefill-chunk 16 \
	    --prefix-cache 16 --verify-prefix

# fleet-prefix smoke (PR 10): 2-replica fleet with the fleet-shared
# prefix tier under a hot-system-prompt trace — populate one replica,
# then route the rest through locality-aware steering and assert
# nonzero remote hits, zero lost, outputs token-identical to cold
# prefill
smoke-fleet-prefix:
	python -m repro.launch.serve --arch deepseek-7b --smoke \
	    --requests 10 --new-tokens 4 --prefill-chunk 16 \
	    --prefix-cache 16 --replicas 2 --verify-fleet-prefix

# self-tuning-knob smoke (PR 9): serve with --prefill-chunk auto — the
# analytic perf model picks the chunk at the per-bucket efficiency knee
# — and assert the chosen chunk sits on the ladder, with outputs
# token-identical to a hand-set reference chunk
smoke-autotune:
	python -m repro.launch.serve --arch deepseek-7b --smoke \
	    --requests 8 --new-tokens 4 --slots 2 --max-len 64 \
	    --prefill-chunk auto --verify-autotune

ci: test properties smoke smoke-router smoke-chunked smoke-steal \
	smoke-quant smoke-elastic smoke-prefix smoke-fleet-prefix \
	smoke-autotune
