# Launch conventions: the analog of the reference's per-model Makefiles
# (ResNet/pytorch/Makefile train_*/resume_* nohup targets,
# CycleGAN/tensorflow/Makefile tb/ps monitor targets), over the single
# config-registry CLI instead of 12 per-model scripts.
#
#   make train MODEL=resnet50            # background train, log to file
#   make resume MODEL=resnet50           # resume from latest checkpoint
#   make train-fg MODEL=lenet5 ARGS=--fake-data
#   make tb                              # tensorboard on ./runs
#   make test / make dryrun

TIME := $(shell date "+%Y-%m-%dT%H-%M-%S")
MODEL ?= resnet50
DATA ?= ./dataset
ARGS ?=

train:
	mkdir -p checkpoints logs
	nohup python -u train.py -m $(MODEL) --data-dir $(DATA) \
	  --tensorboard-dir runs/$(MODEL)-$(TIME) $(ARGS) \
	  > logs/$(MODEL)-$(TIME).log 2>&1 &
	@echo "started; tail -f logs/$(MODEL)-$(TIME).log"

resume:
	mkdir -p checkpoints logs
	nohup python -u train.py -m $(MODEL) --data-dir $(DATA) -c auto \
	  --tensorboard-dir runs/$(MODEL)-$(TIME) $(ARGS) \
	  > logs/$(MODEL)-$(TIME).log 2>&1 &
	@echo "resumed; tail -f logs/$(MODEL)-$(TIME).log"

train-fg:
	python -u train.py -m $(MODEL) --data-dir $(DATA) $(ARGS)

test:
	python -m pytest tests/ -x -q

# static analysis (lint/): the review-time teeth behind the obs/ runtime
# signals — fails on any non-baselined DV001-DV007 (JAX/TPU contracts),
# DV101-DV104 (concurrency pack, lint/concur.py), or DV201-DV205
# (distributed-correctness pack, lint/distlint.py) finding, then audits
# the curated sharding tables semantically (tools/shard_check.py:
# coverage floors over abstract eval_shape trees — zero devices, zero
# compiles). Runs first in verify: it is the cheapest gate (warm lint
# cache ~0.1s; shard_check ~2s on a cold jax import)
lint:
	python -m deep_vision_tpu.lint
	JAX_PLATFORMS=cpu python tools/shard_check.py

# accept the current findings into the checked-in baseline (use after an
# intentional change; review the diff of .jaxlint-baseline.json like code)
lint-baseline:
	python -m deep_vision_tpu.lint --write-baseline

# the tier-1 gate, verbatim from ROADMAP.md: run before shipping any PR
# (bash, not sh: the command uses pipefail and PIPESTATUS); lint, then
# obs-smoke and chaos-smoke — the telemetry artifacts must validate and
# the resilience contracts must hold before the tests count
verify: SHELL := /bin/bash
verify: lint preflight obs-smoke chaos-smoke data-smoke host-smoke serve-smoke fleet-smoke fleetnet-smoke cache-smoke shard-smoke perf-gate live-smoke
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# environment preflight: backend liveness + libtpu/client version
# handshake, device-count/mesh-shape sanity, and checkpoint-dir
# writability — the run-killers that otherwise burn minutes (a libtpu
# skew dies only after the whole compile; a backend that does not answer
# holds the run) fail in seconds, before anything compiles. Also
# the first act of every train_cli run (--skip-preflight opts out)
preflight:
	JAX_PLATFORMS=cpu python -m deep_vision_tpu.tools.preflight \
	  --ckpt-dir artifacts/preflight_probe

# observability smoke: a tiny CPU train with tracing + health guard +
# flight recorder + a static profiler window on, then validate the
# journal/trace artifacts against the obs/ schemas (profile_capture
# events included) and assert the clean exit left NO flight bundle —
# the recorder must disarm on a healthy run
obs-smoke:
	rm -rf artifacts/obs_smoke
	mkdir -p artifacts/obs_smoke
	JAX_PLATFORMS=cpu python train.py -m lenet5 --fake-data --epochs 1 \
	  --ckpt-dir artifacts/obs_smoke/ckpt \
	  --journal artifacts/obs_smoke/journal.jsonl \
	  --trace artifacts/obs_smoke/trace.json \
	  --flight-dir artifacts/obs_smoke/flight \
	  --profile-dir artifacts/obs_smoke/prof --profile-window 1:3 \
	  --health-policy warn --watchdog-timeout 300
	python tools/check_journal.py artifacts/obs_smoke/journal.jsonl \
	  --trace artifacts/obs_smoke/trace.json --strict
	python tools/obs_report.py artifacts/obs_smoke/journal.jsonl \
	  --trace artifacts/obs_smoke/trace.json
	@if [ -n "$$(ls -A artifacts/obs_smoke/flight 2>/dev/null)" ]; then \
	  echo "obs-smoke: clean run left a flight bundle"; exit 1; fi

# serving smoke: a real multi-model CPU server (YOLO + pose @64x64)
# through the whole serve/ contract — AOT warmup compiles exactly the
# bucket menu, a mixed-size request stream causes ZERO additional
# compilations, injected data.read faults degrade single requests,
# clean shutdown passes check_journal --strict with no flight bundle,
# and a SIGTERM'd child flushes all accepted requests and leaves a
# crc-valid preempt bundle (tools/serve_smoke.py). The locksmith lock
# sanitizer (obs/locksmith.py) is armed throughout and must report
# zero lock_order_violation events
serve-smoke:
	JAX_PLATFORMS=cpu python tools/serve_smoke.py --workdir artifacts/serve_smoke

# fleet smoke: the serving layer at fleet shape (tools/loadgen.py) — a
# 3-replica pool under seeded load survives an injected replica death
# request-scoped (typed replica_lost/replica_recovered + supervised
# respawn), promotes a canary weight swap AND auto-rolls-back a
# poisoned one under live traffic, sheds an overload blast by policy
# (serve_shed accounting exact, p99 of admitted traffic held), drains
# clean with a balanced fleet ledger, and compiles NOTHING after
# warmup — including across both swaps. Locksmith armed throughout;
# journals pass check_journal --strict; no stray flight bundles
fleet-smoke:
	JAX_PLATFORMS=cpu python tools/loadgen.py --workdir artifacts/fleet_smoke

# front-door smoke: the socket transport + process-replica fleet
# (tools/fleetnet_smoke.py) — N spawned replica PROCESSES (each its
# own engine, HTTP endpoint, and rendezvous lease) behind the parent's
# HTTP front door; every replica warms at ZERO backend compiles off
# the parent-seeded executable cache; a mid-traffic SIGKILL fails only
# the dead process's in-flight requests (typed ReplicaLost behind
# retryable 503s) and the respawn rebirths from cache; a canary
# PROCESS serves shadow weights and promote hot-swaps the whole fleet
# over /control/promote; an overload blast gets real 429s with
# Retry-After that a retrying client honors; offered == ok+err+shed
# holds across client, transport ledger, and journal; strict
# check_journal on parent + every surviving child journal, with the
# SIGKILLed incarnation's journal flagged as the forensic record
fleetnet-smoke:
	JAX_PLATFORMS=cpu python tools/fleetnet_smoke.py --workdir artifacts/fleetnet_smoke

# cold-path smoke: the persistent executable cache + int8 quantization
# contracts (tools/cache_smoke.py) — run A compiles and populates the
# cache (one excache_store per pair), run B in a FRESH process warms
# with ZERO backend compiles (recompile-counter delta == 0, all
# excache_hit, bit-identical outputs), a deliberately version-skewed
# entry journals a typed excache_invalid and falls through to the
# compiler, and the int8 engine passes the accuracy-delta gate and
# serves the same traffic with SLO before/after printed (a poisoned
# calibration is REFUSED). Journals pass check_journal --strict
cache-smoke:
	JAX_PLATFORMS=cpu python tools/cache_smoke.py --workdir artifacts/cache_smoke

# shard smoke: declarative sharding on a forced 8-device CPU mesh
# (tools/shard_smoke.py) — ViT and the V-MoE variant train GENUINELY
# sharded multi-step (table-resolved NamedShardings on device, zero
# recompiles after warmup), tp_sharded_leaves clears each family's
# declared floor via the TABLE (and beats the size heuristic it
# replaces), a deliberately gutted table fails at startup NAMING the
# replicated leaves, scaling efficiency is measured at data={1,2,4,8}
# sub-meshes, and the journals (typed sharding_resolved + bench
# events) pass check_journal --strict with obs_report rendering the
# sharding section
shard-smoke:
	JAX_PLATFORMS=cpu python tools/shard_smoke.py --workdir artifacts/shard_smoke

# perf-attribution smoke: two seeded CPU bench runs build the crc-
# manifested ledger, a third run slowed through the fault-injection
# plane must FAIL the noise-aware MAD gate (CLI exits nonzero, typed
# perf_regression journaled, failed row excluded from future
# baselines), --bless re-anchors, corrupt ledger rows quarantine, and
# the sharded ViT step's parsed all-reduce inventory must match its
# gradient-tree bytes within 5% (tools/perf_gate.py --smoke)
perf-gate:
	JAX_PLATFORMS=cpu python tools/perf_gate.py --smoke --workdir artifacts/perf_gate

# live-telemetry smoke: a REAL train.py subprocess is scraped MID-RUN
# through its discovery file (/metrics parses as Prometheus, /healthz
# 200, /statusz shows a live step, obs_poll renders the one-liner); a
# data-service subprocess and an in-process client journal ONE traced
# request that obs_report --merged stitches into a single cross-process
# causal timeline; and a locksmith-armed probe proves concurrent
# scraping causes zero recompiles, zero lock-order violations, and
# <2% step-time overhead at a 1 Hz poll. Journals pass --strict with
# typed telemetry_server events (tools/live_smoke.py)
live-smoke:
	JAX_PLATFORMS=cpu python tools/live_smoke.py --workdir artifacts/live_smoke

# resilience smoke: a record-backed CPU train under injected faults
# (skipped bad records within budget, SIGKILL mid-checkpoint-save,
# quarantine-and-fall-back resume), journals validated --strict, plus a
# no-fault overhead probe on the injection points (tools/chaos_run.py).
# Children run with DVT_LOCKSMITH=1 (zero violations asserted), a forced
# A->B/B->A inversion must be detected at runtime, and the disabled
# locksmith wrapper is overhead-probed
chaos-smoke:
	JAX_PLATFORMS=cpu python tools/chaos_run.py --workdir artifacts/chaos_smoke

# host-churn smoke: the multi-host half of the elastic arc
# (tools/host_smoke.py) — three REAL processes (forced 2-device CPU
# worlds) rendezvous, train a checkpointed run at world 3, and one is
# SIGKILLed mid-epoch: the survivors must detect within the heartbeat
# deadline (typed host_lost, no collective hang), re-rendezvous at
# generation 1 / world 2, rebuild the mesh, resume at the EXACT
# checkpointed step via the cross-mesh restore, and re-derive a
# disjoint+covering host-shard assignment (typed data_reshard).
# Locksmith armed throughout (zero violations); surviving journals
# pass check_journal --strict; obs_report renders the membership
# timeline
host-smoke:
	JAX_PLATFORMS=cpu python tools/host_smoke.py --workdir artifacts/host_smoke

# data-plane smoke: the production data plane's contracts
# (tools/data_smoke.py) — a record-backed CPU train SIGKILLed mid-epoch
# resumes from the crc32c sidecar with a byte-identical batch stream
# (content hashes; typed data_resume event), and a 2-consumer shared
# dataset service streams with zero recompiles and zero starvation,
# absorbs an injected worker crash via supervised respawn
# (data_worker_lost/recovered) and a dropped connection via client
# reconnect; journals pass check_journal --strict
data-smoke:
	JAX_PLATFORMS=cpu python tools/data_smoke.py --workdir artifacts/data_smoke

demo:
	python -m deep_vision_tpu.tools.convergence_run --model yolov3 \
	  --holdout --render-dir examples/output
	python -m deep_vision_tpu.tools.convergence_run --model hourglass \
	  --holdout --render-dir examples/output

demo-gan:
	python -m deep_vision_tpu.tools.convergence_run --model dcgan \
	  --render-dir examples/output --out artifacts/dcgan_convergence.json
	python -m deep_vision_tpu.tools.convergence_run --model cyclegan \
	  --render-dir examples/output --out artifacts/cyclegan_convergence.json

demo-real:
	python examples/real_photo_demo.py

dryrun:
	python __graft_entry__.py 8

tb:
	tensorboard --logdir=./runs

ps:
	ps -ef | grep python

native:
	$(MAKE) -C native

.PHONY: train resume train-fg test lint lint-baseline verify preflight obs-smoke chaos-smoke data-smoke host-smoke serve-smoke fleet-smoke fleetnet-smoke cache-smoke shard-smoke perf-gate live-smoke demo demo-gan demo-real dryrun tb ps native
