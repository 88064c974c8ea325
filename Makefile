# Build front-end — parity with the reference Makefile (Makefile:18-39)
# and its generic build helper (include/build.mk:12-16).
#
# Typical flow (reference notebook order):
#   make build smoke push      # 00_CreateImageAndTest
#   make provision setup       # 01_CreateResources
#   make submit stream         # 01_Train*
#   make teardown
#
# Registry/infra knobs come from the environment or .env (dotenv), like
# the reference's DOCKER_REPOSITORY/EXT_PWD exports (Makefile:22-29).

DOCKER_REPOSITORY ?= local
IMAGE             ?= $(DOCKER_REPOSITORY)/ddl-tpu
TAG               ?= latest
TPU               ?=
ZONE              ?=
BUCKET            ?=
ACCELERATOR_TYPE  ?= v5litepod-8
SCRIPT            ?= examples/imagenet_keras_tpu.py
JOB               ?= ddl-train
PY                ?= python

.PHONY: build login push run jupyter smoke test test-fast test-smoke check \
        lint \
        notebooks bench chip-smoke recertify decode-audit heavy-refresh \
        obs-report \
        obs-watch trace-report bench-trend accum-memory fault-suite \
        elastic-drill \
        serve-bench serve-bench-spec fleet-bench chaos-bench coloc-bench \
        disagg-bench \
        stream-shards \
        stream-bench native \
        provision setup submit stream status stop teardown

## Image tier (reference 00_CreateImageAndTest + Makefile build/push)
build:
	docker build -t $(IMAGE):$(TAG) .

login:	## docker login from .env (DOCKER_USER/DOCKER_PASSWORD, reference cell-11 parity)
	$(PY) -c "import sys; from distributeddeeplearning_tpu.utils.env import docker_login; sys.exit(docker_login())"

push: login
	docker push $(IMAGE):$(TAG)

run:	## run the image's default smoke command locally
	docker run --rm -it $(IMAGE):$(TAG)

# Reference Makefile:22-29 parity: its `jupyter` target mounts PWD + data
# into the operator container and serves the notebooks.
jupyter:	## serve the notebook tier from the image
	docker run --rm -it -p 8888:8888 \
	    -v $(CURDIR):/workspace -v $(or $(DATA),/tmp/data):/data \
	    -e DOCKER_REPOSITORY=$(DOCKER_REPOSITORY) \
	    $(IMAGE):$(TAG) \
	    jupyter lab --ip=0.0.0.0 --port=8888 --allow-root --no-browser notebooks/

## Local verification (reference's mpirun -np 2 smoke, no docker needed)
smoke:
	$(PY) launch.py --num-processes 2 --devices-per-process 4 \
	    --platform cpu --timeout 540 \
	    --env FAKE=True --env FAKE_DATA_LENGTH=128 --env EPOCHS=1 \
	    --env BATCHSIZE=4 --env IMAGE_SIZE=32 --env NUM_CLASSES=8 \
	    --env MODEL=resnet18 $(SCRIPT)

test:	## full suite (~52 min on a 1-vCPU host; see docs/TESTING.md)
	$(PY) -m pytest tests/ -x -q

test-fast:	## deselect the measured-heavy oracles (tests/heavy_tests.txt)
	$(PY) -m pytest tests/ -x -q -m "not heavy"

lint:	## ddlint static-analysis suite (docs/ANALYSIS.md): AST host-sync/
	## tracer lint over the hot paths, HLO donation/collective/cache-key
	## audit of every engine step + the SlotEngine program set, and the
	## env/obs/protocol contract cross-checks. Writes lint.json. Single
	## rule: $(PY) scripts/ddlint.py --rule <name> (--list for the
	## catalogue)
	$(PY) scripts/ddlint.py

check:	## CI gate: heavy-list drift guard + the ddlint suite (one
	## command — heavy_refresh --check chains ddlint --changed-ok),
	## then the fast tier — a new slow test that skipped
	## tests/heavy_tests.txt fails here instead of silently bloating
	## every fast run (scripts/heavy_refresh.py)
	$(PY) scripts/heavy_refresh.py --check
	$(MAKE) test-fast

test-smoke:	## sub-minute loop: pure-host logic + mesh/collective semantics
	$(PY) -m pytest tests/test_collectives.py tests/test_config.py \
	    tests/test_timer.py tests/test_env_utils.py tests/test_schedules.py \
	    tests/test_synthetic_data.py tests/test_native.py -x -q

notebooks:	## execute the notebook tier headlessly; fails on any broken cell
	$(PY) scripts/run_notebooks.py

bench:	## measures the TPU only: exits non-zero with no chip, prints no record
	$(PY) bench.py

chip-smoke:	## the quickest proof that trainer and server still start on
	## the chip (one process; last line {"ok", "device"}; exits non-zero
	## without a TPU). From a sandbox with no chip: chiprun -- python chip_smoke.py
	$(PY) chip_smoke.py

recertify:	## all headline protocols at one HEAD -> RECERT.json (round 5)
	$(PY) scripts/recertify.py

decode-audit:	## decode-tier roofline + batch sweep (round 5; --kv-dtype/
	## --weight-dtype int8 audit the quantized floor, scales itemized)
	$(PY) scripts/decode_audit.py

serve-bench:	## continuous batching vs sequential generate under Poisson
	## load (docs/SERVING.md protocol; SERVE_*/BENCH_VOCAB knobs;
	## SERVE_KV_DTYPE/SERVE_WEIGHT_DTYPE=int8 run the quant compare;
	## SERVE_SPEC_K>0 runs the speculative compare)
	$(PY) scripts/serve_bench.py

serve-bench-spec:	## speculative-decode compare: greedy vs int8 self-draft
	## spec engine at K=4 on a decode-heavy backlog — gates bitwise
	## greedy parity + >=1.4x tokens/sec + closed program sets
	## (docs/SERVING.md speculative tier; serve_lm_spec recertify row)
	SERVE_SPEC_K=$(or $(SPEC_K),4) SERVE_SPEC_DRAFT=$(or $(SPEC_DRAFT),int8) \
	    SERVE_MAX_NEW=64 SERVE_REQUESTS=24 SERVE_RATE_RPS=0 \
	    SERVE_PREFILLS_PER_STEP=8 $(PY) scripts/serve_bench.py

fleet-bench:	## multi-replica fleet: 1 vs SERVE_REPLICAS(=2) replicas on a
	## seeded multi-tenant load — gates scaling (CPU-honest basis), flat
	## p99 TTFT, weighted fairness, bitwise per-request parity, closed
	## program sets per replica (docs/SERVING.md fleet tier;
	## serve_lm_fleet recertify row)
	$(PY) scripts/fleet_bench.py

chaos-bench:	## seeded mixed-verb fault storm over a closed 3-tenant
	## backlog on 2+ replicas: every non-shed request must finish with
	## bitwise splice parity, the corrupt injection detected+healed
	## (never delivered), the flap crash-loop must open the breaker,
	## program sets stay closed and p99 TTFT holds within the declared
	## multiple (docs/ROBUSTNESS.md serving failure model;
	## serve_lm_chaos recertify row; SERVE_CHAOS_PLAN/SERVE_CHAOS_SEED)
	$(PY) scripts/chaos_bench.py

disagg-bench:	## disaggregated prefill/decode pools vs the colocated
	## fleet at equal replica count on a bimodal storm with a hot
	## shared system prefix — gates strictly-better p99 TTFT, bounded
	## inter-token p99, bitwise parity vs sequential generate,
	## prefill-once-per-fleet via the prefix directory, one scheduled
	## zero-drop live migration, and closed program sets per pool
	## (docs/SERVING.md disaggregation; serve_lm_disagg recertify row)
	$(PY) scripts/disagg_bench.py

coloc-bench:	## combined fault+chaos storm over ONE device pool: a
	## serving surge drives the brownout ladder to exhaustion, the
	## arbiter shrinks training via the capacity file, the controller's
	## scale-up is lease-gated, then reclaim drains the leased replica
	## zero-drop and training grows back — training trajectory must
	## re-join the uninterrupted run at f32 ULP, p99 TTFT holds the
	## COLOC_TTFT_SLO_MS bound, zero dropped or mixed-version requests
	## (docs/ROBUSTNESS.md colocation; lm_coloc recertify row)
	$(PY) scripts/coloc_bench.py

accum-memory:	## host-side proof: compiled activation bytes vs ACCUM_STEPS (PROFILE.md)
	$(PY) scripts/accum_memory.py

stream-shards:	## local streamed-shard fixture: seeded token shards + index
	## under stream_fixture/tokens (DATA_FORMAT=stream smoke target;
	## scripts/streamgen.py builds real corpora the same way)
	$(PY) scripts/streamgen.py tokens --out stream_fixture/tokens \
	    --records 512 --seq-len 64 --vocab 256 --shard-records 128

stream-bench:	## streamed pretrain -> checkpoint -> SlotEngine serve e2e:
	## gates restored-params round trip, manifest data_cursor, and
	## served streams token-equal to inference.generate
	## (docs/DATA.md; lm_stream recertify row)
	$(PY) scripts/stream_bench.py

heavy-refresh:	## prune tests/heavy_tests.txt against --collect-only + print tier numbers
	$(PY) scripts/heavy_refresh.py

fault-suite:	## fast fault-injection battery: plan grammar, supervisor e2e,
	## heartbeat, NaN guard, checkpoint keying + corrupt-latest fallback
	## (the heavy resume-equivalence oracles run with the full suite)
	$(PY) -m pytest tests/test_faults.py tests/test_fault_tolerance.py \
	    -x -q -m "not heavy"

elastic-drill:	## fast elastic battery: shrink/restore grammar, capacity
	## probe, checkpoint portability across 1/4/8 devices, global data
	## topology, and the jax-light supervisor shrink->resume->grow e2e
	## (the heavy trajectory oracles run with the full suite;
	## docs/ROBUSTNESS.md elasticity section)
	$(PY) -m pytest tests/test_elastic.py -x -q -m "not heavy"

# Render the observability report for the most recent run directory
# (OBS_RUN=dir overrides; runs land under runs/ by convention — the
# launcher's --obs-dir, bench --events, or OBS_DIR on any entry point).
obs-report:	## event-bus run report for the newest runs/<dir> (docs/OBSERVABILITY.md)
	$(PY) scripts/obs_report.py $(or $(OBS_RUN),$(shell ls -td runs/*/ 2>/dev/null | head -1))

obs-watch:	## live dashboard for the newest runs/<dir>: rollups + SLO burn
	## rates, publishes rollup.json (OBS_RUN=dir, SLO_SPEC honored)
	$(PY) scripts/obs_watch.py $(or $(OBS_RUN),$(shell ls -td runs/*/ 2>/dev/null | head -1))

trace-report:	## per-request critical-path digest for the newest runs/<dir>:
	## top-K-slowest decomposed per phase vs fleet p50, chaos causes,
	## orphans, per-step training attribution (OBS_RUN=dir, TOP=K); for
	## each profiler capture under it, device time by scope group and
	## idle gaps by ddl: span (OBS_RUN may be a bare capture directory)
	$(PY) scripts/trace_report.py $(or $(OBS_RUN),$(shell ls -td runs/*/ 2>/dev/null | head -1)) --top $(or $(TOP),5)

bench-trend:	## regression sentinel over archived bench records
	## (RECORDS='dir/BENCH_r*.json'): fails on a >10% like-for-like
	## drop; rounds that did not measure are listed, never compared
	$(PY) scripts/bench_trend.py --glob '$(RECORDS)'

## Native IO tier (built on demand by the Python bindings too)
native:	## named after the hash of native/ddl_native.cc, so never stale
	$(PY) -c "from distributeddeeplearning_tpu import native; assert native.native_available()"

## Cluster tier (reference 01_CreateResources / 01_Train*)
# --tpu/--zone live on the PARENT parser (before the subcommand) and are
# only passed when set, so TPU_NAME/ZONE from .env keep working.
TPU_FLAGS = $(if $(TPU),--tpu $(TPU),) $(if $(ZONE),--zone $(ZONE),)

provision:
	$(PY) -m distributeddeeplearning_tpu.orchestration.provision \
	    $(TPU_FLAGS) pod-create --accelerator-type $(ACCELERATOR_TYPE)

setup:
	$(PY) -m distributeddeeplearning_tpu.orchestration.provision \
	    $(TPU_FLAGS) setup $(if $(BUCKET),--bucket $(BUCKET),)

submit:
	$(PY) -m distributeddeeplearning_tpu.orchestration.submit \
	    $(TPU_FLAGS) run --job $(JOB) --detach \
	    --manifest $(JOB).json $(SCRIPT)

stream:
	$(PY) -m distributeddeeplearning_tpu.orchestration.submit \
	    $(TPU_FLAGS) stream --job $(JOB)

status:
	$(PY) -m distributeddeeplearning_tpu.orchestration.submit \
	    $(TPU_FLAGS) status --job $(JOB)

stop:
	$(PY) -m distributeddeeplearning_tpu.orchestration.submit \
	    $(TPU_FLAGS) stop --job $(JOB)

teardown:
	$(PY) -m distributeddeeplearning_tpu.orchestration.provision \
	    $(TPU_FLAGS) pod-delete
