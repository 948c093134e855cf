# Tier-1 test lanes + lint + benchmark entry points.

PY := python

.PHONY: test test-all lint bench regen-golden nightly-grid serve chaos \
	chaos-drill

test:  ## fast lane: what CI runs (slow-marked distributed tests excluded)
	PYTHONPATH=src $(PY) -m pytest -q -m "not slow"

lint:  ## ruff lane (configured in ruff.toml; pip install ruff)
	$(PY) -m ruff check src tests benchmarks examples

test-all:  ## full tier-1 suite (ROADMAP verify command)
	PYTHONPATH=src $(PY) -m pytest -x -q

chaos:  ## full chaos suite: fault-injection drills + codec property tests
	PYTHONPATH=src $(PY) -m pytest -q tests/test_chaos.py tests/test_codecs.py

chaos-drill:  ## seeded acceptance drills outside pytest -> artifacts/chaos/
	PYTHONPATH=src $(PY) benchmarks/chaos_drill.py --seeds $${REPRO_CHAOS_SEEDS:-0}

serve:  ## prediction-service demo: daemon + TCP tenants + retrain cycle
	PYTHONPATH=src $(PY) examples/predict_service.py

bench:  ## paper figure reproductions (scaled-down)
	PYTHONPATH=src $(PY) -m benchmarks.run

regen-golden:  ## re-bless tests/data/determinism_golden.json (intentional!)
	PYTHONPATH=src $(PY) benchmarks/regen_golden.py

nightly-grid:  ## Table-4-scale full-field sweep (what the nightly lane runs)
	PYTHONPATH=src $(PY) benchmarks/nightly_grid.py
