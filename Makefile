# Convenience targets; verify.sh is the source of truth for the gate.

.PHONY: verify test lint smoke

verify:
	./verify.sh

test:
	python -m pytest tests/ -q -m 'not slow' \
	    --continue-on-collection-errors -p no:cacheprovider \
	    -p no:xdist -p no:randomly

lint:
	python -m access_control_srv_tpu.analysis

smoke:
	python chip_smoke.py
