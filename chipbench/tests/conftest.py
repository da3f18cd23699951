"""CPU self-tests of the benchmark (run by hand:
``JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests``)."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
