"""Self-tests of the benchmark harness: CPU, tiny sizes, run by path
(``python -m pytest bench/tests``)."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
