"""The benchmark's tests: every generator a configuration names is
registered with ``benchlib.tables`` before any test is collected."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from benchlib import bigratings  # noqa: E402,F401
