import sys
from pathlib import Path

# the benchmark's own modules (``harness``) live under perfbench/
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "perfbench"))
