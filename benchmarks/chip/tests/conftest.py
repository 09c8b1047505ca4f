import sys
from pathlib import Path

_root = Path(__file__).resolve().parents[3]
for p in (str(_root / "src"), str(_root)):
    if p not in sys.path:
        sys.path.insert(0, p)
