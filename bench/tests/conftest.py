"""Put the checkout and the program's ``src`` on the import path, and
hold JAX to the CPU (the Pallas kernel runs in interpret mode)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
