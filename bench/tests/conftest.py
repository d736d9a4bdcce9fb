"""The benchmark's own tests run on the CPU; the repository root and its
src/ go on the import path so that ``bench`` and ``repro`` import."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
