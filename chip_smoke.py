"""Smoke run of the graph-ANN serving path on a TPU.

    python chip_smoke.py [--chips 4] [--seed S]

The body lives in ``src/repro/launch/chip_smoke.py``; see its docstring.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.launch.chip_smoke import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
