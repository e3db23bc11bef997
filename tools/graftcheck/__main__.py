"""``python -m tools.graftcheck`` entry point (also works when invoked
from anywhere — the repo root is put on sys.path first)."""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from tools.graftcheck.core import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
