#!/usr/bin/env python3
"""Readings of the control of ``correct`` at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

See ``bench/lib/control.py``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib.control import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
