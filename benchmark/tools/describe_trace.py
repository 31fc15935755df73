#!/usr/bin/env python3
"""Print what a trace directory holds: every plane and line, its event count
and the names that took most time. Look at this by hand before trusting a
reduction (``lib/trace.py``).

    python3 benchmark/tools/describe_trace.py <trace_dir> [--extract out.json]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import trace as T  # noqa: E402

if __name__ == "__main__":
    print(json.dumps(T.describe(sys.argv[1]), indent=1))
    if "--extract" in sys.argv:
        T.save_extract(T.load(sys.argv[1]), sys.argv[sys.argv.index("--extract") + 1])
