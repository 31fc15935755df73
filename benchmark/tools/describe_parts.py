#!/usr/bin/env python3
"""Print where a traced train step's device time goes, part by part
(``lib/scopes.py``): ms per execution of each part of the step, of the
fusions that join parts and of what no part holds, and the operations that
took most of each, a shared fusion's under the parts it joins
(``mixer+optimizer``). What a builder summed
by hand with a scratch script before PR 37.

    python3 benchmark/tools/describe_parts.py <trace_dir> [--top N] [--extract out.json]

``--extract`` writes one execution small enough to keep beside the tests
(``lib/scopes.py::save_extract``; ``tests/data/parts_trinity_train.json``).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import scopes  # noqa: E402


def top_ops(found: dict, top: int) -> dict:
    """Each part's ``top`` operations by device ms per execution, loops left
    out; an operation is named by its instruction and result's shape."""
    by_part = {}
    for name, _, dur, part in found["ops"]:
        if not scopes.is_container(name):
            ops = by_part.setdefault(part, {})
            key = name.split("{", 1)[0][:120]
            ops[key] = ops.get(key, 0) + dur
    return {part: [[k, v / 1e6 / found["executions"]]
                   for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]]
            for part, ops in by_part.items()}


if __name__ == "__main__":
    found = scopes.load_ops(sys.argv[1])
    if found is None:
        sys.exit(f"no parts in the newest trace under {sys.argv[1]}")
    top = int(sys.argv[sys.argv.index("--top") + 1]) if "--top" in sys.argv else 8
    print(json.dumps({"executions": found["executions"],
                      "ms_by_part": scopes.ms_by_part(found),
                      "top": top_ops(found, top)}, indent=1))
    if "--extract" in sys.argv:
        scopes.save_extract(found, sys.argv[sys.argv.index("--extract") + 1],
                            execution=min(1, found["executions"] - 1))
