"""Share of the rows the held experts' walk takes that are real assignments:
100 x ``moe_held_assignments`` / ``moe_held_rows_walked``, the expert layers'
counters summed over the last epoch of the window's ``fit``, as ``Trainer``
sets them on its ``train.epoch`` span (``lib/spans.py::ring_of``). The rest
of a step's rows are padding at the end of a window of experts or past the
load. ``None`` for a program that sets neither."""

from lib import spans as S

ROOT = "train.fit"
EPOCH = "train.epoch"
REAL, WALKED = "moe_held_assignments", "moe_held_rows_walked"


def read(ctx):
    found = S.last_root(S.ring_of(ctx), ROOT)
    if found is None:
        return None
    trace, root = found
    epochs = [s for s in S.children(trace, root) if s["name"] == EPOCH]
    attrs = (epochs[-1].get("attrs") or {}) if epochs else {}
    if not attrs.get(WALKED) or REAL not in attrs:
        return None
    return 100.0 * float(attrs[REAL]) / float(attrs[WALKED])
