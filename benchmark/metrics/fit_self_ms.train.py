"""What a ``Trainer.fit`` call costs outside its steps: the measured
window's ``train.fit`` span (the newest in the program's ring) less what
its ``train.epoch`` children cover. The prefetch worker's start and its
join in ``prefetched.close()`` are in it."""

from lib import spans as S

ROOT = "train.fit"


def read(ctx):
    found = S.last_root(S.ring_of(ctx), ROOT)
    if found is None:
        return None
    trace, fit = found
    return S.self_seconds(trace, fit) * 1e3
