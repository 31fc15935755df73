"""Device time a train step spends in the state-space scan's chunked kernels
(``ops/pallas/ssd.py``, named ``ssd_fwd`` and ``ssd_bwd`` in the trace by
``ops/pallas/scope.py``): the kernels' events inside ``jit_train_step``
executions, per execution. With remat the forward kernel runs twice a layer;
both runs are in it. ``ssd_roofline`` divides by this time."""

from lib import spans as S

PROGRAM = ("jit_train_step",)
KERNEL = (("ssd_", "tpu_custom_call"),)


def read(ctx):
    return S.kernel_ms_per_execution(ctx.get("trace"), PROGRAM, KERNEL)
