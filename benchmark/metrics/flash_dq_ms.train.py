"""Device time a train step spends in the flash-attention backward dQ kernel
(``ops/pallas/flash_attention.py``, named ``flash_dq`` in the trace by
``ops/pallas/scope.py``): the kernel's events inside ``jit_train_step``
executions, per execution. The three ``flash_*_ms.train`` sum to the kernel
time that ``flash_attention_roofline`` divides by."""

from lib import spans as S

PROGRAM = ("jit_train_step",)
KERNEL = (("flash_dq", "tpu_custom_call"),)


def read(ctx):
    return S.kernel_ms_per_execution(ctx.get("trace"), PROGRAM, KERNEL)
