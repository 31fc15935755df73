"""Device time a train step spends in the windowed flash-attention launches
(``ops/pallas/flash_attention.py`` with a ``window``, named
``window_flash_fwd`` / ``window_flash_dq`` / ``window_flash_dkv`` in the trace
by ``ops/pallas/scope.py``): their events inside ``jit_train_step`` executions,
per execution, all three kernels of all window layers; with remat the forward
runs twice and both runs are in it. The ``flash_*_ms.train`` readers match
these launches too (``flash_fwd`` is part of ``window_flash_fwd``): each of
them counts its kernel over the window layers and the global one together."""

from lib import spans as S

PROGRAM = ("jit_train_step",)
KERNEL = (("window_flash_fwd", "tpu_custom_call"), ("window_flash_dq", "tpu_custom_call"),
          ("window_flash_dkv", "tpu_custom_call"))


def read(ctx):
    return S.kernel_ms_per_execution(ctx.get("trace"), PROGRAM, KERNEL)
