"""``pallas_call``s the host traced, and lowers to Mosaic, in this
process: the sum of the program's ``kernels.traced.*`` counters
(``flash_fwd``, ``flash_dq``, ``flash_dkv``, ``ssd_fwd``, ``ssd_bwd``,
``gmm``, ``tgmm``), each bumped where the kernel's wrapper is traced.
What a loop body's unrolling or a second form of a kernel costs set-up
goes with it. Nothing for a program that counts none."""
import horovod_tpu.common.metrics as program_metrics

LAYER = "Kernels"
UNIT = "x"


def read(ctx):
    traced = [n for name, n in program_metrics.counters().items()
              if name.startswith("kernels.traced.")]
    return sum(traced) if traced else None
